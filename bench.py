"""Benchmark: flagship transformer training throughput on one TPU chip,
plus labeled long-context points and the submit-to-first-step latency of
the full orchestration path.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"} with the
extra points under "detail". The reference repo publishes no performance
numbers (SURVEY.md §6 — verified absence); vs_baseline is always null —
compare two runs with ``--against``.

The default suite measures the device: with no TPU it fails instead of
timing a toy on the CPU. A point that raises is recorded as
``{"error": ...}`` so the others still run, and the process then exits
non-zero after the JSON is printed.

Phase order matters: the orchestration-latency point submits a REAL job
(client → coordinator → tpu-slice backend → executor → user script) whose
worker needs exclusive use of the TPU, so it runs BEFORE this process
initializes the JAX backend (backend init = chip lock). A chip belongs to
one process: after ``import jax`` in ``main`` nothing here may spawn a
child that needs the chip.
"""

import json
import os
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)


def _point_errors(node, path="detail"):
    """Paths of every ``{"error": ...}`` a failed point left in the doc."""
    if not isinstance(node, dict):
        return []
    found = [path] if "error" in node else []
    for k, v in node.items():
        found += _point_errors(v, f"{path}.{k}")
    return found


def _compile_cache_conf():
    """``--conf`` arguments placing the job's XLA compile cache: wherever
    JAX_COMPILATION_CACHE_DIR already points (the executor lets the
    environment win), else a fixed git-ignored directory in the checkout —
    a cache that moves never hits."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return []
    return ["--conf", "tony.jax.compilation-cache-dir="
            + os.path.join(REPO, ".jax_cache")]


def _span_first_step_latency(history_root):
    """submit_to_first_step_s measured from the REAL trace spans (the
    client.submit span's start to the executor.first_step span's end),
    not wall-clock guesses — and a tracing regression check in the same
    breath: a missing span tree (no log, no submit span, no first-step
    span, or unclosed spans) raises, failing the orchestration point
    loudly instead of silently reporting a probe-local number.

    Returns (latency_s, breakdown): the headline number plus the
    per-phase decomposition (tracing.cold_start_breakdown) whose phase
    durations are consecutive boundary intervals and sum EXACTLY to the
    headline — so a future regression is attributable to one phase from
    the BENCH json alone, without re-running the job."""
    from tony_tpu import constants as tony_constants
    from tony_tpu import tracing
    from tony_tpu.events import history as tony_history

    job_dirs = tony_history.list_job_dirs(history_root)
    if not job_dirs:
        raise RuntimeError(f"span check: no job dirs under {history_root}")
    (app, job_dir), = list(job_dirs.items())[:1]
    path = os.path.join(job_dir, tony_constants.TRACE_FILE)
    records = tracing.load_records(path)
    if not records:
        raise RuntimeError(
            f"span tree MISSING for {app}: no records at {path} — "
            f"tracing is broken (tony.trace.enabled off, or a span-log "
            f"regression)")
    payload = tracing.to_trace_events(records)
    if payload["unclosedSpans"]:
        raise RuntimeError(
            f"span tree for {app} has unclosed spans: "
            f"{payload['unclosedSpans']} — tracing regression")
    spans = {e["name"]: e for e in payload["traceEvents"]
             if e.get("ph") == "X"}
    submit = spans.get("client.submit")
    first = spans.get("executor.first_step")
    if submit is None or first is None:
        raise RuntimeError(
            f"span tree for {app} lacks "
            f"{'client.submit' if submit is None else 'executor.first_step'}"
            f" (have: {sorted(spans)}) — tracing regression")
    latency = ((first["ts"] + first.get("dur", 0)) - submit["ts"]) / 1e6
    breakdown = tracing.cold_start_breakdown(records)
    return latency, breakdown


def bench_orchestration_latency():
    """Submit-to-first-step seconds through the FULL stack (BASELINE.json
    named metric): a 1-worker job on the tpu-slice backend (LocalSim host
    channel — the executor/barrier/runtime-env path a real slice uses),
    whose user script jits one step on whatever accelerator is visible.
    Since the tracing PR the headline number comes from the job's OWN
    trace spans (client.submit → executor.first_step), so the bench
    trajectory doubles as a tracing regression check; the probe's
    self-reported wall-clock stays as a cross-check. Must run before this
    process touches the JAX backend: the worker needs the chip."""
    tmp = tempfile.mkdtemp(prefix="tony-bench-orch-")
    result = os.path.join(tmp, "result.json")
    env = dict(os.environ)
    env.update({
        "TONY_BENCH_T0": str(time.time()),
        "TONY_BENCH_RESULT": result,
        "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
    })
    r = subprocess.run(
        [sys.executable, "-m", "tony_tpu.cli", "submit",
         "--conf", "tony.application.backend=tpu-slice",
         "--conf", "tony.slice.provisioner=fake",
         "--conf", "tony.slice.num-hosts=1",
         "--conf", "tony.worker.instances=1",
         "--conf", "tony.worker.command="
                   f"{sys.executable} "
                   f"{os.path.join(REPO, 'benchmarks', 'first_step_probe.py')}",
         "--conf", "tony.application.timeout-s=600",
         "--conf", f"tony.history.location={os.path.join(tmp, 'history')}",
         *_compile_cache_conf(),
         "--workdir", os.path.join(tmp, "work")],
        env=env, capture_output=True, text=True, timeout=900)
    if r.returncode != 0 or not os.path.exists(result):
        raise RuntimeError(
            f"orchestration bench job failed (rc={r.returncode}): "
            f"{r.stdout[-2000:]}\n{r.stderr[-2000:]}")
    with open(result) as f:
        out = json.load(f)
    # The probe's wall-clock number becomes the cross-check; the headline
    # is span-derived (and raises if the span tree is missing/unclosed).
    out["probe_self_reported_s"] = out.pop("submit_to_first_step_s", None)
    latency, breakdown = _span_first_step_latency(
        os.path.join(tmp, "history"))
    out["submit_to_first_step_s"] = round(latency, 2)
    # Per-phase cold-start decomposition (consecutive boundary intervals;
    # sums exactly to the headline): the artifact that makes a
    # submit-latency regression attributable from the BENCH json alone.
    out["phases"] = breakdown["phases"]
    out["phase_total_s"] = breakdown["total_s"]
    out["phase_span_durations"] = breakdown["span_durations"]
    return out


def _time_scan(run_steps, state, inputs_for_rep, reps,
               time_inputs=False):
    """The shared timing discipline (one place, three callers): warmup
    with rep-0 inputs (same program shape — a different scan length would
    put the compile inside the timed region), then best-of-N reps, MIN dt.
    ``time_inputs`` moves the input construction INSIDE the
    timed region — the token-file point exists to measure host reads +
    H2D, the synthetic points to exclude them. Returns
    (min_dt, final_loss, state)."""
    import jax

    def warmup(s):
        s, losses = run_steps(s, inputs_for_rep(0))
        jax.block_until_ready(losses)
        return s

    state = warmup(state)
    dt = float("inf")
    final_loss = 0.0
    for rep in range(1, reps + 1):
        inp = None if time_inputs else inputs_for_rep(rep)
        t0 = time.perf_counter()
        if inp is None:
            inp = inputs_for_rep(rep)
        state, losses = run_steps(state, inp)
        final_loss = float(losses[-1])    # value readback = device sync
        dt = min(dt, time.perf_counter() - t0)
    return dt, final_loss, state


def build_flagship_config(seq, matmul_dtype=None):
    """The ~300M-param flagship: bf16 activations + lm_head, flash blocks
    from the v5e sweeps (see ops/attention.py).

    head_dim 128, not 64 (8 heads / 4 kv at dim 1024 — llama3's own head
    width): the MXU contracts 128 lanes per pass, so d=64 half-fills both
    flash contractions (q·kᵀ over d, p·v producing d) and caps the
    attention kernels at ~50% matmul rate.

    ``matmul_dtype`` opts the attention/MLP projections into the
    quantized path (tony.train.matmul-dtype; v5e runs int8 at 2x the
    bf16 MXU rate) — None keeps the bitwise bf16 path."""
    from tony_tpu.models import TransformerConfig

    bq = int(os.environ.get("TONY_BENCH_BLOCK_Q", "1024"))
    bk = int(os.environ.get("TONY_BENCH_BLOCK_K", "1024"))
    return TransformerConfig(
        vocab_size=32000, dim=1024, n_layers=16, n_heads=8,
        n_kv_heads=4, mlp_dim=4096, max_seq_len=seq, remat=False,
        attn_block_q=min(bq, seq),
        attn_block_k=min(bk, seq),
        matmul_dtype=matmul_dtype or None)


def measure_point(cfg, batch, seq, steps, chunked=False, loss_chunk=2048,
                  reps=3, mu_dtype=None):
    """Train `steps` steps (one compiled lax.scan program) and return
    {tokens_per_sec, mfu, loss, params}. K steps chained in ONE program:
    host dispatch is paid once per K steps, not per step."""
    import functools

    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import optax

    from tony_tpu.models import Transformer
    from tony_tpu.models.transformer import (causal_lm_loss,
                                             chunked_causal_lm_loss)
    from tony_tpu.parallel import MeshSpec, build_mesh, init_sharded_state
    from tony_tpu.parallel.sharding import DEFAULT_RULES

    mesh = build_mesh(MeshSpec())  # dp over whatever is visible (1 chip)
    model = Transformer(cfg)
    tokens = jax.random.randint(jax.random.key(0), (batch, seq), 0,
                                cfg.vocab_size)
    # mu_dtype=bf16 halves Adam's first moment — the lever that fits the
    # ~1B memory-pressure point: f32 param+m+v+grad is 16 B/param, and at
    # 16 GB HBM the grad buffer alone (4 B/param) is what pushes ≥0.95B
    # over.
    state, _ = init_sharded_state(
        model, tokens, optax.adamw(3e-4, mu_dtype=mu_dtype), mesh)
    n_params = sum(x.size for x in jax.tree.leaves(state.params))

    def one_step(state, rng):
        # Fresh synthetic tokens each step (device-side randint, negligible
        # cost): training on one fixed batch memorizes it within a few
        # dozen steps and the reported loss degenerates to ~0.
        step_tokens = jax.random.randint(rng, (batch, seq), 0,
                                         cfg.vocab_size)

        def loss(p):
            with nn.logical_axis_rules(list(DEFAULT_RULES)):
                if chunked:
                    # Long-context path: the [B,S,vocab] logits tensor (not
                    # attention) is the memory wall — never materialize it.
                    h = model.apply({"params": p}, step_tokens,
                                    return_hidden=True)
                    return chunked_causal_lm_loss(
                        h, p["lm_head"]["kernel"], step_tokens,
                        chunk_size=loss_chunk,
                        head_dtype=cfg.lm_head_dtype)
                return causal_lm_loss(
                    model.apply({"params": p}, step_tokens), step_tokens)
        l, grads = jax.value_and_grad(loss)(state.params)
        return state.apply_gradients(grads), l

    @functools.partial(jax.jit, donate_argnums=0)
    def run_steps(state, rngs):
        return jax.lax.scan(one_step, state, rngs)

    dt, final_loss, state = _time_scan(
        run_steps, state,
        lambda rep: jax.random.split(jax.random.key(1 + rep), steps), reps)

    tokens_per_sec = batch * seq * steps / dt
    # Model FLOPs: 6·params per token (fwd+bwd) + causal attention term
    # (12·L·dim·S/2, fwd+bwd, causal halves the score matrix). Remat
    # recompute is intentionally NOT counted (standard MFU accounting).
    flops_per_token = 6 * n_params + 12 * cfg.n_layers * cfg.dim * seq // 2
    from tony_tpu.telemetry import peak_bf16_flops   # raises on a miss

    peak = peak_bf16_flops(jax.devices()[0].device_kind)
    mfu = tokens_per_sec * flops_per_token / peak
    return {"tokens_per_sec": round(tokens_per_sec, 2),
            "mfu_vs_peak_bf16": round(mfu, 4),
            "loss": round(final_loss, 4),
            "params": n_params, "batch": batch, "seq": seq}


def measure_vision_point(kind, batch, steps, reps=3, image=224):
    """samples/sec/chip for the BASELINE.json named vision workloads —
    ResNet-50 (HorovodRuntime ImageNet analogue; MFU from the standard
    analytic 4.089 GFLOPs/224²-image count scaled by resolution — XLA's
    cost_analysis undercounted convs ~4× on this backend) and the MNIST
    MLP (mnist-tensorflow / mnist-pytorch analogue). Same discipline as
    measure_point: K steps in one compiled scan, fresh device-side data
    per step, best-of-N."""
    import functools

    import jax
    import jax.numpy as jnp
    import optax

    from tony_tpu.parallel import MeshSpec, build_mesh, init_sharded_state

    if kind == "resnet50":
        from tony_tpu.models import ResNet, ResNetConfig
        model = ResNet(ResNetConfig.resnet50())
        sample = jax.random.normal(jax.random.key(0),
                                   (batch, image, image, 3), jnp.bfloat16)
        classes = 1000

        def make_batch(rng):
            r1, r2 = jax.random.split(rng)
            return (jax.random.normal(r1, sample.shape, jnp.bfloat16),
                    jax.random.randint(r2, (batch,), 0, classes))
    else:
        from tony_tpu.models import MnistMLP
        model = MnistMLP(hidden=128)
        sample = jax.random.normal(jax.random.key(0), (batch, 28, 28, 1))
        classes = 10

        def make_batch(rng):
            r1, r2 = jax.random.split(rng)
            return (jax.random.normal(r1, sample.shape),
                    jax.random.randint(r2, (batch,), 0, classes))

    from tony_tpu.models.mlp import classification_loss

    mesh = build_mesh(MeshSpec())
    state, _ = init_sharded_state(
        model, sample, optax.sgd(0.1, momentum=0.9), mesh)
    n_params = sum(x.size for x in jax.tree.leaves(state.params))

    def one_step(state, rng):
        x, y = make_batch(rng)

        def loss(p):
            return classification_loss(model.apply({"params": p}, x), y)
        l, grads = jax.value_and_grad(loss)(state.params)
        return state.apply_gradients(grads), l

    @functools.partial(jax.jit, donate_argnums=0)
    def run_steps(state, rngs):
        return jax.lax.scan(one_step, state, rngs)

    dt, final_loss, state = _time_scan(
        run_steps, state,
        lambda rep: jax.random.split(jax.random.key(1 + rep), steps), reps)
    samples_per_sec = batch * steps / dt
    out = {"samples_per_sec": round(samples_per_sec, 2),
           "loss": round(final_loss, 4), "params": n_params,
           "batch": batch}
    if kind == "resnet50":
        # Standard accounting: 4.089 GFLOPs fwd per 224² image (scaled by
        # the actual resolution — conv FLOPs go with spatial area), ×3
        # for training. The conv trunk is expected to be HBM-bound, so
        # MFU against the matmul peak is reported for comparability only.
        from tony_tpu.telemetry import peak_bf16_flops  # raises on a miss

        peak = peak_bf16_flops(jax.devices()[0].device_kind)
        flops_per_sample = 3 * 4.089e9 * (image / 224) ** 2
        out["mfu_vs_peak_bf16"] = round(
            samples_per_sec * flops_per_sample / peak, 4)
    return out


def measure_token_file_point(cfg, batch, seq, steps, reps=3):
    """The flagship config trained from a REAL mmap .bin corpus through
    ShardedBatchIterator (prefetch on): K prefetched batches stack into
    one scan dispatch, so the timed
    region covers host reads + H2D + compute — the number that proves the
    input pipeline keeps up with the synthetic headline."""
    import functools
    import tempfile as tf_mod

    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from tony_tpu.data import token_file_batches, write_token_file
    from tony_tpu.models import Transformer
    from tony_tpu.models.transformer import causal_lm_loss
    from tony_tpu.parallel import MeshSpec, build_mesh, init_sharded_state
    from tony_tpu.parallel.sharding import DEFAULT_RULES

    import shutil

    mesh = build_mesh(MeshSpec())
    model = Transformer(cfg)
    corpus = np.random.default_rng(0).integers(
        0, cfg.vocab_size, size=4_000_000, dtype=np.int64)
    tmpdir = tf_mod.mkdtemp(prefix="tony-bench-tok-")
    it = None
    try:
        path = os.path.join(tmpdir, "corpus.bin")
        write_token_file(path, corpus, dtype=np.uint16)
        # One iterator batch per DISPATCH (steps·batch rows, reshaped to
        # [K, B, S] on device): the scan wants K steps of data per
        # dispatch, and fetching it as one prefetched global array costs
        # one H2D instead of K small ones.
        it = token_file_batches(mesh, path, global_batch=batch * steps,
                                seq=seq)
        tokens0 = jnp.asarray(next(it)["tokens"][:batch])
        state, _ = init_sharded_state(
            model, tokens0, optax.adamw(3e-4), mesh)
        n_params = sum(x.size for x in jax.tree.leaves(state.params))

        def one_step(state, step_tokens):
            def loss(p):
                with nn.logical_axis_rules(list(DEFAULT_RULES)):
                    return causal_lm_loss(
                        model.apply({"params": p}, step_tokens),
                        step_tokens)
            l, grads = jax.value_and_grad(loss)(state.params)
            return state.apply_gradients(grads), l

        @functools.partial(jax.jit, donate_argnums=0)
        def run_steps(state, tokens_k):          # [K, B, S]
            return jax.lax.scan(one_step, state, tokens_k)

        def gather(rep):
            return jnp.asarray(next(it)["tokens"]).reshape(steps, batch,
                                                           seq)

        dt, final_loss, state = _time_scan(run_steps, state, gather, reps,
                                           time_inputs=True)
        return {"tokens_per_sec": round(batch * seq * steps / dt, 2),
                "loss": round(final_loss, 4), "params": n_params,
                "batch": batch, "seq": seq,
                "source": "mmap .bin + prefetch"}
    finally:
        # The next phase (0.95B) is sized to the edge of HBM: the
        # prefetch thread's buffered device arrays must not survive this
        # point, nor the corpus dir survive the run.
        if it is not None:
            it.close()
        shutil.rmtree(tmpdir, ignore_errors=True)


def measure_phase_point(steps=16, batch=64):
    """Steady-state step-time attribution probe: a tiny telemetry-
    instrumented loop (host batch build → H2D → block_until_ready'd
    compute) through the SAME phase pipeline production jobs feed
    (telemetry.phase → ring → phase_stats), recorded into the BENCH json
    as per-phase seconds/step — so a future input-pipeline or dispatch
    regression is attributable to a phase from the jsons alone
    (`tony-tpu bench diff` compares these with the rest). Cheap by
    design (an MLP, sub-second) and backend-agnostic: the CPU smoke run
    records it too."""
    import functools

    import jax
    import numpy as np
    import optax

    from tony_tpu import telemetry
    from tony_tpu.models import MnistMLP
    from tony_tpu.models.mlp import classification_loss
    from tony_tpu.parallel import MeshSpec, build_mesh, init_sharded_state

    telemetry._reset_phase_state()
    mesh = build_mesh(MeshSpec())
    model = MnistMLP(hidden=64)
    rng = np.random.default_rng(0)
    sample = jax.numpy.asarray(
        rng.standard_normal((batch, 28, 28, 1), dtype=np.float32))
    state, _ = init_sharded_state(model, sample, optax.sgd(0.1), mesh)

    @functools.partial(jax.jit, donate_argnums=0)
    def one_step(state, x, y):
        def loss(p):
            return classification_loss(model.apply({"params": p}, x), y)
        l, grads = jax.value_and_grad(loss)(state.params)
        return state.apply_gradients(grads), l

    # Warmup outside the attribution window (compile must not land in
    # step_compute — same discipline as _time_scan).
    x0 = jax.numpy.asarray(rng.standard_normal((batch, 28, 28, 1),
                                               dtype=np.float32))
    y0 = jax.numpy.asarray(rng.integers(0, 10, size=batch))
    state, l = one_step(state, x0, y0)
    jax.block_until_ready(l)
    telemetry._reset_phase_state()
    for _ in range(steps):
        with telemetry.step():
            with telemetry.phase("data_wait"):
                xb = rng.standard_normal((batch, 28, 28, 1),
                                         dtype=np.float32)
                yb = rng.integers(0, 10, size=batch)
            with telemetry.phase("h2d"):
                x = jax.device_put(jax.numpy.asarray(xb))
                y = jax.device_put(jax.numpy.asarray(yb))
            with telemetry.phase("step_compute") as p:
                state, l = one_step(state, x, y)
                p.block_until_ready(l)
    stats = telemetry.phase_stats()
    n = max(1.0, float(stats.get("steps", 1.0)))
    per_step = {k: round(v / n, 6)
                for k, v in (stats.get("cum") or {}).items()}
    from tony_tpu.profiling import classify, phase_fractions

    fr = phase_fractions(stats.get("cum") or {},
                         float(stats.get("wall_s", 0.0)))
    return {"step_phases_s": per_step,
            "seconds_per_step": round(
                float(stats.get("wall_s", 0.0)) / n, 6),
            # Comms share of the step wall (grad_sync's bucketed sync
            # books here on multislice meshes; ~0 on one chip). Recorded
            # per bench point so `tony-tpu bench diff` gates comms
            # regressions — direction: lower-better (benchdiff._LOWER).
            "comms_fraction": round(fr.get("comms", 0.0), 4),
            "verdict": classify(fr)["category"] if fr else None,
            "steps": int(n), "batch": batch}


def measure_scale_point(width, hb_interval_ms=500, sustain_s=6.0,
                        monitor_interval_ms=100, pump_threads=16):
    """One BENCH_SCALE width point: a gang of ``width`` beat-only
    virtual executors (tony.scale.virtual-executors — real RPC frames,
    real journal records, no user processes) against ONE coordinator,
    measuring the control plane itself: rendezvous time, beats/s
    sustained, active tick duration, journal records/s + fsync stall
    fraction, and resize latency at width. Runs entirely on CPU — no
    jax, CI-sized time — because the thing under test is the
    coordinator's O(n) loops, not the device."""
    import shutil
    import tempfile
    import threading

    from tony_tpu.cluster.local import VirtualExecutorBackend
    from tony_tpu.conf import keys as K
    from tony_tpu.conf.config import TonyTpuConfig
    from tony_tpu.coordinator.coordinator import Coordinator
    from tony_tpu.profiling import classify_coord

    tmp = tempfile.mkdtemp(prefix=f"tony-bench-scale-{width}-")
    conf = TonyTpuConfig()
    conf.set("tony.worker.instances", width)
    conf.set("tony.worker.command", "virtual")
    conf.set(K.SCALE_VIRTUAL_EXECUTORS, True)
    conf.set(K.SCALE_VIRTUAL_PUMP_THREADS, pump_threads)
    conf.set(K.TASK_HEARTBEAT_INTERVAL_MS, hb_interval_ms)
    conf.set(K.COORDINATOR_MONITOR_INTERVAL_MS, monitor_interval_ms)
    conf.set(K.ELASTIC_ENABLED, True)
    conf.set(K.ELASTIC_BARRIER_TIMEOUT_S, 60)
    # Bench hygiene: no client to signal finish, and the teardown must
    # not spend seconds diagnosing the deliberate stop.
    conf.set(K.APPLICATION_NUM_CLIENTS_TO_WAIT, False)
    conf.set(K.DIAGNOSIS_ENABLED, False)
    backend = VirtualExecutorBackend.from_conf(
        conf, os.path.join(tmp, "work"))
    coord = Coordinator(conf, f"bench_scale_{width}", backend,
                        os.path.join(tmp, "history"), user="bench")
    runner = threading.Thread(target=coord.run, daemon=True,
                              name=f"scale-coord-{width}")
    point = {"tasks": width,
             "hb_interval_ms": hb_interval_ms}
    try:
        t0 = time.monotonic()
        runner.start()
        deadline = t0 + 120
        while not coord.session.all_registered() \
                and time.monotonic() < deadline:
            time.sleep(0.02)
        if not coord.session.all_registered():
            raise RuntimeError(
                f"rendezvous of {width} virtual tasks did not complete "
                f"within 120s ({coord.session.num_registered} "
                f"registered)")
        point["rendezvous_s"] = round(time.monotonic() - t0, 3)
        # Steady state: let the beats/journal/tick machinery run, then
        # read the coordinator's own phase accounting.
        time.sleep(sustain_s)
        snap = coord.coordphases.snapshot()
        fractions = coord.coordphases.fractions()
        cum = snap.get("cum") or {}
        wall = float(snap.get("wall_s", 0.0) or 0.0)
        point.update({
            "beats_per_sec": round(
                float(snap.get("beats_per_sec", 0.0)), 2),
            "tick_duration_s": round(
                float(snap.get("tick_active_s", 0.0)), 6),
            "journal_records_per_sec": round(
                float(snap.get("journal_records_per_sec", 0.0)), 2),
            "journal_fsync_p99_s": round(
                float(snap.get("journal_fsync_p99_s", 0.0)), 6),
            # Fraction of the coordinator's wall spent inside fsync'd
            # journal appends — the group-commit target number.
            "fsync_stall_fraction": round(
                fractions.get("journal_fsync", 0.0), 4),
            # Acceptance invariant: per-tick phases sum to the tick
            # wall; the cumulative ratio must be ~1.0.
            "phase_sum_ratio": round(
                sum(cum.values()) / wall, 4) if wall > 0 else None,
            "coord_phases": {k: round(v, 4)
                             for k, v in sorted(fractions.items())},
        })
        if fractions:
            point["verdict"] = classify_coord(fractions)["category"]
        # Resize at width: shrink by one through the real
        # drain→remesh→barrier path; latency = request → op complete.
        t1 = time.monotonic()
        res = coord.resize_application(width - 1)
        if res.get("ok"):
            while coord.elastic is not None and coord.elastic.resizing \
                    and time.monotonic() - t1 < 90:
                time.sleep(0.02)
            if coord.elastic is not None and not coord.elastic.resizing:
                point["resize_latency_s"] = round(
                    time.monotonic() - t1, 3)
            else:
                point["resize_error"] = "resize did not complete in 90s"
        else:
            point["resize_error"] = str(res.get("message", "refused"))
    finally:
        coord.request_stop("scale bench point complete")
        runner.join(timeout=60)
        shutil.rmtree(tmp, ignore_errors=True)
    return point


def run_scale_suite(widths=None, sustain_s=6.0):
    """The BENCH_SCALE family (persisted as BENCH_SCALE_r*.json, gated
    by `tony-tpu bench diff` like every other family): control-plane
    capacity vs gang width. Headline = beats/s sustained at the widest
    point (the number 'a thousand tasks on one control plane' hangs
    off)."""
    if widths is None:
        widths = [int(w) for w in os.environ.get(
            "TONY_BENCH_SCALE_WIDTHS", "128,256,512").split(",")
            if w.strip()]
    detail = {"suite": "scale"}
    headline = None
    for width in widths:
        label = f"w{width}"
        try:
            point = measure_scale_point(width, sustain_s=sustain_s)
            detail[label] = point
            headline = point
        except Exception as e:  # noqa: BLE001 — keep the other widths
            print(f"# scale point {label} failed: {e}", file=sys.stderr)
            detail[label] = {"error": str(e)[:300]}
    return {
        "metric": "coord_beats_per_sec_at_max_width",
        "value": headline.get("beats_per_sec") if headline else None,
        "unit": "beats/s",
        "vs_baseline": None,
        "detail": detail,
    }


def run_fleet_suite(n_jobs=50, tick_s=0.2, timeout_s=420):
    """The BENCH_FLEET family (persisted as BENCH_FLEET_r*.json, gated
    by `tony-tpu bench diff` like every other family): the 50-job
    synthetic tenant mix — 3 tenants, quotas, priorities 0-10, sizes
    1-8, one whole-pool elastic victim preempted by a priority-10
    arrival — drained through ONE in-process fleet daemon spawning
    real `tony-tpu submit` clients on LocalSim virtual executors.
    Headline = fleet goodput_fraction from the ledger; queue-wait
    p50/p99, preemptions/job, warm-start fraction ride along. A live
    warm executor pool (tony_tpu/pool.py) backs the mix — a handful of
    the jobs run REAL 1-host executors that adopt from it, so the
    ledger's warm_start_fraction measures the adoption path instead of
    pinning 0.0. CPU-only, no jax in this process (the virtual
    executors beat, they don't compute; the pool preloads nothing)."""
    import shutil
    import tempfile
    import threading

    from tony_tpu.fleet.daemon import FleetDaemon
    from tony_tpu.pool import PoolDaemon

    tmp = tempfile.mkdtemp(prefix="tony-bench-fleet-")
    fleet_dir = os.path.join(tmp, "fleet")
    pool_dir = os.path.join(tmp, "pool")
    virtual = {
        "tony.worker.command": "virtual",
        "tony.scale.virtual-executors": "true",
        "tony.task.heartbeat-interval-ms": "300",
        "tony.coordinator.monitor-interval-ms": "100",
        "tony.diagnosis.enabled": "false",
    }
    # The warm-adoption jobs: real executors (the pool's adoption path
    # lives in LocalProcessBackend), a no-op user command, 1 host each.
    real = {
        "tony.worker.command": "true",
        "tony.task.heartbeat-interval-ms": "300",
        "tony.coordinator.monitor-interval-ms": "100",
        "tony.diagnosis.enabled": "false",
    }
    warm_jobs = 4

    def conf(run_s):
        c = dict(virtual)
        c["tony.scale.virtual-run-s"] = str(run_s)
        return c

    pool = PoolDaemon(pool_dir, size=2, preload="", max_lease_age_s=600)
    pool_runner = threading.Thread(target=pool.run, daemon=True,
                                   name="bench-fleet-pool")
    daemon = FleetDaemon(fleet_dir, slices=2, hosts_per_slice=4,
                         quotas="capped=2", tick_s=tick_s,
                         ledger_interval_s=2.0, pool_dir=pool_dir)
    runner = threading.Thread(target=daemon.run, daemon=True,
                              name="bench-fleet-daemon")
    point = {"jobs": n_jobs, "pool_hosts": 8, "warm_jobs": warm_jobs}
    try:
        t0 = time.monotonic()
        pool_runner.start()
        pool_deadline = t0 + 60
        while pool.status()["ready"] < 1 \
                and time.monotonic() < pool_deadline:
            time.sleep(0.2)
        runner.start()
        # One whole-pool elastic victim; once it RUNS, a priority-10
        # demander arrives into the full pool — the preempt-to-reclaim
        # + grow-back shape in the mix (submitted after the victim is
        # up, else priority ordering simply grants the demander first).
        daemon.submit("bulk", 8, priority=0, min_hosts=2,
                      conf=conf(15.0))
        victim_deadline = t0 + 60
        while time.monotonic() < victim_deadline:
            rows = {r["job"]: r for r in daemon.status()["jobs"]}
            row = rows.get("fj-0001", {})
            if row.get("state") == "RUNNING" and row.get("app_id"):
                break
            time.sleep(0.5)
        daemon.submit("prod", 4, priority=10, conf=conf(1.0))
        sizes = (1, 2, 3, 4)
        submitted = 2
        for i in range(n_jobs - 10 - warm_jobs):
            tenant = "alpha" if i % 2 == 0 else "bravo"
            daemon.submit(tenant, sizes[i % 4], priority=i % 3,
                          conf=conf(0.5))
            submitted += 1
        for i in range(warm_jobs):
            daemon.submit("alpha" if i % 2 == 0 else "bravo", 1,
                          priority=1, conf=dict(real))
            submitted += 1
        for i in range(n_jobs - submitted):
            daemon.submit("capped", 1 + i % 2, conf=conf(0.5))
        deadline = t0 + timeout_s
        while time.monotonic() < deadline:
            snap = daemon.status()
            rows = snap.get("jobs", [])
            if len(rows) == n_jobs and all(
                    r["state"] in ("FINISHED", "FAILED", "CANCELLED")
                    for r in rows):
                break
            time.sleep(1.0)
        else:
            raise RuntimeError(
                f"fleet mix did not drain within {timeout_s}s "
                f"({sum(1 for r in daemon.status()['jobs'] if r['state'] in ('FINISHED', 'FAILED', 'CANCELLED'))}/{n_jobs})")
        point["drain_s"] = round(time.monotonic() - t0, 1)
        snap = daemon.status()
        failed = [r["job"] for r in snap["jobs"]
                  if r["state"] != "FINISHED"]
        point["failed_jobs"] = len(failed)
        qw = snap.get("queue_wait") or {}
        point["queue_wait_p50_s"] = qw.get("p50_s")
        point["queue_wait_p99_s"] = qw.get("p99_s")
        grants = daemon.metrics.counter("tony_fleet_grants_total").value
        preempts = daemon.metrics.counter(
            "tony_fleet_preemptions_total").value
        point["preemptions_per_job"] = round(
            preempts / max(1.0, grants), 4)
        led = (snap.get("ledger") or {}).get("fleet") or {}
        point["fleet_goodput_fraction"] = led.get("goodput_fraction")
        point["warm_start_fraction"] = led.get("warm_start_fraction")
        point["held_chip_s"] = led.get("held_chip_s")
        point["lost_preempted_chip_s"] = led.get(
            "lost_preempted_chip_s")
        point["phase_chip_s"] = led.get("phase_chip_s")
        incident = None
        try:
            with open(os.path.join(
                    fleet_dir, "fleet.incident.json")) as f:
                incident = json.load(f)
        except (OSError, ValueError):
            pass
        if incident:
            point["verdict"] = (incident.get("verdict")
                                or {}).get("category")
    finally:
        daemon.request_stop()
        runner.join(timeout=60)
        pool.request_stop()
        pool_runner.join(timeout=30)
        shutil.rmtree(tmp, ignore_errors=True)
    return {
        "metric": "fleet_goodput_fraction",
        "value": point.get("fleet_goodput_fraction"),
        "unit": "chip-seconds useful / chip-seconds held",
        "vs_baseline": None,
        "detail": {"suite": "fleet", "mix": point},
    }


def run_whatif_suite(journal_path="", sim_budget_s=5.0):
    """The BENCH_WHATIF family: the fleet time machine's cost and its
    payoff on the checked-in 50-job recorded tenant mix
    (tests/fixtures/whatif_mix, regenerated by
    tests/scripts/gen_whatif_mix.py). Three gates ride the diff:

    * ``parity_mismatches`` must stay 0 — the simulator and the policy
      engine share one scheduling brain (lower better);
    * ``sim_wall_s`` — full report (parity + base + counterfactual +
      3-point quota sweep) must fold in under ``sim_budget_s`` (lower
      better; the portal /whatif view recomputes per request);
    * headline ``value`` = the quota-bump counterfactual's improvement
      fraction on the starved tenant's queue-wait p99 (higher better —
      the number the whole subsystem exists to produce).

    Deterministic and sub-second: safe for the CI bench-smoke lane."""
    from tony_tpu.fleet import simulator as fsim
    from tony_tpu.fleet import timeline as ftimeline

    if not journal_path:
        journal_path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "tests",
            "fixtures", "whatif_mix", "fleet.journal.jsonl")
    t0 = time.monotonic()
    tl = ftimeline.load(path=journal_path)
    ov = fsim.build_overrides(quotas=["capped=4"])
    report = fsim.whatif(tl, ov, sweeps=["quota.capped=2,3,4"])
    sim_wall_s = round(time.monotonic() - t0, 3)
    par = report["parity"]
    if not par["ok"]:
        raise RuntimeError(
            f"whatif parity broke on the recorded mix: "
            f"{par['mismatch_counts']} {par['mismatches'][:2]}")
    if sim_wall_s > sim_budget_s:
        raise RuntimeError(
            f"whatif report took {sim_wall_s}s (budget {sim_budget_s}s)")
    base = report["base"]
    cf = report["counterfactuals"][0]
    base_p99 = base["per_tenant"]["capped"]["queue_wait_p99_s"]
    cf_p99 = cf["per_tenant"]["capped"]["queue_wait_p99_s"]
    improvement = round((base_p99 - cf_p99) / base_p99, 4) \
        if base_p99 else 0.0
    point = {
        "jobs": report["jobs"],
        "records": report["records"],
        "sim_wall_s": sim_wall_s,
        "parity_mismatches": len(par["mismatches"]),
        "parity_records_checked": par["counts"]["grant"]
        + par["counts"]["preempt"] + par["counts"]["decision"],
        "queue_wait_p99_s": base["metrics"]["queue_wait_p99_s"],
        "capped_queue_wait_p99_s": base_p99,
        "capped_whatif_queue_wait_p99_s": cf_p99,
        "p99_improvement_fraction": improvement,
        "quota_hold_s": base["metrics"]["quota_hold_s"],
        "whatif_quota_hold_s": cf["metrics"]["quota_hold_s"],
        "makespan_s": base["metrics"]["makespan_s"],
        "whatif_makespan_s": cf["metrics"]["makespan_s"],
        "utilization_fraction": base["metrics"]["utilization_fraction"],
        "sweep_points": len(report["counterfactuals"]) - 1,
    }
    return {
        "metric": "p99_improvement_fraction",
        "value": improvement,
        "unit": "fractional queue-wait-p99 reduction for the starved "
                "tenant under --quota capped=4",
        "vs_baseline": None,
        "detail": {"suite": "whatif", "whatif": point},
    }


def measure_migrate_point(width=16, target="slice-1", hb_interval_ms=300,
                          monitor_interval_ms=100):
    """One BENCH_MIGRATE move point: a gang of ``width`` beat-only
    virtual executors against ONE coordinator; ``migrate_application``
    drives the real drain→park→relaunch→barrier path to ``target`` and
    the point records the wall from the operator request to the op
    completing (every member re-registered on the destination). What a
    live migration costs the control plane — the number the spot-
    survival story hangs off (an evacuation must beat the preemption
    notice's deadline)."""
    import shutil
    import threading

    from tony_tpu.conf import keys as K
    from tony_tpu.conf.config import TonyTpuConfig
    from tony_tpu.cluster.local import VirtualExecutorBackend
    from tony_tpu.coordinator.coordinator import Coordinator

    tmp = tempfile.mkdtemp(prefix=f"tony-bench-migrate-{width}-")
    conf = TonyTpuConfig()
    conf.set("tony.worker.instances", width)
    conf.set("tony.worker.command", "virtual")
    conf.set(K.SCALE_VIRTUAL_EXECUTORS, True)
    conf.set(K.TASK_HEARTBEAT_INTERVAL_MS, hb_interval_ms)
    conf.set(K.COORDINATOR_MONITOR_INTERVAL_MS, monitor_interval_ms)
    conf.set(K.ELASTIC_ENABLED, True)
    conf.set(K.ELASTIC_BARRIER_TIMEOUT_S, 60)
    conf.set(K.APPLICATION_NUM_CLIENTS_TO_WAIT, False)
    conf.set(K.DIAGNOSIS_ENABLED, False)
    backend = VirtualExecutorBackend.from_conf(
        conf, os.path.join(tmp, "work"))
    coord = Coordinator(conf, f"bench_migrate_{width}", backend,
                        os.path.join(tmp, "history"), user="bench")
    runner = threading.Thread(target=coord.run, daemon=True,
                              name=f"migrate-coord-{width}")
    point = {"tasks": width, "target": target}
    try:
        t0 = time.monotonic()
        runner.start()
        deadline = t0 + 120
        while not coord.session.all_registered() \
                and time.monotonic() < deadline:
            time.sleep(0.02)
        if not coord.session.all_registered():
            raise RuntimeError(
                f"rendezvous of {width} virtual tasks did not complete "
                f"within 120s ({coord.session.num_registered} "
                f"registered)")
        point["rendezvous_s"] = round(time.monotonic() - t0, 3)
        # The elastic manager marks the gang established one monitor
        # tick after the barrier opens; a migrate before that is
        # (correctly) refused.
        while (coord.elastic is None or not coord.elastic.established) \
                and time.monotonic() < deadline:
            time.sleep(0.02)
        t1 = time.monotonic()
        res = coord.migrate_application(target)
        if not res.get("ok"):
            raise RuntimeError(
                f"migration refused: {res.get('message', '?')}")
        while coord.elastic is not None and coord.elastic.resizing \
                and time.monotonic() - t1 < 90:
            time.sleep(0.02)
        if coord.elastic is not None and coord.elastic.resizing:
            raise RuntimeError("migration did not complete in 90s")
        point["migration_wall_s"] = round(time.monotonic() - t1, 3)
        pool = coord.session.jobs.get("worker")
        point["destination_pinned"] = bool(
            pool is not None and pool.node_pool == target)
    finally:
        coord.request_stop("migrate bench point complete")
        runner.join(timeout=60)
        shutil.rmtree(tmp, ignore_errors=True)
    return point


def measure_migrate_ckpt_point(saves=10, payload_mb=4.0, step_s=0.05):
    """The async-snapshot layer under the move: overlapped saves
    (checkpoint/manager.py) of a ``payload_mb`` state against the same
    loop run synchronously. ``ckpt_stall_fraction`` is save() blocking
    time over the loop wall in overlapped mode — the number a
    regression back to synchronous saves would spike — and the headline
    ``ckpt_overlap_fraction`` is the share of the synchronous save cost
    the background writer hides. Local disk, CPU-only jax (the host-
    snapshot copy), CI-sized."""
    import shutil

    import numpy as np

    from tony_tpu.checkpoint.manager import CheckpointManager

    tmp = tempfile.mkdtemp(prefix="tony-bench-migrate-ckpt-")
    state = {"params": np.zeros(int(payload_mb * 1024 * 1024 / 4),
                                dtype=np.float32)}
    point = {"saves": saves, "payload_mb": payload_mb}

    def loop(async_save, sub):
        mgr = CheckpointManager(os.path.join(tmp, sub), max_to_keep=2,
                                async_save=async_save)
        block = 0.0
        t0 = time.monotonic()
        for step in range(saves):
            t = time.monotonic()
            mgr.save(step, state, force=True)
            block += time.monotonic() - t
            time.sleep(step_s)     # the training step the save overlaps
        wall = time.monotonic() - t0
        t = time.monotonic()
        mgr.wait()
        drain = time.monotonic() - t
        mgr.close()
        if mgr.async_errors:
            raise RuntimeError(
                f"async save errors: {mgr.async_errors[:3]}")
        return block, wall, drain

    try:
        sync_block, _, _ = loop(False, "sync")
        block, wall, drain = loop(True, "overlap")
        point["ckpt_stall_fraction"] = round(block / wall, 4)
        point["ckpt_overlap_fraction"] = round(
            max(0.0, 1.0 - block / sync_block), 4) if sync_block > 0 \
            else None
        point["sync_save_block_s"] = round(sync_block, 3)
        point["ckpt_drain_s"] = round(drain, 3)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return point


def run_migrate_suite(width=16):
    """The BENCH_MIGRATE family (persisted as BENCH_MIGRATE_r*.json,
    gated by `tony-tpu bench diff` like every other family): what a
    live migration costs, at its two layers — the control-plane move
    (drain→park→relaunch→barrier wall at width, on virtual executors)
    and the async snapshot under it (save-stall fraction vs the
    synchronous baseline). Headline = ckpt_overlap_fraction (1.0 =
    snapshots cost the training loop nothing). The e2e drills
    (tests/test_e2e_migrate.py) pin the OTHER family numbers —
    steps_lost == 0 and retry budget untouched — so the suite measures
    cost, not correctness. CPU-only, CI-sized."""
    detail = {"suite": "migrate"}
    try:
        detail["move"] = measure_migrate_point(width)
    except Exception as e:  # noqa: BLE001 — keep the ckpt point
        print(f"# migrate move point failed: {e}", file=sys.stderr)
        detail["move"] = {"error": str(e)[:300]}
    try:
        detail["ckpt"] = measure_migrate_ckpt_point()
    except Exception as e:  # noqa: BLE001
        print(f"# migrate ckpt point failed: {e}", file=sys.stderr)
        detail["ckpt"] = {"error": str(e)[:300]}
    return {
        "metric": "ckpt_overlap_fraction",
        "value": (detail.get("ckpt") or {}).get("ckpt_overlap_fraction"),
        "unit": "fraction of sync save cost hidden by overlap",
        "vs_baseline": None,
        "detail": detail,
    }


def _emit(doc, args):
    """Print the one JSON line (and --out), run the --against gate, and
    exit non-zero if the gate regressed or any point recorded an error —
    a failed point never reads as a passing run."""
    print(json.dumps(doc))
    if args.out:
        with open(args.out, "w", encoding="utf-8") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
            f.write("\n")
    failed = _point_errors(doc.get("detail"))
    if failed:
        print(f"# failed points: {', '.join(failed)}", file=sys.stderr)
    if args.against:
        # Regression gate (tony_tpu/profiling/benchdiff.py): compare this
        # run against the given baseline json; a regression past the
        # tolerance fails the bench run loudly.
        from tony_tpu.profiling import benchdiff

        with open(args.against) as f:
            base = json.load(f)
        result = benchdiff.diff_bench(base, doc, tolerance=args.tolerance)
        print(benchdiff.format_report(result, args.against, "(this run)"),
              file=sys.stderr)
        failed = failed or result["regressions"]
    if failed:
        sys.exit(1)


def main(argv=None):
    import argparse

    ap = argparse.ArgumentParser(prog="bench.py")
    ap.add_argument("--against", default="",
                    help="baseline bench json (raw or BENCH_r*): after "
                         "the run, diff this run's numbers against it "
                         "(tony-tpu bench diff) and exit nonzero on "
                         "regression")
    ap.add_argument("--tolerance", type=float, default=0.10,
                    help="relative regression tolerance for --against")
    ap.add_argument("--suite",
                    choices=("default", "scale", "fleet", "migrate",
                             "whatif"),
                    default="default",
                    help="'scale' runs the control-plane width family "
                         "(BENCH_SCALE: rendezvous/beats/tick/journal/"
                         "resize vs gang size on virtual executors — "
                         "CPU-only, no jax); 'fleet' replays the "
                         "50-job synthetic tenant mix through one "
                         "fleet daemon (BENCH_FLEET: goodput fraction, "
                         "queue-wait p50/p99, preemptions/job, warm-"
                         "start fraction); 'migrate' measures a live "
                         "migration's two layers (BENCH_MIGRATE: "
                         "drain→relaunch wall at width, async-snapshot "
                         "stall vs the sync baseline) instead of the "
                         "training bench; 'whatif' folds the checked-in "
                         "50-job recorded mix through the fleet time "
                         "machine (BENCH_WHATIF: parity gate, report "
                         "wall, counterfactual queue-wait payoff — "
                         "deterministic, sub-second, no daemon)")
    ap.add_argument("--out", default="",
                    help="also write the bench json to this path")
    args = ap.parse_args(argv)

    if args.suite in ("scale", "fleet", "migrate", "whatif"):
        doc = {"scale": run_scale_suite,
               "fleet": run_fleet_suite,
               "migrate": run_migrate_suite,
               "whatif": run_whatif_suite}[args.suite]()
        _emit(doc, args)
        return

    detail = {}

    # Phase 0 — BEFORE backend init (see module docstring).
    if os.environ.get("TONY_BENCH_ORCH", "1") != "0":
        try:
            detail["orchestration"] = bench_orchestration_latency()
        except Exception as e:  # noqa: BLE001 — keep the device points
            print(f"# orchestration point failed: {e}", file=sys.stderr)
            detail["orchestration"] = {"error": str(e)[:300]}

    # From here on this process holds the chip: nothing below may spawn a
    # child that needs it.
    import jax

    if jax.default_backend() != "tpu":
        sys.exit(f"bench.py: the default suite measures a TPU and found "
                 f"backend {jax.default_backend()!r} — nothing is timed on "
                 f"a fallback (the CPU control-plane suites are --suite "
                 f"scale|fleet|migrate|whatif)")

    # Headline runs the int8 projection path by default (ROADMAP S5 judges
    # it); set TONY_BENCH_MATMUL_DTYPE="" to bench pure bf16 as the
    # headline. The bf16 twin below stays in the json so the unquantized
    # path is gated for noise-floor regressions alongside it.
    md = os.environ.get("TONY_BENCH_MATMUL_DTYPE", "int8")
    headline = measure_point(build_flagship_config(2048, md), batch=4,
                             seq=2048, steps=50)
    detail["matmul_dtype_note"] = (
        f"headline matmul-dtype={md or 'bf16'}; flagship_bf16 is the "
        f"unquantized twin (same geometry)")
    try:
        detail["flagship_bf16"] = measure_point(
            build_flagship_config(2048), batch=4, seq=2048, steps=50,
            reps=2)
    except Exception as e:  # noqa: BLE001 — keep the other points
        print(f"# flagship_bf16 point failed: {e}", file=sys.stderr)
        detail["flagship_bf16"] = {"error": str(e)[:300]}

    # Long-context labeled points: chunked cross-entropy training at 8k
    # and 32k on one chip.
    if os.environ.get("TONY_BENCH_EXTRA", "1") != "0":
        # Both points run remat-OFF: they fit (chunked CE removes the
        # logits wall); remat is a fit lever here, not a speed lever. See
        # the big point below for remat under real memory pressure. Batch
        # and loss-chunk sizes come from sweeps on an earlier installation
        # and have not been re-measured on this one.
        for label, seq, batch, steps, chunk in (
                ("longctx_8k_chunked_ce", 8192, 4, 12, 2048),
                ("longctx_32k_chunked_ce", 32768, 1, 8, 8192)):
            try:
                detail[label] = measure_point(
                    build_flagship_config(seq),
                    batch=batch, seq=seq, steps=steps, chunked=True,
                    loss_chunk=chunk, reps=2)
            except Exception as e:  # noqa: BLE001
                print(f"# {label} failed: {e}", file=sys.stderr)
                detail[label] = {"error": str(e)[:300]}
        # The 8×8192 memory-pressure point with SELECTIVE remat
        # (remat_skip_every=2): every 2nd layer keeps its activations.
        try:
            from tony_tpu.models import TransformerConfig
            cfg8 = TransformerConfig(
                vocab_size=32000, dim=1024, n_layers=16, n_heads=8,
                n_kv_heads=4, mlp_dim=4096, max_seq_len=8192, remat=True,
                remat_skip_every=2, attn_block_q=1024, attn_block_k=1024)
            detail["longctx_8k_b8_selective_remat"] = measure_point(
                cfg8, batch=8, seq=8192, steps=8, chunked=True,
                loss_chunk=2048, reps=2)
        except Exception as e:  # noqa: BLE001
            print(f"# 8k selective-remat point failed: {e}",
                  file=sys.stderr)
            detail["longctx_8k_b8_selective_remat"] = {"error": str(e)[:300]}

    # The BASELINE.json NAMED metrics (VERDICT r4 missing #2): MNIST and
    # ResNet-50 samples/sec/chip, measured with the same discipline as the
    # transformer points.
    if os.environ.get("TONY_BENCH_VISION", "1") != "0":
        for label, kind_, batch, steps in (
                ("resnet50_train", "resnet50",
                 int(os.environ.get("TONY_BENCH_RESNET_BATCH", "256")), 8),
                ("mnist_mlp_train", "mnist", 4096, 50)):
            try:
                detail[label] = measure_vision_point(
                    kind_, batch=batch, steps=steps, reps=2)
            except Exception as e:  # noqa: BLE001
                print(f"# {label} failed: {e}", file=sys.stderr)
                detail[label] = {"error": str(e)[:300]}

    # Token-file input path (VERDICT r4 weak #7): the flagship trained
    # from a real mmap corpus through the prefetching iterator — proves
    # the input pipeline keeps pace with the device-synthetic headline.
    if os.environ.get("TONY_BENCH_TOKFILE", "1") != "0":
        try:
            detail["tokenfile_train"] = measure_token_file_point(
                build_flagship_config(2048), batch=4, seq=2048, steps=20,
                reps=2)
            if "error" not in detail["tokenfile_train"]:
                detail["tokenfile_train"]["pct_of_synthetic"] = round(
                    100.0 * detail["tokenfile_train"]["tokens_per_sec"]
                    / headline["tokens_per_sec"], 2)
        except Exception as e:  # noqa: BLE001
            print(f"# tokenfile point failed: {e}", file=sys.stderr)
            detail["tokenfile_train"] = {"error": str(e)[:300]}

    # Stretch (VERDICT r3 #10) — MFU under memory pressure: a ~1.4B model
    # with selective remat + chunked CE, the largest-class single-chip
    # config. Off by default to bound bench wall time.
    if os.environ.get("TONY_BENCH_BIG", "0") == "1":
        import jax.numpy as jnp

        from tony_tpu.models import TransformerConfig

        # Selective remat via remat_skip_every=2
        # (benchmarks/remat_sweep.py): every 2nd layer keeps its
        # activations.
        big = TransformerConfig(
            vocab_size=32000, dim=1536, n_layers=24, n_heads=12,
            n_kv_heads=6, mlp_dim=6144, max_seq_len=2048, remat=True,
            remat_skip_every=2, attn_block_q=1024, attn_block_k=1024)
        try:
            detail["big_0p95b_remat_bf16mu"] = measure_point(
                big, batch=4, seq=2048, steps=12, chunked=True,
                loss_chunk=1024, reps=2, mu_dtype=jnp.bfloat16)
        except Exception as e:  # noqa: BLE001
            print(f"# big point failed: {e}", file=sys.stderr)
            detail["big_0p95b_remat_bf16mu"] = {"error": str(e)[:300]}

    # Steady-state phase-attribution probe (any backend): the per-phase
    # seconds/step the regression gate diffs alongside the headline.
    if os.environ.get("TONY_BENCH_PHASES", "1") != "0":
        try:
            detail["phase_probe"] = measure_phase_point()
        except Exception as e:  # noqa: BLE001 — keep the other points
            print(f"# phase probe failed: {e}", file=sys.stderr)
            detail["phase_probe"] = {"error": str(e)[:300]}

    detail.update({
        "params": headline["params"], "batch": headline["batch"],
        "seq": headline["seq"], "backend": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "device_count": len(jax.devices()), "loss": headline["loss"],
        "mfu_vs_peak_bf16": headline["mfu_vs_peak_bf16"],
    })
    doc = {
        "metric": "transformer_train_tokens_per_sec_per_chip",
        "value": headline["tokens_per_sec"],
        "unit": "tokens/s",
        "vs_baseline": None,
        "detail": detail,
    }
    _emit(doc, args)


if __name__ == "__main__":
    main()
