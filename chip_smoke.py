#!/usr/bin/env python3
"""Chip smoke: does the system still start on the TPU?  (A smoke, not a
benchmark: nothing it prints is a performance claim.)

``python chip_smoke.py`` — no arguments, no environment — drives the path a
user's job takes, once:

    tony-tpu submit → coordinator → local backend → executor → user process
    → build_mesh → init_sharded_state → jit_train_step (flash attention,
    full remat, chunked loss, AdamW) inside telemetry.step

at the published widths of Llama-3-8B (``TransformerConfig.llama3_8b``: dim
4096, 32 q / 8 kv heads of 128, mlp 14336, rope 500000), weights random from
a seed. The cuts, none of them a width:

- depth: 2 layers of the 32;
- vocabulary rows: 128,256 × devices / 4 — the share of a four-chip host
  (32,064 on one chip, all 128,256 on four; the full table with Adam state
  is 16.8 GB and cannot fit one 16 GB chip);
- sequence 8192; global batch 2 on one chip, 4 on four (``fsdp=2,tp=2``).

Two processes, one chip owner. The PARENT (this mode) never imports jax —
a process that touches JAX holds the chip, and the job's worker needs it.
It submits one ``worker`` whose command is this file's ``--worker`` mode,
waits, and judges the job from its artifacts alone. The WORKER is the only
process that touches JAX; it gets ``JAX_PLATFORMS=tpu`` so that a chip that
fails to initialise is an error inside JAX, never a quiet CPU run.

The default invocation FAILS without a TPU (and in a directory that holds
nothing else of the repo). ``--cpu-rehearsal`` is the explicit opt-in that
walks the same plumbing on four virtual CPU devices at tiny widths with the
kernels in interpret mode; it says so in its output and proves nothing
about a chip.

On success the last two lines of stdout are one JSON line of details
(labelled a smoke) and the verdict
``{"ok": true, "device": {"platform", "kind", "count"}}``. On any failure
nothing is printed to stdout and the exit code is 1. Artifacts
(coordinator.log, the worker's stdout/stderr, the jhist, the span log, the
JSON) go to ``chiprun_out/chip_smoke/<run>/``.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import re
import shutil
import signal
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))
RUN_MARKER_ENV = "CHIP_SMOKE_RUN"      # tags every process of one run
JOB_TIMEOUT_S = 900                    # the coordinator's own limit
WAIT_TIMEOUT_S = 1000                  # ours, inside the contract's 1200 s
MIN_STEPS_AFTER_COMPILE = 5

# Kernel check tolerances. Inputs are bf16 and the reference is float32
# arithmetic on the same values. The kernels round three times on the way:
# q·scale to bf16, the probabilities to bf16 for the p·v matmul, and the
# output to bf16 — each 2^-9 relative — and accumulate in f32. On the CPU
# interpreter that measures ≤ 0.006 (max) and ≤ 0.003 (mean), normalised
# as below. The bounds leave 2-3x headroom over that and no more: softmax
# statistics kept in bf16, or a lost f32 accumulator, is a ≥ 2^-5 error.
KERNEL_MAX_ERR = 2.0 ** -6     # max|got-want| / max|want|
KERNEL_MEAN_ERR = 2.0 ** -7    # mean|got-want| / mean|want|


class SmokeFailure(Exception):
    """An assertion of the smoke did not hold."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# ---------------------------------------------------------------------------
# Compiled-HLO evidence (pure text; the tier-1 ahead-of-time test uses it too)
# ---------------------------------------------------------------------------
def _balanced(text: str, open_ch: str, close_ch: str) -> int:
    """Index just past the bracket that closes ``text[0]``."""
    depth = 0
    for i, ch in enumerate(text):
        depth += (ch == open_ch) - (ch == close_ch)
        if depth == 0:
            return i + 1
    return len(text)


def _opcode(rest: str) -> str:
    """Opcode of an HLO instruction given the text after ``%name = ``."""
    if rest.startswith("("):                 # tuple type: skip to its close
        rest = rest[_balanced(rest, "(", ")"):]
    else:
        rest = rest.partition(" ")[2]
    return rest.strip().partition("(")[0]


def kernel_operands(hlo_text: str) -> list:
    """One entry per Mosaic kernel in a compiled module:
    ``{"name", "shapes": [[dims...]...], "producers": [opcode...]}`` — the
    operand shapes as the kernel sees them (per-shard, if it was mapped)
    and the opcode that produces each operand."""
    opcodes, calls = {}, []
    for line in hlo_text.splitlines():
        m = re.match(r"\s*(?:ROOT\s+)?%?([\w.\-]+) = (.*)", line)
        if not m:
            continue
        opcodes[m.group(1)] = _opcode(m.group(2))
        if 'custom_call_target="tpu_custom_call"' in line:
            calls.append((m.group(1), line))
    out = []
    for name, line in calls:
        args = line.partition("custom-call(")[2].partition(")")[0]
        operands = re.findall(r"%([\w.\-]+)", args)
        layout = "{" + line.partition("operand_layout_constraints={")[2]
        layout = layout[:_balanced(layout, "{", "}")]
        shapes = [[int(d) for d in dims.split(",")]
                  for dims in re.findall(r"\w+\[([\d,]+)\]", layout)]
        out.append({"name": name, "shapes": shapes,
                    "producers": [opcodes.get(o, "?") for o in operands]})
    return out


def check_kernels_per_shard(hlo_text: str, q_shard: tuple,
                            kv_shard: tuple, min_calls: int) -> dict:
    """Every Mosaic kernel of the step runs on per-shard q/k/v, and no
    all-gather feeds one."""
    calls = kernel_operands(hlo_text)
    check(len(calls) >= min_calls,
          f"expected >= {min_calls} tpu_custom_calls in the compiled step, "
          f"found {len(calls)}")
    for c in calls:
        check(list(q_shard) in c["shapes"] and list(kv_shard) in c["shapes"],
              f"kernel {c['name']} does not see per-shard q {q_shard} / kv "
              f"{kv_shard}: operands {c['shapes']}")
        gathered = [p for p in c["producers"] if p.startswith("all-gather")]
        check(not gathered,
              f"kernel {c['name']} is fed by {gathered}: {c['producers']}")
    return {"tpu_custom_calls": len(calls), "q_shard": list(q_shard),
            "kv_shard": list(kv_shard)}


# ---------------------------------------------------------------------------
# Worker: the one process that touches JAX
# ---------------------------------------------------------------------------
def _kernel_check(seq: int, heads: int, kv_heads: int) -> dict:
    """Flash forward and gradients against the float32 reference, on the
    device, at head_dim 128 with bf16 inputs."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tony_tpu.ops.attention import flash_attention, reference_attention

    d, g = 128, heads // kv_heads
    kq, kk, kv, kw = jax.random.split(jax.random.key(0), 4)
    q = jax.random.normal(kq, (1, seq, heads, d), jnp.bfloat16)
    k = jax.random.normal(kk, (1, seq, kv_heads, d), jnp.bfloat16)
    v = jax.random.normal(kv, (1, seq, kv_heads, d), jnp.bfloat16)
    w = jax.random.normal(kw, (1, seq, heads, d), jnp.float32)

    def flash(q, k, v):
        o = flash_attention(q, k, v, causal=True)
        return jnp.sum(o.astype(jnp.float32) * w), o

    def reference(q, k, v):
        o = reference_attention(q, jnp.repeat(k, g, axis=2),
                                jnp.repeat(v, g, axis=2), causal=True)
        return jnp.sum(o * w), o

    def grad(f):
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2),
                                          has_aux=True))

    (_, o), grads = grad(flash)(q, k, v)
    # float32 copies of the same bf16 values: reference_attention then asks
    # for HIGHEST itself, and the context covers every other op.
    with jax.default_matmul_precision("highest"):
        (_, o_ref), grads_ref = grad(reference)(
            *(x.astype(jnp.float32) for x in (q, k, v)))
    errs = {}
    for name, got, want in zip(("o", "dq", "dk", "dv"), (o, *grads),
                               (o_ref, *grads_ref)):
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        check(got.shape == want.shape and bool(np.isfinite(got).all()),
              f"flash {name}: shape {got.shape} vs {want.shape}, or "
              f"non-finite values")
        diff = np.abs(got - want)
        e_max = float(diff.max() / np.abs(want).max())
        e_mean = float(diff.mean() / np.abs(want).mean())
        errs[name] = {"max": round(e_max, 5), "mean": round(e_mean, 5)}
        check(e_max <= KERNEL_MAX_ERR and e_mean <= KERNEL_MEAN_ERR,
              f"flash {name} disagrees with the reference: max {e_max:.4g} "
              f"(bound {KERNEL_MAX_ERR:.4g}), mean {e_mean:.4g} (bound "
              f"{KERNEL_MEAN_ERR:.4g})")
    return {"seq": seq, "heads": heads, "kv_heads": kv_heads, "head_dim": d,
            "rel_err": errs,
            "bounds": {"max": KERNEL_MAX_ERR, "mean": KERNEL_MEAN_ERR}}


def worker_main(result_path: str, rehearsal: bool) -> None:
    t_start = time.time()
    import collections

    import jax
    import jaxlib
    import optax

    import tony_tpu  # noqa: F401 — starts the telemetry reporter
    from tony_tpu import constants, telemetry
    from tony_tpu.models import Transformer, TransformerConfig
    from tony_tpu.models.transformer import chunked_causal_lm_loss
    from tony_tpu.ops import attention
    from tony_tpu.parallel import (MeshSpec, build_mesh, init_sharded_state,
                                   jit_train_step)

    phases = {}                 # where the worker's wall time went

    def lap(name: str, since: float) -> float:
        now = time.time()
        phases[name] = round(now - since, 1)
        return now

    devices = jax.devices()
    n = len(devices)
    dev0 = devices[0]
    t = lap("import_and_device_init", t_start)
    if rehearsal:
        check(dev0.platform == "cpu",
              f"rehearsal wants the CPU, found {dev0.platform!r}")
    else:
        # Before anything else: this is a TPU the repo knows, and the
        # kernels will be compiled, not interpreted.
        check(dev0.platform == "tpu",
              f"no TPU: jax.devices()[0].platform == {dev0.platform!r}")
        telemetry.peak_bf16_flops(dev0.device_kind)   # raises on a miss
        check(attention._interpret() is False,
              "flash kernels would run in Pallas interpret mode")
    check(n in (1, 4), f"the smoke knows 1 device or 4, found {n}")
    spec = MeshSpec(dp=1) if n == 1 else MeshSpec(dp=1, fsdp=2, tp=2)

    # Compile-cache accounting from jax's own monitoring events.
    events: collections.Counter = collections.Counter()
    durations: collections.Counter = collections.Counter()
    jax.monitoring.register_event_listener(
        lambda e, **kw: events.update([e]))
    jax.monitoring.register_event_duration_secs_listener(
        lambda e, d, **kw: durations.update({e: d}))
    cache_dir = os.environ.get(constants.JAX_COMPILATION_CACHE_DIR, "")
    check(bool(cache_dir), "JAX_COMPILATION_CACHE_DIR did not reach the task")

    # Phase 1 — the compiled kernels give the right numbers.
    kernel = _kernel_check(256, 4, 2) if rehearsal else \
        _kernel_check(2048, 32, 8)
    t = lap("kernel_check", t)

    # Phase 2 — train through the library path.
    if rehearsal:
        seq, batch, full_vocab, chunk = 256, n, 1024, 128
        cfg = TransformerConfig.tiny(
            n_layers=2, vocab_size=full_vocab * n // 4, max_seq_len=seq,
            attn_impl="flash", remat=True)
    else:
        seq, batch, full_vocab, chunk = 8192, 2 if n == 1 else 4, 128256, \
            2048
        cfg = TransformerConfig.llama3_8b(
            n_layers=2, vocab_size=full_vocab * n // 4, max_seq_len=seq,
            attn_impl="flash", remat=True, remat_policy=None)
    mesh = build_mesh(spec)
    model = Transformer(cfg)
    tokens = jax.random.randint(jax.random.key(1), (batch, seq), 0,
                                cfg.vocab_size)
    state, state_sh = init_sharded_state(
        model, tokens, optax.adamw(3e-4, weight_decay=0.1), mesh,
        rng=jax.random.key(0))
    n_params = sum(x.size for x in jax.tree.leaves(state.params))
    jax.block_until_ready(state)
    t = lap("init_sharded_state", t)

    def loss_fn(params, batch, rng):
        h = model.apply({"params": params}, batch, return_hidden=True)
        return chunked_causal_lm_loss(
            h, params["lm_head"]["kernel"], batch, chunk_size=chunk,
            head_dtype=cfg.lm_head_dtype), {}

    step = jit_train_step(loss_fn, mesh, state_sh, tokens)
    rng = jax.random.key(2)
    flops = 6 * n_params * batch * seq \
        + 12 * cfg.n_layers * cfg.dim * seq // 2 * batch * seq
    events.clear()          # count the step's own compile from here
    durations.clear()
    losses, step_s = [], []
    for _ in range(1 + MIN_STEPS_AFTER_COMPILE):
        t0 = time.perf_counter()
        with telemetry.step(flops=flops, tokens=batch * seq):
            state, metrics = step(state, tokens, rng)
            jax.block_until_ready(metrics["loss"])
        step_s.append(round(time.perf_counter() - t0, 4))
        losses.append(float(metrics["loss"]))
    t = lap("train_steps", t)
    hits = events["/jax/compilation_cache/cache_hits"]
    misses = events["/jax/compilation_cache/cache_misses"]
    compile_s = durations["/jax/core/compile/backend_compile_duration"]

    check(all(math.isfinite(x) for x in losses), f"non-finite loss: {losses}")
    # The head is lecun-normal over an RMS-normed hidden state: logits of
    # unit variance, so the untrained loss is ln(V) + 1/2, not ln(V)
    # (on the v5e: +0.52 at 32,064 rows, +0.50 at 128,256; PERF.md).
    expected0 = math.log(cfg.vocab_size) + 0.5
    check(abs(losses[0] - expected0) < 0.25,
          f"step-0 loss {losses[0]:.4f} is not the untrained "
          f"ln({cfg.vocab_size}) + 0.5 = {expected0:.4f}")
    check(losses[-1] < losses[0],
          f"loss did not fall on one fixed batch: {losses}")

    # Where the state lives, and what the chip says it holds.
    leaves = jax.tree.leaves((state.params, state.opt_state))
    mem = [d.memory_stats() or {} for d in devices]
    in_use = [int(m.get("bytes_in_use", 0)) for m in mem]
    peak = [int(m.get("peak_bytes_in_use", 0)) for m in mem]
    if n == 4:
        for x in leaves:
            check(len(x.sharding.device_set) == 4,
                  f"a state leaf {x.shape} is not on all four devices")
            if x.ndim >= 2:     # every matrix: all but norm scales + count
                shard = x.addressable_shards[0].data
                check(shard.size * 4 == x.size,
                      f"leaf {x.shape} holds {shard.shape} a device, not a "
                      f"quarter")
        if not rehearsal:
            check(min(in_use) > 0 and max(in_use) <= 1.25 * min(in_use),
                  f"bytes_in_use uneven or zero across devices: {in_use}")
    if not rehearsal:
        check(min(peak) > 0, f"memory_stats() reports no peak HBM: {peak}")

    # The compiled step itself (a cache hit by now): kernel operands at
    # per-shard shapes, and its memory.
    compiled = step.lower(state, tokens, rng).compile()
    head_dim = cfg.dim // cfg.n_heads
    batch_ways, tp = mesh.shape["fsdp"], mesh.shape["tp"]
    hlo = None
    if not rehearsal:      # interpreted kernels leave no custom call
        hlo = check_kernels_per_shard(
            compiled.as_text(),
            (batch // batch_ways, cfg.n_heads // tp, seq, head_dim),
            (batch // batch_ways, cfg.n_kv_heads // tp, seq, head_dim),
            min_calls=3 * cfg.n_layers)
    ma = compiled.memory_analysis()
    compiled_bytes = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                      - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    lap("compiled_step_evidence", t)

    try:
        import libtpu
        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = None
    result = {
        "ok": True,
        "rehearsal": rehearsal,
        "device": {"platform": dev0.platform, "kind": dev0.device_kind,
                   "count": n},
        "versions": {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                     "libtpu": libtpu_version,
                     "python": sys.version.split()[0]},
        "interpret": attention._interpret(),
        "mesh": {a: s for a, s in mesh.shape.items() if s > 1},
        "model": {"dim": cfg.dim, "n_heads": cfg.n_heads,
                  "n_kv_heads": cfg.n_kv_heads, "head_dim": head_dim,
                  "mlp_dim": cfg.mlp_dim, "n_layers": cfg.n_layers,
                  "vocab_rows": cfg.vocab_size, "params": int(n_params),
                  "batch": batch, "seq": seq},
        "kernel_check": kernel,
        "compile_cache": {"dir": cache_dir, "warm": hits > 0 and not misses,
                          "step_cache_hits": hits,
                          "step_cache_misses": misses},
        "compile_s": round(compile_s, 2),
        "step_s": step_s,
        "losses": [round(x, 4) for x in losses],
        "loss0_minus_ln_vocab": round(losses[0] - math.log(cfg.vocab_size),
                                      4),
        "hbm_bytes_in_use": in_use,
        "hbm_peak_bytes": peak,
        "compiled_step_bytes_per_device": int(compiled_bytes),
        "hlo": hlo,
        "worker_phases_s": phases,
        "worker_wall_s": round(time.time() - t_start, 1),
    }
    # One deterministic snapshot for the executor's monitor (the reporter
    # thread's own cadence is 3 s), then the result for the parent.
    check(telemetry.write_stats_once(os.environ[constants.METRICS_FILE]),
          "telemetry wrote no stats snapshot")
    with open(result_path + ".tmp", "w", encoding="utf-8") as f:
        json.dump(result, f)
    os.replace(result_path + ".tmp", result_path)
    print("chip_smoke worker:", json.dumps(result))


# ---------------------------------------------------------------------------
# Parent: never imports jax
# ---------------------------------------------------------------------------
def _stop_run(marker: str) -> list:
    """SIGKILL every process that still carries this run's marker in its
    environment (the job's coordinator, executor and worker inherit it).
    Returns the pids found — after a clean job there are none."""
    needle = f"{RUN_MARKER_ENV}={marker}".encode()
    found = []
    for pid in filter(str.isdigit, os.listdir("/proc")):
        if int(pid) == os.getpid():
            continue
        try:
            with open(f"/proc/{pid}/environ", "rb") as f:
                if needle not in f.read().split(b"\0"):
                    continue
            os.kill(int(pid), signal.SIGKILL)
            found.append(int(pid))
        except (OSError, ValueError):
            continue
    return found


def _submit(cmd: list, env: dict, log_path: str) -> int:
    """Run ``tony-tpu submit`` to its end. On our own time limit, TERM it —
    the CLI's handler kills the application — then KILL what is left."""
    with open(log_path, "wb") as log:
        proc = subprocess.Popen(cmd, env=env, cwd=REPO, stdout=log,
                                stderr=subprocess.STDOUT)
        try:
            return proc.wait(timeout=WAIT_TIMEOUT_S)
        except subprocess.TimeoutExpired:
            proc.terminate()
            try:
                proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()
            raise SmokeFailure(
                f"submit did not finish in {WAIT_TIMEOUT_S} s") from None


def _tail(path: str, n: int = 40) -> str:
    try:
        with open(path, errors="replace") as f:
            return "".join(f.readlines()[-n:])
    except OSError as e:
        return f"<{e}>"


def _keep(artifacts: dict, out_dir: str) -> None:
    for name, path in artifacts.items():
        try:
            shutil.copyfile(path, os.path.join(out_dir, name))
        except OSError:
            pass        # a failed job may not have written all of them


def parent_main(rehearsal: bool) -> int:
    marker = f"{int(time.time())}-{os.getpid()}"
    out_dir = os.path.join(REPO, "chiprun_out", "chip_smoke", marker)
    os.makedirs(out_dir, exist_ok=True)
    run_dir = tempfile.mkdtemp(prefix="chip-smoke-")   # work dirs + history
    result_path = os.path.join(run_dir, "worker-result.json")
    history = os.path.join(run_dir, "history")
    submit_log = os.path.join(out_dir, "submit.log")

    task_env = "JAX_PLATFORMS=tpu"
    if rehearsal:
        task_env = ("JAX_PLATFORMS=cpu,"
                    "XLA_FLAGS=--xla_force_host_platform_device_count=4")
    worker_cmd = f"{sys.executable} {os.path.abspath(__file__)} --worker " \
                 f"--result {result_path}" \
                 + (" --cpu-rehearsal" if rehearsal else "")
    cmd = [sys.executable, "-m", "tony_tpu.cli", "submit",
           "--conf", "tony.worker.instances=1",
           "--conf", f"tony.worker.command={worker_cmd}",
           "--conf", f"tony.application.execution-env={task_env}",
           "--conf", f"tony.application.timeout-s={JOB_TIMEOUT_S}",
           "--conf", f"tony.history.location={history}",
           "--workdir", os.path.join(run_dir, "work")]
    env = dict(os.environ)
    env[RUN_MARKER_ENV] = marker
    env["PYTHONPATH"] = (REPO + os.pathsep
                         + env.get("PYTHONPATH", "")).rstrip(os.pathsep)
    # The compile cache is placed from outside if the environment says
    # where (the executor lets it win); otherwise at a fixed git-ignored
    # path in the checkout. Work dirs and history are temporary; the cache
    # never is — its path is part of every key.
    if not env.get("JAX_COMPILATION_CACHE_DIR"):
        cmd += ["--conf", "tony.jax.compilation-cache-dir="
                + os.path.join(REPO, ".jax_cache")]

    artifacts = {}
    try:
        t0 = time.time()
        rc = _submit(cmd, env, submit_log)
        submit_wall = time.time() - t0

        from tony_tpu import constants, tracing
        from tony_tpu.events import history as tony_history

        jobs = tony_history.list_job_dirs(history)
        check(len(jobs) == 1, f"expected one job under {history}: {jobs}")
        (app, job_dir), = jobs.items()
        client_dir = os.path.join(run_dir, "work", "jobs", app)
        task_dir = os.path.join(client_dir, "tasks", "worker_0")
        artifacts = {
            "coordinator.log": os.path.join(client_dir, "coordinator.log"),
            "worker.stdout.log": os.path.join(task_dir, "stdout.log"),
            "worker.stderr.log": os.path.join(task_dir, "stderr.log"),
            "user-metrics.json": os.path.join(task_dir,
                                              "user-metrics.json"),
            constants.TRACE_FILE: os.path.join(job_dir,
                                               constants.TRACE_FILE),
        }
        for name in os.listdir(job_dir):
            if name.endswith(".jhist.jsonl"):
                artifacts[name] = os.path.join(job_dir, name)

        check(rc == 0, f"tony-tpu submit exited {rc}")
        check(any(n.endswith("-SUCCEEDED.jhist.jsonl") for n in artifacts),
              f"no *-SUCCEEDED.jhist.jsonl in {job_dir}")
        inv = subprocess.run(
            [sys.executable, "-m", "tony_tpu.devtools.invariants", job_dir],
            env=env, cwd=REPO, capture_output=True, text=True, timeout=120)
        check(inv.returncode == 0,
              f"invariants not clean:\n{inv.stdout}\n{inv.stderr}")

        records = tracing.load_records(artifacts[constants.TRACE_FILE])
        payload = tracing.to_trace_events(records)
        closed = {e["name"] for e in payload["traceEvents"]
                  if e.get("ph") == "X"}
        check("executor.first_step" in closed
              and not payload["unclosedSpans"],
              f"no closed executor.first_step span (closed: "
              f"{sorted(closed)}; unclosed: {payload['unclosedSpans']})")
        cold = tracing.cold_start_breakdown(records)

        with open(result_path, encoding="utf-8") as f:
            worker = json.load(f)
        check(worker.get("ok") is True, f"worker result not ok: {worker}")
        check(worker["rehearsal"] == rehearsal, "worker ran the other mode")
        with open(artifacts["user-metrics.json"], encoding="utf-8") as f:
            metrics = json.load(f)
        check(int(metrics.get("device_count", 0))
              == worker["device"]["count"],
              f"user-metrics.json device_count "
              f"{metrics.get('device_count')} != devices found "
              f"{worker['device']['count']}")
        if not rehearsal:       # the CPU backend reports no memory stats
            check(float(metrics.get("hbm_bytes_in_use", 0)) > 0,
                  "user-metrics.json hbm_bytes_in_use is 0")
            check(worker["device"]["platform"] == "tpu", "not a TPU run")

        leftover = _stop_run(marker)
        check(not leftover, f"processes outlived the job: {leftover}")
        check("jax" not in sys.modules, "the parent imported jax")
    except Exception as e:  # noqa: BLE001 — every failure: report, exit 1
        _stop_run(marker)
        print(f"chip_smoke FAILED: {type(e).__name__}: {e}", file=sys.stderr)
        for name in ("worker.stderr.log", "coordinator.log"):
            if name in artifacts:
                print(f"--- tail of {name}\n{_tail(artifacts[name])}",
                      file=sys.stderr)
        print(f"--- tail of submit.log\n{_tail(submit_log)}\n"
              f"artifacts kept in {out_dir}", file=sys.stderr)
        _keep(artifacts, out_dir)
        shutil.rmtree(run_dir, ignore_errors=True)
        return 1

    detail = {
        "what": ("CPU REHEARSAL of the chip smoke: tiny widths, virtual "
                 "devices, kernels in interpret mode — says nothing about "
                 "a chip") if rehearsal else
                "chip smoke (not a benchmark; no number here is a "
                "performance claim)",
        **worker,
        "submit_to_first_step_s": cold["total_s"],
        "cold_start_phases_s": cold["phases"],
        "submit_wall_s": round(submit_wall, 1),
        "invariants": inv.stdout.strip().splitlines(),
        "user_metrics": {k: metrics.get(k) for k in (
            "device_count", "hbm_bytes_in_use", "steps_completed")},
        "artifacts": os.path.relpath(out_dir, REPO),
    }
    with open(os.path.join(out_dir, "chip_smoke.json"), "w",
              encoding="utf-8") as f:
        json.dump(detail, f, indent=1)
        f.write("\n")
    _keep(artifacts, out_dir)
    shutil.rmtree(run_dir, ignore_errors=True)
    print(json.dumps(detail))
    verdict = {"ok": True, "device": worker["device"]}
    if rehearsal:
        verdict["rehearsal"] = True
    print(json.dumps(verdict))
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="walk the plumbing on 4 virtual CPU devices at "
                         "tiny widths (interpret-mode kernels); proves "
                         "nothing about a chip")
    ap.add_argument("--worker", action="store_true",
                    help="internal: the job's user process")
    ap.add_argument("--result", help="internal: worker result path")
    args = ap.parse_args(argv)
    if args.worker:
        sys.path.insert(0, REPO)
        worker_main(args.result, args.cpu_rehearsal)   # raises on failure
        return 0
    return parent_main(args.cpu_rehearsal)


if __name__ == "__main__":
    sys.exit(main())
