#!/usr/bin/env python3
"""The benchmark's training job: the user process that the executor spawns.

The shape of ``examples/llama3-8b/train_llama3.py``: ``build_mesh`` →
``init_sharded_state`` → ``jit_train_step`` inside ``telemetry.step``, batches
from ``data.synthetic_lm_batches`` through the prefetching
``ShardedBatchIterator``. No private loop, no scan over steps. The sizes come
from the cell's configuration file and traffic file, the weights and ids from
``--seed``.

What the model is comes from the configuration's architecture
(``--architecture``, a directory that ``arch.find`` gave the parent): its
``program.py`` builds the program's model and the loss handed to
``jit_train_step``, its ``reference.py`` says the leaves and the plain loss,
its ``counts.py`` the operations a token costs. No architecture, model class
or parameter is named in this code, and none chooses how it is timed or
judged: the mesh, the optimizer, the feed, the weights from the seed, the
step, the window, the trace, the memory reading and the comparison are here.

One object, the compiled step with its state, is built once. Set-up drives it
through its first steps (which also warm it up) and reads what ``correct`` is
decided on; the window then drives that same object with the same call and
feed. After the window has closed, the memory has been read and the state is
freed, the plain reference follows the same first steps from the seed and the
two are compared (``reference.compare``).

This process is the only one of a run that touches JAX. It fails where JAX
finds no TPU, or fewer chips than the cell asks for, unless ``--rehearsal``
says that a CPU walk-through is wanted (tiny sizes, interpreted kernels: it
proves nothing about a chip and its result says so).
"""

from __future__ import annotations

import argparse
import collections
import gc
import glob
import json
import math
import os
import shutil
import statistics
import sys
import time

T_PROCESS_START = time.time()
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

TRACE_MIN_STEPS = 3
TRACE_MIN_SECONDS = 2.5
TRACE_MAX_STEPS = 8


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--architecture", required=True,
                    help="the directory arch.find gives for --config")
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--limits", required=True)
    ap.add_argument("--chips", type=int, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--out", required=True)
    ap.add_argument("--rehearsal", action="store_true")
    # The control of "How correct is decided": the program's own quantized
    # matmul path switched on. Never set by a benchmark run.
    ap.add_argument("--control", default="",
                    choices=("", "int8", "fp8_e4m3"))
    return ap.parse_args(argv)


def load_json(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def optimizer_settings(cfg: dict) -> dict:
    return cfg["train"]["adamw"]


def adam_moments(opt_state):
    """The ScaleByAdamState inside optax.adamw's chain."""
    import jax

    found = [s for s in jax.tree.leaves(
        opt_state, is_leaf=lambda x: hasattr(x, "mu")) if hasattr(s, "mu")]
    if len(found) != 1:
        raise RuntimeError(f"expected one Adam state, found {len(found)}")
    return found[0]


class Cell:
    """The compiled step with its state, its feed, and the one call that
    both set-up and the window make."""

    def __init__(self, opts, cfg, traffic, break_step=None):
        import jax
        import jax.numpy as jnp
        import optax

        import tony_tpu  # noqa: F401 — starts the telemetry reporter
        from tony_tpu import data, telemetry
        from tony_tpu.parallel import (MeshSpec, build_mesh,
                                       init_sharded_state, jit_train_step)

        import arch
        import reference

        self.jax, self.telemetry, self.reference = jax, telemetry, reference
        self.opts, self.cfg, self.traffic = opts, cfg, traffic
        self.batch, self.seq = traffic["global_batch"], traffic["seq"]
        self.tokens_per_step = self.batch * self.seq
        # All that the architecture has a say in: its leaves and plain loss,
        # the operations a token costs, the model and the loss's wiring. How
        # the step is built, fed, timed and judged is the same for every one.
        self.model_ref = arch.load(opts.architecture, "reference")
        self.flops_per_step = arch.load(
            opts.architecture, "counts").model_flops_per_token(
                cfg, self.seq) * self.tokens_per_step
        model, loss_fn = arch.load(opts.architecture, "program").build(
            cfg, traffic, opts.control)
        devices = jax.devices()[:opts.chips]
        self.mesh = build_mesh(MeshSpec.from_string(traffic["mesh"]),
                               devices=devices)
        opt = optimizer_settings(cfg)
        tx = optax.adamw(opt["learning_rate"], b1=opt["b1"], b2=opt["b2"],
                         eps=opt["eps"], weight_decay=opt["weight_decay"])
        self.batches = data.synthetic_lm_batches(
            self.mesh, self.batch, self.seq, cfg["vocab_size"],
            seed=opts.seed)
        sample = {"tokens": jnp.zeros((self.batch, self.seq), jnp.int32)}
        t0 = time.time()
        state, state_sh = init_sharded_state(
            model, sample["tokens"], tx, self.mesh,
            rng=jax.random.key(opts.seed & 0x7FFFFFFF))
        jax.block_until_ready(state)
        self.init_state_s = time.time() - t0
        # The weights are the benchmark's, made on the device from the seed
        # in one call (reference.make_params), so that the reference can make
        # the same ones without taking anything from the program.
        jax.tree.map(lambda x: x.delete(), state.params)
        params = jax.jit(
            lambda key: reference.make_params(self.model_ref, cfg, key),
            out_shardings=state_sh.params)(reference.seed_key(opts.seed))
        self.state = state.replace(params=params)
        self.step = jit_train_step(loss_fn, self.mesh, state_sh, sample)
        if break_step is not None:        # the tests' planted faults
            self.step = break_step(self.step)
        self.rng = jax.random.key(1)
        self.last_batch = None

    def one_step(self):
        """One whole step: feed, dispatch, wait. Returns (loss on the device,
        seconds in next(batches), seconds in all)."""
        jax = self.jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.next_batch"):
            batch = next(self.batches)
        t1 = time.perf_counter()
        with self.telemetry.step(flops=self.flops_per_step,
                                 tokens=self.tokens_per_step):
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                self.state, metrics = self.step(self.state, batch, self.rng)
            with jax.profiler.TraceAnnotation("bench.wait"):
                jax.block_until_ready(metrics["loss"])
        t2 = time.perf_counter()
        self.last_batch = batch
        return metrics["loss"], t1 - t0, t2 - t0

    def first_steps(self, steps: int) -> dict:
        """Set-up's part: the first ``steps`` steps through ``one_step`` (as
        many as the reference follows; they also warm the step up), and the
        readings the reference is compared with."""
        import numpy as np

        ref, opt = self.reference, optimizer_settings(self.cfg)
        out = {"losses": [], "feed_mismatch": 0, "step_s": []}
        for i in range(steps):
            loss, _, dt = self.one_step()
            out["losses"].append(float(loss))
            out["step_s"].append(dt)
            fed = np.asarray(self.last_batch["tokens"])
            want = ref.token_rows(self.opts.seed, i, self.batch, self.seq,
                                  self.cfg["vocab_size"])
            out["feed_mismatch"] += int((fed != want).sum())
            if i == 0:
                t0 = out["first_step_done_wall"] = time.time()
                # mu_1 = (1 - b1) g_1: the first gradient as Adam got it.
                mu = ref.flat(adam_moments(self.state.opt_state).mu)
                out["grad_norms"] = (
                    ref.leaf_norms(mu) / (1 - opt["b1"])).tolist()
                out["grad_sample"] = [
                    [v / (1 - opt["b1"]) for v in leaf]
                    for leaf in ref.sample_entries(self.opts.seed, mu)]
                out["read_grad_s"] = time.time() - t0
        t0 = time.time()
        out["change_norms"] = ref.change_norms(
            self.model_ref, self.cfg, self.opts.seed,
            ref.flat(self.state.params)).tolist()
        out["read_change_s"] = time.time() - t0
        return out

    def compiled_bytes_per_device(self) -> int:
        compiled = self.step.lower(self.state, self.last_batch,
                                   self.rng).compile()
        ma = compiled.memory_analysis()
        return int(ma.argument_size_in_bytes + ma.output_size_in_bytes
                   - ma.alias_size_in_bytes + ma.temp_size_in_bytes)

    def free(self) -> None:
        self.batches.close()
        self.jax.tree.map(lambda x: x.delete(), self.state)
        self.state = self.last_batch = None


def trace_steps(cell: Cell, step_s: float, out_dir: str) -> dict:
    """A few whole steps under the profiler, after the window has closed."""
    import jax

    import trace_reduce

    n = min(TRACE_MAX_STEPS,
            max(TRACE_MIN_STEPS, math.ceil(TRACE_MIN_SECONDS / step_s)))
    trace_dir = os.path.join(out_dir, "trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        for _ in range(n):
            cell.one_step()
    finally:
        jax.profiler.stop_trace()
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(paths) != 1:
        raise RuntimeError(f"expected one xplane file, found {paths}")
    trace_bytes = os.path.getsize(paths[0])
    reduced = trace_reduce.reduce_trace(trace_reduce.load_xplane(paths[0]))
    shutil.rmtree(trace_dir, ignore_errors=True)    # write little to disk
    reduced["steps"] = n
    reduced["xplane_bytes"] = trace_bytes
    return reduced


def high_percentile(values: list) -> tuple:
    """(the 95th percentile, or the highest value that still has a sample
    beyond it; how many samples lie beyond it)."""
    ordered = sorted(values)
    i = max(0, min(int(0.95 * len(ordered)), len(ordered) - 2))
    return ordered[i], len(ordered) - 1 - i


def run_cell(opts, break_step=None) -> dict:
    """Build, warm up, measure, read memory, free, follow the reference,
    compare. Returns the result the parent reads."""
    cfg, traffic = load_json(opts.config), load_json(opts.traffic)
    held = load_json(opts.limits)
    import jax

    import arch
    import counts
    import reference

    phases = {"imports_s": time.time() - T_PROCESS_START}
    devices = jax.devices()
    dev0 = devices[0]
    if opts.rehearsal:
        if dev0.platform != "cpu":
            raise RuntimeError("a rehearsal wants the CPU")
    else:
        if dev0.platform != "tpu":
            raise RuntimeError(f"no TPU: platform is {dev0.platform!r}")
        counts.peaks(dev0.device_kind)            # a miss is an error
    if len(devices) < opts.chips:
        raise RuntimeError(f"the cell asks for {opts.chips} chips, JAX "
                           f"finds {len(devices)}")
    devices = devices[:opts.chips]
    phases["devices_s"] = time.time() - T_PROCESS_START

    events: collections.Counter = collections.Counter()
    jax.monitoring.register_event_listener(lambda e, **kw: events.update([e]))

    cell = Cell(opts, cfg, traffic, break_step)
    jax.block_until_ready(cell.state)
    phases["state_s"] = time.time() - T_PROCESS_START
    phases["init_sharded_state_alone_s"] = cell.init_state_s
    program = cell.first_steps(held["reference"]["steps"])
    phases["read_grad_alone_s"] = program["read_grad_s"]
    phases["read_change_alone_s"] = program["read_change_s"]
    phases["first_step_s"] = program["first_step_done_wall"] - T_PROCESS_START
    jax.block_until_ready(cell.state)
    setup_misses = events["/jax/compilation_cache/cache_misses"]
    setup_hits = events["/jax/compilation_cache/cache_hits"]
    events.clear()
    # Set-up's leftovers must not land in the window: what it compiled went
    # to the persistent cache (write it back now), and tracing left garbage
    # behind (collect it now, and keep the survivors out of later passes). A
    # run that compiled its step read 1-1.6 % low with a 0.5-1.7 s step in it.
    os.sync()
    gc.collect()
    gc.freeze()

    # ---- the window: whole steps until --seconds have passed -------------
    window_open_wall = time.time()
    phases["window_open_s"] = window_open_wall - T_PROCESS_START
    t_open = time.perf_counter()
    losses, waits, step_s = [], [], []
    while time.perf_counter() - t_open < opts.seconds or not step_s:
        loss, wait, dt = cell.one_step()
        losses.append(loss)
        waits.append(wait)
        step_s.append(dt)
    window_s = time.perf_counter() - t_open
    window_compiles = events["/jax/compilation_cache/cache_misses"] \
        + events["/jax/compilation_cache/cache_hits"]
    losses = [float(x) for x in losses]
    failed = sum(not math.isfinite(x) for x in losses)

    traced, compiled_bytes = {}, None
    if opts.trace:
        traced = trace_steps(cell, statistics.median(step_s), opts.out)
        compiled_bytes = cell.compiled_bytes_per_device()
    stats = [d.memory_stats() or {} for d in devices]
    memory_peak = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
    cell.free()

    # ---- the reference, and the comparison -------------------------------
    t_ref = time.time()
    n_params = arch.load(opts.architecture, "counts").total_params(cfg)
    ref = reference.follow(
        cell.model_ref, cfg, optimizer_settings(cfg), opts.seed, cell.batch,
        cell.seq, held["reference"]["steps"], devices=devices,
        offload_moments=held["reference"]["offload_moments"])
    verdict = reference.compare(program, ref, held["limits"])
    os.sync()       # likewise the reference's programs, before the next run
    reference_s = time.time() - t_ref

    p95, beyond = high_percentile(step_s)
    result = {
        "rehearsal": opts.rehearsal,
        "control": opts.control,
        "correct": verdict["correct"] and failed == 0,
        "checks": verdict["checks"],
        "attempted": len(step_s),
        "failed": failed,
        "device": {"platform": dev0.platform, "kind": dev0.device_kind,
                   "count": len(devices), "memory_peak_bytes": memory_peak},
        "window": {"seconds": window_s, "steps": len(step_s),
                   "tokens": len(step_s) * cell.tokens_per_step,
                   "open_wall": window_open_wall,
                   "compiles_inside": window_compiles,
                   "data_wait_s": sum(waits),
                   "step_s_p50": statistics.median(step_s),
                   "step_s_p95": p95, "step_s_p95_samples_beyond": beyond,
                   "loss_first": losses[0], "loss_last": losses[-1]},
        "setup": {"phases": phases,
                  "cache_misses": setup_misses, "cache_hits": setup_hits,
                  "warmup_step_s": program["step_s"]},
        "program": {k: program[k] for k in ("losses", "feed_mismatch")},
        "reference": {"losses": ref["losses"], "seconds": reference_s},
        "compiled_bytes_per_device": compiled_bytes,
        "trace": traced,
        "params": n_params,
    }
    return result, step_s


def main(argv=None) -> int:
    opts = parse(argv)
    os.makedirs(opts.out, exist_ok=True)
    result, step_s = run_cell(opts)
    with open(os.path.join(opts.out, "step_seconds.json"), "w",
              encoding="utf-8") as f:
        json.dump(step_s, f)
    print("bench step_seconds:", json.dumps([round(x, 5) for x in step_s]))
    tmp = os.path.join(opts.out, "result.json.tmp")
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(result, f)
    os.replace(tmp, os.path.join(opts.out, "result.json"))
    print("bench worker result:", json.dumps(
        {k: result[k] for k in ("correct", "checks", "attempted", "failed",
                                "device", "window")}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
