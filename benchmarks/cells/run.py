#!/usr/bin/env python3
"""The benchmark's one command:

    python3 benchmarks/cells/run.py --workload <name> --seed <n>
        --seconds <s> --trace <0|1>

A cell is its entry in ``BENCHMARK.json`` plus the files that entry names: the
configuration's file, ``traffic/<traffic>.json`` and ``limits/<workload>.json``
beside this file. The configuration's ``model_type`` names its architecture,
a directory of three files under ``architectures/`` (``arch.py``). A
per-layer metric is one file under ``metrics/``, found by listing the
directory. No cell, no metric and no architecture is named in this code.

This process never imports JAX: a process that touches JAX holds the chip,
and the job's user process needs it. It submits one job through ``tony-tpu
submit`` (client → coordinator → local backend → executor), whose one worker
is ``train.py`` with ``JAX_PLATFORMS=tpu``, waits, and reads the job's
artifacts. ``setup_s`` runs from this process's start to the moment the first
measured step may begin; ``tokens_per_s_per_chip`` is every whole step of the
window over the window's whole time.

The last line of standard output is the result object and nothing else.
Without a TPU, or in a directory without the program, the exit code is not 0
and no result is printed. ``--rehearsal`` walks the same plumbing on the CPU
(virtual devices, interpreted kernels) for cells of a rehearsal table; its
line says ``"rehearsal": true`` and carries no metric.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import time

import arch

T_START = time.time()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
RUN_MARKER_ENV = "BENCH_CELLS_RUN"     # tags every process of one run
JOB_TIMEOUT_S = 1100                   # the coordinator's own limit
WAIT_TIMEOUT_S = 1150                  # ours, inside the contract's 1200 s


class BenchFailure(Exception):
    """The run cannot give a result."""


def load_json(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def find_cell(bench: dict, workload: str) -> tuple:
    cells = [w for w in bench["workloads"] if w["name"] == workload]
    if len(cells) != 1:
        raise BenchFailure(f"no workload {workload!r} in the table")
    cell = cells[0]
    config, = [c for c in bench["configs"] if c["name"] == cell["config"]]
    # The cell's data files sit under the table's first path.
    return dict(cell, base=bench["paths"][0]), config


def load_metrics() -> list:
    """Every per-layer metric's reader: one module per file of metrics/."""
    out = []
    folder = os.path.join(HERE, "metrics")
    for name in sorted(os.listdir(folder)):
        if not name.endswith(".py"):
            continue
        spec = importlib.util.spec_from_file_location(
            "bench_metric_" + name[:-3], os.path.join(folder, name))
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        out.append(module)
    return out


def stop_run(marker: str) -> list:
    """SIGKILL every process that still carries this run's marker in its
    environment (coordinator, executor and worker inherit it), and wait for
    each to go. After a clean job there is none."""
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
    deadline = time.time() + 30
    while time.time() < deadline and any(
            os.path.exists(f"/proc/{pid}") for pid in found):
        time.sleep(0.1)
    return found


def submit(cmd: list, env: dict, log_path: str) -> int:
    """``tony-tpu submit`` to its end. On our own time limit TERM it (the
    CLI's handler kills the application), then KILL what is left."""
    with open(log_path, "wb") as log:
        proc = subprocess.Popen(cmd, env=env, cwd=ROOT, stdout=log,
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
            raise BenchFailure(
                f"submit did not finish in {WAIT_TIMEOUT_S} s") from None


def tail(path: str, n: int = 30) -> str:
    try:
        with open(path, errors="replace") as f:
            return "".join(f.readlines()[-n:])
    except OSError as e:
        return f"<{e}>"


def drive(args, cell: dict, config: dict, out_dir: str, run_dir: str,
          marker: str) -> dict:
    """Submit the job, wait, and gather what the metrics read."""
    history = os.path.join(run_dir, "history")
    submit_log = os.path.join(out_dir, "submit.log")
    task_env = "JAX_PLATFORMS=tpu"
    if args.rehearsal:
        task_env = ("JAX_PLATFORMS=cpu,XLA_FLAGS="
                    f"--xla_force_host_platform_device_count={cell['chips']}")
    # Every program of the job goes to the compile cache, however small, so
    # that a run after the cell's first compiles nothing.
    task_env += (",JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS=0,"
                 "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES=-1")
    base = os.path.join(ROOT, cell["base"])
    config_path = os.path.join(ROOT, config["file"])
    cfg = load_json(config_path)
    architecture = arch.find(cfg, config_path, base)
    worker = [sys.executable, os.path.join(HERE, "train.py"),
              "--config", config_path, "--architecture", architecture,
              "--traffic", os.path.join(base, "traffic",
                                        cell["traffic"] + ".json"),
              "--limits", os.path.join(base, "limits",
                                       cell["name"] + ".json"),
              "--chips", str(cell["chips"]), "--seed", str(args.seed),
              "--seconds", str(args.seconds), "--trace", str(args.trace),
              "--out", out_dir]
    if args.rehearsal:
        worker.append("--rehearsal")
    cmd = [sys.executable, "-m", "tony_tpu.cli", "submit",
           "--conf", "tony.worker.instances=1",
           "--conf", "tony.worker.command=" + " ".join(worker),
           "--conf", f"tony.application.execution-env={task_env}",
           "--conf", f"tony.application.timeout-s={JOB_TIMEOUT_S}",
           "--conf", f"tony.history.location={history}",
           "--workdir", os.path.join(run_dir, "work")]
    env = dict(os.environ)
    env[RUN_MARKER_ENV] = marker
    env["PYTHONPATH"] = (ROOT + os.pathsep
                         + env.get("PYTHONPATH", "")).rstrip(os.pathsep)
    # The compile cache: where the environment says, if it says; otherwise a
    # fixed path inside the checkout (the path is part of every key).
    if not env.get("JAX_COMPILATION_CACHE_DIR"):
        cmd += ["--conf", "tony.jax.compilation-cache-dir="
                + os.path.join(ROOT, ".jax_cache")]
    rc = submit(cmd, env, submit_log)

    sys.path.insert(0, ROOT)
    from tony_tpu import constants, tracing
    from tony_tpu.events import history as tony_history

    jobs = tony_history.list_job_dirs(history)
    logs = {}
    if len(jobs) == 1:
        (app, job_dir), = jobs.items()
        task_dir = os.path.join(run_dir, "work", "jobs", app, "tasks",
                                "worker_0")
        logs = {"worker.stdout.log": os.path.join(task_dir, "stdout.log"),
                "worker.stderr.log": os.path.join(task_dir, "stderr.log"),
                "coordinator.log": os.path.join(run_dir, "work", "jobs", app,
                                                "coordinator.log"),
                constants.TRACE_FILE: os.path.join(job_dir,
                                                   constants.TRACE_FILE)}
        for name, path in logs.items():
            try:
                shutil.copyfile(path, os.path.join(out_dir, name))
            except OSError:
                pass
    result_path = os.path.join(out_dir, "result.json")
    if rc != 0 or len(jobs) != 1 or not os.path.exists(result_path):
        detail = "".join(f"--- tail of {n}\n{tail(os.path.join(out_dir, n))}"
                         for n in ("worker.stderr.log", "coordinator.log",
                                   "submit.log"))
        raise BenchFailure(f"the job did not finish (submit exit {rc}, "
                           f"{len(jobs)} job dirs)\n{detail}")
    spans = tracing.cold_start_breakdown(
        tracing.load_records(logs[constants.TRACE_FILE]))
    for line in tail(os.path.join(out_dir, "worker.stdout.log"), 5).split(
            "\n"):
        if line.startswith("bench step_seconds:"):
            print(line)
    return {"worker": load_json(result_path), "spans": spans,
            "harness_start_wall": T_START,
            "config": cfg, "architecture": architecture,
            "traffic": load_json(worker[worker.index("--traffic") + 1]),
            "cell": cell}


def metrics_of(run: dict, bench: dict, trace: int) -> dict:
    """The cell's end-to-end metrics (``--trace 0``) or its per-layer
    metrics (``--trace 1``), each under its name with its unit."""
    worker, cell = run["worker"], run["cell"]
    window = worker["window"]
    if not trace:
        values = {
            "tokens_per_s_per_chip":
                window["tokens"] / window["seconds"] / cell["chips"],
            "setup_s": window["open_wall"] - run["harness_start_wall"],
        }
        return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                for m in bench["end_to_end"]
                if cell["name"] in m.get("workloads", [cell["name"]])}
    readers = {module.NAME: module for module in load_metrics()}
    out = {}
    for entry in bench["per_layer"]:
        module = readers.get(entry["name"])
        if module is None or cell["name"] not in entry.get(
                "workloads", [cell["name"]]):
            continue
        value = module.read(run)
        if value is None:       # nothing to read: the metric is left out
            continue
        out[entry["name"]] = {"value": value, "unit": entry["unit"]}
        if hasattr(module, "note"):
            print(f"bench note {entry['name']}: {module.note(run)}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="CPU walk-through of a rehearsal table's cell; "
                         "prints no metric")
    ap.add_argument("--table", default=os.path.join(ROOT, "BENCHMARK.json"),
                    help="the cell table (a rehearsal names its own)")
    args = ap.parse_args(argv)

    marker = f"{int(T_START)}-{os.getpid()}"
    out_dir = os.path.join(
        ROOT, "chiprun_out", "cells",
        f"{args.workload}.seed{args.seed}.trace{args.trace}")
    run_dir = None
    try:
        if not os.path.isdir(os.path.join(ROOT, "tony_tpu")):
            raise BenchFailure(f"the program is not in {ROOT}")
        bench = load_json(args.table)
        cell, config = find_cell(bench, args.workload)
        shutil.rmtree(out_dir, ignore_errors=True)
        os.makedirs(out_dir)
        run_dir = tempfile.mkdtemp(prefix="bench-cells-")
        run = drive(args, cell, config, out_dir, run_dir, marker)
        worker = run["worker"]
        if worker["rehearsal"] != args.rehearsal:
            raise BenchFailure("the worker ran the other mode")
        if not args.rehearsal and worker["device"]["platform"] != "tpu":
            raise BenchFailure("not a TPU run")
        metrics = {} if args.rehearsal else metrics_of(run, bench,
                                                       args.trace)
        leftover = stop_run(marker)
        if leftover:
            raise BenchFailure(f"processes outlived the job: {leftover}")
        if "jax" in sys.modules:
            raise BenchFailure("the parent imported jax")
    except Exception as e:  # noqa: BLE001 — every failure: report, exit 1
        stop_run(marker)
        print(f"bench FAILED: {type(e).__name__}: {e}", file=sys.stderr)
        return 1
    finally:
        if run_dir:
            shutil.rmtree(run_dir, ignore_errors=True)

    device = dict(worker["device"])
    line = {"correct": worker["correct"], "attempted": worker["attempted"],
            "failed": worker["failed"], "metrics": metrics, "device": device}
    if args.rehearsal:
        line["rehearsal"] = True
    if args.trace and not args.rehearsal:
        import trace_reduce

        device["busy_s"] = worker["trace"]["busy_s"]
        device["window_s"] = worker["trace"]["window_s"]
        line["breakdown"] = trace_reduce.breakdown(worker["trace"])
    # Each number compared, beside its limit: last on standard error, and
    # last in the result's line.
    checks = {k: [v["value"], v["limit"]] for k, v in
              worker["checks"].items()}
    line["compared"] = checks
    print(f"bench window: {json.dumps(worker['window'])}")
    print(f"bench setup: {json.dumps(worker['setup'])}")
    print(f"bench reference: {json.dumps(worker['reference'])}")
    for name, (value, limit) in checks.items():
        print(f"compared {name}: {value!r} limit {limit!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
