"""User-process-side accelerator telemetry reporter.

The executor's TaskMonitor samples process-tree RSS fine, but HBM belongs
to the *user* process — the one that initialized the TPU runtime and so
holds the chips; the executor must never bring up a backend of its own
(the reference has the same split: ``TaskMonitor.java`` samples inside
the container alongside the training process, :109-170).

Mechanism: the executor exports ``TONY_METRICS_FILE`` into the user
process's environment; importing ``tony_tpu`` there auto-starts a daemon
thread (``maybe_start``) that periodically writes device stats to that file
via atomic replace. The TaskMonitor tails the file and merges the values
into the metrics it pushes — so TASK_FINISHED events carry real HBM
numbers without the user writing a line of code. Scripts that never import
``tony_tpu`` simply keep RSS-only metrics (never an error).

The reporter NEVER imports jax itself: it only reads stats once the user's
own code has brought the runtime up (jax present in sys.modules), so a
non-JAX task doesn't get a TPU runtime forced into it. The same holds for
the spans of the user process (further down): the profiler annotations
and the compile listeners exist only where ``jax`` is already loaded.
"""

from __future__ import annotations

import atexit
import collections
import contextlib
import json
import os
import sys
import threading
import time
from typing import Any, Deque, Dict, List, Optional

from tony_tpu import constants

_started = threading.Lock()
_thread: Optional[threading.Thread] = None
_stopping = threading.Event()       # set at interpreter exit

# ---------------------------------------------------------------------------
# Step-time utilization (the reference samples GPU duty cycle via
# nvidia-smi, TaskMonitor.java:116-170 + GpuDiscoverer.java:88-131 — on TPU
# there is no device-side util counter to shell out to, so the signal is
# derived from the training loop itself: wrap each step in
# ``with telemetry.step(flops=...)`` and the reporter publishes steps/s,
# duty cycle, and — when FLOPs are declared and the device kind has a known
# peak — MFU).
# ---------------------------------------------------------------------------
_step_lock = threading.Lock()
_steps = {"count": 0, "busy_s": 0.0, "flops": 0.0, "tokens": 0.0,
          "first_start": 0.0, "last_end": 0.0, "first_end_wall": 0.0}

# Peak dense bf16 matmul FLOP/s of one device — THE table (chip_smoke.py
# reads it from here). Keys are the exact ``device_kind``
# strings jax reports, as libtpu 0.0.34 names them (read off
# ``jax.experimental.topologies`` for v4 / v5e / v5p / v6e); values are
# Google Cloud's per-chip figures ("System architecture" pages for TPU v4,
# v5e, v5p and v6e). No prefix matching: a kind that is not a key has no
# peak — a measurement raises (``peak_bf16_flops``), the reporter below
# leaves the MFU field out.
PEAK_BF16_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,      # v5e
    "TPU v5": 459e12,           # v5p
    "TPU v6 lite": 918e12,      # v6e (Trillium)
}


def peak_bf16_flops(device_kind: str) -> float:
    """The table's peak for exactly this ``device_kind``; KeyError (naming
    the known kinds) for any other — never a default."""
    try:
        return PEAK_BF16_FLOPS[device_kind]
    except KeyError:
        raise KeyError(
            f"no bf16 peak on record for device_kind {device_kind!r} "
            f"(known: {sorted(PEAK_BF16_FLOPS)}); add it to "
            f"tony_tpu.telemetry.PEAK_BF16_FLOPS with its source") from None


# ---------------------------------------------------------------------------
# Per-step PHASE accounting (steady-state step-time attribution).
#
# ``step()``/``step_stats()`` answer "how fast"; nothing answered "where
# does the step go". The Gemma-on-TPU comparison (PAPERS.md) is built on
# exactly this decomposition — input wait vs device compute vs collective
# vs checkpoint stall — so the ``phase(name)`` context manager times any
# slice of the training loop, and ``step_done`` folds the accumulated
# phase seconds into a ring of per-step records whose attribution
# interval runs from the PREVIOUS step's end to this step's end (so
# between-step work — the prefetch queue wait, a checkpoint save — is
# attributed to the step that paid for it).
#
# Three of the five canonical phases come free:
# - ``data_wait``: ShardedBatchIterator.__next__ (tony_tpu/data.py)
# - ``ckpt_stall``: CheckpointManager.save/wait (checkpoint/manager.py)
# - ``step_compute``: defaults to the step() busy time when no explicit
#   step_compute phase was recorded (``block_until_ready``-anchor it
#   yourself via ``with telemetry.phase("step_compute") as p: ...;
#   p.block_until_ready(loss)`` for dispatch-gap-free numbers).
# ``h2d``, ``comms`` and ``eval`` are one `with` statement each.
# Everything unattributed lands in the synthetic ``other`` bucket, so the
# per-step phases ALWAYS sum to the wall interval.
# ---------------------------------------------------------------------------
#: canonical phase names (free-form names are accepted; these are the
#: ones the bottleneck classifier (tony_tpu/profiling/verdict.py) reads).
PHASES = ("data_wait", "h2d", "step_compute", "comms", "ckpt_stall",
          "eval")
#: synthetic bucket: wall time no phase claimed (host-side gaps).
OTHER_PHASE = "other"

_phase_lock = threading.Lock()
_phase_acc: Dict[str, float] = {}   # seconds since the last step boundary
_phase_cum: Dict[str, float] = {}   # job-cumulative, folded per step
_phase_wall_cum = 0.0               # cumulative attribution wall
_phase_steps = 0
#: steps the recent-means ring holds.
PHASE_RING_STEPS = 256
_phase_ring: Deque[dict] = collections.deque(maxlen=PHASE_RING_STEPS)

# ---------------------------------------------------------------------------
# Spans of the user process itself. Two sinks, two clocks:
#
# - the PROFILER's trace, on its clock: ``step()`` and ``phase(name)`` open
#   a ``jax.profiler`` annotation (``tony.step`` with its ``step_num``,
#   ``tony.phase.<name>``) for their duration, so any capture — the
#   benchmark's traced steps, ``profiler.trace_window``, ``tony-tpu
#   profile`` — shows the loop's host spans beside the device's operations
#   and an idle gap has an owner. With no capture running an annotation is
#   one TraceMe object that records nothing.
# - the job's SPAN LOG, on the wall clock: a short bounded list of closed
#   spans (``record_span``), and the executor emits each once under the
#   task's run span (executor._forward_user_spans). Only what is rare
#   goes this way — boot (``user.pre_import``, ``user.backend_init``,
#   ``user.init_state``) and every compile (``user.compile``: a recompile
#   at step 4,000 shows with its step) and, where the step was compiled
#   ahead of time, its map from instruction to scope
#   (``user.step_scopes``, ``parallel/train.py``: 12–65 KB, once) — never
#   a per-step span. The list
#   has a file of its own beside the metrics file (``spans_file``),
#   rewritten only when it grew; the metrics file, rewritten every tick,
#   carries the counters and ``spans_kept``, which tells the executor
#   when to look. The list inside the metrics file was measured: the
#   13 KB it added to every tick put a step 60–110 ms long into most 40 s
#   windows of the shortest-step cell, with the parent's code as well
#   (PERF.md, PR 25).
# ---------------------------------------------------------------------------
#: closed spans kept for the executor; past the cap only the count grows.
SPAN_CAP = 256
#: the stages of one jit compile, as ``jax.monitoring`` names their spans.
COMPILE_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}

#: the persistent compile cache's events, and the counter each feeds.
CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "compile_cache_hits",
    "/jax/compilation_cache/cache_misses": "compile_cache_misses",
}

#: how long after a new span the reporter writes the list out.
SPAN_FLUSH_DELAY_S = 0.1

_span_lock = threading.Lock()
_span_added = threading.Event()     # wakes the reporter (``_loop``)
_spans: List[Dict[str, Any]] = []
_spans_closed = 0                   # kept + dropped; a span's ``seq``
_compile_stats = {"compiles": 0, "compile_cache_hits": 0,
                  "compile_cache_misses": 0, "compile_seconds": 0.0,
                  "compiles_after_first_step": 0}
_compile_nesting = threading.local()
_jax_hooks_installed = False
_spans_written: tuple = ("", 0)     # (metrics path, spans in its spans file)


def record_span(name: str, start: float, end: float, keep: bool = False,
                **attrs: Any) -> None:
    """Keep one closed span of this process (wall-clock seconds) for the
    job's span log. Past ``SPAN_CAP`` the span is counted and dropped,
    unless ``keep`` says it is one a caller asked for by hand (a compiled
    step's map, ``user.step_scopes``) and no burst can repeat."""
    global _spans_closed
    with _span_lock:
        _spans_closed += 1
        if keep or len(_spans) < SPAN_CAP:
            _spans.append({"seq": _spans_closed, "name": name,
                           "start": start, "end": max(end, start),
                           "args": attrs})
            _span_added.set()


@contextlib.contextmanager
def span(name: str, **attrs: Any):
    """``record_span`` around a block: wall-anchored start, monotonic
    duration (the span log's own rule, tracing.py)."""
    install_jax_hooks()
    start, t0 = time.time(), time.monotonic()
    try:
        yield
    finally:
        record_span(name, start, start + time.monotonic() - t0, **attrs)


def span_stats() -> Dict[str, Any]:
    """The kept spans, how many there are and how many were dropped, and
    the compile counters; {} before the first span."""
    with _span_lock:
        if not _spans_closed:
            return {}
        return {"spans": list(_spans), "spans_kept": len(_spans),
                "spans_dropped": _spans_closed - len(_spans),
                **_compile_stats}


def spans_file(metrics_path: str) -> str:
    """Where the span list of the process that writes ``metrics_path``
    lies: ``{"pid", "spans"}``, the spans in ``seq`` order."""
    return metrics_path + ".spans"


def _process_start_wall() -> Optional[float]:
    """When this process started, on the wall clock: field 22 of
    ``/proc/self/stat`` is the start in clock ticks since boot. None where
    /proc does not say."""
    try:
        with open("/proc/self/stat", encoding="ascii") as f:
            ticks = int(f.read().rsplit(") ", 1)[1].split()[19])
        # The process's age on the boot clock, off a wall-clock anchor.
        age_s = (time.clock_gettime(time.CLOCK_BOOTTIME)
                 - ticks / os.sysconf("SC_CLK_TCK"))
        now = time.time()
        return now - age_s
    except (OSError, ValueError, IndexError, AttributeError):
        return None


def mark_import_done() -> None:
    """Last line of ``import tony_tpu`` inside a task: process start → now
    is ``user.pre_import`` (the interpreter, and whatever the script
    imported before tony_tpu: jax and the backend, if it went first)."""
    if not os.environ.get(constants.METRICS_FILE):
        return
    start = _process_start_wall()
    if start is not None:
        record_span("user.pre_import", start, time.time())
    install_jax_hooks()


def _on_jax_scalar(event: str, value: float, **kw: Any) -> None:
    # jax records a scalar (the start time) as each compile stage BEGINS:
    # the only sign of nesting there is (a jit traced inside a trace).
    if event in COMPILE_STAGES:
        _compile_nesting.depth = getattr(_compile_nesting, "depth", 0) + 1


def _on_jax_time_span(event: str, start: float, end: float,
                      **kw: Any) -> None:
    stage = COMPILE_STAGES.get(event)
    if stage is None:
        return
    depth = max(0, getattr(_compile_nesting, "depth", 1) - 1)
    _compile_nesting.depth = depth
    if depth:
        return          # inside an outer stage, whose span covers this one
    with _step_lock:
        step_count = _steps["count"]
    with _span_lock:
        _compile_stats["compile_seconds"] += max(0.0, end - start)
        if stage == "backend":
            _compile_stats["compiles"] += 1
            if step_count:
                _compile_stats["compiles_after_first_step"] += 1
    record_span("user.compile", start, end, stage=stage,
                fun_name=str(kw.get("fun_name", "")), step=step_count)


def _on_jax_event(event: str, **kw: Any) -> None:
    key = CACHE_EVENTS.get(event)
    if key:
        with _span_lock:
            _compile_stats[key] += 1


def install_jax_hooks() -> bool:
    """Register the three ``jax.monitoring`` listeners, once, as soon as
    the process has jax loaded (never imports it). Called wherever the
    loop touches this module, and from the reporter's tick."""
    global _jax_hooks_installed
    if _jax_hooks_installed:
        return True
    monitoring = sys.modules.get("jax.monitoring")
    register = [getattr(monitoring, name, None) for name in (
        "register_scalar_listener", "register_event_time_span_listener",
        "register_event_listener")]
    if not all(register):
        return False
    with _span_lock:
        if _jax_hooks_installed:
            return True
        _jax_hooks_installed = True
    for reg, listener in zip(register, (_on_jax_scalar, _on_jax_time_span,
                                        _on_jax_event)):
        reg(listener)
    return True


def _profiler_annotation(kind: str, name: str, **kw: Any):
    """``jax.profiler.<kind>(name, **kw)`` where the process has jax
    loaded, a no-op context elsewhere."""
    profiler = getattr(sys.modules.get("jax"), "profiler", None)
    if profiler is None:
        return contextlib.nullcontext()
    return getattr(profiler, kind)(name, **kw)


def _reset_span_state() -> None:
    """Tests: forget every span and compile count (listeners stay)."""
    global _spans_closed, _spans_written
    with _span_lock:
        _spans.clear()
        _spans_closed = 0
        _spans_written = ("", 0)
        for k in _compile_stats:
            _compile_stats[k] = type(_compile_stats[k])()


class _PhaseSpan:
    """Handle yielded by ``phase()``: ``block_until_ready(x)`` anchors the
    phase end on device completion (a dispatch-async step would otherwise
    time only the enqueue). No-op passthrough without a live jax."""

    @staticmethod
    def block_until_ready(x):
        jax = sys.modules.get("jax")
        if jax is not None:
            try:
                return jax.block_until_ready(x)
            except Exception:  # noqa: BLE001 — timing aid, never fatal
                return x
        return x


@contextlib.contextmanager
def phase(name: str):
    """Attribute the enclosed wall time to step-phase ``name``:
    ``with telemetry.phase("data_wait"): batch = next(it)``. Folded into
    the per-step ring at the next ``step_done`` and shipped on the
    heartbeat metrics beacon as ``tony_step_phase_seconds``; a profiler
    capture shows it as ``tony.phase.<name>``."""
    t0 = time.monotonic()
    try:
        with _profiler_annotation("TraceAnnotation", "tony.phase." + name):
            yield _PhaseSpan()
    finally:
        dt = time.monotonic() - t0
        with _phase_lock:
            _phase_acc[name] = _phase_acc.get(name, 0.0) + dt


def _fold_phases(interval_s: float, busy_s: float) -> None:
    """Close one attribution interval (step_done): drain the accumulator
    into the ring + cumulative totals, defaulting step_compute to the
    step's busy time and booking the unattributed remainder as other."""
    global _phase_wall_cum, _phase_steps
    with _phase_lock:
        acc = dict(_phase_acc)
        _phase_acc.clear()
        if "step_compute" not in acc:
            acc["step_compute"] = busy_s
        wall = max(interval_s, 0.0)
        attributed = sum(acc.values())
        if attributed > wall:
            # Overlapped phases (an async save timed across several
            # steps) can over-attribute; widen the wall rather than
            # invent a negative other bucket.
            wall = attributed
        acc[OTHER_PHASE] = wall - attributed
        for k, v in acc.items():
            _phase_cum[k] = _phase_cum.get(k, 0.0) + v
        _phase_wall_cum += wall
        _phase_steps += 1
        _phase_ring.append({"wall_s": wall, "phases": acc})


def phase_stats() -> Dict[str, object]:
    """Step-time attribution snapshot: cumulative seconds per phase (sum
    EXACTLY equals ``wall_s`` — ``other`` holds the unattributed rest)
    plus recent per-step means over the ring. {} before the first step."""
    with _phase_lock:
        if not _phase_steps:
            return {}
        out: Dict[str, object] = {
            "steps": float(_phase_steps),
            "wall_s": _phase_wall_cum,
            "cum": dict(_phase_cum),
        }
        n = len(_phase_ring)
        if n:
            recent: Dict[str, float] = {}
            rwall = 0.0
            for rec in _phase_ring:
                rwall += rec["wall_s"]
                for k, v in rec["phases"].items():
                    recent[k] = recent.get(k, 0.0) + v
            out["recent"] = {k: v / n for k, v in recent.items()}
            out["recent_wall_s"] = rwall / n
            out["recent_steps"] = float(n)
    return out


def _reset_phase_state() -> None:
    """Tests/bench probes: start attribution from a clean slate."""
    global _phase_wall_cum, _phase_steps
    with _phase_lock:
        _phase_acc.clear()
        _phase_cum.clear()
        _phase_wall_cum = 0.0
        _phase_steps = 0
        _phase_ring.clear()


# The newest step's own counters (a model's aux metrics: jit_train_step
# hands them over as they come back from the device, unread). The reporter
# reads their values when it writes the metrics file, off the step's path.
_counter_lock = threading.Lock()
_step_counters: Dict[str, Any] = {}


def note_step_counters(counters: Dict[str, Any]) -> None:
    """Keep ``counters`` (name → scalar, device arrays welcome) as the
    newest step's; they appear under ``step_counters`` in the task's
    metrics file. Costs the step nothing: no value is read here."""
    with _counter_lock:
        _step_counters.clear()
        _step_counters.update(counters)


def step_counters() -> Dict[str, float]:
    with _counter_lock:
        held = dict(_step_counters)
    out = {}
    for name, value in held.items():
        try:
            out[name] = float(value)
        except Exception:  # noqa: BLE001 — a deleted buffer, a non-scalar
            continue
    return out


def step_done(started_at: float, flops: float = 0.0,
              tokens: float = 0.0) -> None:
    """Record one completed training step that began at ``started_at``
    (``time.monotonic()``). Prefer the ``step()`` context manager."""
    from tony_tpu import faults

    if faults.fire("user.hang"):
        # Injected user hang: the recording is silently dropped, so the
        # published step counter freezes while the process (and its
        # executor's heartbeats) keep running — exactly the shape the
        # coordinator's progress-based liveness must catch.
        return
    delay = faults.fire_amount("user.slow_step")
    if delay:
        # Injected straggler skew: stretch this step by the configured
        # amount BEFORE timestamping, so the slowdown lands in the step
        # rate the gang-median policing compares.
        time.sleep(delay)
    now = time.monotonic()
    with _step_lock:
        if not _steps["first_start"]:
            _steps["first_start"] = started_at
            # Wall-clock completion of the FIRST step: the one absolute
            # timestamp the executor's first-step trace span (and the
            # bench's submit→first-step metric) anchors on.
            _steps["first_end_wall"] = time.time()
        prev_end = _steps["last_end"]
        busy = max(0.0, now - started_at)
        _steps["count"] += 1
        _steps["busy_s"] += busy
        _steps["flops"] += flops
        _steps["tokens"] += tokens
        _steps["last_end"] = now
    # Attribution interval: previous step end → this step end, so the
    # data wait / checkpoint stall BETWEEN steps lands on the step that
    # paid for it; the first step's interval is its own busy time
    # (compile/restore before it was never on the clock).
    _fold_phases(now - prev_end if prev_end else busy, busy)
    _profile_on_step_boundary()


@contextlib.contextmanager
def step(flops: float = 0.0, tokens: float = 0.0):
    """Time one training step: ``with telemetry.step(flops=6*params*B*S):``.
    Feeds steps/s, duty-cycle, and MFU into the task's metrics stream; a
    profiler capture shows it as ``tony.step`` with its ``step_num``."""
    install_jax_hooks()
    with _step_lock:
        step_num = _steps["count"]
    t0 = time.monotonic()
    try:
        # Closed before step_done: an on-demand capture starts and stops
        # there, and must hold whole tony.step spans only.
        with _profiler_annotation("StepTraceAnnotation", "tony.step",
                                  step_num=step_num):
            yield
    finally:
        step_done(t0, flops=flops, tokens=tokens)


def step_stats() -> Dict[str, float]:
    """Derived utilization over the window since the first recorded step;
    {} until a step completes."""
    with _step_lock:
        s = dict(_steps)
    if not s["count"]:
        return {}
    wall = max(s["last_end"] - s["first_start"], 1e-9)
    out = {
        "steps_completed": float(s["count"]),
        "steps_per_sec": s["count"] / wall,
        # Fraction of wall time spent inside steps: the duty-cycle proxy
        # (host-side; dispatch gaps and eval/checkpoint pauses count as
        # idle, which is exactly the signal an operator wants).
        "step_duty_cycle": min(1.0, s["busy_s"] / wall),
    }
    if s["tokens"]:
        out["tokens_per_sec"] = s["tokens"] / wall
    if s["flops"]:
        out["model_flops_per_sec"] = s["flops"] / wall
    if s["first_end_wall"]:
        out["first_step_done_ts"] = s["first_end_wall"]
    return out


# ---------------------------------------------------------------------------
# On-demand device profiling (live, any task, mid-run).
#
# `tony-tpu profile <app>` turns the static chief-only trace_window()
# contract (tony_tpu/profiler.py: edit user code, decide before launch)
# into a live directive: the coordinator rides a PROFILE request on the
# heartbeat response, the executor writes it to the request file this
# module polls (TONY_PROFILE_REQUEST_FILE, reporter-loop cadence), and
# the NEXT step boundary arms ``jax.profiler`` for N steps — the capture
# brackets whole steps, never a half-dispatched one. The result (or the
# failure: fault site ``profile.capture``) rides the metrics file back
# onto the next beat. Capture must never kill or stall training: every
# failure shape degrades to a reported PROFILE_FAILED.
# ---------------------------------------------------------------------------
_profile_lock = threading.Lock()
_profile: Dict[str, object] = {
    "last_id": 0,        # highest request id ever seen (the dedup fence)
    "pending": None,     # request waiting for the next step boundary
    "active": None,      # {"req":..., "remaining": n} while tracing
    "result": None,      # last terminal {"id","status","dir"|"error",...}
}


def _poll_profile_request(path: str = "") -> None:
    """Reporter-loop tick: adopt a new profile request from the request
    file (executor-written, atomic replace). Dedup on the request id —
    the directive is re-sent every beat until the result lands."""
    path = path or os.environ.get(constants.PROFILE_REQUEST_ENV, "")
    if not path:
        return
    try:
        with open(path, encoding="utf-8") as f:
            req = json.load(f)
        req_id = int(req.get("id", 0))
    except (OSError, ValueError, TypeError):
        return
    if req_id <= 0:
        return
    with _profile_lock:
        if req_id <= int(_profile["last_id"]):  # type: ignore[arg-type]
            return
        _profile["last_id"] = req_id
        _profile["pending"] = {
            "id": req_id,
            "steps": max(1, int(req.get("steps", 1) or 1)),
            "dir": str(req.get("dir", "") or ""),
        }


def _profile_on_step_boundary() -> None:
    """step_done hook: start a pending capture at this step boundary, or
    advance/stop an active one. Never raises — a failed capture becomes a
    PROFILE_FAILED result on the beacon and the loop keeps training."""
    with _profile_lock:
        pending = _profile["pending"]
        active = _profile["active"]
    if active is not None:
        active["remaining"] -= 1
        if active["remaining"] > 0:
            return
        req = active["req"]
        result = {"id": req["id"], "steps": req["steps"]}
        try:
            sys.modules["jax"].profiler.stop_trace()
            result.update(status="captured", dir=req["dir"])
        except Exception as e:  # noqa: BLE001 — capture is best-effort
            result.update(status="failed", error=f"stop_trace: {e}"[:300])
        with _profile_lock:
            _profile["active"] = None
            _profile["result"] = result
        return
    if pending is None:
        return
    result = {"id": pending["id"], "steps": pending["steps"]}
    try:
        from tony_tpu import faults

        faults.check("profile.capture")
        jax = sys.modules.get("jax")
        if jax is None:
            raise RuntimeError("jax is not initialized in this process")
        dest = pending["dir"] or os.path.join(
            os.getcwd(), "profile", f"ondemand-{pending['id']}")
        try:
            os.makedirs(dest, exist_ok=True)
        except OSError:
            # Directive named a dir this host can't write (remote-host
            # task vs. coordinator job dir): capture locally and report
            # where the artifact actually is.
            dest = os.path.join(os.getcwd(), "profile",
                                f"ondemand-{pending['id']}")
            os.makedirs(dest, exist_ok=True)
        pending["dir"] = dest
        jax.profiler.start_trace(dest)
    except Exception as e:  # noqa: BLE001 — never stall training
        with _profile_lock:
            _profile["pending"] = None
            _profile["result"] = {**result, "status": "failed",
                                  "error": str(e)[:300]}
        return
    with _profile_lock:
        _profile["pending"] = None
        _profile["active"] = {"req": pending,
                              "remaining": pending["steps"]}


def profile_state() -> Optional[Dict[str, object]]:
    """Beacon payload: the capture in flight or the last terminal result
    (kept until a newer request supersedes it); None = nothing to say."""
    with _profile_lock:
        if _profile["active"] is not None:
            req = _profile["active"]["req"]  # type: ignore[index]
            return {"id": req["id"], "status": "active",
                    "dir": req["dir"], "steps": req["steps"]}
        if _profile["result"] is not None:
            return dict(_profile["result"])  # type: ignore[arg-type]
    return None


def _reset_profile_state() -> None:
    """Tests: forget every request/capture/result."""
    with _profile_lock:
        _profile.update(last_id=0, pending=None, active=None, result=None)


def collect_device_stats() -> Dict[str, float]:
    """Best-effort per-process accelerator + step stats; {} when neither is
    available. Step stats publish WITHOUT a jax runtime — a PyTorch or
    plain-Python loop wrapped in telemetry.step() still feeds the progress
    beacon the coordinator's hang detection watches (device stats alone
    stay jax-gated: this module never imports jax itself)."""
    out: Dict[str, float] = {}
    kinds: List[str] = []
    jax = None
    if "jax" in sys.modules:
        try:
            jax = sys.modules["jax"]
            devices = jax.local_devices()
        except Exception:  # noqa: BLE001 — telemetry must never break the task
            jax, devices = None, []
        if jax is not None:
            out["device_count"] = float(len(devices))
            in_use = 0.0
            for d in devices:
                try:
                    stats = d.memory_stats() or {}
                except Exception:  # noqa: BLE001
                    stats = {}
                in_use += float(stats.get("bytes_in_use", 0) or 0)
                kinds.append(str(getattr(d, "device_kind", "?")))
            out["hbm_bytes_in_use"] = in_use
    util = step_stats()
    if util:
        out.update(util)
        peak_fl = PEAK_BF16_FLOPS.get(kinds[0] if kinds else "")
        if jax is not None and peak_fl \
                and util.get("model_flops_per_sec"):
            # flops passed to step() are the model's GLOBAL per-step FLOPs
            # (the 6·N·B·S convention over the global batch), so the
            # denominator must be the GLOBAL device pool — local devices
            # alone would overstate MFU by process_count on multi-host
            # slices.
            try:
                n_global = jax.device_count()
            except Exception:  # noqa: BLE001
                n_global = len(kinds) or 1
            out["mfu_vs_peak_bf16"] = (util["model_flops_per_sec"]
                                       / (peak_fl * n_global))
    # Boot and compile spans for the span log, and the compile counters.
    out.update(span_stats())
    phases = phase_stats()
    if phases:
        # Step-time attribution: rides the metrics file → heartbeat
        # beacon → tony_step_phase_seconds gauges + the `top` phase bar.
        out["step_phases"] = phases  # type: ignore[assignment]
    counters = step_counters()
    if counters:
        out["step_counters"] = counters  # type: ignore[assignment]
    prof = profile_state()
    if prof is not None:
        # On-demand device capture status/result (the coordinator emits
        # TASK_PROFILED and the CLI polls it off profile.status).
        out["profile"] = prof  # type: ignore[assignment]
    quant = sys.modules.get("tony_tpu.ops.quant")
    if quant is not None:
        # One-time quantization-fallback event (tony.train.matmul-dtype
        # refused on this backend → degraded to bf16): surfaced on the
        # beacon so the degrade is visible in metrics/top, not only in a
        # log line. Checked via sys.modules so a job that never touched
        # the quant path never imports it (or jax) from here.
        fb = quant.fallback_events()
        if fb:
            out["quant_fallback"] = fb  # type: ignore[assignment]
    return out


def write_stats_once(path: str) -> bool:
    global _spans_written
    stats = collect_device_stats()
    if not stats:
        return False
    stats["pid"] = os.getpid()
    spans = stats.pop("spans", [])
    try:
        from tony_tpu.utils.durable import atomic_write

        if (path, len(spans)) != _spans_written:
            # Before the metrics file, whose spans_kept sends the reader
            # here: what it then finds is at least that long.
            atomic_write(spans_file(path), json.dumps(
                {"pid": stats["pid"], "spans": spans}).encode("utf-8"))
            _spans_written = (path, len(spans))
        atomic_write(path, json.dumps(stats).encode("utf-8"))
        return True
    except OSError:
        return False


def _loop(path: str, interval_s: float) -> None:
    while not _stopping.is_set():
        # On-demand profiling directive intake first, so a request
        # written just before this tick arms at the very next boundary.
        try:
            _poll_profile_request()
            install_jax_hooks()
        except Exception:  # noqa: BLE001 — telemetry must never die
            pass
        write_stats_once(path)
        # A new span cuts the sleep short, so that the list is on disk
        # while the compile burst that made it is hardly over (boot, or a
        # recompile), not up to a tick later in the middle of the steps
        # that follow; the short wait lets the burst finish first.
        if _span_added.wait(interval_s):
            time.sleep(SPAN_FLUSH_DELAY_S)
            _span_added.clear()


def _stop_reporter() -> None:
    """At interpreter exit, before the threads' teardown: let the reporter
    finish the write it is in and end. A daemon thread that the teardown
    catches inside jax's native code (``memory_stats``) takes the process
    down with an abort after its work is done; a span closed just before
    the exit (a compile, a step's map) wakes the reporter exactly then."""
    _stopping.set()
    _span_added.set()
    if _thread is not None:
        _thread.join(timeout=2.0)


def maybe_start(interval_s: float = 3.0) -> bool:
    """Start the reporter iff TONY_METRICS_FILE is set and it isn't running
    yet. Called from tony_tpu/__init__ — a bare import inside a task is
    enough to light up HBM telemetry. ``TONY_TELEMETRY_INTERVAL_S``
    overrides the cadence (progress-liveness tests tighten it so step
    counters publish faster than the progress deadline)."""
    global _thread
    path = os.environ.get(constants.METRICS_FILE, "")
    if not path:
        return False
    try:
        interval_s = float(
            os.environ.get(constants.TELEMETRY_INTERVAL_ENV, "")
            or interval_s)
    except ValueError:
        pass
    with _started:
        if _thread is not None and _thread.is_alive():
            return True
        _thread = threading.Thread(target=_loop, args=(path, interval_s),
                                   name="tony-telemetry", daemon=True)
        _thread.start()
        atexit.register(_stop_reporter)
        return True


def read_stats(path: str) -> Dict[str, float]:
    """Monitor side: read the latest reporter snapshot ({} if absent)."""
    try:
        with open(path, encoding="utf-8") as f:
            return json.load(f)
    except (OSError, ValueError):
        return {}


# ---------------------------------------------------------------------------
# Hung-task diagnostics: pre-registered all-thread stack dump.
#
# When the coordinator declares a task HUNG (progress frozen, heartbeats
# alive — coordinator/liveness.py) the executor signals the USER process
# group with the signal it exported as TONY_STACKDUMP_SIGNAL. This handler
# — registered at `import tony_tpu`, i.e. before the user code can wedge —
# makes that signal dump every thread's stack to stderr (the task log),
# turning "it just stopped" postmortems into tracebacks.
# ---------------------------------------------------------------------------
_dump_registered = False


def install_stack_dump_handler(stream=None) -> bool:
    """Register a faulthandler all-thread stack dump on the signal named by
    ``TONY_STACKDUMP_SIGNAL`` (exported by the executor into the user
    env). No-op without the env var. A handler the user already installed
    on that signal is detected and warned about, never broken: the dump
    chains to it (both run). Returns True iff the dump handler is armed."""
    global _dump_registered
    spec = os.environ.get(constants.STACKDUMP_SIGNAL, "")
    if not spec:
        return False
    if _dump_registered:
        return True
    try:
        signum = int(spec)
    except ValueError:
        return False
    import faulthandler
    import logging
    import signal as _signal

    try:
        existing = _signal.getsignal(signum)
    except (ValueError, OSError):
        return False
    chain = callable(existing) and \
        existing is not _signal.default_int_handler
    if chain:
        # The user process got here with its own handler already on the
        # dump signal (framework or user code). Do not break it — chain —
        # but say so, because a handler that exits would still cut the
        # dump short. Chaining over SIG_DFL would instead re-run the
        # signal's DEFAULT action (terminate, for SIGUSR1/2) and kill the
        # process we are trying to diagnose — hence callable-only.
        logging.getLogger(__name__).warning(
            "signal %d already has a user handler (%r); chaining the "
            "tony-tpu stack-dump handler in front of it — hung-task "
            "dumps will run both", signum, existing)
    try:
        faulthandler.register(signum, file=stream or sys.stderr,
                              all_threads=True, chain=chain)
    except (ValueError, OSError, RuntimeError, AttributeError):
        # Non-main interpreter, closed stderr, or a platform without
        # faulthandler signals: diagnostics are best-effort, never fatal.
        return False
    _dump_registered = True
    return True
