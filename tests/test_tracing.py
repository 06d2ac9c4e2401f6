"""Fast deterministic unit suite for the distributed-tracing layer
(tony_tpu/tracing.py): span record grammar (B/E/X/I), file vs buffer
sinks, Perfetto export with unclosed-span detection, trace-id recovery,
RPC trace-context propagation through real wire frames, the RPC
latency/observability hooks, and the new ``rpc.slow`` fault site.
Select with ``pytest -m faults``.
"""

import json
import os
import time

import pytest

from tony_tpu import faults, tracing
from tony_tpu.rpc.wire import RpcClient, RpcServer

pytestmark = pytest.mark.faults


@pytest.fixture(autouse=True)
def _clean():
    faults.uninstall()
    tracing.clear_rpc_context()
    yield
    faults.uninstall()
    tracing.clear_rpc_context()


# ---------------------------------------------------------------------------
# Span records + sinks
# ---------------------------------------------------------------------------
def test_file_sink_begin_end_records(tmp_path):
    """A file-sink tracer writes B at open and E at close — a crashed
    process leaves evidence of what was in flight."""
    path = str(tmp_path / "trace.spans.jsonl")
    t = tracing.Tracer(service="coordinator", path=path)
    span = t.start_span("coordinator.run", attrs={"app": "a1"})
    child = t.start_span("session.epoch", parent=span, task="worker:0")
    child.end(status="SUCCEEDED")
    span.end()
    t.close()
    recs = tracing.load_records(path)
    assert [r["ev"] for r in recs] == ["B", "B", "E", "E"]
    assert recs[0]["name"] == "coordinator.run"
    assert recs[1]["parent"] == recs[0]["span"]
    assert recs[1]["task"] == "worker:0"
    # E merges close-time attrs; export folds them into the span.
    assert recs[2]["args"] == {"status": "SUCCEEDED"}


def test_buffer_sink_only_ships_complete_spans():
    """Buffer-mode tracers (executors) emit nothing at open: a lost push
    can drop spans but never manufacture an unclosed one."""
    t = tracing.Tracer(service="executor:worker:0")
    span = t.start_span("executor.run")
    assert t.drain() == []          # nothing until the span closes
    span.end(exit_code=0)
    recs = t.drain()
    assert len(recs) == 1 and recs[0]["ev"] == "X"
    assert recs[0]["args"] == {"exit_code": 0}
    assert recs[0]["dur_us"] >= 0
    assert t.drain() == []          # drained exactly once


def test_span_end_is_idempotent_and_monotonic():
    t = tracing.Tracer(service="x")
    span = t.start_span("s")
    span.end(first=True)
    span.end(second=True)           # ignored
    recs = t.drain()
    assert len(recs) == 1
    assert recs[0]["args"] == {"first": True}


def test_disabled_tracer_is_inert(tmp_path):
    path = str(tmp_path / "t.jsonl")
    t = tracing.Tracer(service="x", path=path, enabled=False)
    span = t.start_span("never")
    assert span is tracing.NULL_SPAN
    span.end()
    t.emit("e", start_us=0, end_us=1)
    t.instant("i")
    assert not os.path.exists(path)


def test_write_records_validates_and_appends(tmp_path):
    """trace.push intake: well-formed records land, junk is dropped."""
    path = str(tmp_path / "t.jsonl")
    t = tracing.Tracer(service="coordinator", path=path)
    good = {"ev": "X", "trace": t.trace_id, "span": "s1", "parent": "",
            "name": "executor.run", "svc": "executor:w:0", "task": "w:0",
            "ts_us": 5, "dur_us": 2, "args": {}}
    n = t.write_records([good, {"ev": "??"}, "junk", None])
    t.close()
    assert n == 1
    assert tracing.load_records(path) == [good]


def test_existing_trace_id_recovery(tmp_path):
    """A --recover coordinator rejoins the ORIGINAL trace by reading the
    id back from the span log."""
    path = str(tmp_path / "t.jsonl")
    t1 = tracing.Tracer(service="coordinator", path=path)
    t1.start_span("coordinator.run")   # left unclosed: the crash shape
    t1.close()
    assert tracing.existing_trace_id(path) == t1.trace_id
    t2 = tracing.Tracer(trace_id=tracing.existing_trace_id(path),
                        service="coordinator", path=path)
    assert t2.trace_id == t1.trace_id
    assert tracing.existing_trace_id(str(tmp_path / "absent.jsonl")) == ""


def test_load_records_tolerates_torn_tail(tmp_path):
    path = str(tmp_path / "t.jsonl")
    with open(path, "w") as f:
        f.write(json.dumps({"ev": "I", "trace": "t", "span": "s",
                            "name": "a", "svc": "c", "ts_us": 1,
                            "args": {}}) + "\n")
        f.write('{"ev": "B", "trunc')     # torn final line
    recs = tracing.load_records(path)
    assert len(recs) == 1 and recs[0]["name"] == "a"


# ---------------------------------------------------------------------------
# Perfetto export
# ---------------------------------------------------------------------------
def test_to_trace_events_complete_tree_and_metadata(tmp_path):
    path = str(tmp_path / "t.jsonl")
    t = tracing.Tracer(service="coordinator", path=path)
    root = t.start_span("coordinator.run")
    t.emit("executor.first_step", start_us=root.start_us + 10,
           end_us=root.start_us + 50, parent=root, task="worker:0")
    t.instant("application.finished", parent=root,
              attrs={"status": "SUCCEEDED"})
    root.end()
    t.close()
    payload = tracing.to_trace_events(tracing.load_records(path))
    assert payload["unclosedSpans"] == []
    assert payload["traceId"] == t.trace_id
    xs = {e["name"]: e for e in payload["traceEvents"]
          if e.get("ph") == "X"}
    assert set(xs) == {"coordinator.run", "executor.first_step"}
    assert xs["executor.first_step"]["dur"] == 40
    assert xs["executor.first_step"]["args"]["parent"] == root.span_id
    # instant + process metadata present
    phs = {e["ph"] for e in payload["traceEvents"]}
    assert {"X", "i", "M"} <= phs
    # valid JSON end-to-end (the Perfetto loadability contract)
    assert json.loads(json.dumps(payload))["displayTimeUnit"] == "ms"


def test_unclosed_span_detection(tmp_path):
    path = str(tmp_path / "t.jsonl")
    t = tracing.Tracer(service="coordinator", path=path)
    t.start_span("task.lifecycle", task="worker:1")   # never ended
    done = t.start_span("session.epoch")
    done.end()
    t.close()
    payload = tracing.to_trace_events(tracing.load_records(path))
    assert payload["unclosedSpans"] == ["task.lifecycle"]
    assert [e["name"] for e in payload["traceEvents"]
            if e.get("ph") == "X"] == ["session.epoch"]


# ---------------------------------------------------------------------------
# RPC integration: trace context, observability hooks, rpc.slow
# ---------------------------------------------------------------------------
class _Service:
    def __init__(self):
        self.seen_ctx = None

    def ping(self, x: int = 0) -> int:
        self.seen_ctx = tracing.get_rpc_context()
        return x + 1

    def boom(self) -> None:
        raise ValueError("nope")


def _server_client(**client_kw):
    svc = _Service()
    requests = []
    server = RpcServer(svc, on_request=lambda m, s, ok:
                       requests.append((m, s, ok)))
    server.start()
    client = RpcClient("127.0.0.1", server.port, max_retries=2,
                       retry_sleep_s=0.05, **client_kw)
    return svc, server, client, requests


def test_trace_context_propagates_through_frames():
    """The 'tc' field rides the inner request next to 'gen'; the server
    parks it thread-locally around dispatch and clears it after."""
    svc, server, client, requests = _server_client()
    try:
        client.trace_context = ("trace123", "span456")
        assert client.call("ping", x=1) == 2
        assert svc.seen_ctx == ("trace123", "span456")
        # cleared between requests: an untraced call sees nothing
        client.trace_context = None
        client.call("ping", x=1)
        assert svc.seen_ctx is None
    finally:
        client.close()
        server.stop()


def test_on_request_hook_times_every_dispatch_including_errors():
    svc, server, client, requests = _server_client()
    try:
        client.call("ping", x=0)
        with pytest.raises(Exception):
            client.call("boom")
    finally:
        client.close()
        server.stop()
    assert [(m, ok) for m, _, ok in requests] == [("ping", True),
                                                  ("boom", False)]
    assert all(s >= 0 for _, s, _ in requests)


def test_on_latency_hook_fires_on_success_only():
    latencies = []
    svc, server, client, _ = _server_client(
        on_latency=lambda m, s: latencies.append((m, s)))
    try:
        client.call("ping", x=0)
        with pytest.raises(Exception):
            client.call("boom")
    finally:
        client.close()
        server.stop()
    assert [m for m, _ in latencies] == ["ping"]
    assert latencies[0][1] >= 0


def test_rpc_slow_fault_injects_latency_without_dropping():
    """rpc.slow: the deterministic exercise for latency histograms and
    spans — the call is delayed by amt seconds, then SUCCEEDS (no retry,
    no connection error)."""
    assert "rpc.slow" in faults.SITES
    faults.install(faults.parse_spec("rpc.slow=first:1,amt:0.08"))
    latencies = []
    svc, server, client, _ = _server_client(
        on_latency=lambda m, s: latencies.append(s))
    try:
        t0 = time.monotonic()
        assert client.call("ping", x=5) == 6       # fired: delayed
        slow_dt = time.monotonic() - t0
        assert client.call("ping", x=5) == 6       # past first:1 — fast
    finally:
        client.close()
        server.stop()
    assert slow_dt >= 0.08
    # the injected delay happens BEFORE the timed send: measured latency
    # reflects the genuine wire time, the wall-clock shows the injection
    assert len(latencies) == 2


def test_rpc_slow_conf_key_registered():
    from tony_tpu.conf import keys as K

    assert K.fault_key("rpc.slow") == "tony.fault.rpc-slow"
    assert "tony.fault.rpc-slow" in K.registry()


# ---------------------------------------------------------------------------
# Cold-start decomposition (cold_start_breakdown)
# ---------------------------------------------------------------------------
def _x(name, ts_us, dur_us=0, task="", svc="svc", **args):
    return {"ev": "X", "trace": "t1", "span": f"{name}@{ts_us}",
            "parent": "", "name": name, "svc": svc, "task": task,
            "ts_us": ts_us, "dur_us": dur_us, "args": dict(args)}


def _cold_start_records(task="worker:0"):
    """A synthetic but shape-faithful submit→first-step span tree
    (timestamps in µs; total 10 s)."""
    return [
        _x("client.submit", 0, 10_000_000, svc="client"),
        _x("client.stage", 100_000, 900_000, svc="client"),        # →1.0s
        _x("task.lifecycle", 2_000_000, 7_000_000, task=task,
           svc="coordinator"),
        _x("pool.lease", 2_100_000, 50_000, task=task,
           svc="coordinator", worker="w1"),
        _x("executor.run", 3_500_000, 6_000_000, task=task,
           svc="executor"),
        _x("executor.localize", 3_550_000, 200_000, task=task,
           svc="executor"),
        _x("executor.register", 3_600_000, 900_000, task=task,
           svc="executor"),
        _x("executor.user_process", 5_000_000, 4_800_000, task=task,
           svc="executor"),
        _x("executor.first_step", 9_000_000, 1_000_000, task=task,
           svc="executor"),
    ]


def test_cold_start_breakdown_phases_sum_exactly():
    bd = tracing.cold_start_breakdown(_cold_start_records())
    assert bd["task"] == "worker:0"
    assert bd["total_s"] == 10.0
    assert bd["phases"] == {"stage": 1.0, "provision": 1.0, "spawn": 1.5,
                            "register": 1.0, "launch": 0.5,
                            "user_boot": 5.0}
    # the property the BENCH artifact relies on: consecutive boundary
    # intervals — the phases sum EXACTLY to the headline
    assert round(sum(bd["phases"].values()), 6) == bd["total_s"]
    # raw (possibly overlapping) span durations ride along, incl. the
    # pool adoption span
    assert bd["span_durations"]["pool.lease"] == 0.05
    assert bd["span_durations"]["executor.localize"] == 0.2


def test_cold_start_breakdown_missing_phase_folds_forward():
    """A missing intermediate span folds its time into the next phase —
    the sum stays exact, nothing is silently dropped."""
    recs = [r for r in _cold_start_records()
            if r["name"] not in ("task.lifecycle", "executor.register")]
    bd = tracing.cold_start_breakdown(recs)
    assert "provision" not in bd["phases"]
    assert "register" not in bd["phases"]
    assert round(sum(bd["phases"].values()), 6) == bd["total_s"] == 10.0
    # lifecycle's slice lands in spawn, register's in launch
    assert bd["phases"]["spawn"] == 2.5
    assert bd["phases"]["launch"] == 1.5


def test_cold_start_breakdown_anchors_on_first_finishing_task():
    """Multi-task gang: the breakdown follows the task whose first_step
    ENDED first, ignoring the other task's boundary spans."""
    recs = _cold_start_records(task="worker:1")
    # worker:0 reaches its first step earlier
    recs += [
        _x("executor.run", 2_500_000, 6_000_000, task="worker:0",
           svc="executor"),
        _x("executor.register", 2_600_000, 400_000, task="worker:0",
           svc="executor"),
        _x("executor.user_process", 3_100_000, 4_000_000, task="worker:0",
           svc="executor"),
        _x("executor.first_step", 6_000_000, 1_000_000, task="worker:0",
           svc="executor"),
    ]
    bd = tracing.cold_start_breakdown(recs)
    assert bd["task"] == "worker:0"
    assert bd["total_s"] == 7.0
    assert bd["phases"]["spawn"] == 1.5          # 1.0 (stage end) → 2.5
    assert round(sum(bd["phases"].values()), 6) == 7.0


def test_cold_start_breakdown_raises_without_anchor_spans():
    with pytest.raises(RuntimeError, match="cold-start breakdown needs"):
        tracing.cold_start_breakdown(
            [_x("client.submit", 0, 1_000_000, svc="client")])
    with pytest.raises(RuntimeError, match="cold-start breakdown needs"):
        tracing.cold_start_breakdown(
            [_x("executor.first_step", 0, 1_000_000, task="worker:0")])


def test_cold_start_breakdown_clamps_out_of_window_boundaries():
    """A boundary past the first-step end (e.g. a straggler's register)
    is clamped into the window; monotonicity and the exact sum hold."""
    recs = _cold_start_records()
    for r in recs:
        if r["name"] == "executor.user_process":
            r["ts_us"] = 11_000_000          # pathological: after the end
    bd = tracing.cold_start_breakdown(recs)
    assert round(sum(bd["phases"].values()), 6) == bd["total_s"] == 10.0


# ---------------------------------------------------------------------------
# The user process's own spans: user_boot split, executor forwarding
# ---------------------------------------------------------------------------
def _user_spans(task="worker:0"):
    """What the executor forwards of a user process's boot, inside the
    5 s user_boot phase (5.0 s → 10.0 s) of ``_cold_start_records``."""
    return [
        _x("user.pre_import", 5_100_000, 1_900_000, task=task),  # → 7.0
        _x("user.init_state", 7_200_000, 1_000_000, task=task),  # → 8.2
        # inside init_state: its compile is the child, not double-counted
        _x("user.compile", 7_300_000, 600_000, task=task, stage="backend"),
        _x("user.compile", 8_500_000, 1_200_000, task=task, stage="trace"),
        # after the first step's end (10.0 s): outside the phase
        _x("user.compile", 10_500_000, 300_000, task=task, stage="lower"),
        # another task's span never counts
        _x("user.compile", 5_000_000, 4_000_000, task="worker:9"),
    ]


def test_cold_start_breakdown_splits_user_boot_into_self_times():
    bd = tracing.cold_start_breakdown(_cold_start_records()
                                      + _user_spans())
    assert bd["user_boot"] == {
        "user.pre_import": 1.9,
        "user.init_state": 0.4,          # 1.0 less the 0.6 inside it
        "user.compile": 1.8,             # 0.6 + 1.2; the late one is out
        "unattributed": 0.9}
    assert round(sum(bd["user_boot"].values()), 6) \
        == bd["phases"]["user_boot"] == 5.0
    # The compiles' self time by stage: the backend compile inside
    # init_state and the trace after it; the late lowering is out.
    assert bd["user_boot_compile"] == {"trace": 1.2, "lower": 0.0,
                                       "backend": 0.6}


def test_cold_start_breakdown_is_unchanged_without_user_spans():
    plain = tracing.cold_start_breakdown(_cold_start_records())
    assert "user_boot" not in plain
    with_spans = tracing.cold_start_breakdown(_cold_start_records()
                                              + _user_spans())
    for key in ("total_s", "task", "phases", "span_durations"):
        assert with_spans[key] == plain[key], key
    assert list(plain) == ["total_s", "task", "phases", "span_durations"]
    assert plain["phases"]["user_boot"] == 5.0
    assert plain["span_durations"]["executor.first_step"] == 1.0


def _executor(tmp_path, monkeypatch, trace_id):
    from tony_tpu import constants
    from tony_tpu.executor.executor import TaskExecutor

    monkeypatch.chdir(tmp_path)
    env = {constants.JOB_NAME: "worker", constants.TASK_INDEX: "0",
           constants.TASK_NUM: "1", constants.COORDINATOR_HOST: "127.0.0.1",
           constants.COORDINATOR_PORT: "1"}
    if trace_id:
        env[constants.TRACE_ID_ENV] = trace_id
    ex = TaskExecutor(env=env)
    ex._metrics_file = str(tmp_path / "user-metrics.json")
    ex._run_span = ex.tracer.start_span("executor.run", task=ex.task_id)
    return ex


def _write_user_metrics(ex, pid, spans, spans_pid=None):
    """The user process's two files, as telemetry.write_stats_once leaves
    them: the span list, then the metrics file that counts it."""
    import json

    from tony_tpu import telemetry

    with open(telemetry.spans_file(ex._metrics_file), "w",
              encoding="utf-8") as f:
        json.dump({"pid": spans_pid or pid, "spans": spans}, f)
    with open(ex._metrics_file, "w", encoding="utf-8") as f:
        json.dump({"pid": pid, "device_count": 1.0,
                   "spans_kept": len(spans), "spans_dropped": 0}, f)


def _user_span(seq, name, start, end, **args):
    return {"seq": seq, "name": name, "start": start, "end": end,
            "args": args}


def test_executor_forwards_each_user_span_once_under_the_run_span(
        tmp_path, monkeypatch):
    import os

    from tony_tpu import telemetry

    ex = _executor(tmp_path, monkeypatch, trace_id="feedfacefeedface")
    boot = [_user_span(1, "user.pre_import", 100.0, 102.5),
            _user_span(2, "user.compile", 103.0, 103.25, stage="backend",
                       fun_name="jit(step)", step=0)]
    _write_user_metrics(ex, 4242, boot)
    ex._progress_beacon()
    # The same files: nothing new, and the list is not even opened.
    os.unlink(telemetry.spans_file(ex._metrics_file))
    ex._progress_beacon()
    _write_user_metrics(ex, 4242, boot + [
        _user_span(3, "user.compile", 900.0, 901.0, stage="backend",
                   fun_name="jit(step)", step=4000),
        {"seq": "garbage"}])
    ex._progress_beacon()
    got = [r for r in ex.tracer.drain() if r["name"].startswith("user.")]
    assert [(r["name"], r["ts_us"], r["dur_us"]) for r in got] == [
        ("user.pre_import", 100_000_000, 2_500_000),
        ("user.compile", 103_000_000, 250_000),
        ("user.compile", 900_000_000, 1_000_000)]
    assert all(r["parent"] == ex._run_span.span_id
               and r["task"] == "worker:0" and r["ev"] == "X"
               and r["trace"] == "feedfacefeedface" for r in got)
    assert got[2]["args"] == {"stage": "backend", "fun_name": "jit(step)",
                              "step": 4000}
    # A relaunched user process (another pid) numbers its spans anew; a
    # list that the process before it left behind is not its list.
    relaunched = [_user_span(1, "user.pre_import", 2000.0, 2001.0)]
    _write_user_metrics(ex, 4343, relaunched, spans_pid=4242)
    ex._progress_beacon()
    assert ex.tracer.drain() == []
    _write_user_metrics(ex, 4343, relaunched)
    ex._progress_beacon()
    again = [r for r in ex.tracer.drain() if r["name"].startswith("user.")]
    assert [r["ts_us"] for r in again] == [2_000_000_000]


def test_executor_forwards_no_user_span_with_tracing_off(tmp_path,
                                                         monkeypatch):
    # tony.trace.enabled=false: the coordinator exports no trace id.
    ex = _executor(tmp_path, monkeypatch, trace_id="")
    assert not ex.tracer.enabled
    _write_user_metrics(ex, 4242, [
        _user_span(1, "user.pre_import", 100.0, 102.5)])
    ex._progress_beacon()
    assert ex.tracer.drain() == []
    assert ex._user_span_fence == (None, 0)


def test_cli_cold_start_prints_user_boot_sub_lines(tmp_path, capsys):
    import json

    from tony_tpu import constants
    from tony_tpu.cli.main import main

    job_dir = tmp_path / constants.HISTORY_INTERMEDIATE / "app_x"
    job_dir.mkdir(parents=True)
    with open(job_dir / constants.TRACE_FILE, "w", encoding="utf-8") as f:
        for rec in _cold_start_records() + _user_spans():
            f.write(json.dumps(rec) + "\n")
    assert main(["trace", "app_x", "--cold-start",
                 "--history-root", str(tmp_path)]) == 0
    lines = capsys.readouterr().out.splitlines()
    at = next(i for i, line in enumerate(lines)
              if line.startswith("  user_boot"))
    assert lines[at].split()[1] == "5.00s"
    under = lines[at + 1:at + 8]
    sub = {line.split()[0]: line.split()[1] for line in under
           if not line.startswith("      ")}
    assert sub == {"user.pre_import": "1.90s", "user.init_state": "0.40s",
                   "user.compile": "1.80s", "unattributed": "0.90s"}
    assert all(line.startswith("    ") for line in under)
    # Under the compile line, its three stages (the late ``lower`` span
    # lies outside the phase).
    at_compile = next(i for i, line in enumerate(under)
                      if line.split()[0] == "user.compile")
    assert [line.split() for line in under[at_compile + 1:at_compile + 4]] \
        == [["trace", "1.20s"], ["lower", "0.00s"], ["backend", "0.60s"]]
