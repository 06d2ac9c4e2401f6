"""Step-time attribution pipeline units (tony_tpu/profiling/ +
telemetry phase accounting + the on-demand capture path) and the slow
e2e drill: `tony-tpu profile` against a live 2-task job.

Units cover: phase ring bounds and sum-to-wall, the bottleneck
classifier's golden matrix (all five verdicts), the executor's
profile-directive dedup, the beacon round-trip into Prometheus text /
metrics.live / perf.json, profile.start refusal shapes, and the
profile.capture fault site degrading cleanly.
"""

import collections
import json
import os
import threading
import time

import pytest

from tony_tpu import constants, faults, telemetry
from tony_tpu.conf import keys as K
from tony_tpu.conf.config import TonyTpuConfig
from tony_tpu.events.events import EventType
from tony_tpu.profiling import (CKPT_BOUND, COMMS_BOUND, COMPUTE_BOUND,
                                COORD_HEALTHY, HEARTBEAT_BOUND,
                                INPUT_BOUND, JOURNAL_BOUND,
                                RENDEZVOUS_BOUND, RPC_BOUND,
                                UNDERUTILIZED, build_perf_report,
                                classify, classify_coord, phase_fractions)

pytestmark = pytest.mark.faults


@pytest.fixture(autouse=True)
def _clean_telemetry_state():
    """Phase/profile/step accounting is module-global in the user
    process by design; tests must not leak state into each other (or
    into test_telemetry's derivation checks)."""
    telemetry._reset_phase_state()
    telemetry._reset_profile_state()
    telemetry._steps.update(count=0, busy_s=0.0, flops=0.0, tokens=0.0,
                            first_start=0.0, last_end=0.0,
                            first_end_wall=0.0)
    yield
    telemetry._reset_phase_state()
    telemetry._reset_profile_state()
    telemetry._steps.update(count=0, busy_s=0.0, flops=0.0, tokens=0.0,
                            first_start=0.0, last_end=0.0,
                            first_end_wall=0.0)
    faults.uninstall()


# ---------------------------------------------------------------------------
# Phase accounting
# ---------------------------------------------------------------------------
def test_phases_sum_exactly_to_wall_with_default_compute():
    for _ in range(3):
        with telemetry.phase("data_wait"):
            time.sleep(0.01)
        with telemetry.step():
            time.sleep(0.02)
    st = telemetry.phase_stats()
    assert st["steps"] == 3.0
    cum = st["cum"]
    # data.py-style between-step wait attributed to the following step
    assert cum["data_wait"] >= 0.015
    # step_compute defaults to the step() busy time when not explicit
    assert cum["step_compute"] >= 0.04
    assert cum["other"] >= 0.0
    assert sum(cum.values()) == pytest.approx(st["wall_s"], abs=1e-9)
    # recent window carries per-step means that also sum to the wall
    recent = st["recent"]
    assert sum(recent.values()) == pytest.approx(st["recent_wall_s"],
                                                 abs=1e-9)


def test_explicit_step_compute_and_block_until_ready_anchor():
    import jax

    with telemetry.step():
        with telemetry.phase("step_compute") as p:
            out = p.block_until_ready(jax.numpy.ones(4) * 2)
    assert float(out.sum()) == 8.0
    cum = telemetry.phase_stats()["cum"]
    assert "step_compute" in cum and cum["step_compute"] > 0


def test_phase_ring_is_bounded_while_cumulative_keeps_counting(
        monkeypatch):
    monkeypatch.setattr(telemetry, "_phase_ring",
                        collections.deque(maxlen=8))
    for _ in range(30):
        with telemetry.step():
            pass
    st = telemetry.phase_stats()
    assert st["steps"] == 30.0                      # cumulative: all 30
    assert st["recent_steps"] == 8.0                # ring: bounded
    assert len(telemetry._phase_ring) == 8


def test_first_step_interval_excludes_preceding_compile_time():
    # Work BEFORE the first step (compile/restore) is never attributed.
    time.sleep(0.03)
    with telemetry.step():
        time.sleep(0.01)
    st = telemetry.phase_stats()
    assert st["wall_s"] < 0.03


# ---------------------------------------------------------------------------
# Bottleneck classifier: golden matrix for all five verdicts
# ---------------------------------------------------------------------------
GOLDEN = [
    ({"data_wait": 0.20, "h2d": 0.05, "step_compute": 0.70,
      "other": 0.05}, INPUT_BOUND),
    ({"ckpt_stall": 0.12, "step_compute": 0.85, "other": 0.03},
     CKPT_BOUND),
    ({"comms": 0.25, "step_compute": 0.70, "other": 0.05}, COMMS_BOUND),
    ({"step_compute": 0.95, "data_wait": 0.02, "other": 0.03},
     COMPUTE_BOUND),
    ({"step_compute": 0.50, "other": 0.50}, UNDERUTILIZED),
]


@pytest.mark.parametrize("fractions,expected", GOLDEN)
def test_classifier_golden_matrix(fractions, expected):
    v = classify(fractions)
    assert v["category"] == expected
    assert v["evidence"], "every verdict must be evidence-backed"
    assert 0 < v["confidence"] <= 1


# ---------------------------------------------------------------------------
# Control-plane classifier: golden matrix for the four coordinator
# verdicts + the healthy case (coordinator/coordphases.py fractions)
# ---------------------------------------------------------------------------
COORD_GOLDEN = [
    ({"journal_fsync": 0.25, "rpc_serve": 0.10, "hb_scan": 0.02,
      "beacon_fold": 0.03, "idle": 0.55, "other": 0.05},
     JOURNAL_BOUND),
    ({"hb_scan": 0.12, "beacon_fold": 0.10, "journal_fsync": 0.05,
      "rpc_serve": 0.08, "idle": 0.60, "other": 0.05},
     HEARTBEAT_BOUND),
    ({"rendezvous_barrier": 0.30, "journal_fsync": 0.05,
      "rpc_serve": 0.10, "idle": 0.50, "other": 0.05},
     RENDEZVOUS_BOUND),
    ({"rpc_serve": 0.40, "journal_fsync": 0.08, "hb_scan": 0.02,
      "idle": 0.45, "other": 0.05}, RPC_BOUND),
    ({"journal_fsync": 0.02, "rpc_serve": 0.03, "hb_scan": 0.01,
      "beacon_fold": 0.01, "idle": 0.90, "other": 0.03},
     COORD_HEALTHY),
]


@pytest.mark.parametrize("fractions,expected", COORD_GOLDEN)
def test_coord_classifier_golden_matrix(fractions, expected):
    v = classify_coord(fractions)
    assert v["category"] == expected
    assert v["evidence"], "every coord verdict must be evidence-backed"
    assert 0 < v["confidence"] <= 1
    # the advice names a restructure/knob, never an empty shrug
    assert v["advice"]


def test_coord_classifier_largest_fired_wins_and_names_the_others():
    v = classify_coord({"journal_fsync": 0.20, "rpc_serve": 0.35,
                        "idle": 0.40, "other": 0.05})
    assert v["category"] == RPC_BOUND
    assert any("JOURNAL_BOUND" in e for e in v["evidence"])


def test_coord_classifier_advice_names_the_future_knobs():
    assert "group-commit" in classify_coord(
        {"journal_fsync": 0.3})["advice"]
    assert "batch/coalesce" in classify_coord(
        {"hb_scan": 0.1, "beacon_fold": 0.1})["advice"]
    assert "incremental cluster-spec" in classify_coord(
        {"rendezvous_barrier": 0.3})["advice"]


def test_classifier_largest_waste_class_wins_and_names_the_others():
    v = classify({"data_wait": 0.18, "ckpt_stall": 0.30,
                  "step_compute": 0.50, "other": 0.02})
    assert v["category"] == CKPT_BOUND
    assert any("INPUT_BOUND" in e for e in v["evidence"])


def test_perf_report_totals_sum_to_wall():
    per_task = {
        "worker:0": {"cum": {"data_wait": 2.0, "step_compute": 7.0,
                             "other": 1.0}, "wall_s": 10.0, "steps": 100},
        "worker:1": {"cum": {"data_wait": 1.0, "step_compute": 8.0,
                             "other": 1.0}, "wall_s": 10.0, "steps": 100},
    }
    doc = build_perf_report("app_x", per_task, status="SUCCEEDED")
    assert sum(doc["phases_s"].values()) == pytest.approx(
        doc["wall_s"], rel=1e-6)
    assert doc["verdict"]["category"] == INPUT_BOUND
    assert doc["tasks"]["worker:0"]["verdict"] == INPUT_BOUND
    assert doc["tasks"]["worker:1"]["fractions"]["step_compute"] == \
        pytest.approx(0.8)
    assert doc["steps"] == 200.0


def test_phase_fractions_degrades_on_garbage():
    assert phase_fractions({}, 0) == {}
    assert phase_fractions({"a": "x"}, "nan-ish") == {}
    assert phase_fractions({"a": 1.0, "b": "bad"}, 2.0) == {"a": 0.5}


# ---------------------------------------------------------------------------
# On-demand capture: request intake, step-boundary arming, fault site
# ---------------------------------------------------------------------------
def _write_request(path, req_id, steps, dest):
    with open(path, "w", encoding="utf-8") as f:
        json.dump({"id": req_id, "steps": steps, "dir": dest}, f)


def test_capture_arms_at_step_boundary_and_reports_artifact(tmp_path):
    import jax  # noqa: F401 — the capture requires a live jax

    req = str(tmp_path / "req.json")
    dest = str(tmp_path / "cap")
    _write_request(req, 1, 2, dest)
    telemetry._poll_profile_request(req)
    # re-polling the SAME id must not re-arm (directive re-rides beats)
    telemetry._poll_profile_request(req)
    for _ in range(4):
        with telemetry.step():
            pass
    prof = telemetry.profile_state()
    assert prof["status"] == "captured" and prof["dir"] == dest
    assert sum(len(fs) for _, _, fs in os.walk(dest)) > 0
    # an older/equal id never supersedes
    _write_request(req, 1, 2, str(tmp_path / "cap2"))
    telemetry._poll_profile_request(req)
    assert telemetry.profile_state()["status"] == "captured"


def test_capture_fault_site_degrades_to_failed_and_training_continues(
        tmp_path):
    faults.install(faults.FaultInjector({"profile.capture": "first:1"}))
    req = str(tmp_path / "req.json")
    _write_request(req, 7, 3, str(tmp_path / "cap"))
    telemetry._poll_profile_request(req)
    for _ in range(5):
        with telemetry.step():
            pass
    prof = telemetry.profile_state()
    assert prof["status"] == "failed"
    assert "injected fault at profile.capture" in prof["error"]
    # training kept counting steps through the failure
    assert telemetry.step_stats()["steps_completed"] == 5.0


def test_profile_capture_site_is_registered_and_conf_drivable():
    assert "profile.capture" in faults.SITES
    conf = TonyTpuConfig()
    conf.set(K.FAULT_PROFILE_CAPTURE, "at:1")
    assert faults.install_from_conf(conf) is True
    with pytest.raises(faults.InjectedFault):
        faults.check("profile.capture")


def test_executor_profile_directive_dedup(tmp_path, monkeypatch):
    """The directive re-rides every heartbeat until the result lands;
    the executor must write the request file exactly once per id."""
    from tony_tpu.executor.executor import TaskExecutor

    monkeypatch.chdir(tmp_path)
    ex = TaskExecutor(env={
        constants.JOB_NAME: "worker", constants.TASK_INDEX: "1",
        constants.TASK_NUM: "2", constants.COORDINATOR_HOST: "127.0.0.1",
        constants.COORDINATOR_PORT: "1",
    })
    path = ex._profile_request_path()
    ex._on_profile_directive({"id": 3, "steps": 2, "dir": "/x"})
    first = open(path).read()
    os.unlink(path)                       # detect any re-write
    ex._on_profile_directive({"id": 3, "steps": 2, "dir": "/x"})
    assert not os.path.exists(path), "duplicate id must not re-write"
    ex._on_profile_directive({"id": 4, "steps": 5, "dir": "/y"})
    assert json.load(open(path))["id"] == 4
    ex._on_profile_directive({"id": "garbage", "steps": 1})
    assert json.load(open(path))["id"] == 4
    assert json.loads(first)["id"] == 3


# ---------------------------------------------------------------------------
# Coordinator: beacon round-trip → Prometheus / metrics.live / perf.json
# ---------------------------------------------------------------------------
def _coord(tmp_path, **extra):
    from tony_tpu.cluster.local import LocalProcessBackend
    from tony_tpu.coordinator.coordinator import Coordinator

    conf = TonyTpuConfig()
    conf.set("tony.worker.instances", 2)
    conf.set("tony.worker.command", "true")
    for k, v in extra.items():
        conf.set(k, v)
    backend = LocalProcessBackend(str(tmp_path / "work"))
    return Coordinator(conf, "app_prof", backend,
                       str(tmp_path / "history"), user="t")


def _close(coord):
    coord.journal.close()
    coord.rpc._server.server_close()


_PHASE_BEACON = {
    "steps": 10, "age_s": 0.1,
    "phases": {"cum": {"data_wait": 2.0, "step_compute": 6.0,
                       "other": 0.5},
               "wall_s": 8.5, "steps": 10,
               "recent": {"data_wait": 0.2, "step_compute": 0.6,
                          "other": 0.05},
               "recent_wall_s": 0.85},
}


def test_beacon_roundtrip_prometheus_live_view_and_perf_json(tmp_path):
    coord = _coord(tmp_path)
    events = []
    coord.events.emit = events.append
    try:
        coord.register_worker_spec("worker:0", "h", 1, session_id=0)
        coord.register_worker_spec("worker:1", "h", 2, session_id=0)
        res = coord.profile_start(0, "")
        assert res["ok"] and res["task"] == "worker:0"
        assert res["steps"] == 5          # tony.profile.default-steps
        # the directive rides the target's beats (and only the target's)
        hb = coord.heartbeat("worker:0", session_id=0)
        assert hb["profile"]["id"] == res["id"]
        assert coord.heartbeat("worker:1", session_id=0) is True
        # phases + capture result ride one beacon back
        beacon = dict(_PHASE_BEACON)
        beacon["profile"] = {"id": res["id"], "status": "captured",
                             "dir": res["dir"], "steps": 5}
        coord.heartbeat("worker:0", session_id=0, progress=beacon)
        # Prometheus text exposition carries the per-phase gauges
        text = coord.metrics.render()
        assert ('tony_step_phase_seconds{app="app_prof",'
                'phase="data_wait",task="worker:0"} 2') in text
        assert ('tony_step_phase_seconds{app="app_prof",'
                'phase="step_compute",task="worker:0"} 6') in text
        # metrics.live: per-task fractions + the live job verdict
        live = coord.metrics_live()
        row = next(t for t in live["tasks"] if t["task"] == "worker:0")
        assert row["phases"]["data_wait"] == pytest.approx(0.2353,
                                                           abs=1e-3)
        assert live["perf"]["verdict"] == INPUT_BOUND
        # the top renderer shows the verdict + a phase bar
        from tony_tpu.cli.main import _render_top

        frame = _render_top(live)
        assert "INPUT_BOUND" in frame and "PHASES" in frame
        assert "d" in frame and "C" in frame
        # terminal transition: TASK_PROFILED emitted once, directive
        # stops riding, status surface reports captured
        profiled = [e for e in events
                    if e.type == EventType.TASK_PROFILED]
        assert len(profiled) == 1
        assert profiled[0].payload["status"] == "captured"
        coord.heartbeat("worker:0", session_id=0, progress=beacon)
        assert len([e for e in events
                    if e.type == EventType.TASK_PROFILED]) == 1
        assert coord.heartbeat("worker:0", session_id=0) is True
        st = coord.profile_status()
        assert st["requests"][0]["status"] == "captured"
        # perf.json at finish: totals sum to wall, verdict attached
        coord.final_status = coord.session.status
        coord._write_perf_report()
        doc = json.load(open(os.path.join(coord.job_dir,
                                          constants.PERF_FILE)))
        assert sum(doc["phases_s"].values()) == pytest.approx(
            doc["wall_s"], rel=0.05)
        assert doc["verdict"]["category"] == INPUT_BOUND
        # ... and the diagnosis bundle attaches it as the perf advisory
        from tony_tpu import diagnosis

        incident = diagnosis.diagnose_job_dir(coord.job_dir,
                                              app_id="app_prof",
                                              provisional=True)
        assert incident["perf"]["verdict"] == INPUT_BOUND
        assert "INPUT_BOUND" in diagnosis.render_text(incident)
    finally:
        _close(coord)


def test_profile_start_refusal_shapes(tmp_path):
    coord = _coord(tmp_path, **{K.PROFILE_ENABLED: False})
    try:
        res = coord.profile_start(0, "")
        assert not res["ok"] and "disabled" in res["message"]
    finally:
        _close(coord)
    coord = _coord(tmp_path / "b", **{K.PROFILE_MAX_ARTIFACTS: 1})
    try:
        coord.register_worker_spec("worker:0", "h", 1, session_id=0)
        assert not coord.profile_start(0, "worker:9")["ok"]
        # at the artifact ceiling the request is refused
        os.makedirs(os.path.join(coord.job_dir, "profile",
                                 "ondemand-000-old"))
        res = coord.profile_start(0, "")
        assert not res["ok"] and "max-artifacts" in res["message"]
    finally:
        _close(coord)


# ---------------------------------------------------------------------------
# Slow e2e: live capture + INPUT_BOUND flip, through the real CLI
# ---------------------------------------------------------------------------
@pytest.mark.slow
@pytest.mark.timeout_s(170)
def test_e2e_profile_live_job_and_input_bound_verdict(tmp_path, capsys):
    """The acceptance drill: a 2-task job with an injected 50 ms/step
    input stall runs; `tony-tpu profile` captures N steps from a LIVE
    task (artifact in the job dir, portal lists it), an injected
    profile.capture failure on the other task degrades cleanly, `top`
    shows INPUT_BOUND live, and at finish perf.json phase totals sum to
    within 5% of wall with the INPUT_BOUND verdict in `diagnose`."""
    import urllib.request

    from tony_tpu.cli.main import main as cli_main
    from tony_tpu.portal import PortalServer

    from test_e2e import make_conf, submit

    conf = make_conf(tmp_path, "train_phases.py", workers=2, extra={
        K.TASK_HEARTBEAT_INTERVAL_MS: 200,
        K.METRICS_EXPORT_INTERVAL_S: 0.3,
        # the capture on worker:0 fails by injection; worker:1 works
        K.FAULT_PROFILE_CAPTURE: "first:1,task:worker:0",
        K.EXECUTION_ENV: "TONY_TEST_STEPS=400,"
                         "TONY_TEST_DATA_STALL_S=0.05,"
                         "TONY_TELEMETRY_INTERVAL_S=0.2",
    })
    workdir = str(tmp_path / "work")
    history_root = str(tmp_path / "history")
    result = {}

    def _run():
        client, rec, code = submit(conf, tmp_path)
        result.update(app_id=rec.app_id, code=code)

    runner = threading.Thread(target=_run, daemon=True)
    runner.start()

    def _wait_for(pred, timeout_s=60, what="condition"):
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            v = pred()
            if v:
                return v
            time.sleep(0.2)
        raise AssertionError(f"timed out waiting for {what}")

    jobs_dir = os.path.join(workdir, "jobs")
    app_id = _wait_for(
        lambda: (os.listdir(jobs_dir)[:1] or [None])[0]
        if os.path.isdir(jobs_dir) else None, what="job dir")
    job_dir = os.path.join(history_root, "intermediate", app_id)

    _wait_for(lambda: os.path.exists(
        os.path.join(workdir, "jobs", app_id, "coordinator.addr")),
        what="coordinator address")

    # -- live capture from worker:1 (no restart) ----------------------
    rc = cli_main(["profile", app_id, "--steps", "3",
                   "--task", "worker:1", "--workdir", workdir,
                   "--timeout", "60"])
    out = capsys.readouterr()
    assert rc == 0, f"profile failed: {out.out}\n{out.err}"
    assert "captured:" in out.out
    ondemand = [d for d in os.listdir(os.path.join(job_dir, "profile"))
                if d.startswith("ondemand-")]
    assert ondemand, "artifact must land under <job_dir>/profile"
    art = os.path.join(job_dir, "profile", ondemand[0])
    assert sum(len(fs) for _, _, fs in os.walk(art)) > 0

    # -- portal lists it at /profile/<app> ----------------------------
    portal = PortalServer(history_root, port=0, mover_interval_s=3600,
                          purger_interval_s=3600)
    portal.start()
    try:
        with urllib.request.urlopen(
                f"{portal.url}/profile/{app_id}?format=json",
                timeout=10) as r:
            listed = json.loads(r.read().decode())
        assert any(t["name"].startswith("ondemand-") for t in listed)
    finally:
        portal.stop()

    # -- injected capture failure on worker:0 degrades cleanly --------
    rc = cli_main(["profile", app_id, "--steps", "2",
                   "--task", "worker:0", "--workdir", workdir,
                   "--timeout", "60"])
    out = capsys.readouterr()
    assert rc == 1 and "FAILED" in out.err
    assert "injected fault at profile.capture" in out.err

    # -- live INPUT_BOUND verdict in top ------------------------------
    def _top_verdict():
        if cli_main(["top", app_id, "--workdir", workdir,
                     "--once"]) != 0:
            capsys.readouterr()
            return None
        frame = capsys.readouterr().out
        return frame if "INPUT_BOUND" in frame else None

    frame = _wait_for(_top_verdict, timeout_s=60,
                      what="INPUT_BOUND in top")
    assert "perf: INPUT_BOUND" in frame

    # -- job finishes despite both captures ---------------------------
    runner.join(timeout=120)
    assert not runner.is_alive(), "job did not finish"
    assert result["code"] == 0, f"job failed: {result}"

    # perf.json: totals sum to within 5% of wall, INPUT_BOUND verdict
    doc = json.load(open(os.path.join(job_dir, constants.PERF_FILE)))
    assert sum(doc["phases_s"].values()) == pytest.approx(
        doc["wall_s"], rel=0.05)
    assert doc["verdict"]["category"] == INPUT_BOUND
    assert doc["fractions"]["data_wait"] > 0.15

    # ... and diagnose (on the finished job) carries the perf advisory
    assert cli_main(["diagnose", app_id, "--history-root",
                     history_root, "--fresh"]) == 0
    out = capsys.readouterr()
    assert "perf advisory: INPUT_BOUND" in out.out
