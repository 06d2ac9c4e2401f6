"""Control-plane width drills: coordinator self-observation
(coordinator/coordphases.py) + the virtual-executor harness
(executor/virtual.py, cluster/local.py VirtualExecutorBackend).

Units cover the phase accountant's fold discipline (sum-to-wall,
nested-phase disjointness, dispatch subtraction), the journal observer,
the histogram quantile helper, and the coord.slow-tick fault site. The
acceptance drill runs a REAL coordinator against 256 beat-only virtual
tasks — real RPC frames, real journal records — and asserts the
span/phase invariants at width in tier-1 time.
"""

import json
import os
import threading
import time

import pytest

from tony_tpu import constants, faults, tracing
from tony_tpu.cluster.local import VirtualExecutorBackend
from tony_tpu.conf import keys as K
from tony_tpu.conf.config import TonyTpuConfig
from tony_tpu.coordinator.coordinator import Coordinator
from tony_tpu.coordinator.coordphases import (CoordPhases,
                                              histogram_quantile)
from tony_tpu.coordinator.journal import SessionJournal
from tony_tpu.coordinator.session import SessionStatus
from tony_tpu.profiling import JOURNAL_BOUND, classify_coord

pytestmark = pytest.mark.faults

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")


@pytest.fixture(autouse=True)
def _clean_faults():
    yield
    faults.uninstall()


# ---------------------------------------------------------------------------
# CoordPhases: fold discipline
# ---------------------------------------------------------------------------
def test_tick_fold_sums_exactly_to_wall():
    cp = CoordPhases(ring_ticks=8)
    cp.tick_done()                       # anchor
    with cp.phase("hb_scan"):
        time.sleep(0.01)
    with cp.phase("idle"):
        time.sleep(0.02)
    cp.tick_done()
    snap = cp.snapshot()
    assert snap["ticks"] == 1.0
    cum = snap["cum"]
    assert cum["hb_scan"] >= 0.009
    assert cum["idle"] >= 0.019
    assert cum["other"] >= 0.0
    assert sum(cum.values()) == pytest.approx(snap["wall_s"], abs=1e-9)


def test_nested_phases_stay_disjoint():
    """A journal append inside hb_scan books to journal_fsync and is
    SUBTRACTED from hb_scan — phases never double-count."""
    cp = CoordPhases(ring_ticks=8)
    cp.tick_done()
    with cp.phase("hb_scan"):
        time.sleep(0.01)
        with cp.phase("journal_fsync"):
            time.sleep(0.02)
    cp.tick_done()
    cum = cp.snapshot()["cum"]
    assert cum["journal_fsync"] >= 0.019
    assert cum["hb_scan"] < 0.02          # the nested 20ms was removed
    assert sum(cum.values()) == pytest.approx(
        cp.snapshot()["wall_s"], abs=1e-9)


def test_dispatch_booking_subtracts_handler_phase_work():
    """note_dispatch (the _on_rpc_request seam) books only the dispatch
    wall NOT already attributed — the beacon fold inside a heartbeat
    handler lands in beacon_fold, not twice."""
    cp = CoordPhases(ring_ticks=8)
    cp.tick_done()
    t0 = time.monotonic()
    with cp.phase("beacon_fold"):
        time.sleep(0.02)
    seconds = time.monotonic() - t0 + 0.01   # dispatch wall incl. 10ms
    cp.note_dispatch("task_executor_heartbeat", seconds)
    cp.tick_done()
    snap = cp.snapshot()
    cum = snap["cum"]
    assert cum["beacon_fold"] >= 0.019
    assert 0.0 <= cum["rpc_serve"] <= 0.015
    assert snap["beats_total"] == 1
    assert sum(cum.values()) == pytest.approx(snap["wall_s"], abs=1e-9)


def test_concurrent_overattribution_widens_wall_never_negative_other():
    """Handler-thread work concurrent with the tick can exceed the tick
    interval; the fold widens the wall (telemetry._fold_phases
    discipline) instead of inventing a negative other bucket."""
    cp = CoordPhases(ring_ticks=8)
    cp.tick_done()

    def handler():
        with cp.phase("rpc_serve"):
            time.sleep(0.05)

    threads = [threading.Thread(target=handler, daemon=True)
               for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    cp.tick_done()
    snap = cp.snapshot()
    cum = snap["cum"]
    assert cum["other"] >= 0.0
    assert cum["rpc_serve"] >= 0.15       # 4 × 50ms concurrent
    assert sum(cum.values()) == pytest.approx(snap["wall_s"], abs=1e-9)


def test_journal_observer_feeds_phase_histogram_and_rates(tmp_path):
    cp = CoordPhases(ring_ticks=8)
    cp.tick_done()
    j = SessionJournal(str(tmp_path / "j.jsonl"),
                       observer=cp.note_journal_append)
    for i in range(5):
        j.task(f"worker:{i}", "SCHEDULED", 0)
    j.close()
    cp.tick_done()
    snap = cp.snapshot()
    assert snap["journal_records_total"] == 5
    assert snap["journal_bytes_total"] > 100
    assert snap["cum"]["journal_fsync"] > 0
    assert snap["fsync"]["count"] == 5
    assert snap["journal_fsync_p99_s"] > 0


def test_journal_observer_failure_never_fails_an_append(tmp_path):
    def bad_observer(n, s):
        raise RuntimeError("observer bug")

    j = SessionJournal(str(tmp_path / "j.jsonl"), observer=bad_observer)
    j.task("worker:0", "SCHEDULED", 0)     # must not raise
    j.close()
    from tony_tpu.coordinator import journal as journal_mod

    st = journal_mod.replay(str(tmp_path / "j.jsonl"))
    assert st.records == 1 and not st.torn_tail


def test_histogram_quantile_interpolates_and_clamps():
    from tony_tpu.metrics import Histogram

    h = Histogram((0.001, 0.01, 0.1))
    for _ in range(99):
        h.observe(0.0005)
    h.observe(5.0)                           # overflow
    snap = h.snapshot()
    assert histogram_quantile(snap, 0.5) <= 0.001
    assert histogram_quantile(snap, 0.999) == pytest.approx(0.1)
    assert histogram_quantile({"buckets": [], "counts": [],
                               "count": 0}, 0.99) == 0.0


# ---------------------------------------------------------------------------
# coord.slow-tick fault site
# ---------------------------------------------------------------------------
def test_coord_slow_tick_site_registered_and_conf_drivable():
    assert "coord.slow-tick" in faults.SITES
    conf = TonyTpuConfig()
    conf.set(K.FAULT_COORD_SLOW_TICK, "at:1,amt:0.25")
    assert faults.install_from_conf(conf) is True
    assert faults.fire_amount("coord.slow-tick") == 0.25
    assert faults.fire_amount("coord.slow-tick") is None


# ---------------------------------------------------------------------------
# Virtual-width coordinator drills (real coordinator, real RPC frames)
# ---------------------------------------------------------------------------
def _scale_conf(width, hb_ms=300, monitor_ms=100, **extra):
    conf = TonyTpuConfig()
    conf.set("tony.worker.instances", width)
    conf.set("tony.worker.command", "virtual")
    conf.set(K.SCALE_VIRTUAL_EXECUTORS, True)
    conf.set(K.TASK_HEARTBEAT_INTERVAL_MS, hb_ms)
    conf.set(K.COORDINATOR_MONITOR_INTERVAL_MS, monitor_ms)
    conf.set(K.APPLICATION_NUM_CLIENTS_TO_WAIT, False)
    conf.set(K.DIAGNOSIS_ENABLED, False)
    for k, v in extra.items():
        conf.set(k, v)
    return conf


def _run_coord(tmp_path, conf, app_id):
    backend = VirtualExecutorBackend.from_conf(
        conf, str(tmp_path / "work"))
    coord = Coordinator(conf, app_id, backend, str(tmp_path / "history"),
                        user="t")
    runner = threading.Thread(target=coord.run, daemon=True)
    runner.start()
    return coord, runner


def _wait(pred, timeout_s, what):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        v = pred()
        if v:
            return v
        time.sleep(0.02)
    raise AssertionError(f"timed out waiting for {what}")


@pytest.mark.timeout_s(90)
def test_virtual_width_256_phase_and_span_invariants(tmp_path):
    """The acceptance drill: 256 registered beat-only tasks on ONE
    coordinator in tier-1 time — per-tick coordinator phases sum to
    wall (within 5%; exact by construction), the self-observation
    surfaces carry real numbers, and the trace closes with zero
    unclosed spans."""
    conf = _scale_conf(256)
    coord, runner = _run_coord(tmp_path, conf, "app_w256")
    try:
        _wait(coord.session.all_registered, 45, "256 registrations")
        assert coord.session.num_registered == 256
        time.sleep(2.5)                       # sustain: beats + ticks
        snap = coord.coordphases.snapshot()
        assert snap["ticks"] >= 5
        # THE acceptance invariant: phases sum to wall within 5%.
        assert sum(snap["cum"].values()) == pytest.approx(
            snap["wall_s"], rel=0.05)
        assert snap["beats_total"] >= 256       # ≥1 beat per task
        assert snap["journal_records_total"] >= 256
        assert snap["beats_per_sec"] > 50
        assert snap["fsync"]["count"] == snap["journal_records_total"]
        # live surfaces: the coordinator self row is populated
        live = coord.metrics_live()
        row = live["coord"]
        assert row["registered_tasks"] == 256
        assert row["beats_per_s"] > 0
        assert row["journal_fsync_p99_s"] > 0
        assert abs(sum(row["phases"].values()) - 1.0) < 0.05
        assert row["verdict"] in ("COORD_HEALTHY", "JOURNAL_BOUND",
                                  "HEARTBEAT_BOUND", "RPC_BOUND",
                                  "RENDEZVOUS_BOUND")
        from tony_tpu.cli.main import _render_top

        frame = _render_top(live)
        assert "coord: tick=" in frame and "beats/s=" in frame
        # exposition: the new families land in metrics.prom
        coord._maybe_write_prom(force=True)
        prom = open(os.path.join(coord.job_dir,
                                 constants.METRICS_PROM_FILE)).read()
        assert "tony_coord_phase_seconds" in prom
        assert "tony_coord_tick_seconds" in prom
        assert "tony_coord_beats_total" in prom
        assert "tony_journal_fsync_seconds_bucket" in prom
        assert 'tony_coord_registered_tasks{app="app_w256"} 256' in prom
    finally:
        coord.request_stop("drill complete")
        runner.join(timeout=60)
    assert not runner.is_alive(), "coordinator did not stop"
    # zero unclosed spans on the full-width run
    records = tracing.load_records(
        os.path.join(coord.job_dir, constants.TRACE_FILE))
    payload = tracing.to_trace_events(records)
    assert payload["unclosedSpans"] == []


@pytest.mark.timeout_s(60)
def test_virtual_gang_self_finish_succeeds_through_result_path(tmp_path):
    """run_s-bounded virtual tasks report exit 0 over the REAL
    register_execution_result path and the job SUCCEEDS."""
    conf = _scale_conf(8, **{K.SCALE_VIRTUAL_RUN_S: 1.5})
    coord, runner = _run_coord(tmp_path, conf, "app_vfin")
    runner.join(timeout=45)
    assert not runner.is_alive()
    assert coord.final_status == SessionStatus.SUCCEEDED


@pytest.mark.timeout_s(60)
def test_virtual_resize_at_width_completes(tmp_path):
    """Elastic shrink at width through the real drain→remesh→barrier
    path: every survivor parks (re-registers under the new mgen) via
    the resize directive riding its heartbeat response."""
    conf = _scale_conf(32, **{K.ELASTIC_ENABLED: True,
                              K.ELASTIC_BARRIER_TIMEOUT_S: 45})
    coord, runner = _run_coord(tmp_path, conf, "app_vrz")
    try:
        # established flips on the monitor tick AFTER the barrier opens
        # — resizes are refused against an unestablished gang.
        _wait(lambda: coord.elastic.established, 30, "established gang")
        res = coord.resize_application(31)
        assert res["ok"], res
        _wait(lambda: not coord.elastic.resizing, 45, "resize to land")
        assert coord.session.jobs["worker"].instances == 31
        assert coord.elastic.mgen == 2
        assert coord.session.status == SessionStatus.RUNNING
    finally:
        coord.request_stop("drill complete")
        runner.join(timeout=45)


@pytest.mark.timeout_s(60)
def test_virtual_migrate_at_width_completes(tmp_path):
    """A live move at width through the real drain→park→relaunch→barrier
    path: the operator's request is accepted, every member re-registers
    on the destination under the op's mgen, and the gang is pinned there
    with the session still RUNNING and no retry epoch burned."""
    conf = _scale_conf(16, **{K.ELASTIC_ENABLED: True,
                              K.ELASTIC_BARRIER_TIMEOUT_S: 45})
    coord, runner = _run_coord(tmp_path, conf, "app_vmig")
    try:
        _wait(lambda: coord.elastic.established, 30, "established gang")
        res = coord.migrate_application("slice-1")
        assert res["ok"], res
        _wait(lambda: not coord.elastic.resizing, 45, "migration to land")
        assert coord.session.jobs["worker"].node_pool == "slice-1"
        assert coord.session.jobs["worker"].instances == 16
        assert coord.elastic.mgen == 2
        assert coord.session.status == SessionStatus.RUNNING
        assert coord._infra_retries_used == 0
    finally:
        coord.request_stop("drill complete")
        runner.join(timeout=45)


@pytest.mark.timeout_s(120)
def test_fleet_daemon_drains_a_tenant_mix_of_virtual_gangs(tmp_path):
    """A live fleet daemon spawning real `tony-tpu submit` clients on
    virtual executors drains a small tenant mix (a quota-capped tenant,
    priorities, sizes 1–4 on a 2×4 pool): every job ends FINISHED, and
    the goodput ledger's rollup of the run sums to the held
    chip-seconds."""
    from tony_tpu.fleet.daemon import FleetDaemon

    job_conf = {"tony.worker.command": "virtual",
                K.SCALE_VIRTUAL_EXECUTORS: "true",
                K.SCALE_VIRTUAL_RUN_S: "0.5",
                K.TASK_HEARTBEAT_INTERVAL_MS: "300",
                K.COORDINATOR_MONITOR_INTERVAL_MS: "100",
                K.DIAGNOSIS_ENABLED: "false"}
    daemon = FleetDaemon(str(tmp_path / "fleet"), slices=2,
                         hosts_per_slice=4, quotas="capped=2",
                         tick_s=0.2, ledger_interval_s=1.0)
    runner = threading.Thread(target=daemon.run, daemon=True)
    runner.start()
    try:
        mix = [("alpha", 4, 2), ("bravo", 3, 1), ("alpha", 2, 0),
               ("capped", 2, 0), ("capped", 1, 0), ("bravo", 1, 2)]
        for tenant, hosts, priority in mix:
            assert daemon.submit(tenant, hosts, priority=priority,
                                 conf=dict(job_conf))["ok"]

        def drained():
            rows = daemon.status().get("jobs", [])
            return len(rows) == len(mix) and all(
                r["state"] in ("FINISHED", "FAILED", "CANCELLED")
                for r in rows) and rows
        rows = _wait(drained, 100, "the mix to drain")
        assert [r["state"] for r in rows] == ["FINISHED"] * len(mix), rows
        fleet = daemon.status()["ledger"]["fleet"]
        assert fleet["held_chip_s"] > 0
        assert 0 < fleet["goodput_fraction"] <= 1
        assert sum(fleet["phase_chip_s"].values()) == pytest.approx(
            fleet["held_chip_s"], rel=0.05)
    finally:
        daemon.request_stop()
        runner.join(timeout=45)
    assert not runner.is_alive(), "fleet daemon did not stop"


@pytest.mark.timeout_s(60)
def test_coord_slow_tick_shows_in_tick_accounting(tmp_path):
    """An injected 50ms/tick control-plane stall must surface in the
    self-observation tick numbers (the incident shape `top`'s coord row
    exists for)."""
    conf = _scale_conf(2, monitor_ms=50,
                       **{K.FAULT_COORD_SLOW_TICK: "every:1,amt:0.05"})
    coord, runner = _run_coord(tmp_path, conf, "app_vslow")
    try:
        _wait(coord.session.all_registered, 30, "registrations")
        time.sleep(1.5)
        snap = coord.coordphases.snapshot()
        # ticks run at 50ms interval + 50ms injected stall: the recent
        # mean tick WALL must show the stall (≥ ~80ms).
        assert snap["recent_wall_s"] >= 0.08
    finally:
        coord.request_stop("drill complete")
        runner.join(timeout=45)


# ---------------------------------------------------------------------------
# The coordinator classifier on fractions a 512-wide gang produced
# ---------------------------------------------------------------------------
def test_classify_coord_on_real_bench_fractions():
    """Phase fractions recorded from a 512-wide virtual gang classify
    JOURNAL_BOUND — fsync-per-record is the first loop to fall over,
    which is where a group commit (ROADMAP D4) would aim."""
    with open(os.path.join(FIXTURES, "coord_phases_w512.json")) as f:
        w512 = json.load(f)
    v = classify_coord(w512["coord_phases"])
    assert v["category"] == w512["verdict"] == JOURNAL_BOUND
    assert any("journal_fsync" in e for e in v["evidence"])
