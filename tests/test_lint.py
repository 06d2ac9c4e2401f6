"""tonylint + lock-sanitizer suite (tony_tpu/devtools/).

Three layers:

1. **Golden fixtures** — for every rule, one minimal bad snippet in a
   synthetic repo asserting the exact finding (rule id + line), and one
   clean snippet asserting silence; plus suppression-comment behavior.
2. **The repo gate** — the real repository lints clean (this is the
   tier-1 invariant: deleting a conf key / fault site / EventType that
   is still referenced makes THIS test fail with a file:line finding;
   the registry-deletion drills prove the detection actually fires).
3. **Sanitizer units** — a constructed lock-order cycle and a
   hold-while-sleeping hazard on an isolated State (never the global
   one: the suite-wide sanitizer must stay clean).
"""

from __future__ import annotations

import json
import os
import textwrap
import threading

import pytest

from tony_tpu.devtools import sanitizer, tonylint
from tony_tpu.devtools.tonylint import Linter, run_lint

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# fixture harness: a synthetic repo the rules run against
# ---------------------------------------------------------------------------
def _lint_snippet(tmp_path, code: str, rules, rel="tony_tpu/snippet.py"):
    """Drop ``code`` at ``rel`` inside a synthetic repo and run the given
    rules. Returns (findings-for-that-file, linter)."""
    path = tmp_path / rel
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(textwrap.dedent(code))
    linter = Linter(str(tmp_path))
    linter.run(rules=rules)
    rel_norm = os.path.normpath(rel)
    return ([f for f in linter.findings
             if os.path.normpath(f.file) == rel_norm], linter)


@pytest.mark.faults
def test_conf_key_bad_and_clean(tmp_path):
    bad, _ = _lint_snippet(tmp_path, '''
        KEY = "tony.bogus.key"
    ''', ["conf-key"])
    assert [(f.rule, f.line) for f in bad] == [("conf-key", 2)]
    assert "tony.bogus.key" in bad[0].message

    clean, _ = _lint_snippet(tmp_path, '''
        A = "tony.application.name"          # registered
        B = "tony.worker.instances"          # dynamic per-jobtype
        C = "tony.fault"                     # family prefix mention
        D = "job.tony.json"                  # a file name, not a key
        E = f"tony.trace.enabled={1}"        # key inside an f-string
        F = "tony.step tony.phase.data_wait"  # profiler-trace names
        G = "tony.loss_and_grad/tony.optimizer" + "tony.phase." + "x"
    ''', ["conf-key"])
    assert clean == []


@pytest.mark.faults
def test_fault_site_bad_and_clean(tmp_path):
    bad, _ = _lint_snippet(tmp_path, '''
        from tony_tpu import faults
        def f():
            faults.check("not.a.site")
            faults.fire(some_variable)
    ''', ["fault-site"])
    assert ("fault-site", 4) in [(f.rule, f.line) for f in bad]
    assert ("fault-site", 5) in [(f.rule, f.line) for f in bad]

    clean, _ = _lint_snippet(tmp_path, '''
        from tony_tpu import faults
        def f():
            faults.check("rpc.send")
    ''', ["fault-site"])
    assert clean == []


@pytest.mark.faults
def test_fault_site_missing_call_site_detected(tmp_path):
    """The OTHER direction: a site listed in SITES with no call site
    anywhere is flagged (anchored at the SITES definition)."""
    _, linter = _lint_snippet(tmp_path, '''
        from tony_tpu import faults
        def f():
            faults.check("rpc.send")
    ''', ["fault-site"])
    dead = [f for f in linter.findings if "no fire/check" in f.message]
    # every canonical site except rpc.send is unreferenced in the
    # synthetic repo
    from tony_tpu import faults as real_faults

    assert len(dead) == len(real_faults.SITES) - 1


@pytest.mark.faults
def test_event_type_bad_and_clean(tmp_path):
    bad, _ = _lint_snippet(tmp_path, '''
        from tony_tpu.events.events import Event, EventType
        def f(events, b):
            events.emit(Event(EventType.NOT_A_REAL_EVENT, {}))
            events.emit(Event("TASK_STARTED", {}))
            b.events_of("BOGUS_EVENT")
    ''', ["event-type"])
    lines = [(f.rule, f.line) for f in bad]
    assert ("event-type", 4) in lines       # unknown member
    assert ("event-type", 5) in lines       # raw string construction
    assert ("event-type", 6) in lines       # events_of unknown name

    clean, _ = _lint_snippet(tmp_path, '''
        from tony_tpu.events.events import Event, EventType
        def f(events, b):
            events.emit(Event(EventType.TASK_STARTED, {"x": 1}))
            b.events_of("TASK_FINISHED")
    ''', ["event-type"])
    assert clean == []


@pytest.mark.faults
def test_rpc_parity_bad_and_clean(tmp_path):
    bad, _ = _lint_snippet(tmp_path, '''
        from tony_tpu.rpc.wire import RpcServer

        class _Svc:
            def dead__handler(self):
                return 1

        def go(client):
            server = RpcServer(_Svc())
            client.call("no_such_method")
    ''', ["rpc-parity"])
    lines = [(f.rule, f.line) for f in bad]
    assert ("rpc-parity", 5) in lines       # dead handler (def line)
    assert ("rpc-parity", 10) in lines      # unknown method call

    clean, _ = _lint_snippet(tmp_path, '''
        from tony_tpu.rpc.wire import RpcServer

        class _Svc:
            def live__handler(self):
                return 1

        def go(client):
            server = RpcServer(_Svc())
            client.call("live.handler")
    ''', ["rpc-parity"])
    assert clean == []


@pytest.mark.faults
def test_durable_write_bad_and_clean(tmp_path):
    bad, _ = _lint_snippet(tmp_path, '''
        import os, json
        def f(d, obj):
            with open(os.path.join(d, "lease.json"), "w") as fh:
                json.dump(obj, fh)
            os.replace("a.tmp", "a")
    ''', ["durable-write"])
    lines = [(f.rule, f.line) for f in bad]
    assert ("durable-write", 4) in lines    # artifact via bare open
    assert ("durable-write", 6) in lines    # hand-rolled replace

    clean, _ = _lint_snippet(tmp_path, '''
        import json
        from tony_tpu.utils.durable import atomic_write
        def f(path, obj, scratch):
            atomic_write(path, json.dumps(obj).encode())
            with open(scratch, "w") as fh:   # non-artifact scratch: fine
                fh.write("x")
    ''', ["durable-write"])
    assert clean == []


@pytest.mark.faults
def test_clock_bad_and_clean(tmp_path):
    bad, _ = _lint_snippet(tmp_path, '''
        import time
        def f(deadline):
            d = time.time() + 10
            while time.time() < deadline:
                pass
    ''', ["clock"])
    assert [(f.rule, f.line) for f in bad] == [("clock", 4), ("clock", 5)]

    clean, _ = _lint_snippet(tmp_path, '''
        import time
        def f(deadline):
            d = time.monotonic() + 10            # monotonic deadline
            anchor = time.time()                 # wall anchor: fine
            ts_ms = int(time.time() * 1000)      # stamp conversion: fine
            return d, anchor, ts_ms
    ''', ["clock"])
    assert clean == []


@pytest.mark.faults
def test_span_leak_bad_and_clean(tmp_path):
    bad, _ = _lint_snippet(tmp_path, '''
        def f(tracer):
            span = tracer.start_span("x")
            return 1
    ''', ["span-leak"])
    assert [(f.rule, f.line) for f in bad] == [("span-leak", 3)]

    clean, _ = _lint_snippet(tmp_path, '''
        def f(tracer):
            span = tracer.start_span("x")
            try:
                return 1
            finally:
                span.end()

        def g(tracer):
            with tracer.start_span("y"):
                return 2
    ''', ["span-leak"])
    assert clean == []


@pytest.mark.faults
def test_thread_leak_bad_and_clean(tmp_path):
    bad, _ = _lint_snippet(tmp_path, '''
        import threading
        def f(work):
            t = threading.Thread(target=work)
            t.start()
    ''', ["thread-leak"])
    assert [(f.rule, f.line) for f in bad] == [("thread-leak", 4)]

    clean, _ = _lint_snippet(tmp_path, '''
        import threading
        def f(work):
            t = threading.Thread(target=work, daemon=True)
            t.start()
        def g(work):
            t = threading.Thread(target=work)
            t.start()
            t.join()
    ''', ["thread-leak"])
    assert clean == []


@pytest.mark.faults
def test_lock_blocking_bad_and_clean(tmp_path):
    bad, _ = _lint_snippet(tmp_path, '''
        import threading, time

        class C:
            def __init__(self):
                self._lock = threading.Lock()

            def f(self):
                with self._lock:
                    time.sleep(1)
    ''', ["lock-blocking"], rel="tony_tpu/coordinator/snippet.py")
    assert [(f.rule, f.line) for f in bad] == [("lock-blocking", 10)]

    clean, _ = _lint_snippet(tmp_path, '''
        import threading, time

        class C:
            def __init__(self):
                self._lock = threading.Lock()

            def f(self):
                with self._lock:
                    x = 1
                time.sleep(1)
                return ", ".join(["a", "b"])   # str.join: not blocking
    ''', ["lock-blocking"], rel="tony_tpu/coordinator/snippet.py")
    assert clean == []


@pytest.mark.faults
def test_bare_except_bad_and_clean(tmp_path):
    bad, _ = _lint_snippet(tmp_path, '''
        def f():
            try:
                pass
            except:
                pass
    ''', ["bare-except"])
    assert [(f.rule, f.line) for f in bad] == [("bare-except", 5)]

    clean, _ = _lint_snippet(tmp_path, '''
        def f():
            try:
                pass
            except ValueError:
                pass
    ''', ["bare-except"])
    assert clean == []


@pytest.mark.faults
def test_suppression_comment(tmp_path):
    """`# tony: lint-ignore[rule]` on the finding's line suppresses that
    rule only; a different rule id does not."""
    hit, linter = _lint_snippet(tmp_path, '''
        import time
        def f():
            a = time.time() + 10  # tony: lint-ignore[clock]
            b = time.time() + 10  # tony: lint-ignore[bare-except]
            return a, b
    ''', ["clock"])
    assert [(f.rule, f.line) for f in hit] == [("clock", 5)]
    assert [(f.rule, f.line) for f in linter.suppressed] == [("clock", 4)]


# ---------------------------------------------------------------------------
# v2 protocol rules (tony_tpu/devtools/protocol.py): multi-file golden
# fixtures — each rule extracts BOTH halves of a protocol, so the
# synthetic repo needs both files.
# ---------------------------------------------------------------------------
def _lint_files(tmp_path, files, rules):
    """Drop ``{rel: code}`` into a synthetic repo, run ``rules``; returns
    the linter."""
    for rel, code in files.items():
        path = tmp_path / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(textwrap.dedent(code))
    linter = Linter(str(tmp_path))
    linter.run(rules=rules)
    return linter


_COORD_HEARTBEAT_OK = '''
    def heartbeat(self, task_id):
        resp = {}
        resp["dump"] = True
        resp["resize"] = {"mgen": 2}
        return {"ok": True, **resp}
'''

_EXEC_HEARTBEAT_OK = '''
    class H:
        def run(self):
            res = self._client.call("task_executor_heartbeat", task_id=1)
            if res.get("dump"):
                self._on_dump()
            if isinstance(res.get("resize"), dict):
                self._on_resize(res["resize"])

    def _on_resize(self, directive):
        mgen = int(directive.get("mgen", -1))
        if mgen <= self.mgen:
            return
        self.mgen = mgen
'''


@pytest.mark.faults
def test_directive_parity_bad_and_clean(tmp_path):
    linter = _lint_files(tmp_path, {
        "tony_tpu/coordinator/coordinator.py": '''
            def heartbeat(self, task_id):
                resp = {}
                resp["dump"] = True
                resp["vanish"] = True        # no executor branch
                return {"ok": True, **resp}
        ''',
        "tony_tpu/executor/executor.py": '''
            class H:
                def run(self):
                    res = self._client.call("task_executor_heartbeat")
                    if res.get("dump"):
                        pass
                    if isinstance(res.get("ghost"), dict):  # no writer
                        pass
        ''',
    }, ["directive-parity"])
    msgs = [(f.rule, f.message) for f in linter.findings]
    assert any("'vanish'" in m and "no executor heartbeat branch" in m
               for _, m in msgs), msgs
    assert any("'ghost'" in m and "no coordinator heartbeat path" in m
               for _, m in msgs), msgs

    clean = _lint_files(tmp_path / "clean", {
        "tony_tpu/coordinator/coordinator.py": _COORD_HEARTBEAT_OK,
        "tony_tpu/executor/executor.py": _EXEC_HEARTBEAT_OK,
    }, ["directive-parity"])
    assert clean.findings == []


@pytest.mark.faults
def test_directive_parity_missing_dedup_guard(tmp_path):
    """A stateful (dict-payload) directive whose handler never compares
    an mgen/id is flagged: the drain would re-fire every beat."""
    linter = _lint_files(tmp_path, {
        "tony_tpu/coordinator/coordinator.py": _COORD_HEARTBEAT_OK,
        "tony_tpu/executor/executor.py": '''
            class H:
                def run(self):
                    res = self._client.call("task_executor_heartbeat")
                    if res.get("dump"):
                        pass
                    if isinstance(res.get("resize"), dict):
                        self._on_resize(res["resize"])

            def _on_resize(self, directive):
                self.drain(directive)        # acts every time: no guard
        ''',
    }, ["directive-parity"])
    assert any("no dedup/mgen guard" in f.message
               for f in linter.findings), linter.findings


@pytest.mark.faults
def test_journal_parity_bad_and_clean(tmp_path):
    linter = _lint_files(tmp_path, {
        "tony_tpu/coordinator/journal.py": '''
            REC_GOOD = "good"
            REC_NOREPLAY = "noreplay"    # appended, no replay branch
            REC_DEAD = "dead"            # declared, never appended

            class J:
                def good(self):
                    self.append({"t": REC_GOOD})

                def noreplay(self):
                    self.append({"t": REC_NOREPLAY})

                def literal(self):
                    self.append({"t": "sneaky"})   # bypasses constants

            def replay(path):
                t = "x"
                if t == REC_GOOD:
                    pass
        ''',
    }, ["journal-parity"])
    msgs = [f.message for f in linter.findings]
    assert any("REC_NOREPLAY" in m and "no branch" in m for m in msgs), msgs
    assert any("REC_DEAD" in m and "never appended" in m for m in msgs), msgs
    assert any("'sneaky'" in m and "string literal" in m for m in msgs), msgs

    clean = _lint_files(tmp_path / "clean", {
        "tony_tpu/coordinator/journal.py": '''
            REC_GOOD = "good"

            class J:
                def good(self):
                    self.append({"t": REC_GOOD})

            def replay(path):
                t = "x"
                if t == REC_GOOD:
                    pass
        ''',
    }, ["journal-parity"])
    assert clean.findings == []


@pytest.mark.faults
def test_fence_coverage_bad_and_clean(tmp_path):
    bad, _ = _lint_snippet(tmp_path, '''
        from tony_tpu.rpc.wire import RpcServer

        class _Svc:
            def mutate_unfenced(self, task_id):
                t = self.session.get_task(task_id)
                t.tb_url = "x"
                return True

        def go():
            RpcServer(_Svc())
    ''', ["fence-coverage"], rel="tony_tpu/coordinator/coordinator.py")
    assert [(f.rule, f.line) for f in bad] == [("fence-coverage", 5)]
    assert "mutate_unfenced" in bad[0].message

    clean, _ = _lint_snippet(tmp_path / "clean", '''
        from tony_tpu.rpc.wire import RpcServer

        class _Svc:
            def mutate_fenced(self, task_id, session_id=-1):
                self._check_epoch(task_id, session_id)
                t = self.session.get_task(task_id)
                t.tb_url = "x"
                return True

            def _check_epoch(self, task_id, session_id):
                pass

            def operator_surface(self, size):
                self.session.fail("operator kill")   # no task_id: exempt
                return True

        def go():
            RpcServer(_Svc())
    ''', ["fence-coverage"], rel="tony_tpu/coordinator/coordinator.py")
    assert clean == []


@pytest.mark.faults
def test_fence_coverage_sees_through_delegation(tmp_path):
    """The thin RPC-wrapper shape: the handler delegates to a same-named
    coordinator method whose body does the unfenced mutation."""
    bad, _ = _lint_snippet(tmp_path, '''
        from tony_tpu.rpc.wire import RpcServer

        class _Svc:
            def register_thing(self, task_id):
                return self._c.register_thing(task_id)

        class Coordinator:
            def register_thing(self, task_id):
                self.session.mark_killed(task_id)
                return True

        def go():
            RpcServer(_Svc())
    ''', ["fence-coverage"], rel="tony_tpu/coordinator/coordinator.py")
    assert [(f.rule, f.line) for f in bad] == [("fence-coverage", 5)]


@pytest.mark.faults
def test_beacon_parity_bad_and_clean(tmp_path):
    linter = _lint_files(tmp_path, {
        "tony_tpu/executor/executor.py": '''
            def _progress_beacon(self):
                beacon = {}
                beacon["steps"] = 1.0
                beacon["junk"] = "never read"
                nested = {}
                nested["sub"] = 1     # not the returned dict: ignored
                return beacon or None
        ''',
        "tony_tpu/coordinator/coordinator.py": '''
            def _observe_beacon(self, progress):
                if "steps" in progress:
                    return progress["steps"]
                return progress.get("ghost")
        ''',
    }, ["beacon-parity"])
    msgs = [f.message for f in linter.findings]
    assert any("'junk'" in m and "no coordinator fold reads" in m
               for m in msgs), msgs
    assert any("'ghost'" in m and "no executor beacon writes"
               in m for m in msgs), msgs
    assert not any("'sub'" in m for m in msgs), msgs

    clean = _lint_files(tmp_path / "clean", {
        "tony_tpu/executor/executor.py": '''
            def _progress_beacon(self):
                beacon = {}
                beacon["steps"] = 1.0
                return beacon or None
        ''',
        "tony_tpu/coordinator/coordinator.py": '''
            def _observe_beacon(self, progress):
                return progress.get("steps")
        ''',
    }, ["beacon-parity"])
    assert clean.findings == []


@pytest.mark.faults
def test_terminal_state_bad_and_clean(tmp_path):
    bad, _ = _lint_snippet(tmp_path, '''
        def promote(session, task_id):
            t = session.get_task(task_id)
            t.status = "RUNNING"
    ''', ["terminal-state"], rel="tony_tpu/coordinator/session.py")
    assert [(f.rule, f.line) for f in bad] == [("terminal-state", 4)]

    clean, _ = _lint_snippet(tmp_path / "clean", '''
        def promote(session, task_id):
            t = session.get_task(task_id)
            if t.status.terminal:
                return
            t.status = "RUNNING"

        def absorb_loss(t):
            t.status = "FAILED"       # the absorb path: exempt

        def reduce(self):
            self.status = "FAILED"    # session reduction, not a task
    ''', ["terminal-state"], rel="tony_tpu/coordinator/session.py")
    assert clean == []


@pytest.mark.faults
def test_metrics_registry_bad_and_clean(tmp_path):
    bad, _ = _lint_snippet(tmp_path, '''
        def export(metrics):
            metrics.gauge("tony_bogus_series", {}).set(1)
    ''', ["metrics-registry"])
    assert [(f.rule, f.line) for f in bad] == [("metrics-registry", 3)]
    assert "tony_bogus_series" in bad[0].message

    clean, _ = _lint_snippet(tmp_path / "clean", '''
        def export(metrics):
            metrics.gauge("tony_tasks", {}).set(1)          # registered
            prefix = "tony_coord_"                          # family match
            path = "tony_tpu/metrics.py"                    # not a series
    ''', ["metrics-registry"])
    assert clean == []


@pytest.mark.faults
def test_metrics_registry_dead_entry_detected(tmp_path):
    """The OTHER direction: every registered series must be referenced
    somewhere — a synthetic repo referencing only one leaves the rest
    flagged at the registry."""
    from tony_tpu.metrics import SERIES

    _, linter = _lint_snippet(tmp_path, '''
        def export(metrics):
            metrics.gauge("tony_tasks", {}).set(1)
    ''', ["metrics-registry"])
    dead = [f for f in linter.findings
            if "dead registry entry" in f.message]
    assert len(dead) == len(SERIES) - 1


# ---------------------------------------------------------------------------
# the repo gate
# ---------------------------------------------------------------------------
def test_repo_is_lint_clean():
    """THE invariant: `tony-tpu lint` on this repository reports zero
    findings, and the suppression budget stays within the documented
    cap (docs/development.md: max 3, each with an inline justification).
    """
    findings, suppressed = run_lint(REPO_ROOT)
    assert findings == [], "\n".join(str(f) for f in findings)
    assert len(suppressed) <= 3, (
        "suppression budget exceeded (max 3 justified lint-ignores):\n"
        + "\n".join(str(f) for f in suppressed))


@pytest.mark.faults
def test_deleting_referenced_conf_key_is_caught(monkeypatch):
    """Drill the acceptance property: removing a conf key that call
    sites still reference must surface as a file:line finding."""
    from tony_tpu.conf import keys as K

    assert "tony.pool.dir" in K._REGISTRY
    monkeypatch.delitem(K._REGISTRY, "tony.pool.dir")
    findings, _ = run_lint(REPO_ROOT, rules=["conf-key", "defaults-md"])
    assert any(f.rule == "conf-key" and "tony.pool.dir" in f.message
               for f in findings), findings
    # and the registry↔defaults.md parity breaks too
    assert any(f.rule == "defaults-md" for f in findings)


@pytest.mark.faults
def test_deleting_fault_site_is_caught(monkeypatch):
    from tony_tpu import faults

    trimmed = tuple(s for s in faults.SITES if s != "rpc.send")
    monkeypatch.setattr(faults, "SITES", trimmed)
    findings, _ = run_lint(REPO_ROOT, rules=["fault-site"])
    assert any("'rpc.send'" in f.message and f.file.endswith("wire.py")
               for f in findings), findings


@pytest.mark.faults
def test_cli_lint_json(capsys):
    """`tony-tpu lint --json` emits machine-readable findings and exits
    zero on the clean repo."""
    rc = tonylint.main(["--json", "--root", REPO_ROOT])
    out = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert out["findings"] == []
    assert isinstance(out["suppressed"], list)


# ---------------------------------------------------------------------------
# lock sanitizer units (isolated State: the suite-wide one stays clean)
# ---------------------------------------------------------------------------
@pytest.mark.faults
def test_sanitizer_detects_lock_order_cycle():
    st = sanitizer.State()
    la = sanitizer.sanitize_lock(threading.Lock(), "a.py:1", st)
    lb = sanitizer.sanitize_lock(threading.Lock(), "b.py:2", st)

    def order_ab():
        with la:
            with lb:
                pass

    def order_ba():
        with lb:
            with la:
                pass

    t1 = threading.Thread(target=order_ab, daemon=True)
    t1.start()
    t1.join()
    t2 = threading.Thread(target=order_ba, daemon=True)
    t2.start()
    t2.join()
    cycles = st.cycles()
    assert cycles, "A→B and B→A orders must form a cycle"
    assert sorted(cycles[0]) == ["a.py:1", "b.py:2"]
    rep = st.report()
    assert rep["edges"] == 2 and rep["cycles"]


@pytest.mark.faults
def test_sanitizer_no_cycle_for_consistent_order():
    st = sanitizer.State()
    la = sanitizer.sanitize_lock(threading.Lock(), "a.py:1", st)
    lb = sanitizer.sanitize_lock(threading.Lock(), "b.py:2", st)
    for _ in range(3):
        with la:
            with lb:
                pass
    assert st.cycles() == []
    assert st.report()["edges"] == 1


@pytest.mark.faults
def test_sanitizer_hold_while_blocking_hazard():
    st = sanitizer.State()
    lk = sanitizer.sanitize_lock(threading.Lock(), "c.py:3", st)
    st.note_blocking("time.sleep")          # not holding: no hazard
    assert st.report()["hazards"] == []
    with lk:
        st.note_blocking("time.sleep")
    hazards = st.report()["hazards"]
    assert len(hazards) == 1
    assert hazards[0]["blocking"] == "time.sleep"
    assert hazards[0]["held"] == ["c.py:3"]
    # deduped: the same (blocking, where, held) is recorded once
    with lk:
        st.note_blocking("time.sleep",
                         where=hazards[0]["where"])
    assert len(st.report()["hazards"]) == 1


@pytest.mark.faults
def test_sanitizer_rlock_reentrancy_no_self_edge():
    st = sanitizer.State()
    rl = sanitizer.sanitize_lock(threading.RLock(), "r.py:4", st)
    with rl:
        with rl:                            # reentrant: no A→A edge
            pass
    assert st.report()["edges"] == 0
    assert st.cycles() == []


@pytest.mark.faults
def test_sanitizer_suite_wide_state_is_armed_and_clean():
    """The conftest enables the global sanitizer for tier-1; whatever
    the suite has executed so far must show zero cycles/hazards (the
    sessionfinish gate enforces it again over the FULL run + all
    subprocesses)."""
    if not sanitizer.enabled():
        pytest.skip("sanitizer disabled via TONY_LOCK_SANITIZER=0")
    rep = sanitizer.state().report()
    assert rep["cycles"] == [], rep
    assert rep["hazards"] == [], rep
    assert rep["locks_sanitized"] > 0, \
        "no tony_tpu locks sanitized — enablement is broken"
