"""Test configuration: force an 8-device virtual CPU mesh BEFORE jax import.

This is the TPU analogue of the reference's in-process MiniCluster test
substrate (``tony-mini/.../MiniCluster.java:43-63``): all distributed tests run
against host-local virtual devices so CI needs no hardware (SURVEY.md §4.1).
"""

import os
import signal
import sys
import tempfile

import pytest

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

# Persistent XLA compile cache shared across test processes and runs: the
# compute-heavy files (models/ops/parallel/pipeline) are compile-dominated.
# Safe to share: keys include HLO + jax/XLA version. It lives at a FIXED
# path under the checkout (git-ignored) — a cache directory that moves
# never hits — unless the environment already placed it. Nothing imports
# jax before this point, so the environment alone configures it, for this
# process and for every subprocess the tests spawn.
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      os.path.join(_REPO, ".jax_cache"))
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.5")

# Make `import tony_tpu` work no matter where pytest is invoked from.
sys.path.insert(0, _REPO)

# ---------------------------------------------------------------------------
# Lock sanitizer (tony_tpu/devtools/sanitizer.py): the WHOLE tier-1 suite
# runs with every tony_tpu-allocated lock watched for lock-order cycles
# and hold-while-blocking hazards; pytest_sessionfinish below fails the
# run on any finding. Subprocesses (executors, coordinators, pool
# workers) inherit the env vars and dump their own findings into the
# shared directory at exit. Opt out with TONY_LOCK_SANITIZER=0.
# Enabled BEFORE the jax import: patching is cheap either way (non-tony
# allocation sites get raw primitives), but tony_tpu's own module-level
# locks must be constructed after the factories are in place.
# ---------------------------------------------------------------------------
if os.environ.get("TONY_LOCK_SANITIZER", "") != "0":
    os.environ["TONY_LOCK_SANITIZER"] = "1"
    os.environ.setdefault(
        "TONY_LOCK_SANITIZER_DIR",
        tempfile.mkdtemp(prefix="tony-sanitizer-"))
    from tony_tpu.devtools import sanitizer as _sanitizer

    _sanitizer.maybe_enable_from_env()
else:
    _sanitizer = None

# ---------------------------------------------------------------------------
# Data-race detector (tony_tpu/devtools/race.py — tonyrace): the WHOLE
# tier-1 suite runs with the @guarded control-plane classes' GUARDED_BY
# fields watched for lockset-empty/no-happens-before access pairs;
# pytest_sessionfinish fails the run on any race from any process.
# Armed BEFORE tony_tpu's class definitions import (decoration is the
# instrumentation point). Opt out with TONY_RACE_DETECTOR=0. The
# detector needs the sanitizer's lock bookkeeping, so it implies
# TONY_LOCK_SANITIZER=1.
# ---------------------------------------------------------------------------
if os.environ.get("TONY_RACE_DETECTOR", "") != "0" \
        and _sanitizer is not None:
    os.environ["TONY_RACE_DETECTOR"] = "1"
    os.environ.setdefault(
        "TONY_RACE_DETECTOR_DIR",
        tempfile.mkdtemp(prefix="tony-race-"))
    from tony_tpu.devtools import race as _race

    _race.maybe_enable_from_env()
else:
    _race = None


def pytest_sessionfinish(session, exitstatus):
    """Tier-1 acceptance gate: zero lock-order cycles, zero
    hold-while-blocking hazards AND zero data races across the whole
    suite — this process AND every armed subprocess the e2e drills
    spawned."""
    if _sanitizer is not None and _sanitizer.enabled():
        reports = _sanitizer.collect_reports()
        bad = [r for r in reports if r.get("cycles") or r.get("hazards")]
        if bad:
            print("\n=== LOCK SANITIZER FINDINGS "
                  "(tony_tpu/devtools/sanitizer.py) ===")
            print(_sanitizer.format_report(bad))
            session.exitstatus = 1
    if _race is not None and _race.enabled():
        reports = _race.collect_reports()
        bad = [r for r in reports if r.get("races")]
        if bad:
            print("\n=== DATA-RACE DETECTOR FINDINGS "
                  "(tony_tpu/devtools/race.py) ===")
            print(_race.format_report(bad))
            session.exitstatus = 1


# ---------------------------------------------------------------------------
# Per-test watchdog (VERDICT r3 #7: the suite must be un-hangable).
# No pytest-timeout plugin in this image, so a SIGALRM-based guard: a test
# that exceeds its budget fails with a TimeoutError instead of wedging the
# whole run (a round-3 full-suite run survived `timeout`'s SIGTERM for 6+
# minutes inside a hung teardown). Override per test with
# @pytest.mark.timeout_s(N). SIGALRM only fires in the main thread, which
# is exactly where the blocking waits (subprocess.wait, Event.wait) live.
# ---------------------------------------------------------------------------
DEFAULT_TEST_TIMEOUT_S = 180


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "timeout_s(n): per-test watchdog budget in seconds")


def _watchdog(item, phase):
    marker = item.get_closest_marker("timeout_s")
    budget = int(marker.args[0]) if marker else DEFAULT_TEST_TIMEOUT_S

    def _alarm(signum, frame):
        raise TimeoutError(
            f"{item.nodeid} {phase} exceeded its {budget}s watchdog "
            f"(conftest.py; raise with @pytest.mark.timeout_s)")

    old = signal.signal(signal.SIGALRM, _alarm)
    signal.alarm(budget)
    return old


def _disarm(old):
    signal.alarm(0)
    signal.signal(signal.SIGALRM, old)


# Guard all three phases: the round-3 wedge was a HUNG TEARDOWN, so the
# call phase alone would re-admit exactly the motivating failure. (Module-
# scoped fixture setup shared by several tests gets the single budget of
# the first test that triggers it — generous enough in practice.)
@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_setup(item):
    old = _watchdog(item, "setup")
    try:
        yield
    finally:
        _disarm(old)


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    old = _watchdog(item, "call")
    try:
        yield
    finally:
        _disarm(old)


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_teardown(item):
    old = _watchdog(item, "teardown")
    try:
        yield
    finally:
        _disarm(old)


# ---------------------------------------------------------------------------
# Protocol invariant checking of drill artifacts (tonycheck: tony_tpu/
# devtools/invariants.py). Every e2e and virtual-gang drill that ran a
# real coordinator left a job dir (journal + span log + metrics) under
# its tmp_path; verify the control-plane protocol held at teardown, so
# every existing slow drill doubles as a protocol test. Opt out with
# TONY_CHECK_ARTIFACTS=0.
# ---------------------------------------------------------------------------
@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_makereport(item, call):
    outcome = yield
    rep = outcome.get_result()
    setattr(item, "_tony_rep_" + rep.when, rep)


@pytest.fixture(autouse=True)
def _verify_drill_artifacts(request):
    """Autouse teardown gate: run `tony-tpu check` over every job dir
    the test produced. Scoped to the e2e/scale drill modules, and only
    when the test itself PASSED — a failing test's artifacts are
    evidence, not a second failure."""
    # Resolve tmp_path at SETUP (declaring the dependency orders this
    # fixture's teardown before tmp_path's — at teardown time the value
    # is no longer requestable).
    tmp_path = None
    mod = request.module.__name__.rpartition(".")[2]
    if (os.environ.get("TONY_CHECK_ARTIFACTS", "") != "0"
            and (mod.startswith("test_e2e") or mod == "test_scale")
            and "tmp_path" in request.fixturenames):
        tmp_path = request.getfixturevalue("tmp_path")
    yield
    if tmp_path is None:
        return
    rep_call = getattr(request.node, "_tony_rep_call", None)
    if rep_call is None or not rep_call.passed:
        return
    from tony_tpu.devtools import invariants

    reports = invariants.check_tree(str(tmp_path))
    bad = [r for r in reports if not r.ok]
    if bad:
        pytest.fail(
            "protocol invariant violation(s) in this drill's job "
            "artifacts (tony-tpu check):\n"
            + invariants.render_text(bad), pytrace=False)
