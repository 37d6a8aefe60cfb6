"""Test bootstrap: force an 8-device virtual CPU mesh.

Multi-chip TPU hardware is not available in CI; all sharding/parallelism tests
run against ``--xla_force_host_platform_device_count=8`` CPU devices, mirroring
the reference's strategy of testing against fakes rather than real systems
(reference: internal/ctr tests with fake containerd services,
SURVEY.md section 4). ``JAX_PLATFORMS=cpu`` in the environment works too
(the tier-1 command sets it); pinning the platform here as well keeps a bare
``pytest`` off any attached chip.
"""

import os

# Tests must never program real bridges/iptables, even when running as root
# on a host that has the binaries (the runtime's autodetection would).
os.environ["KUKEON_NET_ENFORCE"] = "0"

# Appended last so it wins over any caller-provided count. KUKEON_TEST_DEVICES
# overrides the virtual-chip count (the CI sharded-serving job runs the suite
# at 4 to prove the multi-chip tests hold on a different factorization).
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count="
    + os.environ.get("KUKEON_TEST_DEVICES", "8")
)

# One persistent compile cache per test session, shared with the cells the
# e2e tests spawn (they inherit the variable; with it set the program sets
# no directory of its own). The checkout's .jax_cache is for real runs: a
# session that read what other kinds of processes wrote there would log XLA
# loader warnings into the middle of pytest's progress lines.
if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
    import atexit
    import shutil
    import tempfile

    os.environ["JAX_COMPILATION_CACHE_DIR"] = tempfile.mkdtemp(
        prefix="kukeon-test-jax-cache-")
    atexit.register(shutil.rmtree, os.environ["JAX_COMPILATION_CACHE_DIR"],
                    ignore_errors=True)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running tests excluded from the tier-1 run "
        "(subprocess daemons, real multi-cell federation e2e)")
    config.addinivalue_line(
        "markers",
        "faults: tests that arm KUKEON_FAULTS (the fault-injection harness)")
    config.addinivalue_line(
        "markers",
        "host_exclusive: a module that changes host-global state other "
        "tests' cells depend on; no other test runs while it is on a "
        "pytest-xdist worker")


@pytest.fixture(scope="module", autouse=True)
def _host_exclusive_module(request):
    """A module marked ``host_exclusive`` holds the host-state lock
    exclusively for its whole stay on a pytest-xdist worker
    (tests/host_lock.py says who holds it shared, and when)."""
    import host_lock

    if request.node.get_closest_marker("host_exclusive") is None:
        yield
        return
    host_lock.exclusive()
    try:
        yield
    finally:
        host_lock.release()


@pytest.fixture(autouse=True)
def _host_lock_ends_with_the_test(request):
    """A test that took the host lock shared (it started a daemon) gives it
    back when it ends, whatever became of the daemon."""
    import host_lock

    yield
    if request.node.get_closest_marker("host_exclusive") is None:
        host_lock.release()


@pytest.fixture
def chips2_mesh():
    """A 2-chip tensor-parallel serving mesh on the forced CPU devices —
    the `chips: 2` grant as the engine sees it. Any even virtual-device
    count satisfies it (8 locally, 4 in the CI sharded job)."""
    from kukeon_tpu.parallel import serving_mesh

    return serving_mesh(2)


@pytest.fixture(autouse=True)
def _isolate_profile_spool(tmp_path, monkeypatch):
    """Point the on-demand profiler spool (KUKEON_PROFILE_DIR) at a per-test
    temp dir: captures from one test must never satisfy another test's
    listing, and the shared /tmp default must never accumulate CI garbage."""
    monkeypatch.setenv("KUKEON_PROFILE_DIR", str(tmp_path / "profiles"))


_SANITIZE_SESSION = False   # KUKEON_SANITIZE was set when the session began


def pytest_sessionstart(session):
    """Latch the sanitizer opt-in at session start: individual tests
    monkeypatch KUKEON_SANITIZE for their fixtures, and the per-test gate
    below must key off the *session-level* opt-in, not whatever a test
    left in the environment."""
    global _SANITIZE_SESSION
    from kukeon_tpu import sanitize

    _SANITIZE_SESSION = sanitize.enabled()


@pytest.fixture(autouse=True)
def _sanitize_findings_gate():
    """kukesan per-test gate: under a KUKEON_SANITIZE=1 session, any
    sanitizer finding a test produced (unguarded write to lock-guarded
    state, blocking call under a hot lock, observed lock-order cycle)
    fails THAT test with the recorded stacks. Findings are drained either
    way so fixture tests that deliberately provoke them stay isolated."""
    from kukeon_tpu import sanitize

    leftover = sanitize.drain_findings()
    yield
    found = sanitize.drain_findings()
    if _SANITIZE_SESSION:
        if leftover:
            # Produced between tests (teardown threads of an earlier
            # test): surface rather than silently blaming nobody.
            found = leftover + found
        assert not found, (
            "kukesan findings:\n\n"
            + "\n\n".join(f.render() for f in found))


def pytest_sessionfinish(session, exitstatus):
    """Close the static/dynamic loop: at the end of a sanitized session,
    write the merged lock-graph report (runtime-observed edges vs the
    KUKE006 static graph) to KUKEON_SANITIZE_REPORT when set."""
    out = os.environ.get("KUKEON_SANITIZE_REPORT")
    if not out or not _SANITIZE_SESSION:
        return
    import json

    from kukeon_tpu import sanitize

    with open(out, "w", encoding="utf-8") as f:
        json.dump(sanitize.merge_report(), f, indent=2)


@pytest.fixture(autouse=True)
def _isolate_telemetry_env(monkeypatch):
    """The RPC service constructs a FleetTelemetry (TSDB + alert engine)
    on every instantiation; stray alert-rule / retention env from one test
    must never rewire another test's daemon."""
    for var in ("KUKEON_ALERT_RULES", "KUKEON_ALERT_WEBHOOK",
                "KUKEON_SCRAPE_INTERVAL_S", "KUKEON_TSDB_RETENTION_S",
                "KUKEON_TSDB_MAX_SERIES", "KUKEON_SCALER_DRAIN_TIMEOUT_S"):
        monkeypatch.delenv(var, raising=False)


@pytest.fixture(autouse=True)
def _isolate_faults():
    """Guarantee KUKEON_FAULTS never leaks between tests: an armed fault
    spec surviving one test would fire random failures in the next. Cleared
    (and the parsed table + fire counts reset) on both sides of every test;
    tests arm faults by setting os.environ inside their own body."""
    from kukeon_tpu import faults

    os.environ.pop(faults.ENV, None)
    faults.reset()
    yield
    os.environ.pop(faults.ENV, None)
    faults.reset()
