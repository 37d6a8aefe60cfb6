"""Fault-injection harness: arming syntax, counts, probability, and the
zero-overhead guarantee when KUKEON_FAULTS is unset."""

import os

import pytest

from kukeon_tpu import faults


def test_unarmed_is_a_noop():
    """The guard contract: with KUKEON_FAULTS unset, maybe_fail builds no
    table, takes no lock-protected slow path, and never raises — the seams
    threaded through engine dispatch/transfers stay free in production."""
    assert os.environ.get(faults.ENV) is None
    assert not faults.active()
    for _ in range(1000):
        faults.maybe_fail("engine.decode")
    # Nothing parsed, nothing counted: the armed-path state stays empty.
    assert faults._cached_spec is None
    assert faults._points == {}
    assert faults.stats == {}


class _ForbiddenLock:
    """Stands in for faults._lock: taking it is an error, and is counted."""

    def __init__(self):
        self.taken = 0

    def acquire(self, *a, **kw):
        self.taken += 1
        raise AssertionError("the fault table's lock was taken")

    __enter__ = acquire

    def __exit__(self, *exc):
        return False


def test_unarmed_returns_before_the_lock_and_armed_miss_takes_it(monkeypatch):
    """With KUKEON_FAULTS unset maybe_fail is a bare env lookup: it returns
    without touching the table's lock. Armed for ANOTHER point it has to
    read the table, so it does take the lock."""
    lock = _ForbiddenLock()
    monkeypatch.setattr(faults, "_lock", lock)
    monkeypatch.delenv(faults.ENV, raising=False)
    for _ in range(1000):
        faults.maybe_fail("p")
    assert lock.taken == 0
    assert faults._cached_spec is None        # nothing was parsed either

    monkeypatch.setenv(faults.ENV, "other.point:1")
    with pytest.raises(AssertionError, match="lock was taken"):
        faults.maybe_fail("p")
    assert lock.taken == 1


@pytest.mark.faults
def test_always_fires_and_counts():
    os.environ[faults.ENV] = "engine.decode:1"
    with pytest.raises(faults.FaultInjected):
        faults.maybe_fail("engine.decode")
    with pytest.raises(faults.FaultInjected):
        faults.maybe_fail("engine.decode")
    faults.maybe_fail("engine.prefill")   # unarmed point passes
    assert faults.fired("engine.decode") == 2
    assert faults.fired("engine.prefill") == 0


@pytest.mark.faults
def test_count_cap_exhausts():
    os.environ[faults.ENV] = "cell.http:1:2"
    with pytest.raises(faults.FaultInjected):
        faults.maybe_fail("cell.http")
    with pytest.raises(faults.FaultInjected):
        faults.maybe_fail("cell.http")
    faults.maybe_fail("cell.http")        # cap reached: passes forever after
    faults.maybe_fail("cell.http")
    assert faults.fired("cell.http") == 2


@pytest.mark.faults
def test_multiple_points_and_env_reparse():
    os.environ[faults.ENV] = "a:1, b:1:1"
    with pytest.raises(faults.FaultInjected):
        faults.maybe_fail("a")
    with pytest.raises(faults.FaultInjected):
        faults.maybe_fail("b")
    faults.maybe_fail("b")                # b exhausted
    # Re-arming with a different spec takes effect immediately (no reset).
    os.environ[faults.ENV] = "c:1"
    faults.maybe_fail("a")
    with pytest.raises(faults.FaultInjected):
        faults.maybe_fail("c")


@pytest.mark.faults
def test_probability_zero_never_fires():
    os.environ[faults.ENV] = "p:0"
    for _ in range(200):
        faults.maybe_fail("p")
    assert faults.fired("p") == 0


@pytest.mark.faults
def test_custom_exception_and_message():
    os.environ[faults.ENV] = "io:1"
    with pytest.raises(OSError, match="disk gone"):
        faults.maybe_fail("io", exc=OSError, msg="disk gone")


@pytest.mark.faults
def test_bad_spec_fails_loudly():
    os.environ[faults.ENV] = "point:not-a-prob"
    with pytest.raises(ValueError):
        faults.maybe_fail("point")
