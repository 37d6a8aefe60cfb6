"""The host-state lock of a pytest-xdist run, one hold a worker process.

``tests/test_netpolicy_e2e.py`` (marked ``host_exclusive``) kill -9s every
kukepause / kukeshim / kukecell on the host and deletes every kukeon bridge;
the only tests that can lose one are those that start a daemon with cells of
its own, and they all do it through ``tests.test_runtime_e2e.Daemon``. So
``Daemon`` takes the lock shared (``share``), ``tests/conftest.py`` takes it
exclusively around the marked module and drops a test's hold when the test
ends; a test that starts no process of the runtime never touches it. The
gate makes a waiting writer win: it keeps the gate while it waits, so no
new sharer slips in. A serial run never waits.
"""

import fcntl
import os
import tempfile

_held = None    # this process's hold on the host lock: the open lock file


def _flock(name: str, how: int):
    f = open(os.path.join(tempfile.gettempdir(), f"kukeon-tests-{name}.lock"),
             "w")
    fcntl.flock(f, how)
    return f


def _take(how: int) -> None:
    global _held
    gate = _flock("gate", fcntl.LOCK_EX)
    try:
        _held = _flock("host", how)
    finally:
        gate.close()


def exclusive() -> None:
    _take(fcntl.LOCK_EX)


def share() -> None:
    """Hold the lock shared from here to the end of the test (a second call,
    or one under the exclusive hold, changes nothing)."""
    if _held is None:
        _take(fcntl.LOCK_SH)


def release() -> None:
    global _held
    if _held is not None:
        _held.close()
        _held = None
