"""Which implementation each dispatching op chose.

``gqa_attention``, ``decode_gqa_attention``, the scans and the expert
products pick a Pallas kernel or the XLA path from what they can observe
(backend, devices, shapes); the int8 product has the one XLA path and is
counted with them. That choice is made once per trace and is invisible
afterwards — the compiled program just runs — so the dispatchers count it
here where they make it, and the serving engine exports the counts as
``kukeon_op_impl_traces_total{op=,impl=}``: a scrape (and
``chip_smoke.py``) can say which path the programs on this device took
without lowering anything again.

Process-wide by nature, like ``kukeon_tpu.faults``' fire counts: the ops
are pure functions deep inside a jit trace with no object to hang a
counter on. Counts are traces, not executions or layers — a ``lax.scan``
over 32 layers traces its body once.
"""

from __future__ import annotations

import collections
import threading

_lock = threading.Lock()
_traces: collections.Counter[tuple[str, str]] = collections.Counter()


def note(op: str, impl: str) -> None:
    """Record that ``op`` chose ``impl`` ("pallas" | "xla") in this trace."""
    with _lock:
        _traces[(op, impl)] += 1


def counts() -> dict[tuple[str, str], int]:
    with _lock:
        return dict(_traces)
