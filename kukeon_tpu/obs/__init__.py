"""Unified observability layer: metrics registry, Prometheus exposition,
and per-request trace spans.

Zero-dependency by design (the container bakes no prometheus_client): the
registry is a few hundred lines of locked dicts, the exposition is the
Prometheus text format 0.0.4 by hand, and traces are dataclasses in a ring
buffer. Everything the serving engine, the cells, the runner, and the
daemon report flows through here; ``benchmark/run.py`` reads its counter
metrics off the same ``/metrics`` a production scrape would read.

Naming convention: ``kukeon_<subsystem>_<name>`` with ``_total`` for
counters and ``_seconds`` for latency histograms — e.g.
``kukeon_engine_ttft_seconds``, ``kukeon_runner_cell_restarts_total``,
``kukeon_faults_fired_total{point="engine.decode"}``.
"""

from kukeon_tpu.obs.registry import (  # noqa: F401
    LATENCY_BUCKETS_S,
    Counter,
    Gauge,
    Histogram,
    Registry,
    get_default,
    percentile_from_counts,
)
from kukeon_tpu.obs.expo import (  # noqa: F401
    faults_collector,
    op_impl_collector,
    render,
)
from kukeon_tpu.obs.trace import (  # noqa: F401
    PHASES,
    TRACEPARENT_HEADER,
    Span,
    TraceContext,
    Tracer,
    format_traceparent,
    new_span_id,
    new_trace_id,
    parse_traceparent,
)
from kukeon_tpu.obs.device import (  # noqa: F401
    CompileTracker,
    ProfileBusy,
    ProfileSpool,
    device_memory_collector,
)
from kukeon_tpu.obs.profile import (  # noqa: F401
    PROGRAMS,
    FlightRecorder,
    ProgramTimers,
)
from kukeon_tpu.obs.slo import SloObjectives, SloTracker  # noqa: F401
from kukeon_tpu.obs.tsdb import (  # noqa: F401
    AGGS,
    TSDB,
    parse_expr,
    parse_selector,
    parse_window,
    sparkline,
)
from kukeon_tpu.obs.alerts import (  # noqa: F401
    BUILTIN_RULES,
    AlertEngine,
    Rule,
    load_user_rules,
    validate_rule,
)
