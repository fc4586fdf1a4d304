"""obs: the flight-recorder observability plane.

Stdlib-only span recorder + exporters for every plane crossing the
engine makes (PR 12). The reference Jepsen renders latency graphs and
an HTML timeline from its histories (`checker/perf.py`,
`checker/timeline.py`); our analogue records the TPU plane's OWN
crossings — launches, host syncs, coalesce holds, collect trains,
checkpoint saves, chaos retries — as spans and exports them as
industry-standard artifacts:

- ``obs.trace``: process-wide per-thread ring-buffer recorder
  (``span(...)`` context manager + ``instant(...)`` events, disabled
  by default — the off path is one attribute check, safe in hot paths)
- ``obs.export``: Chrome-trace/Perfetto JSON + JSONL sinks
- ``obs.podtrace``: pod-wide aggregation (PR 15) — per-member ring
  persistence plus ``merge_pod_trace``, which rebases every member
  onto one clock-aligned timeline using the ``init_pod`` handshake's
  offsets and emits a single multi-process Perfetto trace
- ``obs.prom``: Prometheus text exposition folding in every ``*_STATS``
  surface plus trace-derived latency histograms and per-tenant /
  per-device labeled gauge families
- ``obs.xla``: ``xla_trace(dir)`` jax.profiler capture with the
  recorder on, so the spans land on the capture's host plane (raises
  when the profiler cannot start), unified here from
  utils/profiling.py
- ``obs.snapshot``: the ONE consolidated ``engine_snapshot()`` behind
  ``cli._engine_stats``, the daemon's ``/stats``, and the dryrun
  metric line (imported lazily — it pulls the jax-backed checker
  modules, which this package root must not)

planelint Family C (JT301-304) enforces the emission discipline:
spans close via context manager, nothing emits under a plane lock,
no obs call is reachable from jit-traced code, and nothing emits
inside a per-device/per-member fan-out loop.
"""

from jepsen_tpu.obs.trace import (  # noqa: F401
    TRACER,
    current,
    disable,
    enable,
    instant,
    reset,
    span,
    spans,
    trace_stats,
)
from jepsen_tpu.obs.export import (  # noqa: F401
    chrome_trace,
    validate_chrome_trace,
    write_chrome_trace,
    write_jsonl,
)
from jepsen_tpu.obs.podtrace import (  # noqa: F401
    ENV_TRACE_DIR,
    merge_pod_trace,
    persist_member_trace,
)
