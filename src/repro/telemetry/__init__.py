"""repro.telemetry — zero-overhead-when-disabled observability.

Four pieces, one contract:

* :class:`CounterBank` — named monotonic counters + log2-bucket
  histograms; the single counter container used by the engine, the
  serve tier, and the derived controller counters.
* :func:`derive_controller_counters` — post-hoc replay of a
  ``ScheduleResult``/``MuxResult`` command trace into bus-utilization,
  row-buffer, stall, and refresh counters. Derivation only *reads* the
  audit trail the controller already emits, so scheduling stays
  byte-identical whether or not anyone is watching.
  :func:`derive_port_counters` extends the same replay to a
  ``CrossbarTrace``'s per-client-port attribution (grant counts,
  starvation gaps), and :func:`check_timing_invariants` audits any
  trace against the rank-wide tRRD/tFAW/tCCD/bus/refresh contract,
  returning a list of violations (empty = clean).
* :class:`Tracer` / :data:`NULL_TRACER` — span context-managers around
  the fused pipeline's flush phases, exportable as Chrome trace-event
  JSON (opens in Perfetto); every span is also a JAX profiler
  annotation, so a profiler trace carries them on the device's clock.
* :func:`process_counters` — process-wide compile counters
  (``compile.*``), fed by JAX's monitoring events from import on.

See ``docs/observability.md`` for counter definitions, units, and the
span taxonomy.
"""

from repro.telemetry.counters import (CounterBank, check_timing_invariants,
                                      derive_controller_counters,
                                      derive_port_counters)
from repro.telemetry.compiles import process_counters
from repro.telemetry.tracer import NULL_TRACER, Span, Tracer

__all__ = [
    "CounterBank",
    "NULL_TRACER",
    "Span",
    "Tracer",
    "check_timing_invariants",
    "derive_controller_counters",
    "derive_port_counters",
    "process_counters",
]
