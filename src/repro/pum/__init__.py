"""repro.pum — the public API of the PULSAR PuM compute stack.

Everything an application needs is here; nothing else in the repo is a
stable surface. The pieces:

* :class:`PumArray` — ndarray-like handle with operator overloading
  (``& | ^ + - * // % < > <= >=``, ``divmod()``, ``popcount()``,
  ``reduce_bits()``) unifying eager results, fused lazy handles and raw
  packed-bitmap words behind one type;
* :class:`Device` + :class:`EngineConfig` — configuration and lifecycle
  (``pum.device(...)`` as a context manager scopes the default device
  for ``pum.asarray`` and auto-flushes on exit);
* telemetry (:func:`profile`, :class:`Tracer`, :class:`CounterBank`) —
  ``with pum.profile(path="trace.json"):`` traces fused flush phases to
  Chrome trace-event JSON and populates ``Device.counters``; zero
  overhead (and zero behavior change) when not profiling. See
  ``docs/observability.md``.
* reliability (:func:`calibrate`, :class:`ReliabilityMap`,
  :class:`ReliabilityConfig`) — calibrate a simulated chip into a
  per-bank/per-subarray/per-column map, then
  ``EngineConfig(reliability=...)`` (or ``Device.calibrate()``) turns on
  variation-aware replication planning, weak-column steering and —
  opt-in — fault injection with replication-vote correction and retry
  escalation. See ``docs/reliability.md``.
* concurrency (``Device.flush_async`` -> :class:`FlushHandle`,
  ``Device.capture`` -> :class:`CapturedProgram`, ``Device.client``) —
  N client contexts record into one device without interleaving, flushes
  compile/dispatch off the caller's thread, and steady-state programs
  replay a captured pipeline with zero re-recording. See the
  "Concurrent clients & async flush" section of
  ``docs/execution-pipeline.md``.
* autotuning (``Device.autotune`` -> :class:`TunedPlan`, :class:`Tuner`,
  :class:`WorkloadProfile`) — a :class:`WorkloadProfile` extracted from
  measured counters (``Device.reset_counters`` / ``CounterBank``
  snapshot deltas scope the window), a deterministic cost model, and an
  exhaustive search over backend/layout/flush-threshold/REF/lookahead
  knobs. Applied plans change only where/when programs run — outputs
  and ``EngineStats`` stay bit-identical. See ``docs/autotuning.md``.

See ``docs/api.md`` for the full surface, the Device lifecycle, the
fused evaluators and the rule that chooses among them
(``repro.backends``), and the old-call -> new-call migration table.
"""

from repro.autotune import TunedPlan, Tuner, WorkloadProfile
from repro.core.engine import EngineStats, FlushHandle
from repro.kernels.plane_layout import (LAYOUT32, LAYOUT64, PlaneLayout,
                                        get_layout)
from repro.pum.api import (Device, PumArray, asarray, default_device,
                           device, profile)
from repro.pum.capture import CapturedProgram
from repro.pum.config import EngineConfig
from repro.reliability import ReliabilityConfig, ReliabilityMap, calibrate
from repro.telemetry import CounterBank, Tracer

__all__ = [
    "CapturedProgram",
    "CounterBank",
    "Device",
    "EngineConfig",
    "EngineStats",
    "FlushHandle",
    "LAYOUT32",
    "LAYOUT64",
    "PlaneLayout",
    "PumArray",
    "ReliabilityConfig",
    "ReliabilityMap",
    "Tracer",
    "TunedPlan",
    "Tuner",
    "WorkloadProfile",
    "asarray",
    "calibrate",
    "default_device",
    "device",
    "get_layout",
    "profile",
]
