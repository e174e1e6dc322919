"""Process-wide compile counters, fed by JAX's monitoring events.

JAX reports how long it spent tracing a function to a jaxpr, lowering the
jaxpr to an MLIR module, compiling the module for the backend, and
loading an executable from the persistent compilation cache. One
listener, registered when :mod:`repro.telemetry` is imported, sums those
reports into :func:`process_counters` (a :class:`CounterBank`). It is
always on, because a program compiles during set-up, before anyone
attaches a tracer; it costs nothing on the hot path, since the events
fire only when something compiles.

Counters (seconds of wall time):

* ``compile.trace_s`` — tracing Python to jaxprs;
* ``compile.lower_s`` — lowering jaxprs to MLIR modules;
* ``compile.backend_s`` — backend compilation, which holds the
  persistent-cache lookup and load;
* ``compile.cache_load_s`` — loading executables from the persistent
  cache (a part of ``compile.backend_s``);
* ``compile.s`` — wall time in which any of trace, lower or backend
  compile ran: the set-up time compilation costs.

Traces nest (a jitted function calls jitted ``jnp`` functions, each
traced inside the outer trace), so each counter adds the union of its
events' wall intervals, never an interval twice.
"""

from __future__ import annotations

import threading

from jax import monitoring

from repro.telemetry.counters import CounterBank

TIMED = {
    "/jax/core/compile/jaxpr_trace_duration": "compile.trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile.lower_s",
    "/jax/core/compile/backend_compile_duration": "compile.backend_s",
}
CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"
# Disjoint intervals kept per counter: older ones could only be covered
# again by an event still open since then.
_KEEP = 1024

_BANK = CounterBank()
_LOCK = threading.Lock()
_SPANS: dict[str, list[list[float]]] = {}


def process_counters() -> CounterBank:
    """The process's compile counters (live; take a ``snapshot()`` to
    window them)."""
    return _BANK


def union_add(spans: list, a: float, b: float) -> float:
    """Merge ``[a, b]`` into ``spans`` (sorted, disjoint ``[start, end]``
    pairs, changed in place) and return the part of it not yet covered."""
    j = len(spans)
    while j and spans[j - 1][1] >= a:  # ends rise: overlaps are a suffix
        j -= 1
    new, lo, hi, later = b - a, a, b, []
    for s, e in spans[j:]:
        if s > b:
            later.append([s, e])
        else:
            new -= min(e, b) - max(s, a)
            lo, hi = min(lo, s), max(hi, e)
    spans[j:] = [[lo, hi]] + later
    del spans[:-_KEEP]
    return new


def _on_span(event: str, start: float, end: float, **_) -> None:
    name = TIMED.get(event)
    if name is None:
        return
    with _LOCK:
        for key in (name, "compile.s"):
            new = union_add(_SPANS.setdefault(key, []), start, end)
            if new > 0:
                _BANK.inc(key, new)


def _on_duration(event: str, seconds: float, **_) -> None:
    if event == CACHE_LOAD:
        with _LOCK:
            _BANK.inc("compile.cache_load_s", seconds)


monitoring.register_event_time_span_listener(_on_span)
monitoring.register_event_duration_secs_listener(_on_duration)
