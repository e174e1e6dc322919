"""Unpack layer (``core/engine.py`` ``_run_staged``,
``PlaneLayout.from_wire`` and ``join_raw``): host milliseconds per query
turning fetched wire words into the caller's values, read from the
program's ``flush.unpack`` spans."""


def read(w):
    if w.spans is None or not w.n_queries:
        return None
    ns = [t1 - t0 for name, t0, t1, _ in w.spans if name == "flush.unpack"]
    return sum(ns) / 1e6 / w.n_queries if ns else None
