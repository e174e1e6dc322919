"""Leaf placement layer (``core/engine.py`` ``_run_staged``): host
milliseconds per query resolving a flush's cached operands against its
pipeline and committing those not yet on the device under the
pipeline's placement, read from the program's ``flush.place`` spans."""


def read(w):
    if w.spans is None or not w.n_queries:
        return None
    ns = [t1 - t0 for name, t0, t1, _ in w.spans if name == "flush.place"]
    return sum(ns) / 1e6 / w.n_queries if ns else None
