"""Device wait layer (``core/engine.py`` ``_run_staged``): host
milliseconds per query blocked on the device until a flush's outputs are
computed, read from the program's ``flush.wait`` spans."""


def read(w):
    if w.spans is None or not w.n_queries:
        return None
    ns = [t1 - t0 for name, t0, t1, _ in w.spans if name == "flush.wait"]
    return sum(ns) / 1e6 / w.n_queries if ns else None
