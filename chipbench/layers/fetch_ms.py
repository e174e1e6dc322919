"""Copy back layer (``core/engine.py`` ``_run_staged``): host
milliseconds per query copying a flush's outputs from the device, read
from the program's ``flush.fetch`` spans."""


def read(w):
    if w.spans is None or not w.n_queries:
        return None
    ns = [t1 - t0 for name, t0, t1, _ in w.spans if name == "flush.fetch"]
    return sum(ns) / 1e6 / w.n_queries if ns else None
