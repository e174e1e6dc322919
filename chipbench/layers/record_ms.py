"""Recording layer (``pum/api.py`` operators, ``core/engine.py``
``_record`` and ``leaf_id``): host milliseconds per query from the first
recorded op to the flush, read from the program's ``flush.record`` spans."""


def read(w):
    if w.spans is None or not w.n_queries:
        return None
    ns = [t1 - t0 for name, t0, t1, _ in w.spans if name == "flush.record"]
    return sum(ns) / 1e6 / w.n_queries if ns else None
