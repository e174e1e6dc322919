"""Leaf staging and cache layer (``core/engine.py`` ``_LeafCache``,
``_prepare_graph``): megabytes of operands staged for upload per query,
read from the program's ``engine.leaf_bytes_staged`` counter (absent when
nothing was staged)."""


def read(w):
    c = w.counters
    if c is None or not w.n_queries or not c.get("engine.flushes"):
        return None
    return c.get("engine.leaf_bytes_staged", 0) / 1e6 / w.n_queries
