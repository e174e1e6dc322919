"""Leaf staging and cache layer: the share of a flush's operands that the
device-resident leaf cache served, in percent, from the program's
``engine.leaf_cache.hits`` and ``engine.leaf_cache.misses`` counters."""


def read(w):
    c = w.counters
    if c is None:
        return None
    hits = c.get("engine.leaf_cache.hits", 0)
    total = hits + c.get("engine.leaf_cache.misses", 0)
    return 100.0 * hits / total if total else None
