"""Leaf placement layer (``core/engine.py`` ``_resolve_cached_leaves``,
``_LeafCache.device_buffer``; the ``shard-words`` placement): megabytes
of a flush's operands that crossed to a device for it, per query: cache
commits, host operands placed by the pipeline, and host operands handed
to a jitted pipeline. Read from the program's
``engine.leaf_bytes_placed`` counter; a program without the counter
reports nothing."""


def read(w):
    c = w.counters
    if c is None or not w.n_queries or "engine.leaf_bytes_placed" not in c:
        return None
    return c["engine.leaf_bytes_placed"] / 1e6 / w.n_queries
