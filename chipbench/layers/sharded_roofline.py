"""Sharded evaluator layer (``kernels/fused_program.py``
``build_sharded_words_pipeline``, ``shard-words``): the share of the
roofline of the chips a flush is split across, in percent. The least
time is the user-data bytes of the window's queries over n chips' peak
HBM bytes per second, n the most devices a flush's outputs were split
across in the window (the program's ``engine.flush_devices``
histogram); it is divided by the device busy time, which the trace
averages over the cell's chips."""


def read(w):
    c, t = w.counters, w.trace
    if c is None or t is None or t["busy_s"] <= 0 or not w.n_queries:
        return None
    try:
        n = int(c.histogram("engine.flush_devices")["max"])
    except KeyError:
        return None
    if n < 1:
        return None
    least_s = sum(q.nbytes for q in w.answered) \
        / (n * w.peak["hbm_bytes_per_s"])
    return 100.0 * least_s / t["busy_s"]
