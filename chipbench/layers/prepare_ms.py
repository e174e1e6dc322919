"""Flush preparation layer (``core/engine.py`` ``_prepare_graph`` and
the dispatch half of ``_run_staged``): host milliseconds per query from
the end of recording to the dispatched program: normalizing the program,
staging leaves, selecting the pipeline and enqueueing it. Read from the
program's ``flush.optimize``, ``flush.leaf_upload``, ``flush.compile``
and ``flush.dispatch`` spans."""

PHASES = ("flush.optimize", "flush.leaf_upload", "flush.compile",
          "flush.dispatch")


def read(w):
    if w.spans is None or not w.n_queries:
        return None
    ns = [t1 - t0 for name, t0, t1, _ in w.spans if name in PHASES]
    return sum(ns) / 1e6 / w.n_queries if ns else None
