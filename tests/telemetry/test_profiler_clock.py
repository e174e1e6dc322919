"""The flush spans on the JAX profiler's clock, and the process compile
counters.

With a tracer attached under ``jax.profiler.start_trace``, every flush
phase lands in the profiler's ``.xplane.pb`` under its own name, the
``flush.materialize`` children nest inside it, and all spans of one flush
carry its ``flush`` id. A record phase closed on another thread stays
out of the profiler's trace. The compile listener counts a fresh
``jax.jit`` compile, each interval once."""

import glob
import threading

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

import repro.pum as pum
from repro.telemetry import compiles, process_counters

pytestmark = pytest.mark.fused

FLUSH_SPANS = ["flush.record", "flush.optimize", "flush.leaf_upload",
               "flush.compile", "flush.place", "flush.dispatch",
               "flush.materialize", "flush.wait", "flush.fetch",
               "flush.unpack"]
CHILDREN = ["flush.wait", "flush.fetch", "flush.unpack"]


def _profiled(tmp_path, work):
    """Run ``work(dev)`` with a tracer attached under the JAX profiler;
    return the tracer and the path of the ``.xplane.pb``."""
    dev = pum.device(width=16, fuse=True)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with pum.profile(dev) as tr:
            work(dev)
    finally:
        jax.profiler.stop_trace()
    paths = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    assert len(paths) == 1
    return tr, paths[0]


def _flush_events(path):
    """``(name, start_ns, end_ns, stats, line)`` of every host flush span
    in the profiler's trace."""
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("flush."):
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns,
                                {key: v for key, v in e.stats},
                                (plane.name, i)))
    return out


def _one_flush(dev):
    x = dev.asarray(np.arange(256, dtype=np.uint64))
    return ((x + 3) & x).to_numpy()


def test_flush_spans_land_in_the_profiler_trace(tmp_path):
    tr, path = _profiled(tmp_path, _one_flush)
    events = _flush_events(path)
    assert sorted({name for name, *_ in events}) == sorted(FLUSH_SPANS)
    assert len(events) == len(FLUSH_SPANS)  # one flush, one of each
    # All spans of the flush share one id, the tracer's events too.
    ids = {stats["flush"] for *_, stats, _ in events}
    assert len(ids) == 1
    assert {args["flush"] for name, *_, args in tr.events} == ids
    # The three children nest inside flush.materialize, on its thread.
    by_name = {name: (a, b, line) for name, a, b, _, line in events}
    m0, m1, m_line = by_name["flush.materialize"]
    for child in CHILDREN:
        a, b, line = by_name[child]
        assert line == m_line and m0 <= a <= b <= m1, child
    # In order: wait, then fetch, then unpack.
    starts = [by_name[c][0] for c in CHILDREN]
    assert starts == sorted(starts)
    # The fetch names the bytes it copied back.
    fetch = next(stats for name, *_, stats, _ in events
                 if name == "flush.fetch")
    assert fetch["bytes"] > 0


def test_record_span_from_another_thread_stays_out_of_the_trace(tmp_path):
    def work(dev):
        x = dev.asarray(np.arange(256, dtype=np.uint64))
        y = (x + 1) | x              # recorded on this thread
        t = threading.Thread(target=dev.engine.flush_all)
        t.start()
        t.join()                     # flushed on another
        return y.to_numpy()

    tr, path = _profiled(tmp_path, work)
    names = {name for name, *_ in _flush_events(path)}
    assert "flush.record" not in names
    assert {"flush.optimize", "flush.materialize"} <= names
    assert "flush.record" in tr.span_names()  # the tracer keeps it


def test_fresh_jit_compile_is_counted():
    before = process_counters().snapshot()
    f = jax.jit(lambda v: (v * 3 + 1).sum())  # a new function: compiles
    f(np.arange(1000, dtype=np.float32)).block_until_ready()
    d = process_counters().delta(before)
    for name in ("compile.trace_s", "compile.lower_s", "compile.backend_s",
                 "compile.s"):
        assert d.get(name) > 0, name
    stages = [d.get(k) for k in ("compile.trace_s", "compile.lower_s",
                                 "compile.backend_s")]
    # The union of the stages' wall time: no more than their sum, no less
    # than the longest.
    assert max(stages) <= d["compile.s"] <= sum(stages) + 1e-9


@pytest.mark.parametrize("spans, add, new, merged", [
    ([], (1, 3), 2, [[1, 3]]),
    ([[1, 2]], (3, 4), 1, [[1, 2], [3, 4]]),
    ([[1.5, 2], [2.5, 3]], (1, 4), 2, [[1, 4]]),   # an outer trace
    ([[1, 4]], (2, 3), 0, [[1, 4]]),               # a nested one
    ([[1, 2], [5, 6]], (1.5, 3), 1, [[1, 3], [5, 6]]),
])
def test_union_counts_each_interval_once(spans, add, new, merged):
    assert compiles.union_add(spans, *add) == pytest.approx(new)
    assert spans == merged
