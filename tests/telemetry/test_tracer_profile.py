"""Tracer span mechanics, Chrome trace-event export, and the
pum.profile() flush-phase coverage + pipeline-cache counters."""

import json

import numpy as np
import pytest

import repro.pum as pum
from repro.kernels import fused_program as _fused
from repro.telemetry import NULL_TRACER, CounterBank, Tracer

pytestmark = pytest.mark.fused

FLUSH_PHASES = ["flush.record", "flush.optimize", "flush.leaf_upload",
                "flush.compile", "flush.dispatch", "flush.materialize"]


# --------------------------------------------------------------------- #
# Tracer primitives
# --------------------------------------------------------------------- #


def test_span_records_duration_and_args():
    tr = Tracer()
    with tr.span("work", n=3) as sp:
        sp.args["extra"] = "late"
    (name, t0, t1, args), = tr.events
    assert name == "work" and t1 >= t0
    assert args == {"n": 3, "extra": "late"}
    assert sp.dur_ns == t1 - t0


def test_null_tracer_is_inert():
    with NULL_TRACER.span("x", a=1) as sp:
        sp.args["y"] = 2       # writes vanish; no shared state mutated
    assert sp.dur_ns == 0
    assert sp.args == {}


def test_chrome_export_shape(tmp_path):
    tr = Tracer()
    with tr.span("alpha", k="v"):
        pass
    path = tmp_path / "trace.json"
    tr.export(str(path), counters=CounterBank())
    doc = json.loads(path.read_text())
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    complete = [e for e in evs if e["ph"] == "X"]
    instants = [e for e in evs if e["ph"] == "i"]
    assert [e["name"] for e in complete] == ["alpha"]
    assert complete[0]["args"] == {"k": "v"}
    assert complete[0]["dur"] >= 0          # microseconds
    assert [e["name"] for e in instants] == ["counters"]
    assert instants[0]["s"] == "g"
    assert instants[0]["args"] == {"counters": {}, "histograms": {}}


# --------------------------------------------------------------------- #
# pum.profile(): flush-phase coverage + cache counters
# --------------------------------------------------------------------- #


def _work(dev, n=128):
    x = dev.asarray(np.arange(n, dtype=np.uint64))
    return ((x + 3) * x // (x + 1)).to_numpy()


def test_profile_covers_all_flush_phases(tmp_path):
    dev = pum.device(width=16, fuse=True)
    path = tmp_path / "trace.json"
    with pum.profile(dev, path=str(path)) as tr:
        _work(dev)
    names = tr.span_names()
    for phase in FLUSH_PHASES:
        assert phase in names, f"missing span {phase} in {names}"
    # Exported trace carries the same spans plus the counters snapshot.
    doc = json.loads(path.read_text())
    exported = {e["name"] for e in doc["traceEvents"]}
    assert set(FLUSH_PHASES) <= exported
    counter_evs = [e for e in doc["traceEvents"] if e["name"] == "counters"]
    assert len(counter_evs) == 1
    assert counter_evs[0]["args"]["counters"]["engine.flushes"] >= 1


def test_profile_cache_miss_then_hit():
    _fused._cached_pipeline.cache_clear()
    dev = pum.device(width=16, fuse=True)
    with pum.profile(dev):
        _work(dev)          # cold: compile miss
        dev.flush()
        _work(dev)          # identical structure: cache hit
    assert dev.counters["engine.pipeline_cache.miss"] >= 1
    assert dev.counters["engine.pipeline_cache.hit"] >= 1


def test_profile_counts_recorded_ops_and_autoflush():
    dev = pum.device(width=16, fuse=True, flush_threshold=4)
    with pum.profile(dev):
        x = dev.asarray(np.arange(32, dtype=np.uint64))
        for _ in range(6):
            x = x + 1
        x.to_numpy()
    assert dev.counters["engine.ops_recorded"] >= 6
    assert dev.counters["engine.op.add"] >= 6
    assert dev.counters["engine.autoflush.ops"] >= 1
    assert dev.counters["engine.flushes"] >= 2


def test_flush_span_args_carry_graph_shape():
    dev = pum.device(width=16, fuse=True)
    with pum.profile(dev) as tr:
        _work(dev, n=64)
    by_name = {name: args for name, _, _, args in tr.events}
    assert by_name["flush.optimize"]["n_ops_in"] >= 1
    assert by_name["flush.optimize"]["n_ops_out"] >= 1
    assert by_name["flush.dispatch"]["n_lanes"] == 64
    assert by_name["flush.compile"]["cache"] in ("hit", "miss")
