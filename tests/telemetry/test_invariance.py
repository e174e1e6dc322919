"""Telemetry must be provably free: bit-identical results, identical
EngineStats, and identical scheduled command traces whether telemetry is
attached or not — across widths, eager vs fused, and controller="auto"."""

import types

import numpy as np
import pytest

import repro.pum as pum
from repro.controller import MemoryController, retarget_program
from repro.core.cost_model import CostModel

pytestmark = pytest.mark.fused


def _program(dev, a, b):
    x = dev.asarray(a)
    t = (x + b) * x
    t = t ^ b
    t = t & x
    q, r = divmod(t, (x | np.uint64(1)))
    return (q + r).to_numpy()


def _run(width, fuse, controller, profiled, a, b):
    dev = pum.device(width=width, fuse=fuse, controller=controller)
    if profiled:
        with pum.profile(dev) as tr:
            out = _program(dev, a, b)
        assert tr.events or not fuse  # fused runs record flush spans
    else:
        out = _program(dev, a, b)
    return out, dev.stats


@pytest.mark.parametrize("width", [8, 32, 64])
@pytest.mark.parametrize("fuse", [False, True])
def test_profile_does_not_perturb_results_or_stats(width, fuse):
    rng = np.random.default_rng(width)
    a = rng.integers(0, 1 << min(width, 63), 300, dtype=np.uint64)
    b = rng.integers(1, 1 << min(width, 63), 300, dtype=np.uint64)
    base, stats_base = _run(width, fuse, None, False, a, b)
    prof, stats_prof = _run(width, fuse, None, True, a, b)
    np.testing.assert_array_equal(base, prof)
    assert stats_base == stats_prof


def test_profile_invariance_with_controller_auto():
    rng = np.random.default_rng(5)
    a = rng.integers(0, 1 << 16, 200, dtype=np.uint64)
    b = rng.integers(1, 1 << 16, 200, dtype=np.uint64)
    base, stats_base = _run(16, True, "auto", False, a, b)
    prof, stats_prof = _run(16, True, "auto", True, a, b)
    np.testing.assert_array_equal(base, prof)
    assert stats_base == stats_prof


@pytest.mark.parametrize("width", [16, 64])
def test_null_tracer_creates_no_annotation_and_reads_no_clock(width,
                                                              monkeypatch):
    """With no tracer attached the flush path makes no profiler
    annotation and reads no tracer clock, and its results are
    bit-identical to a profiled run's."""
    rng = np.random.default_rng(width + 1)
    a = rng.integers(0, 1 << min(width, 63), 300, dtype=np.uint64)
    b = rng.integers(1, 1 << min(width, 63), 300, dtype=np.uint64)
    prof, stats_prof = _run(width, True, None, True, a, b)

    def forbidden(*args, **kwargs):
        raise AssertionError("the untraced flush path reached the tracer")

    _forbid_tracing(monkeypatch, forbidden)
    base, stats_base = _run(width, True, None, False, a, b)
    np.testing.assert_array_equal(base, prof)
    assert stats_base == stats_prof


def _forbid_tracing(monkeypatch, forbidden):
    """Make the tracer's annotations and clock, and the unpack's ``bytes``
    reading, raise when reached."""
    from repro.core import engine as engine_mod
    from repro.telemetry import tracer as tracer_mod
    monkeypatch.setattr(tracer_mod, "TraceAnnotation", forbidden)
    monkeypatch.setattr(tracer_mod, "time",
                        types.SimpleNamespace(perf_counter_ns=forbidden))
    monkeypatch.setattr(engine_mod, "_buffer_nbytes", forbidden)


def _raw_popcount_flush(dev, a, b):
    """A bitmap-index flush: a raw AND and its popcount, both live."""
    acc = dev.asarray(a) & b
    pc = acc.popcount(width=64)
    return acc.to_numpy(), pc.to_numpy()


@pytest.mark.parametrize("layout", [32, 64])
def test_unpack_bytes_are_the_outputs_own(layout, monkeypatch):
    """A traced raw popcount flush's ``flush.unpack`` names the host bytes
    its values own: one buffer of each output's size. Untraced, the same
    flush reads no clock, computes no ``bytes`` and gives the same
    values."""
    rng = np.random.default_rng(layout)
    a = rng.integers(0, 2**64, 1000, dtype=np.uint64)
    b = rng.integers(0, 2**64, 1000, dtype=np.uint64)
    dev = pum.device(width=16, fuse=True, layout=layout)
    with pum.profile(dev) as tr:
        outs = _raw_popcount_flush(dev, a, b)
    unpack = [args for name, *_, args in tr.events
              if name == "flush.unpack"]
    assert len(unpack) == 1
    assert unpack[0]["bytes"] == sum(o.nbytes for o in outs) == 2 * a.nbytes

    def forbidden(*args, **kwargs):
        raise AssertionError("the untraced flush path reached the tracer")

    _forbid_tracing(monkeypatch, forbidden)
    dev = pum.device(width=16, fuse=True, layout=layout)
    for got, want in zip(_raw_popcount_flush(dev, a, b), outs):
        np.testing.assert_array_equal(got, want)


def test_untraced_device_sum_reads_no_clock(monkeypatch):
    """A bitmap-index query summed on the device gives the profiled
    run's count with no tracer: no clock read, no annotation, and no
    counter."""
    from repro.core import realworld
    days = np.random.default_rng(12).integers(0, 2**64, (4, 900),
                                              dtype=np.uint64)
    dev = pum.device(width=32, fuse=True)
    with pum.profile(dev):
        want, _, _ = realworld.bmi_active_users(dev, days, verify=False)
    assert dev.counters["engine.sums.device"] == 1

    def forbidden(*args, **kwargs):
        raise AssertionError("the untraced flush path reached the tracer")

    _forbid_tracing(monkeypatch, forbidden)
    dev = pum.device(width=32, fuse=True)
    got, _, _ = realworld.bmi_active_users(dev, days)  # NumPy-checked
    assert got == want
    assert len(dev.counters) == 0


def test_counters_not_populated_without_tracer():
    """Zero-overhead contract: with no tracer attached the engine's
    CounterBank stays empty (no per-op work on the disabled path)."""
    dev = pum.device(width=16, fuse=True)
    _program(dev, np.arange(64, dtype=np.uint64),
             np.arange(64, dtype=np.uint64) + 1)
    assert len(dev.counters) == 0
    assert dev.engine.tracer is None


def test_schedule_identical_with_and_without_derivation():
    """Deriving counters replays the audit trail; the schedule itself is
    byte-identical whether or not anyone derives (and across repeats)."""
    unit = CostModel(row_bits=65536).maj_unit_programs(3, 8)
    progs = [retarget_program(p, i % 4) for i in range(8) for p in unit]
    tr1 = MemoryController(n_banks=4).schedule(progs)
    tr1.counters()
    tr2 = MemoryController(n_banks=4).schedule(progs)
    assert tr1.cmds == tr2.cmds
    assert tr1.issue_times == tr2.issue_times
    assert tr1.total_ns == tr2.total_ns
    assert tr1.energy_j == tr2.energy_j


def test_profile_restores_prior_tracer_and_flushes():
    dev = pum.device(width=16, fuse=True)
    a = np.arange(32, dtype=np.uint64)
    with pum.profile(dev) as tr:
        pending = dev.asarray(a) + 1
    # exit flushed the pending graph and detached the tracer
    assert dev.engine.tracer is None
    np.testing.assert_array_equal(pending.to_numpy(), a + 1)
    assert any(n == "flush.dispatch" for n in tr.span_names())
