"""Fused dataplane (fuse=True) vs eager: bit-exactness and cost-plane
invariance, across widths and random op sequences. Ops go through
``PumArray`` operators; the tests read the engine's recorded graph and
``LazyArray`` handles (``x._data``) where the contract is about them."""

import operator

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:  # optional dep: fixed-seed fallback
    from repro.testing import given, settings, st

import repro.pum as pum
from repro.core.engine import (LazyArray, _buffer_nbytes, _unpack_output,
                               _vec_popcount)
from repro.kernels import fused_program
from repro.kernels.plane_layout import LAYOUT32, LAYOUT64

pytestmark = pytest.mark.fused

# Chain ops, applied as t = op(t, pool[i]).
_CHAIN_OPS = ["and", "or", "xor", "add", "sub", "mul", "div", "mod"]
_TAIL_OPS = ["less", "popcount", "reduce_and", "reduce_or", "reduce_xor"]


def _rand_inputs(width, n, seed):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 1 << width, n, dtype=np.uint64)
            for _ in range(3)]


_BINARY = {"and": operator.and_, "or": operator.or_, "xor": operator.xor,
           "add": operator.add, "sub": operator.sub, "mul": operator.mul,
           "div": operator.floordiv, "mod": operator.mod,
           "less": operator.lt}


def _dev(**kw):
    """A device; eager unless ``fuse=True`` is asked for."""
    kw.setdefault("fuse", False)
    return pum.device(**kw)


def _apply(dev, name, t, other):
    x = dev.asarray(t)
    if name in _BINARY:
        return _BINARY[name](x, other)
    if name == "popcount":
        return x.popcount()
    if name.startswith("reduce_"):
        return x.reduce_bits(name.removeprefix("reduce_"))
    raise KeyError(name)


def _run_sequence(dev, inputs, op_seq):
    """Random chain over the input pool; returns every intermediate (so
    flush must materialize intermediates whose handles stay alive)."""
    outs = []
    t = inputs[0]
    for i, name in enumerate(op_seq):
        t = _apply(dev, name, t, inputs[(i + 1) % len(inputs)])
        outs.append(t)
    return [np.asarray(o, np.uint64) for o in outs]


@given(width=st.sampled_from([8, 16, 32]), seed=st.integers(0, 10_000))
@settings(max_examples=6, deadline=None)
def test_fused_matches_eager_random_sequence(width, seed):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(33, 400))  # deliberately not a multiple of 32
    inputs = _rand_inputs(width, n, seed)
    n_ops = int(rng.integers(2, 7))
    op_seq = [str(rng.choice(_CHAIN_OPS)) for _ in range(n_ops - 1)]
    op_seq.append(str(rng.choice(_CHAIN_OPS + _TAIL_OPS)))

    eager = _dev(width=width)
    fused = _dev(width=width, fuse=True)
    want = _run_sequence(eager, inputs, op_seq)
    got = _run_sequence(fused, inputs, op_seq)
    for w, g in zip(want, got):
        np.testing.assert_array_equal(w, g)
    assert eager.stats == fused.stats


@pytest.mark.parametrize("width", [8, 16, 32])
def test_fused_all_opcodes_bit_exact(width):
    inputs = _rand_inputs(width, 256, seed=width)
    seq = ["and", "xor", "or", "add", "sub", "mul", "div", "mod", "less"]
    tails = ["popcount", "reduce_and", "reduce_or", "reduce_xor"]
    eager = _dev(width=width)
    fused = _dev(width=width, fuse=True)

    def run(d):
        outs = _run_sequence(d, inputs, seq)
        base = d.asarray(inputs[0]) + inputs[1]
        outs += [np.asarray(_apply(d, t, base, None), np.uint64)
                 for t in tails]
        return outs

    for w, g in zip(run(eager), run(fused)):
        np.testing.assert_array_equal(w, g)
    assert eager.stats == fused.stats


def test_cost_plane_invariance_with_controller():
    """EngineStats must match eager exactly under controller pricing too
    (latency, energy, sequences, refresh stalls)."""
    inputs = _rand_inputs(32, 128, seed=3)
    seq = ["add", "xor", "sub", "and", "popcount"]
    eager = _dev(width=32, controller="auto")
    fused = _dev(width=32, controller="auto", fuse=True)
    for w, g in zip(_run_sequence(eager, inputs, seq),
                    _run_sequence(fused, inputs, seq)):
        np.testing.assert_array_equal(w, g)
    assert eager.stats == fused.stats
    assert fused.stats.refresh_stall_ns > 0


def test_charges_accrue_at_record_time():
    """The cost plane must not wait for flush(): recording IS charging."""
    import dataclasses
    e = _dev(fuse=True)
    a = _rand_inputs(32, 64, seed=5)[0]
    t = e.asarray(a) + a
    assert e.stats.latency_ns > 0 and e.stats.n_sequences > 0
    before = dataclasses.replace(e.stats)
    _ = np.asarray(t)  # flush: dataplane only
    assert e.stats == before


def test_lazy_array_api_and_flush():
    e = _dev(fuse=True)
    a = _rand_inputs(32, 64, seed=7)[0]
    t = (e.asarray(a) ^ a)._data
    assert isinstance(t, LazyArray)
    assert t.shape == (64,) and t.size == 64 and t.ndim == 1
    assert t.dtype == np.uint64
    assert "pending" in repr(t)
    e.flush()
    assert "materialized" in repr(t)
    np.testing.assert_array_equal(t.materialize(), np.zeros(64, np.uint64))
    e.flush()  # idempotent no-op


def test_lazy_array_eq_and_bool_follow_ndarray_semantics():
    """`==` must compare values (not identity) and truth-testing must
    behave like ndarray — no silent scalars from ported eager code."""
    e = _dev(fuse=True)
    z = e.asarray(np.arange(4, dtype=np.uint64))
    t1 = (z + z)._data
    t2 = (z + z)._data
    np.testing.assert_array_equal(t1 == t2, np.full(4, True))
    np.testing.assert_array_equal(t1 != t2, np.full(4, False))
    with pytest.raises(ValueError):  # ambiguous, exactly like ndarray
        bool((z + z)._data)
    one = (e.asarray(np.ones(1, np.uint64)) + np.zeros(1, np.uint64))._data
    assert bool(one)


def test_mul_div_stay_inside_the_fused_flush():
    """mul/div/mod are in the fused ISA since PR 3: a mixed arithmetic
    chain records as ONE graph (no eager island, no intermediate
    materialization) and still matches eager bit-exactly with identical
    stats."""
    inputs = _rand_inputs(16, 96, seed=11)
    eager = _dev(width=16)
    fused = _dev(width=16, fuse=True)

    def run(dev):
        t = dev.asarray(inputs[0]) + inputs[2]
        m = t * inputs[1]
        d = m // inputs[1]
        r = m % inputs[1]
        s = d - t
        return (t, m, d, r, s)

    want = [np.asarray(x, np.uint64) for x in run(eager)]
    got = run(fused)
    # No eager fallback: every handle is still pending before the flush.
    assert all(isinstance(x._data, LazyArray) and x._data._value is None
               for x in got)
    # add + mul + sub = 3 ops; div and mod each lower to the shared
    # divmod tuple op plus a selector (2 ops each) — flush-time CSE
    # unifies the two divmods into ONE restoring-division pass.
    g = fused.engine._graph
    assert g is not None and len(g.ops) == 7
    opcodes = [op for op, _, _ in g.ops]
    assert opcodes.count("divmod") == 2  # unified to 1 by optimize_program
    for w, g in zip(want, got):
        np.testing.assert_array_equal(w, np.asarray(g, np.uint64))
    assert eager.stats == fused.stats


@given(width=st.sampled_from([8, 16, 32]), seed=st.integers(0, 10_000))
@settings(max_examples=6, deadline=None)
def test_fused_mul_div_property(width, seed):
    """Fused mul/div/mod match eager bit-exactly across widths, including
    div-by-zero lanes and the signed-boundary values (0, 1, 2**(w-1),
    2**w - 1)."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(40, 300))
    a = rng.integers(0, 1 << width, n, dtype=np.uint64)
    b = rng.integers(0, 1 << width, n, dtype=np.uint64)
    edges = np.array([0, 1, 1 << (width - 1), (1 << width) - 1], np.uint64)
    a[:4], b[:4] = edges, edges[::-1]
    b[::5] = 0  # div/mod by zero -> 0, the unsigned NumPy semantics
    eager = _dev(width=width)
    fused = _dev(width=width, fuse=True)
    for op in ("mul", "div", "mod"):
        w = np.asarray(_apply(eager, op, a, b), np.uint64)
        g = _apply(fused, op, a, b)
        assert isinstance(g._data, LazyArray)
        np.testing.assert_array_equal(w, np.asarray(g, np.uint64))
    assert eager.stats == fused.stats


def test_graph_splits_on_element_count_change():
    e = _dev(fuse=True)
    a = _rand_inputs(32, 64, seed=13)[0]
    b = _rand_inputs(32, 128, seed=14)[0]
    x = e.asarray(a) + a
    y = e.asarray(b) + b  # different n: previous graph flushes
    np.testing.assert_array_equal(np.asarray(x),
                                  (a + a) & np.uint64(0xFFFFFFFF))
    np.testing.assert_array_equal(np.asarray(y),
                                  (b + b) & np.uint64(0xFFFFFFFF))


def test_dead_handles_are_dead_code():
    e = _dev(fuse=True)
    a = _rand_inputs(32, 64, seed=17)[0]
    tmp = e.asarray(a) & a
    tmp = tmp ^ a  # first AND's handle dies here
    keep = tmp + a
    del tmp
    lat = e.stats.latency_ns  # dead ops were still charged
    e.flush()
    assert e.stats.latency_ns == lat
    np.testing.assert_array_equal(
        np.asarray(keep), (a + (a ^ (a & a))) & np.uint64(0xFFFFFFFF))


def test_pipeline_cache_reuses_compiled_programs():
    """Same graph structure across batches -> one compiled pipeline."""
    e = _dev(fuse=True)

    def batch(seed):
        a, b, c = _rand_inputs(32, 256, seed)
        t = e.asarray(a) & b
        t = t + c
        return np.asarray(t)

    batch(0)
    info = fused_program._cached_pipeline.cache_info()
    for s in range(1, 4):
        batch(s)
    after = fused_program._cached_pipeline.cache_info()
    assert after.currsize == info.currsize
    assert after.hits == info.hits + 3


def test_fuse_requires_fast_backend():
    """The sim chip model has no word dataplane to fuse over: asking for
    both runs per-op, in the config the engine is built from."""
    dev = _dev(backend="sim", fuse=True)
    assert not dev.config.fuse and not dev.engine.config.fuse
    assert pum.EngineConfig(backend="sim").fuse is False


def test_fused_arithmetic_rejects_out_of_width_operands():
    """Eager arithmetic computes on raw uint64 values; fused computes
    modulo 2**width. Out-of-range operands to arithmetic ops must fail
    loudly, not silently truncate into different answers."""
    e = _dev(width=8, fuse=True)
    big = np.array([256, 1], np.uint64)
    one = np.array([1, 1], np.uint64)
    for op in ("add", "sub", "mul", "div", "mod", "less"):
        with pytest.raises(ValueError, match="modulo"):
            _apply(e, op, big, one)
    # popcount is the exception: out-of-width operands route through the
    # raw planewise graph (like and/or/xor) and the materialize fold sums
    # the per-lane counts — bit-exact with eager's raw-word popcount.
    np.testing.assert_array_equal(np.asarray(e.asarray(big).popcount()),
                                  np.array([1, 1], np.uint64))


def test_fused_raw_popcount_folds_lane_counts():
    """popcount on the raw packed-bitmap path: the evaluators emit
    per-lane partial counts and the materialize fold sums them into the
    caller-visible per-word count; a pending raw popcount consumed by a
    further op materializes (folds) first. Both bit-exact with eager."""
    rng = np.random.default_rng(5)
    a = rng.integers(0, 2**64, 257, dtype=np.uint64)
    b = rng.integers(0, 2**64, 257, dtype=np.uint64)
    want = _vec_popcount(a & b)
    for fuse in (False, True):
        e = _dev(width=32, fuse=fuse)
        pc = (e.asarray(a) & b).popcount(width=64)
        composed = np.asarray(pc * np.full_like(a, 2), np.uint64)
        np.testing.assert_array_equal(np.asarray(pc, np.uint64), want)
        np.testing.assert_array_equal(composed, want * 2)


# Edge words of the unpack: zero, all ones (count 64), the high bit,
# each 32-bit half full, and random words.
_EDGE_WORDS = np.concatenate([
    np.array([0, 2**64 - 1, 1 << 63, 0xFFFFFFFF, 0xFFFFFFFF << 32, 1],
             np.uint64),
    np.random.default_rng(41).integers(0, 2**64, 58, dtype=np.uint64)])


def _lane_counts(lanes):
    return np.array([bin(int(v)).count("1") for v in lanes],
                    lanes.dtype)


@pytest.mark.parametrize("layout", [LAYOUT32, LAYOUT64],
                         ids=lambda l: l.name)
@pytest.mark.parametrize("raw", [False, True], ids=["value", "raw"])
@pytest.mark.parametrize("popcount", [False, True],
                         ids=["bitjoin", "popcount"])
def test_unpack_output_is_one_fresh_writable_buffer(layout, raw, popcount):
    """The unpack of one fetched output: exact against NumPy on edge
    words, and one fresh writable buffer of the value's own size that
    shares no memory with the fetched wire (read-only, like a fetched
    device array's)."""
    words = _EDGE_WORDS if raw else _EDGE_WORDS & np.uint64(0xFFFF)
    if raw:
        lanes = layout.raw_lanes(words)
        if popcount:  # the evaluators' per-lane partial counts
            lanes = _lane_counts(lanes)
        want = _vec_popcount(words) if popcount else words
    else:  # a value lane holds the value (or its count) itself
        lanes = (_vec_popcount(words) if popcount else words
                 ).astype(layout.np_dtype)
        want = lanes.astype(np.uint64)
    n = lanes.size
    padded = np.concatenate([lanes, np.full(32, 7, layout.np_dtype)])
    wire = layout.to_wire(padded)
    wire.setflags(write=False)
    got = _unpack_output(layout, wire, n, raw, popcount)
    assert got.dtype == np.uint64
    np.testing.assert_array_equal(got, want)
    assert got.flags.writeable and not np.shares_memory(got, wire)
    assert _buffer_nbytes(got) == got.nbytes == words.nbytes


@pytest.mark.parametrize("layout", [32, 64])
@pytest.mark.parametrize("raw", [False, True], ids=["value", "raw"])
@pytest.mark.parametrize("popcount", [False, True],
                         ids=["bitjoin", "popcount"])
def test_flushed_values_are_exact_writable_and_handed_off_uncopied(
        layout, raw, popcount):
    """Through a device: each flushed value equals NumPy's on edge words
    and is writable; ``to_numpy()`` hands off the materialized value
    itself and ``np.array(x, copy=True)`` a private copy."""
    a = _EDGE_WORDS if raw else _EDGE_WORDS & np.uint64(0xFFFF)
    b = np.roll(a, 1) | a  # keeps all ones, the high bit and zero
    dev = pum.device(width=16, fuse=True, layout=layout)
    y = dev.asarray(a) & b
    if popcount:
        y = y.popcount(width=64 if raw else None)
    assert isinstance(y._data, LazyArray)
    assert y._data._graph.raw == raw
    got = y.to_numpy()
    want = _vec_popcount(a & b) if popcount else a & b
    np.testing.assert_array_equal(got, want)
    assert got.dtype == np.uint64 and got.flags.writeable
    assert np.shares_memory(got, y._data._value)
    assert np.shares_memory(np.asarray(y._data, np.uint64), got)
    private = np.array(y, copy=True)
    assert not np.shares_memory(private, got)
    np.testing.assert_array_equal(private, want)


def test_fused_planewise_raw_bitmap_path():
    """and_/or_/xor on out-of-width operands route through the raw
    packed-bitmap graph (two 32-bit lanes per 64-bit word) instead of
    rejecting: bit-exact with eager's raw-uint64 semantics — the contract
    realworld's packed-bitmap kernels (set intersection) rely on."""
    rng = np.random.default_rng(31)
    a = rng.integers(0, 2**64, 65, dtype=np.uint64)  # full 64-bit range
    b = rng.integers(0, 2**64, 65, dtype=np.uint64)
    c = rng.integers(0, 2**64, 65, dtype=np.uint64)
    for width in (8, 32):
        eager = _dev(width=width)
        fused = _dev(width=width, fuse=True)

        def chain(dev):
            t = dev.asarray(a) & b
            t = t ^ c
            return t | b

        want = np.asarray(chain(eager), np.uint64)
        got = chain(fused)
        assert isinstance(got._data, LazyArray)
        # one raw graph, no flush between the three plane-wise ops
        g = fused.engine._graph
        assert g is not None and g.raw
        assert len(g.ops) == 3
        np.testing.assert_array_equal(want, np.asarray(got, np.uint64))
        assert eager.stats == fused.stats  # charged on words, not lanes


def test_raw_and_value_graphs_do_not_mix():
    """A raw packed-bitmap graph flushes before a value-mode op records
    (and vice versa); arithmetic on a raw out-of-width result still fails
    loudly at leaf registration."""
    rng = np.random.default_rng(33)
    bm = rng.integers(1 << 40, 2**64, 64, dtype=np.uint64)
    small = rng.integers(0, 256, 64, dtype=np.uint64)
    e = _dev(width=32, fuse=True)
    raw = e.asarray(bm) & bm      # raw graph opens
    assert e.engine._graph.raw
    t = e.asarray(small) + small  # value-mode: raw graph flushed first
    assert raw._data._value is not None and not e.engine._graph.raw
    np.testing.assert_array_equal(np.asarray(raw), bm)
    with pytest.raises(ValueError, match="modulo"):
        (e.asarray(bm) & bm) + small  # arithmetic on raw values: loud
    np.testing.assert_array_equal(np.asarray(t), 2 * small)


def test_temporary_operands_do_not_collide():
    """id()-keyed leaf dedup must pin operands: freed temporaries whose
    addresses get reused by later operands must not resolve to a stale
    leaf snapshot."""
    e = _dev(fuse=True)
    outs = []
    for k in range(8):
        tmp = np.full(64, k, np.uint64)  # dies each iteration
        outs.append(e.asarray(tmp) + tmp)
        del tmp
    for k, o in enumerate(outs):
        np.testing.assert_array_equal(np.asarray(o),
                                      np.full(64, 2 * k, np.uint64))


def test_materialized_handles_release_the_graph():
    e = _dev(fuse=True)
    a = np.arange(64, dtype=np.uint64)
    t = e.asarray(a) + a
    assert any(p is a for p in e.engine._graph._pins)  # id() key held
    np.testing.assert_array_equal(np.asarray(t), 2 * a)
    # snapshots reclaimable
    assert t._data._graph is None and t._data._engine is None


def test_operand_mutation_after_record_does_not_alias():
    """The graph snapshots operands at record time: mutating the caller's
    buffer before flush must not change the result (eager parity)."""
    e = _dev(fuse=True)
    b = np.arange(64, dtype=np.uint64)
    t = e.asarray(b) + b
    b[:] = 0
    np.testing.assert_array_equal(np.asarray(t),
                                  2 * np.arange(64, dtype=np.uint64))


def test_operand_mutation_between_uses_registers_fresh_leaf():
    """Re-feeding the same buffer after an in-place mutation must see the
    new content (eager parity), not dedup to the stale snapshot."""
    e = _dev(fuse=True)
    a = np.zeros(64, dtype=np.uint64)
    t1 = e.asarray(a) + a
    a[:] = 5
    t2 = e.asarray(a) + a
    np.testing.assert_array_equal(np.asarray(t1), np.zeros(64, np.uint64))
    np.testing.assert_array_equal(np.asarray(t2),
                                  np.full(64, 10, np.uint64))


def test_flush_failure_keeps_handles_recoverable(monkeypatch):
    """A transient pipeline failure must not orphan pending handles: the
    graph is restored and a later materialize retries."""
    from repro.core import engine as engine_mod
    e = _dev(fuse=True)
    a = np.arange(64, dtype=np.uint64)
    t = (e.asarray(a) + a)._data

    def boom(*args, **kw):
        raise RuntimeError("transient backend failure")

    real = engine_mod.get_pipeline
    monkeypatch.setattr(engine_mod, "get_pipeline", boom)
    with pytest.raises(RuntimeError, match="transient"):
        t.materialize()
    monkeypatch.setattr(engine_mod, "get_pipeline", real)
    np.testing.assert_array_equal(t.materialize(), 2 * a)


def test_pending_lazy_crosses_engines_via_materialization():
    """A pending handle from one device fed into another fused device must
    materialize through its own engine, not alias the foreign graph."""
    a = _rand_inputs(32, 64, seed=29)[0]
    e1 = _dev(fuse=True)
    e2 = _dev(fuse=True)
    t = e1.asarray(a) + a
    r = e2.asarray(a) ^ t
    np.testing.assert_array_equal(
        np.asarray(r), (((a + a) & np.uint64(0xFFFFFFFF)) ^ a))


# --------------------------------------------------------------------- #
# CSE / dead-node pruning (flush-time graph normalization)
# --------------------------------------------------------------------- #


def test_cse_does_not_change_results_or_stats():
    """Recording duplicate subexpressions (including commutative twins)
    must flush to eager-identical values and leave EngineStats exactly as
    eager charges them — CSE only drops redundant dataplane work."""
    rng = np.random.default_rng(41)
    a = rng.integers(0, 1 << 16, 128, dtype=np.uint64)
    b = rng.integers(0, 1 << 16, 128, dtype=np.uint64)
    eager = _dev(width=16)
    fused = _dev(width=16, fuse=True)

    def run(dev):
        x, y = dev.asarray(a), dev.asarray(b)
        t1 = x + y
        t2 = y + x             # commutative duplicate of t1
        t3 = t1 ^ t2           # == 0
        t4 = t1 * t1
        t5 = t2 * t2           # duplicate of t4 after t1/t2 unify
        return [np.asarray(x, np.uint64) for x in (t1, t2, t3, t4, t5)]

    for w, g in zip(run(eager), run(fused)):
        np.testing.assert_array_equal(w, g)
    assert eager.stats == fused.stats


def test_cse_normalized_programs_share_the_pipeline_cache():
    """Two recordings that differ only in redundant ops must normalize to
    the same program and hit the same compiled pipeline."""
    from repro.kernels import fused_program
    e = _dev(width=32, fuse=True)
    a, b, _ = _rand_inputs(32, 256, seed=43)
    x = e.asarray(a)

    t = x & b
    keep = t + a
    np.asarray(keep)
    info = fused_program._cached_pipeline.cache_info()

    t = x & b
    dup = x & b            # live redundant twin: unified by CSE at flush
    keep = t + a
    np.asarray(keep)
    after = fused_program._cached_pipeline.cache_info()
    assert after.currsize == info.currsize  # no new compiled pipeline
    assert after.hits == info.hits + 1
    # both handles materialized from the one computed value
    np.testing.assert_array_equal(np.asarray(dup), np.asarray(t))


def test_optimizer_prunes_dead_leaves_from_the_pipeline():
    """An op whose handle dies pulls its exclusive leaves out of the
    compiled program too (fewer pipeline inputs, same results)."""
    e = _dev(width=32, fuse=True)
    a, b, c = _rand_inputs(32, 64, seed=47)
    keep = e.asarray(a) + b
    dead = e.asarray(c) ^ c  # only consumer of leaf c
    del dead
    np.testing.assert_array_equal(
        np.asarray(keep), (a + b) & np.uint64(0xFFFFFFFF))


# --------------------------------------------------------------------- #
# Auto-flush thresholds
# --------------------------------------------------------------------- #


def test_autoflush_graph_size_threshold():
    """flush_threshold bounds the recorded graph: the op that reaches the
    bound flushes (its handle materializes eagerly), and recording then
    continues into a fresh graph — results and stats unchanged."""
    a, b, c = _rand_inputs(16, 64, seed=51)
    eager = _dev(width=16)
    fused = _dev(width=16, fuse=True, flush_threshold=3)

    def run(dev):
        t = dev.asarray(a) + b
        t = t ^ c
        t = t * b          # fused: auto-flush fires here
        t = t - a
        t = t | c
        return t

    got = run(fused)
    g = fused.engine._graph
    assert g is not None and len(g.ops) == 2
    want = run(eager)
    np.testing.assert_array_equal(np.asarray(want, np.uint64),
                                  np.asarray(got, np.uint64))
    assert eager.stats == fused.stats


def test_autoflush_memory_threshold():
    e = _dev(width=32, fuse=True, flush_memory_bytes=4 * 64 * 4)
    a, b, _ = _rand_inputs(32, 64, seed=53)
    t = e.asarray(a) + b   # 2 leaves + 1 op = 3 held values: under bound
    assert e.engine._graph is not None
    t2 = t + t             # 4 held values * 4B * 64 lanes: bound reached
    assert e.engine._graph is None and t2._data._value is not None
    np.testing.assert_array_equal(
        np.asarray(t2), (2 * ((a + b) & np.uint64(0xFFFFFFFF)))
        & np.uint64(0xFFFFFFFF))


@pytest.mark.parametrize("width", [8, 32, 64])
def test_autoflush_vmem_block_bound(width):
    """With no op or memory bound, the Pallas kernel's VMEM block still
    bounds every flush: a long chain splits into programs whose leaves and
    ops fit the block budget, results and stats unchanged."""
    from repro.telemetry import Tracer
    rng = np.random.default_rng(57)
    pool = rng.integers(0, 1 << min(width, 63), (1200, 32), dtype=np.uint64)
    eager = _dev(width=width)
    fused = _dev(width=width, fuse=True, flush_threshold=None,
                 flush_memory_bytes=None)
    fused.engine.tracer = Tracer()

    def run(dev):
        t = dev.asarray(pool[0])
        for x in pool[1:]:
            t = t ^ x
        return np.asarray(t, np.uint64)

    np.testing.assert_array_equal(run(eager), run(fused))
    assert eager.stats == fused.stats
    c = fused.counters
    assert c["engine.autoflush.vmem"] >= 1
    biggest = c.histogram("engine.flush_ops")["max"]
    # one leaf per op, plus the chain's first operand
    assert fused_program.block_bytes(2 * biggest + 1, width) \
        < fused_program.VMEM_BLOCK_BUDGET + fused_program.block_bytes(
            3, width)


@pytest.mark.parametrize("div", [False, True], ids=["xor32", "div64"])
def test_word_pipeline_numpy_short_circuits_only_on_cpu(monkeypatch, div):
    """The small-program and 64-bit-divide NumPy paths exist for the host
    CPU: where JAX runs on an accelerator, every call runs jitted."""
    import jax
    from repro.kernels.plane_layout import LAYOUT32, LAYOUT64
    layout = LAYOUT64 if div else LAYOUT32
    prog = fused_program.FusedProgram(
        width=layout.word_bits, n_inputs=2,
        ops=(fused_program.FusedOp("div" if div else "xor", (0, 1)),),
        outputs=(2,), layout=layout)
    rng = np.random.default_rng(59)
    a, b = (rng.integers(1, 1 << 32, 64, dtype=np.uint64) for _ in range(2))
    leaves = [layout.to_wire(x.astype(layout.np_dtype)) for x in (a, b)]
    want = a // b if div else a ^ b

    def lanes(out):
        return layout.from_wire(out)[:64].astype(np.uint64)

    cpu = fused_program.build_words_pipeline(prog)
    (out,) = cpu(*leaves)
    assert isinstance(out, np.ndarray) and not cpu.wants_device(64)
    np.testing.assert_array_equal(lanes(out), want)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    chip = fused_program.build_words_pipeline(prog)
    (out,) = chip(*leaves)
    assert isinstance(out, jax.Array) and chip.wants_device(64)
    np.testing.assert_array_equal(lanes(out), want)


def test_autoflush_disabled_with_none():
    e = _dev(width=16, fuse=True, flush_threshold=None,
             flush_memory_bytes=None)
    a, b, _ = _rand_inputs(16, 64, seed=55)
    t = e.asarray(a)
    for _ in range(64):
        t = t + b
    assert e.engine._graph is not None and len(e.engine._graph.ops) == 64


# --------------------------------------------------------------------- #
# SWAR popcount regression (fixed-iteration replacement for the old
# data-dependent shift loop and the per-element Python path)
# --------------------------------------------------------------------- #


@given(seed=st.integers(0, 1000))
@settings(max_examples=20, deadline=None)
def test_swar_popcount_matches_scalar_oracle(seed):
    rng = np.random.default_rng(seed)
    a = rng.integers(0, 2**64, 257, dtype=np.uint64)  # full 64-bit range
    want = np.array([bin(int(x)).count("1") for x in a], np.uint64)
    np.testing.assert_array_equal(_vec_popcount(a), want)


def test_swar_popcount_edge_values():
    a = np.array([0, 1, 2**63, 2**64 - 1, 0x5555555555555555], np.uint64)
    np.testing.assert_array_equal(_vec_popcount(a),
                                  np.array([0, 1, 1, 64, 32], np.uint64))
    # 2-D shape preserved; input not mutated
    m = np.array([[3, 7], [15, 255]], np.uint64)
    m0 = m.copy()
    np.testing.assert_array_equal(_vec_popcount(m),
                                  np.array([[2, 3], [4, 8]], np.uint64))
    np.testing.assert_array_equal(m, m0)


def test_engine_popcount_small_arrays_use_swar():
    """The old per-element ``bin(int(x))`` path for size<4096 is gone; the
    vector path must be exact at every size."""
    e = _dev(width=32)
    rng = np.random.default_rng(23)
    for n in (1, 31, 33, 4095, 5000):
        a = rng.integers(0, 2**32, n, dtype=np.uint64)
        want = np.array([bin(int(x)).count("1") for x in a], np.uint64)
        np.testing.assert_array_equal(np.asarray(e.asarray(a).popcount()),
                                      want)
