"""Width-64 plane layout: operator parity across the eager, fused and
raw-lane paths at widths 33/48/64 (div-by-zero and boundary values
included), every registered 64-bit evaluator bit-exact against eager
NumPy, the layout-keyed pipeline cache, PumArray slicing, and the
``shard-words`` multi-device fused backend."""

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:  # optional dep: fixed-seed fallback
    from repro.testing import given, settings, st

import repro.pum as pum
from repro.core.engine import LazyArray, PulsarEngine
from repro.kernels import fused_program
from repro.kernels.plane_layout import (LAYOUT32, LAYOUT64, PlaneLayout,
                                        get_layout, layout_for_width)

pytestmark = pytest.mark.fused

WIDE = [33, 48, 64]


def _operands(width, n, seed):
    rng = np.random.default_rng(seed)
    hi = (1 << width) - 1
    a = rng.integers(0, hi, n, dtype=np.uint64)
    b = rng.integers(0, hi, n, dtype=np.uint64)
    # Edge lanes: zeros, ones, the signed boundary, the max value, and
    # div-by-zero divisors.
    edges = np.array([0, 1, 1 << (width - 1), hi], np.uint64)
    a[:4], b[:4] = edges, edges[::-1]
    b[::5] = 0
    return a, b


# --------------------------------------------------------------------- #
# PlaneLayout contract
# --------------------------------------------------------------------- #


def test_layout_constants_derive_from_word_bits():
    assert LAYOUT32.swar_consts == (0x55555555, 0x33333333, 0x0F0F0F0F,
                                    0x01010101)
    assert LAYOUT64.swar_consts == (
        0x5555555555555555, 0x3333333333333333, 0x0F0F0F0F0F0F0F0F,
        0x0101010101010101)
    assert (LAYOUT32.popcount_shift, LAYOUT64.popcount_shift) == (24, 56)
    assert (LAYOUT32.raw_lanes_per_word, LAYOUT64.raw_lanes_per_word) \
        == (2, 1)
    assert (LAYOUT32.wire_words_per_lane, LAYOUT64.wire_words_per_lane) \
        == (1, 2)
    assert get_layout(64) is LAYOUT64 and get_layout(LAYOUT32) is LAYOUT32
    assert layout_for_width(32) is LAYOUT32
    assert layout_for_width(33) is LAYOUT64
    with pytest.raises(ValueError, match="no plane layout"):
        get_layout(48)
    with pytest.raises(ValueError, match="covers width"):
        layout_for_width(65)


def test_layout_wire_roundtrip():
    rng = np.random.default_rng(3)
    words = rng.integers(0, 1 << 64, 64, dtype=np.uint64)
    for layout in (LAYOUT32, LAYOUT64):
        lanes = layout.raw_lanes(words)
        assert lanes.dtype == layout.np_dtype
        np.testing.assert_array_equal(layout.join_raw(lanes), words)
        wire = layout.to_wire(lanes)
        assert wire.dtype == np.int32
        np.testing.assert_array_equal(layout.from_wire(wire), lanes)


def test_layout_is_hashable_and_part_of_program_identity():
    p32 = fused_program.FusedProgram(
        width=16, n_inputs=2, ops=(fused_program.FusedOp("add", (0, 1)),),
        outputs=(2,))
    p64 = fused_program.FusedProgram(
        width=16, n_inputs=2, ops=(fused_program.FusedOp("add", (0, 1)),),
        outputs=(2,), layout=LAYOUT64)
    assert p32.layout is LAYOUT32  # the default keeps old IR valid
    assert p32 != p64 and hash(p32) != hash(p64)
    assert PlaneLayout(name="u64", word_bits=64) == LAYOUT64


# --------------------------------------------------------------------- #
# Operator parity at widths 33/48/64 (eager vs fused)
# --------------------------------------------------------------------- #


@pytest.mark.parametrize("width", WIDE)
def test_wide_fused_all_ops_match_eager(width):
    a, b = _operands(width, 257, seed=width)
    eager = pum.device(width=width, fuse=False)
    fused = pum.device(width=width, fuse=True)
    assert fused.config.fuse and fused.layout.word_bits == 64

    def run(dev):
        x = dev.asarray(a)
        q, r = divmod(x, b)
        outs = [x & b, x | b, x ^ b, x + b, x - b, x * b, x // b, x % b,
                q, r, x < b, x.popcount(),
                x.reduce_bits("and"), x.reduce_bits("or"),
                x.reduce_bits("xor")]
        return [np.asarray(o, np.uint64) for o in outs]

    for w, g in zip(run(eager), run(fused)):
        np.testing.assert_array_equal(w, g)
    assert eager.stats == fused.stats


@given(width=st.sampled_from(WIDE), seed=st.integers(0, 10_000))
@settings(max_examples=6, deadline=None)
def test_wide_fused_random_chain_property(width, seed):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(33, 300))  # deliberately not a multiple of 32
    a, b = _operands(width, n, seed)
    ops = ["add", "sub", "mul", "div", "mod", "and", "or", "xor"]
    seq = [str(rng.choice(ops)) for _ in range(int(rng.integers(2, 7)))]

    def run(dev):
        t = dev.asarray(a)
        outs = []
        for name in seq:
            t = {"add": t + b, "sub": t - b, "mul": t * b, "div": t // b,
                 "mod": t % b, "and": t & b, "or": t | b,
                 "xor": t ^ b}[name]
            outs.append(t)
        return [np.asarray(o, np.uint64) for o in outs]

    eager = pum.device(width=width, fuse=False)
    fused = pum.device(width=width, fuse=True)
    for w, g in zip(run(eager), run(fused)):
        np.testing.assert_array_equal(w, g)
    assert eager.stats == fused.stats


def test_width64_full_range_divmod_shares_one_divider():
    a, b = _operands(64, 128, seed=9)
    fused = pum.device(width=64, fuse=True)
    x = fused.asarray(a)
    q, r = divmod(x, b)
    s = (x // b) ^ (x % b)  # CSEs onto the same divmod tuple op
    with np.errstate(divide="ignore", invalid="ignore"):
        nz = b != 0
        np.testing.assert_array_equal(
            np.asarray(q), np.where(nz, a // np.where(nz, b, 1), 0))
        np.testing.assert_array_equal(
            np.asarray(r), np.where(nz, a % np.where(nz, b, 1), 0))
        np.testing.assert_array_equal(
            np.asarray(s), np.asarray(q) ^ np.asarray(r))


# --------------------------------------------------------------------- #
# Raw-lane path on the 64-bit layout (the un-double-split bugfix)
# --------------------------------------------------------------------- #


def test_raw_planewise_on_64bit_layout_is_single_lane():
    """At a 64-bit layout an out-of-width uint64 word is ONE dataplane
    lane (the old code always split 2x32 — the hardcoded split this PR
    derives from the layout)."""
    rng = np.random.default_rng(21)
    a = rng.integers(0, 1 << 64, 65, dtype=np.uint64)
    b = rng.integers(0, 1 << 64, 65, dtype=np.uint64)
    eager = PulsarEngine(width=48)
    fused = PulsarEngine(width=48, fuse=True)

    def chain(e):
        t = e._and(a, b)
        t = e._xor(t, a)
        return e._or(t, b)

    want = np.asarray(chain(eager), np.uint64)
    got = chain(fused)
    assert isinstance(got, LazyArray)
    g = fused._graph
    assert g is not None and g.raw
    assert g.n == 65 and g.width == 64  # one 64-bit lane per word
    np.testing.assert_array_equal(want, np.asarray(got, np.uint64))
    assert eager.stats == fused.stats


def test_raw_planewise_on_32bit_layout_still_splits():
    rng = np.random.default_rng(23)
    a = rng.integers(0, 1 << 64, 33, dtype=np.uint64)
    e = PulsarEngine(width=16, fuse=True)
    t = e._and(a, a)
    g = e._graph
    assert g.raw and g.n == 66 and g.width == 32
    np.testing.assert_array_equal(np.asarray(t), a)


def test_explicit_64bit_layout_on_narrow_width():
    """layout=64 with width<=32 is legal: narrow values compute on wide
    lanes, and the raw path keeps full words unsplit."""
    rng = np.random.default_rng(25)
    a = rng.integers(0, 1 << 16, 64, dtype=np.uint64)
    bm = rng.integers(0, 1 << 64, 64, dtype=np.uint64)
    eager = pum.device(width=16, fuse=False)
    fused = pum.device(width=16, layout=64, fuse=True)
    assert fused.layout is LAYOUT64 and fused.config.fuse
    np.testing.assert_array_equal(
        np.asarray(eager.asarray(a) * a), np.asarray(fused.asarray(a) * a))
    np.testing.assert_array_equal(
        np.asarray(fused.asarray(bm) ^ bm), np.zeros(64, np.uint64))
    assert fused.engine._graph is None or not fused.engine._graph.ops


# --------------------------------------------------------------------- #
# Every registered 64-bit evaluator is bit-exact
# --------------------------------------------------------------------- #


def test_all_wide_evaluators_bit_exact():
    """words-cpu-64 (NumPy word domain), ref-vertical-64 (jnp planes) and
    pallas-tpu-64 (interpret mode off-TPU) agree with eager NumPy on the
    same wire leaves."""
    rng = np.random.default_rng(27)
    n = 96
    a = rng.integers(0, 1 << 64, n, dtype=np.uint64)
    b = rng.integers(0, 1 << 64, n, dtype=np.uint64)
    prog = fused_program.FusedProgram(
        width=64, n_inputs=2,
        ops=(fused_program.FusedOp("add", (0, 1)),
             fused_program.FusedOp("xor", (2, 0)),
             fused_program.FusedOp("less", (1, 3)),
             fused_program.FusedOp("popcount", (3,))),
        outputs=(3, 4, 5), layout=LAYOUT64)
    leaves = [LAYOUT64.to_wire(x) for x in (a, b)]
    t = (a + b) ^ a
    want = [t, (b < t).astype(np.uint64),
            np.array([bin(int(x)).count("1") for x in t], np.uint64)]
    for name in ("words-cpu-64", "ref-vertical-64", "pallas-tpu-64"):
        outs = fused_program.get_pipeline(prog, backend=name,
                                          interpret=True)(*leaves)
        for w, o in zip(want, outs):
            np.testing.assert_array_equal(
                w, LAYOUT64.from_wire(o)[:n], err_msg=name)


def test_wide_pipeline_rejects_narrow_only_backend():
    prog = fused_program.FusedProgram(
        width=64, n_inputs=1,
        ops=(fused_program.FusedOp("xor", (0, 0)),), outputs=(1,),
        layout=LAYOUT64)
    with pytest.raises(ValueError, match="64-bit plane layout"):
        fused_program.get_pipeline(prog, backend="words-cpu")


# --------------------------------------------------------------------- #
# Layout-keyed pipeline cache
# --------------------------------------------------------------------- #


def test_pipeline_cache_is_layout_keyed():
    """The same op structure at the same width on DIFFERENT layouts is
    two pipelines (cache miss), and re-recording on either layout hits
    its own cached trace."""
    a = np.arange(256, dtype=np.uint64)

    def batch(dev):
        x = dev.asarray(a)
        return np.asarray((x + a) ^ a)

    # Hermetic: earlier suites may have filled the LRU to maxsize, where
    # an insert evicts and currsize no longer grows.
    fused_program._cached_pipeline.cache_clear()
    d32 = pum.device(width=16, fuse=True)
    d64 = pum.device(width=16, layout=64, fuse=True)
    batch(d32)
    info0 = fused_program._cached_pipeline.cache_info()
    batch(d64)  # same structure, new layout: a genuinely new pipeline
    info1 = fused_program._cached_pipeline.cache_info()
    assert info1.currsize == info0.currsize + 1
    assert info1.hits == info0.hits
    batch(d32)
    batch(d64)  # both layouts re-hit their own compiled traces
    info2 = fused_program._cached_pipeline.cache_info()
    assert info2.currsize == info1.currsize
    assert info2.hits == info1.hits + 2


# --------------------------------------------------------------------- #
# PumArray slicing (__getitem__ / __len__)
# --------------------------------------------------------------------- #


def test_getitem_on_eager_values_is_a_view():
    dev = pum.device(width=16, fuse=False)
    a = np.arange(10, dtype=np.uint64)
    x = dev.asarray(a)
    s = x[2:7]
    assert isinstance(s, pum.PumArray) and s.shape == (5,)
    assert s._data.base is not None  # a view, not a copy
    np.testing.assert_array_equal(s.to_numpy(), a[2:7])
    np.testing.assert_array_equal(x[::3].to_numpy(), a[::3])
    assert len(x) == 10 and len(s) == 5


def test_getitem_on_lazy_handles_forces_materialize():
    dev = pum.device(width=16, fuse=True)
    a = np.arange(64, dtype=np.uint64)
    y = dev.asarray(a) + a
    assert isinstance(y._data, LazyArray) and y._data._value is None
    s = y[10:20]  # slicing is a host access: flushes, then slices
    assert y._data._value is not None
    np.testing.assert_array_equal(s.to_numpy(), 2 * a[10:20])
    # sliced arrays feed back into ops as ordinary operands
    np.testing.assert_array_equal(
        np.asarray(s + s), 4 * a[10:20])


def test_getitem_integer_index_yields_0d_pum_array():
    dev = pum.device(width=16, fuse=True)
    y = dev.asarray(np.arange(8, dtype=np.uint64)) + 1
    el = y[3]
    assert isinstance(el, pum.PumArray) and el.shape == ()
    assert int(np.asarray(el)) == 4
    with pytest.raises(TypeError):
        len(el)


# --------------------------------------------------------------------- #
# REF postponing plumbing (EngineConfig.ref_postponing -> auto controller)
# --------------------------------------------------------------------- #


def test_ref_postponing_reaches_the_auto_controller():
    dev = pum.device(width=16, controller="auto", ref_postponing=4)
    assert dev.engine.controller.postponing == 4
    # the policy actually changes the priced refresh schedule
    base = pum.device(width=16, controller="auto")
    a = np.arange(4096, dtype=np.uint64) & np.uint64(0xFFFF)
    for d in (dev, base):
        _ = np.asarray(d.asarray(a) + a)
    assert dev.stats.refresh_stall_ns != base.stats.refresh_stall_ns


def test_ref_postponing_validates_loudly():
    with pytest.raises(ValueError, match="JEDEC"):
        PulsarEngine(width=16, controller="auto", ref_postponing=9)
    with pytest.raises(ValueError, match="JEDEC"):
        pum.EngineConfig(ref_postponing=0)
    # silently-inert combination is rejected, not ignored
    with pytest.raises(ValueError, match="controller='auto'"):
        PulsarEngine(width=16, ref_postponing=4)


# --------------------------------------------------------------------- #
# shard-words fused backend
# --------------------------------------------------------------------- #


@pytest.mark.sharded
def test_shard_words_single_device_parity():
    """Requestable by name even on one device: same results/stats as the
    default fused path and as eager."""
    rng = np.random.default_rng(31)
    a = rng.integers(0, 1 << 32, 500, dtype=np.uint64)
    b = rng.integers(0, 1 << 32, 500, dtype=np.uint64)
    eager = pum.device(width=32, fuse=False)
    sharded = pum.device(width=32, fuse=True,
                         fused_backend="shard-words")
    assert sharded.engine.fused_backend == "shard-words"

    def run(dev):
        x = dev.asarray(a)
        t = (x + b) * x
        return np.asarray(t ^ b)

    np.testing.assert_array_equal(run(eager), run(sharded))
    assert eager.stats == sharded.stats


@pytest.mark.sharded
def test_shard_words_rejects_wide_layout():
    with pytest.raises(ValueError, match="layouts"):
        PulsarEngine(width=48, fuse=True, fused_backend="shard-words")
    with pytest.raises(ValueError, match="no fused"):
        PulsarEngine(width=16, fuse=True, fused_backend="fast")


@pytest.mark.sharded
def test_shard_words_multidevice_parity():
    """One flush executes one program across 8 forced host devices;
    results and EngineStats identical to single-device eager (subprocess:
    the flag must be set before jax initializes)."""
    import os
    import subprocess
    import sys
    import textwrap
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = \
            "--xla_force_host_platform_device_count=8"
        import numpy as np
        import jax
        assert len(jax.devices()) == 8
        import repro.pum as pum
        # multi-device hosts auto-select the sharded pipeline
        assert pum.select_backend(require="fused", width=32,
                                  layout=32).name == "shard-words"
        rng = np.random.default_rng(5)
        a = rng.integers(0, 1 << 32, 1000, dtype=np.uint64)
        b = rng.integers(0, 1 << 32, 1000, dtype=np.uint64)
        b[::7] = 0
        eager = pum.device(width=32, fuse=False)
        fused = pum.device(width=32, fuse=True)
        def run(d):
            x = d.asarray(a)
            t = (x + b) * x
            q, r = divmod(t, b)
            return [np.asarray(v) for v in (t, q, r, t.popcount())]
        with pum.profile(fused):
            for w, g in zip(run(eager), run(fused)):
                np.testing.assert_array_equal(w, g)
        assert eager.stats == fused.stats
        # outputs come back sharded across every device
        assert fused.counters.histogram("engine.flush_devices")["min"] == 8
        print("OK")
    """)
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(repo, "src")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, \
        f"STDOUT:{proc.stdout}\nSTDERR:{proc.stderr}"
    assert "OK" in proc.stdout
