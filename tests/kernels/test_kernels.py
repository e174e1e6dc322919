"""Per-kernel validation: Pallas (interpret mode) vs jnp oracle vs NumPy,
swept over shapes/dtypes, plus hypothesis property tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:  # optional dep: fixed-seed fallback
    from repro.testing import given, settings, st

from repro.core.layout import from_vertical, to_vertical
from repro.kernels import ref
from repro.kernels.bit_transpose import bit_transpose32
from repro.kernels.bitserial_add import bitserial_add
from repro.kernels.charge_share import charge_share
from repro.kernels.maj_n import maj_n


def rand_words(shape, seed, dtype=np.int32):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 2**32, shape, dtype=np.uint64).astype(np.uint32) \
        .view(np.int32).astype(dtype) if dtype == np.int32 else \
        rng.integers(0, 2**32, shape, dtype=np.uint64).astype(dtype)


# --------------------------------------------------------------------- #
# maj_n
# --------------------------------------------------------------------- #

@pytest.mark.parametrize("n,threshold", [(1, 1), (3, 2), (4, 3), (5, 3),
                                         (7, 4), (16, 9), (31, 16), (32, 17)])
@pytest.mark.parametrize("w", [128, 1024, 1536])
def test_maj_n_vs_numpy(n, threshold, w):
    x = rand_words((n, w), seed=n * 100 + w)
    got = np.asarray(maj_n(jnp.asarray(x), threshold, interpret=True))
    bits = ((x.view(np.uint32)[:, :, None] >> np.arange(32)[None, None]) & 1)
    want_bits = (bits.sum(0) >= threshold).astype(np.uint32)
    want = (want_bits << np.arange(32)[None]).sum(-1, dtype=np.uint64) \
        .astype(np.uint32).view(np.int32)
    np.testing.assert_array_equal(got.view(np.int32), want)


@pytest.mark.parametrize("n,threshold", [(3, 2), (5, 3), (9, 5)])
def test_maj_n_ref_matches_pallas(n, threshold):
    x = jnp.asarray(rand_words((n, 2048), seed=7))
    np.testing.assert_array_equal(
        np.asarray(maj_n(x, threshold, interpret=True)),
        np.asarray(ref.maj_n(x, threshold)))


@given(n=st.integers(1, 9), seed=st.integers(0, 50))
@settings(max_examples=15, deadline=None)
def test_maj_n_property_replication_invariance(n, seed):
    """MAJ over k-replicated inputs == MAJ over originals (the paper's
    majority-algebra identity behind input replication, §5.1)."""
    if n % 2 == 0:
        return
    x = jnp.asarray(rand_words((n, 256), seed=seed))
    base = ref.maj_n(x, n // 2 + 1)
    rep = jnp.concatenate([x, x, x], axis=0)  # 3 copies
    got = ref.maj_n(rep, (3 * n) // 2 + 1)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(got))


def test_maj_n_all_ones_zeros():
    ones = jnp.full((5, 256), -1, jnp.int32)
    zeros = jnp.zeros((5, 256), jnp.int32)
    assert (np.asarray(maj_n(ones, 3, interpret=True)) == -1).all()
    assert (np.asarray(maj_n(zeros, 3, interpret=True)) == 0).all()


# --------------------------------------------------------------------- #
# bitserial_add
# --------------------------------------------------------------------- #

@pytest.mark.parametrize("width", [4, 8, 16, 32])
@pytest.mark.parametrize("n_el", [256, 4096])
def test_bitserial_add_vs_int_add(width, n_el):
    rng = np.random.default_rng(width + n_el)
    a = rng.integers(0, 1 << width, n_el, dtype=np.uint64)
    b = rng.integers(0, 1 << width, n_el, dtype=np.uint64)
    pa = to_vertical(a, width).view(np.int32)
    pb = to_vertical(b, width).view(np.int32)
    got_planes = np.asarray(bitserial_add(jnp.asarray(pa), jnp.asarray(pb),
                                          interpret=True))
    got = from_vertical(got_planes.view(np.uint32))
    np.testing.assert_array_equal(got, (a + b) & ((1 << width) - 1))


def test_bitserial_add_ref_matches():
    a = jnp.asarray(rand_words((8, 1024), 1))
    b = jnp.asarray(rand_words((8, 1024), 2))
    np.testing.assert_array_equal(
        np.asarray(bitserial_add(a, b, interpret=True)),
        np.asarray(ref.bitserial_add(a, b)))


@given(seed=st.integers(0, 100))
@settings(max_examples=20, deadline=None)
def test_bitserial_add_property(seed):
    rng = np.random.default_rng(seed)
    a = rng.integers(0, 1 << 16, 64, dtype=np.uint64)
    b = rng.integers(0, 1 << 16, 64, dtype=np.uint64)
    pa = jnp.asarray(to_vertical(a, 16).view(np.int32))
    pb = jnp.asarray(to_vertical(b, 16).view(np.int32))
    got = from_vertical(np.asarray(ref.bitserial_add(pa, pb)).view(np.uint32))
    np.testing.assert_array_equal(got, (a + b) & 0xFFFF)


# --------------------------------------------------------------------- #
# bit_transpose32
# --------------------------------------------------------------------- #

@pytest.mark.parametrize("g", [1, 7, 128, 1024])
def test_transpose_matches_layout(g):
    rng = np.random.default_rng(g)
    vals = rng.integers(0, 2**32, 32 * g, dtype=np.uint64)
    # Horizontal: row k of tile t = vals[32t + k]
    horiz = vals.reshape(g, 32).T.astype(np.uint32).view(np.int32)  # [32, G]
    got = np.asarray(bit_transpose32(jnp.asarray(horiz), interpret=True))
    # Vertical oracle: per tile, plane j = bit j of the tile's 32 values.
    for t in range(min(g, 4)):
        planes = to_vertical(vals[32 * t:32 * (t + 1)], 32)
        np.testing.assert_array_equal(got[:, t].view(np.uint32), planes[:, 0])


def test_transpose_involution():
    x = jnp.asarray(rand_words((32, 256), 3))
    once = ref.bit_transpose32(x)
    twice = ref.bit_transpose32(once)
    np.testing.assert_array_equal(np.asarray(twice), np.asarray(x))


def test_transpose_pallas_vs_ref():
    x = jnp.asarray(rand_words((32, 2048), 4))
    np.testing.assert_array_equal(
        np.asarray(bit_transpose32(x, interpret=True)),
        np.asarray(ref.bit_transpose32(x)))


@given(seed=st.integers(0, 50))
@settings(max_examples=10, deadline=None)
def test_transpose_property_involution(seed):
    x = jnp.asarray(rand_words((32, 64), seed))
    np.testing.assert_array_equal(
        np.asarray(ref.bit_transpose32(ref.bit_transpose32(x))),
        np.asarray(x))


# --------------------------------------------------------------------- #
# charge_share
# --------------------------------------------------------------------- #

@pytest.mark.parametrize("n,b", [(4, 256), (8, 1024), (32, 3000)])
def test_charge_share_vs_ref(n, b):
    rng = np.random.default_rng(n + b)
    v = rng.choice([0.0, 0.6, 1.2], (n, b)).astype(np.float32)
    caps = (20 + 2 * rng.standard_normal((n, b))).astype(np.float32)
    got = np.asarray(charge_share(jnp.asarray(v), jnp.asarray(caps),
                                  vdd=1.2, c_bl=116.0, interpret=True))
    want = np.asarray(ref.charge_share(jnp.asarray(v), jnp.asarray(caps),
                                       vdd=1.2, c_bl=116.0))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7)


def test_charge_share_physics():
    """All-VDD cells give positive dV scaling with N/(N+r)."""
    n, b = 8, 128
    v = np.full((n, b), 1.2, np.float32)
    caps = np.full((n, b), 20.0, np.float32)
    dv = np.asarray(ref.charge_share(jnp.asarray(v), jnp.asarray(caps),
                                     vdd=1.2, c_bl=116.0))
    expected = 8 * 20 * 0.6 / (116 + 8 * 20)
    np.testing.assert_allclose(dv, expected, rtol=1e-6)


@pytest.mark.parametrize("n,threshold", [(3, 2), (7, 4), (31, 16)])
def test_maj_n_fast_matches_oracle(n, threshold):
    x = jnp.asarray(rand_words((n, 1024), seed=99 + n))
    np.testing.assert_array_equal(
        np.asarray(ref.maj_n_fast(x, threshold)),
        np.asarray(ref.maj_n(x, threshold)))


# --------------------------------------------------------------------- #
# fused_program
# --------------------------------------------------------------------- #

from repro.kernels.fused_program import (FusedOp, FusedProgram,  # noqa: E402
                                         get_pipeline, run_program_pallas,
                                         run_program_ref)

_FUSED_DEMO = FusedProgram(
    width=16, n_inputs=3,
    ops=(FusedOp("and", (0, 1)),
         FusedOp("xor", (3, 2)),
         FusedOp("add", (4, 0)),
         FusedOp("sub", (5, 1)),
         FusedOp("less", (6, 2)),
         FusedOp("popcount", (5,)),
         FusedOp("reduce_and", (3,), param=16),
         FusedOp("reduce_or", (6,)),
         FusedOp("reduce_xor", (5,))),
    outputs=(6, 7, 8, 9, 10, 11))


def _fused_demo_stacks(n_el, seed):
    rng = np.random.default_rng(seed)
    vals = [rng.integers(0, 1 << 16, n_el, dtype=np.uint64)
            for _ in range(3)]
    stack = jnp.asarray(np.stack([to_vertical(v, 16).view(np.int32)
                                  for v in vals]))
    return vals, stack


def _fused_demo_oracle(vals):
    a, b, c = vals
    mask = np.uint64(0xFFFF)
    t0 = a & b
    t1 = t0 ^ c
    t2 = (t1 + a) & mask
    t3 = (t2 - b) & mask
    return [t3, (t3 < c).astype(np.uint64),
            np.array([bin(int(x)).count("1") for x in t2], np.uint64),
            (t0 == mask).astype(np.uint64),
            (t3 != 0).astype(np.uint64),
            np.array([bin(int(x)).count("1") & 1 for x in t2], np.uint64)]


@pytest.mark.parametrize("n_el", [256, 4096])
def test_fused_program_ref_vs_numpy(n_el):
    vals, stack = _fused_demo_stacks(n_el, seed=n_el)
    got = np.asarray(run_program_ref(_FUSED_DEMO, stack)).view(np.uint32)
    for plane_stack, want in zip(got, _fused_demo_oracle(vals)):
        np.testing.assert_array_equal(from_vertical(plane_stack), want)


def test_fused_program_pallas_matches_ref():
    from repro.kernels import run_fused_program
    _, stack = _fused_demo_stacks(2048, seed=1)
    want = np.asarray(run_program_ref(_FUSED_DEMO, stack))
    np.testing.assert_array_equal(
        np.asarray(run_program_pallas(_FUSED_DEMO, stack, interpret=True)),
        want)
    # ops-layer dispatch: oracle on CPU, Pallas under force_pallas
    np.testing.assert_array_equal(
        np.asarray(run_fused_program(_FUSED_DEMO, stack)), want)
    np.testing.assert_array_equal(
        np.asarray(run_fused_program(_FUSED_DEMO, stack, force_pallas=True,
                                     interpret=True)), want)


def test_fused_pipeline_end_to_end():
    """get_pipeline handles the framing too, and the CPU word-domain path
    must agree bit-for-bit with the vertical transpose+planes form."""
    vals, _ = _fused_demo_stacks(512, seed=2)
    leaves = [jnp.asarray(v.astype(np.uint32).view(np.int32)) for v in vals]
    outs = get_pipeline(_FUSED_DEMO)(*leaves)
    vert = get_pipeline(_FUSED_DEMO, backend="ref-vertical")(*leaves)
    for got, gvert, want in zip(outs, vert, _fused_demo_oracle(vals)):
        np.testing.assert_array_equal(
            np.asarray(got).view(np.uint32).astype(np.uint64), want)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(gvert))


def test_pallas_pipeline_stages_carry_named_scopes():
    """Each stage of the pallas-tpu pipeline lowers under a stable scope
    name, so a profile can tell the layout copies from the kernel."""
    vals, _ = _fused_demo_stacks(512, seed=2)
    leaves = [jnp.asarray(v.astype(np.uint32).view(np.int32)) for v in vals]
    pipeline = get_pipeline(_FUSED_DEMO, backend="pallas-tpu",
                            interpret=True)
    text = jax.jit(pipeline).lower(*leaves).as_text(debug_info=True)
    for scope in ("pum.stack", "pum.to_planes", "pum.kernel",
                  "pum.from_planes"):
        assert scope in text, scope


@given(seed=st.integers(0, 100))
@settings(max_examples=15, deadline=None)
def test_fused_plane_algebra_property(seed):
    rng = np.random.default_rng(seed)
    a = rng.integers(0, 1 << 16, 64, dtype=np.uint64)
    b = rng.integers(0, 1 << 16, 64, dtype=np.uint64)
    pa = [jnp.asarray(p.view(np.int32)) for p in to_vertical(a, 16)]
    pb = [jnp.asarray(p.view(np.int32)) for p in to_vertical(b, 16)]

    add = np.stack([np.asarray(p).view(np.uint32)
                    for p in ref.plane_add(pa, pb)])
    np.testing.assert_array_equal(from_vertical(add), (a + b) & 0xFFFF)

    diff, borrow = ref.plane_sub(pa, pb)
    diff = np.stack([np.asarray(p).view(np.uint32) for p in diff])
    np.testing.assert_array_equal(from_vertical(diff), (a - b) & 0xFFFF)
    lt = from_vertical(np.asarray(borrow).view(np.uint32)[None])
    np.testing.assert_array_equal(lt, (a < b).astype(np.uint64))

    counts = ref.plane_popcount(pa)
    counts = np.stack([np.asarray(p).view(np.uint32) for p in counts])
    want = np.array([bin(int(x)).count("1") for x in a], np.uint64)
    np.testing.assert_array_equal(from_vertical(counts), want)


@given(seed=st.integers(0, 100))
@settings(max_examples=6, deadline=None)
def test_fused_plane_mul_divmod_property(seed):
    """plane_mul (shift-add) and plane_divmod (restoring division) match
    word arithmetic modulo 2**width, including zero divisors (-> 0)."""
    rng = np.random.default_rng(seed)
    a = rng.integers(0, 1 << 16, 64, dtype=np.uint64)
    b = rng.integers(0, 1 << 16, 64, dtype=np.uint64)
    b[::5] = 0  # div/mod-by-zero lanes
    a[0], b[1], a[2] = 0xFFFF, 0xFFFF, 1 << 15
    pa = [jnp.asarray(p.view(np.int32)) for p in to_vertical(a, 16)]
    pb = [jnp.asarray(p.view(np.int32)) for p in to_vertical(b, 16)]

    prod = np.stack([np.asarray(p).view(np.uint32)
                     for p in ref.plane_mul(pa, pb)])
    np.testing.assert_array_equal(from_vertical(prod), (a * b) & 0xFFFF)

    q, r = ref.plane_divmod(pa, pb)
    q = np.stack([np.asarray(p).view(np.uint32) for p in q])
    r = np.stack([np.asarray(p).view(np.uint32) for p in r])
    safe = np.maximum(b, 1)
    np.testing.assert_array_equal(from_vertical(q),
                                  np.where(b == 0, 0, a // safe))
    np.testing.assert_array_equal(from_vertical(r),
                                  np.where(b == 0, 0, a % safe))


_ARITH_DEMO = FusedProgram(
    width=8, n_inputs=2,
    ops=(FusedOp("mul", (0, 1)),
         FusedOp("div", (0, 1)),
         FusedOp("mod", (0, 1)),
         FusedOp("div", (2, 1)),
         # the PR 4 tuple op: one divider pass feeding both selectors
         FusedOp("divmod", (2, 1)),
         FusedOp("fst", (6,)),
         FusedOp("snd", (6,))),
    outputs=(2, 3, 4, 5, 7, 8))


def test_fused_program_mul_div_mod_all_evaluators():
    """The three evaluators agree on the arithmetic opcodes added in PR 3
    (mul/div/mod) and the PR 4 divmod/fst/snd tuple form, including
    division by zero."""
    rng = np.random.default_rng(9)
    a = rng.integers(0, 256, 2048, dtype=np.uint64)
    b = rng.integers(0, 256, 2048, dtype=np.uint64)
    b[::7] = 0
    stack = jnp.asarray(np.stack([to_vertical(v, 8).view(np.int32)
                                  for v in (a, b)]))
    want = np.asarray(run_program_ref(_ARITH_DEMO, stack))
    np.testing.assert_array_equal(
        np.asarray(run_program_pallas(_ARITH_DEMO, stack, interpret=True)),
        want)
    leaves = [jnp.asarray(v.astype(np.uint32).view(np.int32))
              for v in (a, b)]
    word = get_pipeline(_ARITH_DEMO)(*leaves)
    vert = get_pipeline(_ARITH_DEMO, backend="ref-vertical")(*leaves)
    safe = np.maximum(b, 1)
    oracle = [(a * b) & 0xFF, np.where(b == 0, 0, a // safe),
              np.where(b == 0, 0, a % safe)]
    oracle.append(np.where(b == 0, 0, oracle[0] // safe))
    oracle.append(oracle[3])                       # fst(divmod) == div
    oracle.append(np.where(b == 0, 0, oracle[0] % safe))  # snd == mod
    for got, gvert, w in zip(word, vert, oracle):
        np.testing.assert_array_equal(
            np.asarray(got).view(np.uint32).astype(np.uint64), w)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(gvert))


def test_optimize_program_cse_and_dce():
    from repro.kernels.fused_program import optimize_program
    p = FusedProgram(
        width=16, n_inputs=3,
        ops=(FusedOp("add", (0, 1)),      # 3
             FusedOp("add", (1, 0)),      # 4 == 3 (commutative CSE)
             FusedOp("xor", (3, 4)),      # 5 -> xor(3, 3)
             FusedOp("and", (0, 2)),      # 6: dead (leaf 2 with it)
             FusedOp("sub", (3, 4)),      # 7 -> sub(3, 3) kept: output
             FusedOp("sub", (4, 3))),     # 8 == 7 after canonicalization
        outputs=(5, 7, 8))
    opt, out_pos, leaf_map = optimize_program(p)
    assert leaf_map == (0, 1)             # leaf 2 pruned with the dead and
    assert len(opt.ops) == 3              # add, xor, sub survive
    assert [op.opcode for op in opt.ops] == ["add", "xor", "sub"]
    assert out_pos == (0, 1, 1)           # outputs 7 and 8 share a value
    assert len(opt.outputs) == 2
    # Determinism: the same structure normalizes identically (cache key).
    assert optimize_program(p)[0] == opt


def test_optimize_program_preserves_noncommutative_order():
    from repro.kernels.fused_program import optimize_program
    p = FusedProgram(
        width=8, n_inputs=2,
        ops=(FusedOp("sub", (0, 1)), FusedOp("sub", (1, 0))),
        outputs=(2, 3))
    opt, out_pos, _ = optimize_program(p)
    assert len(opt.ops) == 2              # a-b and b-a must NOT unify
    assert out_pos == (0, 1)
