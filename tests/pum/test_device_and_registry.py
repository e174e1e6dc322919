"""Device lifecycle (context scoping, auto-flush), EngineConfig and its
checks, the fused evaluator table, the op surface, leaf-buffer donation
and the shared-divider divmod lowering."""

import dataclasses
import warnings

import numpy as np
import pytest

import repro.pum as pum
from repro import backends
from repro.core.engine import LazyArray, PulsarEngine
from repro.kernels.fused_program import optimize_program


# --------------------------------------------------------------------- #
# Device lifecycle + EngineConfig
# --------------------------------------------------------------------- #


def test_device_context_scopes_default_and_autoflushes():
    outer = pum.default_device()
    with pum.device(width=16) as dev:
        assert pum.default_device() is dev
        x = pum.asarray(np.array([2, 3], np.uint64))  # scoped device
        assert x.device is dev
        y = x + x
        assert isinstance(y._data, LazyArray) and y._data._value is None
        with pum.device(width=8) as inner:
            assert pum.default_device() is inner
        assert pum.default_device() is dev
    # scope exit flushed the pending graph and popped the stack
    assert y._data._value is not None
    np.testing.assert_array_equal(y.to_numpy(), np.array([4, 6], np.uint64))
    assert pum.default_device() is outer


def test_engine_config_is_frozen_and_validates():
    cfg = pum.EngineConfig(width=16, banks=8)
    with pytest.raises(dataclasses.FrozenInstanceError):
        cfg.width = 32
    assert cfg.replace(width=32).width == 32 and cfg.width == 16
    assert cfg.fuse  # the fused pipeline is the production default
    with pytest.raises(ValueError):
        pum.EngineConfig(width=0)
    with pytest.raises(ValueError):
        pum.EngineConfig(flush_threshold=0)


def test_device_builds_engine_from_config():
    cfg = pum.EngineConfig(mfr="H", width=16, banks=8, use_pulsar=False,
                           fuse=False, flush_threshold=7)
    dev = pum.device(cfg)
    e = dev.engine
    assert e.config is cfg and dev.config is cfg
    assert e.profile.name == "H" and e.layout.word_bits == 32
    # the bare engine takes the same one config object
    assert PulsarEngine(cfg).config is cfg
    # keyword overrides derive a new config
    dev2 = pum.device(cfg, width=32)
    assert dev2.config.width == 32 and dev2.config.mfr == "H"


def test_wide_device_fuses_on_the_64bit_layout():
    """Widths above 32 resolve to the 64-bit plane layout and FUSE (on
    ``words-cpu`` at ``word_bits=64`` on this host), bit-exact against
    eager: every evaluator the host chooses runs the 64-bit layout."""
    dev = pum.device(width=48)
    assert dev.config.fuse and dev.layout.word_bits == 64
    a = np.array([1 << 40, 5], np.uint64)
    np.testing.assert_array_equal(np.asarray(dev.asarray(a) + a), 2 * a)
    q, r = divmod(dev.asarray(a), np.array([3, 0], np.uint64))
    np.testing.assert_array_equal(np.asarray(q),
                                  np.array([(1 << 40) // 3, 0], np.uint64))
    # widths that fit no layout word still refuse loudly
    with pytest.raises(ValueError, match="does not fit"):
        pum.EngineConfig(width=48, layout=32)


def test_sim_backend_device_is_eager_and_bit_exact():
    dev = pum.device(mfr="H", width=8, backend="sim")
    assert not dev.config.fuse  # sim has no word dataplane to fuse over
    n = dev.engine._alu.words * 32
    rng = np.random.default_rng(1)
    a = rng.integers(0, 256, n, dtype=np.uint64)
    b = rng.integers(1, 256, n, dtype=np.uint64)
    np.testing.assert_array_equal(np.asarray(dev.asarray(a) & b), a & b)
    np.testing.assert_array_equal(np.asarray(dev.asarray(a) + b),
                                  (a + b) & np.uint64(0xFF))
    # divmod runs ONE restoring-division pass on the chip model
    ops_before = dev.engine._alu.x.chip.stats.n_ops
    q, r = divmod(dev.asarray(a), b)
    one_pass_ops = dev.engine._alu.x.chip.stats.n_ops - ops_before
    np.testing.assert_array_equal(np.asarray(q), a // b)
    np.testing.assert_array_equal(np.asarray(r), a % b)
    ops_before = dev.engine._alu.x.chip.stats.n_ops
    _ = dev.asarray(a) // b
    div_only_ops = dev.engine._alu.x.chip.stats.n_ops - ops_before
    assert one_pass_ops < 1.5 * div_only_ops  # not 2x: divider shared
    # zero-divisor lanes yield 0 on the sim backend too (the engine-wide
    # unsigned-NumPy contract, not the ALU divider's raw output)
    bz = b.copy()
    bz[::3] = 0
    q, r = divmod(dev.asarray(a), bz)
    want_q = np.where(bz == 0, 0, a // np.maximum(bz, 1))
    want_r = np.where(bz == 0, 0, a % np.maximum(bz, 1))
    np.testing.assert_array_equal(np.asarray(q), want_q)
    np.testing.assert_array_equal(np.asarray(r), want_r)
    np.testing.assert_array_equal(np.asarray(dev.asarray(a) // bz), want_q)
    np.testing.assert_array_equal(np.asarray(dev.asarray(a) % bz), want_r)


def test_scalar_broadcasts_share_one_leaf():
    """Repeated scalar operands must dedup to ONE graph leaf (the device
    caches the broadcast buffer), not snapshot a fresh full-size leaf
    per op."""
    dev = pum.device(width=16, fuse=True)
    x = dev.asarray(np.arange(64, dtype=np.uint64))
    t1 = x + 5
    t2 = x ^ 5
    t3 = x | 5
    g = dev.engine._graph
    assert len(g.leaves) == 2  # x and one shared broadcast of 5
    np.testing.assert_array_equal(np.asarray(t1),
                                  np.arange(64, dtype=np.uint64) + 5)
    np.testing.assert_array_equal(np.asarray(t2),
                                  np.arange(64, dtype=np.uint64) ^ 5)
    np.testing.assert_array_equal(np.asarray(t3),
                                  np.arange(64, dtype=np.uint64) | 5)


# --------------------------------------------------------------------- #
# Fused evaluator table
# --------------------------------------------------------------------- #


def test_evaluator_table_lists_builtin_evaluators():
    """Four evaluators, each with the plane layouts it runs; no ``-64``
    twins: the 64-bit layout runs on the same names."""
    table = {name: layouts
             for name, (_, layouts) in backends.EVALUATORS.items()}
    assert table == {"words-cpu": (32, 64), "pallas-tpu": (32, 64),
                     "shard-words": (32,), "ref-vertical": (32, 64)}
    assert backends.host_evaluators()[0] == "words-cpu"
    assert "ref-vertical" not in backends.host_evaluators()


def test_fused_evaluator_on_this_host():
    # On this one-CPU host the word-domain evaluator runs both layouts
    # (Pallas needs a TPU; shard-words needs >1 device).
    for wb in (32, 64):
        assert backends.fused_evaluator(wb) == "words-cpu"
    assert backends.fused_evaluator(pum.LAYOUT64) == "words-cpu"
    # a device's own choice, and a pinned one it must be able to run
    assert pum.device(width=48).layout.word_bits == 64
    with pytest.raises(ValueError, match="unknown fused evaluator"):
        backends.fused_evaluator(32, pinned="nope")
    with pytest.raises(ValueError, match="unknown fused evaluator"):
        pum.EngineConfig(fused_backend="fast")
    with pytest.raises(ValueError, match="64-bit plane layout"):
        pum.EngineConfig(width=48, fused_backend="shard-words")


@pytest.mark.parametrize("from_env", [True, False], ids=["env", "checkout"])
def test_compile_cache_has_a_fixed_home(monkeypatch, tmp_path, from_env):
    """JAX_COMPILATION_CACHE_DIR wins and is left to JAX; unset, the cache
    goes to .jax_cache at the root of the checkout, the same every call."""
    import os

    import jax
    from repro.backends import use_compile_cache
    before = jax.config.jax_compilation_cache_dir
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        got = use_compile_cache()
        if from_env:
            assert got == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == before
        else:
            assert got == os.path.join(root, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
            assert use_compile_cache() == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_unknown_eager_backend_fails_loudly():
    with pytest.raises(ValueError, match="'fast' or 'sim'"):
        pum.device(backend="warp-drive", fuse=False)
    with pytest.raises(ValueError, match="'fast' or 'sim'"):
        pum.device(backend="words-cpu", fuse=False)


# --------------------------------------------------------------------- #
# Op surface
# --------------------------------------------------------------------- #


def test_pum_api_does_not_warn():
    dev = pum.device(width=16, fuse=True)
    a = np.array([9, 5], np.uint64)
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        x = dev.asarray(a)
        _ = np.asarray((x + a) * a // (x ^ 3) % (x | 1))
        _ = np.asarray(x.popcount())


# --------------------------------------------------------------------- #
# Leaf-buffer donation
# --------------------------------------------------------------------- #


@pytest.mark.fused
def test_donate_leaves_is_bit_exact():
    rng = np.random.default_rng(5)
    n = 4096 + 17
    a = rng.integers(0, 1 << 16, n, dtype=np.uint64)
    b = rng.integers(0, 1 << 16, n, dtype=np.uint64)
    plain = pum.device(width=16, fuse=True)
    donating = pum.device(width=16, fuse=True, donate_leaves=True)
    assert donating.config.donate_leaves

    def prog(dev):
        x, y = dev.asarray(a), dev.asarray(b)
        t = (x + y) * x
        q, r = divmod(t, y)
        return [np.asarray(v, np.uint64) for v in (t, q, r, t ^ y)]

    for w, g in zip(prog(plain), prog(donating)):
        np.testing.assert_array_equal(w, g)
    assert plain.stats == donating.stats
    # operand snapshots live on the host: caller buffers are untouched
    assert a.max() < 1 << 16 and b.max() < 1 << 16
    # and a second flush through the same donated pipeline still works
    for w, g in zip(prog(plain), prog(donating)):
        np.testing.assert_array_equal(w, g)


# --------------------------------------------------------------------- #
# Shared-divider divmod lowering
# --------------------------------------------------------------------- #


def test_divmod_charges_one_division_pass():
    a = np.array([100, 37], np.uint64)
    b = np.array([7, 5], np.uint64)
    one = pum.device(width=16, fuse=False)
    _ = divmod(one.asarray(a), b)
    single = pum.device(width=16, fuse=False)
    _ = single.asarray(a) // b
    assert one.stats == single.stats  # divmod == ONE div charge


def test_div_and_mod_cse_into_one_divider_pass():
    """`a // b` and `a % b` of the same operands lower to two divmod
    records that optimize_program unifies: the compiled pipeline runs ONE
    restoring division."""
    dev = pum.device(width=16, fuse=True)
    a = np.array([100, 37, 8], np.uint64)
    b = np.array([7, 0, 3], np.uint64)
    x = dev.asarray(a)
    q = x // b
    r = x % b
    g = dev.engine._graph
    assert [op for op, _, _ in g.ops].count("divmod") == 2
    # mirror the engine's flush-time normalization and count dividers
    from repro.core.engine import FusedOp, FusedProgram
    n_leaves = len(g.leaves)
    program = FusedProgram(
        width=g.width, n_inputs=n_leaves,
        ops=tuple(FusedOp(op, tuple(
            t[1] if t[0] == "leaf" else n_leaves + t[1] for t in args),
            param) for op, args, param in g.ops),
        outputs=(n_leaves + 1, n_leaves + 3))  # the two selector results
    opt, _, _ = optimize_program(program)
    assert [op.opcode for op in opt.ops].count("divmod") == 1
    np.testing.assert_array_equal(np.asarray(q),
                                  np.array([14, 0, 2], np.uint64))
    np.testing.assert_array_equal(np.asarray(r),
                                  np.array([2, 0, 2], np.uint64))
