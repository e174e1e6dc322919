"""Device lifecycle (context scoping, auto-flush), EngineConfig, the
backend registry contract, the deprecation shim, leaf-buffer donation and
the shared-divider divmod lowering."""

import dataclasses
import warnings

import numpy as np
import pytest

import repro.pum as pum
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
    assert (e.mfr, e.width, e.banks, e.use_pulsar, e.fuse,
            e.flush_threshold) == ("H", 16, 8, False, False, 7)
    # keyword overrides derive a new config
    dev2 = pum.device(cfg, width=32)
    assert dev2.config.width == 32 and dev2.config.mfr == "H"


def test_wide_device_fuses_on_the_64bit_layout():
    """Widths above 32 resolve to the 64-bit plane layout and FUSE (the
    additively registered ``words-cpu-64`` evaluator), bit-exact against
    eager — the old transparent eager fallback is gone because a fused
    evaluator now covers the layout."""
    dev = pum.device(width=48)
    assert dev.config.fuse and dev.layout.word_bits == 64
    a = np.array([1 << 40, 5], np.uint64)
    np.testing.assert_array_equal(np.asarray(dev.asarray(a) + a), 2 * a)
    q, r = divmod(dev.asarray(a), np.array([3, 0], np.uint64))
    np.testing.assert_array_equal(np.asarray(q),
                                  np.array([(1 << 40) // 3, 0], np.uint64))
    # widths that fit no layout word still refuse loudly
    with pytest.raises(ValueError, match="does not fit"):
        PulsarEngine(width=48, layout=32)


def test_device_falls_back_to_eager_without_a_layout_evaluator():
    """When NO registered fused evaluator supports the device's layout,
    fuse downgrades to eager with a warning (the pre-width-64 behavior,
    now reachable only by unregistering the 64-bit evaluators)."""
    saved = {n: pum.get_backend(n)
             for n in ("words-cpu-64", "pallas-tpu-64", "ref-vertical-64")}
    for n in saved:
        pum.unregister_backend(n)
    try:
        with pytest.raises(LookupError, match="64-bit plane layout"):
            pum.select_backend(require="fused", width=48, layout=64)
        with pytest.warns(RuntimeWarning, match="runs eager"):
            dev = pum.device(width=48)
        assert not dev.config.fuse
        a = np.array([1 << 40, 5], np.uint64)
        np.testing.assert_array_equal(np.asarray(dev.asarray(a) + a),
                                      2 * a)
        # the direct engine path still refuses loudly
        with pytest.raises(ValueError, match="no registered fused"):
            PulsarEngine(width=48, fuse=True)
    finally:
        for n, s in saved.items():
            pum.register_backend(
                n, s.builder, capabilities=s.capabilities,
                max_width=s.max_width, priority=s.priority,
                available=s.available, layouts=s.layouts)


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


def test_as_device_wraps_engines_and_passes_devices_through():
    dev = pum.device(width=16)
    assert pum.as_device(dev) is dev
    eng = PulsarEngine(width=16, banks=4)
    wrapped = pum.as_device(eng)
    assert wrapped.engine is eng and wrapped.config.banks == 4
    # the characterization DB carries into the config: a twin derived
    # via wrapped.config.replace(...) prices with the SAME success rates
    assert wrapped.config.success_db is eng.db
    twin = pum.device(wrapped.config.replace(use_pulsar=False))
    assert twin.engine.db is eng.db
    with pytest.raises(TypeError):
        pum.as_device(object())


# --------------------------------------------------------------------- #
# Backend registry
# --------------------------------------------------------------------- #


def test_registry_lists_builtin_backends():
    names = pum.available_backends()
    for n in ("fast", "sim", "words-cpu", "pallas-tpu", "ref-vertical"):
        assert n in names
    assert "fast" in pum.available_backends("eager")
    assert "words-cpu" in pum.available_backends("fused")
    assert "words-cpu" not in pum.available_backends("eager")


def test_select_backend_capability_lookup():
    # On this host the word-domain evaluator wins (Pallas needs a TPU;
    # shard-words needs >1 device).
    spec = pum.select_backend(require="fused", width=32, layout=32)
    assert spec.name in ("words-cpu", "pallas-tpu", "shard-words")
    # width 64 resolves to an evaluator declaring the 64-bit layout
    spec64 = pum.select_backend(require="fused", width=64)
    assert spec64.layouts == frozenset({64})
    with pytest.raises(LookupError):
        pum.select_backend(require="no-such-capability")
    with pytest.raises(LookupError):  # layout filter: sharded is 32-only
        pum.select_backend(require="sharded", layout=64)
    with pytest.raises(KeyError, match="unknown backend"):
        pum.get_backend("nope")


def test_register_backend_is_additive_and_selectable():
    """A new evaluator registers without touching engine/compiler code:
    highest priority + available wins the capability lookup."""
    calls = []

    def builder(program, interpret=False, donate=False):
        calls.append(program)
        from repro.kernels.fused_program import build_words_pipeline
        return build_words_pipeline(program, donate=donate)

    pum.register_backend("test-words", builder, capabilities=("fused",),
                         max_width=32, priority=99)
    try:
        dev = pum.device(width=16, fuse=True)
        a = np.array([5, 6], np.uint64)
        np.testing.assert_array_equal(np.asarray(dev.asarray(a) + a),
                                      2 * a)
        assert len(calls) == 1  # our backend built the pipeline
        # Re-registering the name replaces the builder for FUTURE
        # pipelines even of identical structure: the cache is keyed on
        # the spec, so the replaced builder's pipelines can't be served.
        pum.register_backend("test-words", builder, capabilities=("fused",),
                             max_width=32, priority=99)
        np.testing.assert_array_equal(np.asarray(dev.asarray(a) + a),
                                      2 * a)
        assert len(calls) == 2  # fresh spec -> fresh compile, no stale hit
    finally:
        pum.unregister_backend("test-words")


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
    with pytest.raises(KeyError, match="unknown backend"):
        pum.device(backend="warp-drive", fuse=False)
    with pytest.raises(ValueError, match="no eager dataplane"):
        pum.device(backend="words-cpu", fuse=False)


# --------------------------------------------------------------------- #
# Deprecation shim
# --------------------------------------------------------------------- #


def test_engine_method_surface_emits_deprecation_warnings():
    """The legacy PulsarEngine op methods survive as a compat shim: same
    results, but each call warns toward repro.pum."""
    e = PulsarEngine(width=16)
    a = np.array([9, 5], np.uint64)
    b = np.array([3, 0], np.uint64)
    for name, args, want in [
            ("and_", (a, b), a & b), ("or_", (a, b), a | b),
            ("xor", (a, b), a ^ b), ("add", (a, b), a + b),
            ("sub", (a, b), a - b), ("mul", (a, b), a * b),
            ("div", (a, b), np.array([3, 0], np.uint64)),
            ("mod", (a, b), np.array([0, 0], np.uint64)),
            ("less_than", (a, b), np.zeros(2, np.uint64)),
            ("popcount", (a,), np.array([2, 2], np.uint64)),
            ("reduce_bits", (a, "or"), np.ones(2, np.uint64))]:
        with pytest.warns(DeprecationWarning, match=f"PulsarEngine.{name}"):
            got = getattr(e, name)(*args)
        np.testing.assert_array_equal(np.asarray(got, np.uint64), want,
                                      err_msg=name)
    with pytest.warns(DeprecationWarning, match="PulsarEngine.divmod"):
        q, r = e.divmod(a, np.array([2, 2], np.uint64))
    np.testing.assert_array_equal(q, a // 2)
    np.testing.assert_array_equal(r, a % 2)


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
    assert donating.engine.donate_leaves

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
