"""The fused dataplane's kernels compile for a described TPU v5e.

No chip is needed: the TPU compiler compiles for a described ``v5e:2x2``
topology and refuses what the chip would refuse, such as a block over the
kernel's scoped VMEM. Nothing runs, so these tests check compilation only.
The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every worker imports this
file.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

import repro.core.engine as engine_mod
import repro.pum as pum
from repro.kernels import fused_program as fp
from repro.kernels.bit_transpose import bit_transpose32

WORDS = 1 << 16  # words per plane of each compiled program


@pytest.fixture(scope="module")
def four_chips():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache off.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield list(topo.devices)
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(four_chips):
    return SingleDeviceSharding(four_chips[0])


def _compile_program(program, sharding):
    x = jax.ShapeDtypeStruct((program.n_inputs, program.width, WORDS),
                             jnp.int32, sharding=sharding)
    return jax.jit(lambda v: fp.run_program_pallas(program, v)) \
        .lower(x).compile()


def _bmi_program(days: int, reduced: bool = False) -> fp.FusedProgram:
    ops = [fp.FusedOp("and", (0, 1))]
    for d in range(2, days):
        ops.append(fp.FusedOp("and", (days + len(ops) - 1, d)))
    ops.append(fp.FusedOp("popcount", (days + len(ops) - 1,)))
    last = days + len(ops) - 1
    return fp.FusedProgram(width=32, n_inputs=days, ops=tuple(ops),
                           outputs=(last,),
                           reduced=(last,) if reduced else ())


_LANES = jax.ShapeDtypeStruct((), jnp.int32)  # a flush's real lane count


def _largest_flushed_program(monkeypatch, keep_every_result: bool
                             ) -> fp.FusedProgram:
    """Record far past the VMEM bound on a device with no op or memory
    bound and return the largest program the engine hands a pipeline:
    one long AND chain (many leaves, one output), or independent XORs
    whose results all stay live (many leaves and many outputs)."""
    programs = []
    real = engine_mod.get_pipeline

    def spy(program, **kw):
        programs.append(program)
        return real(program, **kw)

    rng = np.random.default_rng(0)
    leaves = rng.integers(0, 1 << 32, (600, 32), dtype=np.uint64)
    dev = pum.device(width=32, flush_threshold=None,
                     flush_memory_bytes=None)
    monkeypatch.setattr(engine_mod, "get_pipeline", spy)
    if keep_every_result:
        kept = [dev.asarray(leaves[2 * i]) ^ leaves[2 * i + 1]
                for i in range(300)]
    else:
        acc = dev.asarray(leaves[0])
        for leaf in leaves[1:]:
            acc = acc & leaf
        kept = [acc]
    dev.flush()
    monkeypatch.setattr(engine_mod, "get_pipeline", real)
    del kept
    assert len(programs) > 1  # the VMEM bound split the recording
    return max(programs, key=lambda p: p.n_inputs + len(p.outputs))


def test_bit_transpose_compiles(one_chip):
    x = jax.ShapeDtypeStruct((32, WORDS), jnp.int32, sharding=one_chip)
    compiled = jax.jit(bit_transpose32).lower(x).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_bmi_program_compiles(one_chip):
    """The Appendix B query: 30 daily bitmaps, 29 ANDs and a popcount."""
    compiled = _compile_program(_bmi_program(30), one_chip)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("keep_every_result", [False, True],
                         ids=["and-chain", "all-outputs-live"])
def test_largest_admitted_block_compiles(one_chip, monkeypatch,
                                         keep_every_result):
    """The largest block the engine's auto-flush admits compiles; at the
    scoped default VMEM limit, 64 leaves x 32 planes already did not."""
    program = _largest_flushed_program(monkeypatch, keep_every_result)
    n_values = program.n_inputs + len(program.outputs)
    assert program.n_inputs >= 64 or len(program.outputs) >= 64
    assert fp.block_bytes(n_values, 32) > 16 << 20
    _compile_program(program, one_chip)


def test_mul32_compiles(one_chip):
    program = fp.FusedProgram(width=32, n_inputs=2,
                              ops=(fp.FusedOp("mul", (0, 1)),),
                              outputs=(2,))
    _compile_program(program, one_chip)


def test_summed_bmi_pipeline_compiles(one_chip):
    """The Appendix B query as the engine now runs it on one chip: the
    ``pallas-tpu`` pipeline with its popcount output summed on the
    device returns one uint32 partial, not the lanes."""
    program = _bmi_program(30, reduced=True)
    pipeline = fp.build_vertical_pipeline(program, use_pallas=True)
    leaf = jax.ShapeDtypeStruct((WORDS,), jnp.int32, sharding=one_chip)
    compiled = jax.jit(pipeline).lower(*[leaf] * 30, lanes=_LANES).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().output_size_in_bytes < 4096


def test_summed_sharded_pipeline_keeps_partials_on_each_chip(four_chips):
    """``shard-words`` over four described chips sums each chip's block
    of the popcount on that chip: no collective, and the partials stay
    split over the four."""
    from repro.distributed.sharding import words_placement
    program = _bmi_program(30, reduced=True)
    placement = words_placement(four_chips)
    leaf = jax.ShapeDtypeStruct((4 * WORDS,), jnp.int32,
                                sharding=placement.sharding)
    summed = fp.with_sums(program, fp.words_fn(program),
                          devices=placement.devices,
                          sharding=placement.sharding)
    compiled = jax.jit(summed).lower(*[leaf] * 30, lanes=_LANES).compile()
    text = compiled.as_text()
    for collective in ("all-gather", "all-reduce", "all-to-all",
                       "collective-permute", "reduce-scatter"):
        assert collective not in text
    (out,) = compiled.output_shardings
    assert out == placement.sharding
