"""The bitmap-index deployment is the largest power of two of users that
one TPU v5e holds: the whole-table query's program compiles for a
described v5e at the configuration's 2^30 users and is refused at 2^31,
for want of HBM. No chip is needed; the topology is described inside a
fixture, never at import, since one process at a time may load the TPU
library."""

import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep the cache off.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile_whole_table_query(users, days, sharding):
    """The pipeline the engine builds for ``bmi_active_users``: an AND
    chain over the days and a popcount, the AND result kept as a second
    output, on ``pallas-tpu`` at width 32 (one int32 word per 32 users)."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import fused_program as fp
    ops = [fp.FusedOp("and", (0, 1))]
    for d in range(2, days):
        ops.append(fp.FusedOp("and", (days + len(ops) - 1, d)))
    ops.append(fp.FusedOp("popcount", (days + len(ops) - 1,)))
    last = days + len(ops) - 1
    program = fp.FusedProgram(width=32, n_inputs=days, ops=tuple(ops),
                              outputs=(last - 1, last))
    pipeline = fp.build_vertical_pipeline(program, use_pallas=True)
    leaf = jax.ShapeDtypeStruct((users // 32,), jnp.int32,
                                sharding=sharding)
    return jax.jit(pipeline).lower(*[leaf] * days).compile()


def _config():
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "bmi-appb-2p30.json"), encoding="utf-8") as f:
        return json.load(f)


def test_configured_users_fit_one_chip(one_chip):
    cfg = _config()
    compiled = _compile_whole_table_query(cfg["users"], cfg["days"],
                                          one_chip)
    m = compiled.memory_analysis()
    need = m.argument_size_in_bytes + m.temp_size_in_bytes \
        + m.output_size_in_bytes
    assert need < 16e9


def test_twice_the_users_do_not_fit(one_chip):
    cfg = _config()
    with pytest.raises(Exception, match="RESOURCE_EXHAUSTED"):
        _compile_whole_table_query(2 * cfg["users"], cfg["days"], one_chip)
