"""The four-chip bitmap-index cell (``bmi-full-4chip``, configuration
``bmi-appb-2p32``): its data, reference and control at a tiny size, how
the benchmark loads it, the readers of the placement and sharded
evaluator metrics on hand-built windows, and the sharded whole-table
program compiled for a described v5e 2x2 host at the configuration's
2^32 users. No chip is needed; the topology is described inside a
fixture, never at import, since one process at a time may load the TPU
library."""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from chipbench import harness, loadgen  # noqa: E402
from repro.telemetry import CounterBank  # noqa: E402

BENCH = harness.load_bench(ROOT)
TINY = {"users": 1 << 16, "shard_users": 1 << 12, "tenants": 16}


def _config(**sizes):
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "bmi-appb-2p32.json"), encoding="utf-8") as f:
        cfg = json.load(f)
    cfg.update(sizes)
    return cfg


@pytest.fixture(scope="module")
def module():
    return harness.load_module(os.path.join(ROOT, "chipbench", "configs",
                                            "bmi-appb-2p32.py"))


@pytest.fixture(scope="module")
def tiny(module):
    cfg = _config(**TINY)
    module.check(cfg)
    return cfg, module.make_data(cfg, loadgen.rng(2**31 + 7, loadgen.DATA))


def test_data_is_made_from_the_seed(module, tiny):
    cfg, data = tiny
    assert data["days"].shape == (cfg["days"], cfg["users"] // 64)
    again = module.make_data(cfg, loadgen.rng(2**31 + 7, loadgen.DATA))
    np.testing.assert_array_equal(again["days"], data["days"])
    other = module.make_data(cfg, loadgen.rng(2**31 + 8, loadgen.DATA))
    assert not np.array_equal(other["days"], data["days"])


def test_reference_is_an_independent_bit_count(module, tiny):
    cfg, data = tiny
    bits = np.unpackbits(data["days"].view(np.uint8), axis=1,
                         bitorder="little")
    every = bits.all(axis=0)
    per_tenant = every.reshape(cfg["tenants"], -1).sum(axis=1)
    queries = [{}] + [{"tenant": t} for t in range(cfg["tenants"])]
    want = [int(every.sum())] + [int(x) for x in per_tenant]
    assert module.reference(cfg, data, queries) == want
    # About an eighth of the users is active every day.
    assert 0.1 < want[0] / cfg["users"] < 0.15


def test_control_differs_from_the_reference(module, tiny):
    cfg, data = tiny
    queries = [{}] * 3
    refs = module.reference(cfg, data, queries)
    checks = harness.compare(module.control(cfg, data, queries), refs)
    assert not harness.passes(checks)


def test_the_bad_size_is_refused(module):
    with pytest.raises(ValueError):
        module.check(_config(users=(1 << 16) + 32))
    with pytest.raises(ValueError):
        module.check(_config(tenants=3))


def test_the_cell_takes_four_chips_and_its_own_roofline():
    cell = harness.load_cell(BENCH, "bmi-full-4chip", ROOT)
    assert cell.chips == 4
    assert cell.config["users"] == 1 << 32 and cell.config["days"] == 30
    per_layer = {m["name"] for m in cell.per_layer}
    assert "evaluator_roofline" not in per_layer
    assert {"sharded_roofline", "placed_MB", "place_ms"} <= per_layer
    assert {m["name"] for m in cell.end_to_end} == {
        "query_ms_p50", "data_rate", "setup_s"}
    # The leaf cache holds every day's wire, and one query stays one
    # flush: the engine's estimate (4 bytes a lane for each leaf and op
    # of the AND chain and popcount) stays under the auto-flush bound.
    cfg, dev = cell.config, cell.config["device"]
    lanes = cfg["users"] // 32
    assert dev["leaf_cache_bytes"] > cfg["days"] * lanes * 4
    assert dev["flush_memory_bytes"] > 4 * lanes * 2 * cfg["days"]


def _window(spans=None, counters=None, trace=None, n_answered=2,
            nbytes=10**9):
    queries = [harness.Query({}, answer=1, nbytes=nbytes)
               for _ in range(n_answered)]
    return harness.Window(queries, 1.0, 1.0, spans=spans, counters=counters,
                          trace=trace, peak={"hbm_bytes_per_s": 1e12})


def test_placed_reader_reads_the_counter_per_query():
    read = harness.reader("layers", "placed_MB")
    bank = CounterBank()
    assert read(_window(counters=bank)) is None     # a program without it
    bank.inc("engine.leaf_bytes_placed", 0)
    assert read(_window(counters=bank)) == 0.0      # resident: 0 placed
    bank.inc("engine.leaf_bytes_placed", 6e6)
    assert read(_window(counters=bank)) == pytest.approx(3.0)
    assert read(_window(counters=None)) is None     # an untraced run
    assert read(_window(counters=bank, n_answered=0)) is None


def test_place_reader_sums_the_place_spans_per_query():
    read = harness.reader("layers", "place_ms")
    spans = [("flush.place", 0, 3_000_000, {"bytes": 0, "devices": 4}),
             ("flush.dispatch", 3_000_000, 4_000_000, {}),
             ("flush.place", 10_000_000, 11_000_000, {})]
    assert read(_window(spans=spans)) == pytest.approx((3.0 + 1.0) / 2)
    assert read(_window(spans=None)) is None
    assert read(_window(spans=spans[1:2])) is None
    assert read(_window(spans=spans, n_answered=0)) is None


def test_sharded_roofline_divides_by_the_chips_a_flush_spans():
    read = harness.reader("layers", "sharded_roofline")
    bank = CounterBank()
    trace = {"busy_s": 0.5, "window_s": 1.0}
    assert read(_window(counters=bank, trace=trace)) is None  # no flush
    for n in (4, 4, 1):
        bank.observe("engine.flush_devices", n)
    # 2 GB over 4 chips at 1e12 B/s is 0.5 ms; 0.5 s busy.
    assert read(_window(counters=bank, trace=trace)) == pytest.approx(
        100.0 * (2e9 / 4e12) / 0.5)
    assert read(_window(counters=bank, trace=None)) is None
    assert read(_window(counters=bank,
                        trace={"busy_s": 0.0, "window_s": 1.0})) is None
    host = CounterBank()
    host.observe("engine.flush_devices", 0)  # flushes ran in host NumPy
    assert read(_window(counters=host, trace=trace)) is None


@pytest.fixture(scope="module")
def four_chips():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
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
    yield list(topo.devices)
    jax.config.update("jax_enable_compilation_cache", was)


def test_sharded_whole_table_query_fits_each_chip(four_chips):
    """The program ``shard-words`` runs for ``bmi_active_users`` at 2^32
    users (the AND chain over 30 days and its popcount, the AND result a
    second output), each leaf sharded under the pipeline's placement:
    every chip holds its quarter and the outputs stay sharded."""
    import jax
    import jax.numpy as jnp
    from repro.distributed.sharding import words_placement
    from repro.kernels import fused_program as fp
    cfg = _config()
    days = cfg["days"]
    ops = [fp.FusedOp("and", (0, 1))]
    for d in range(2, days):
        ops.append(fp.FusedOp("and", (days + len(ops) - 1, d)))
    ops.append(fp.FusedOp("popcount", (days + len(ops) - 1,)))
    last = days + len(ops) - 1
    program = fp.FusedProgram(width=32, n_inputs=days, ops=tuple(ops),
                              outputs=(last - 1, last))
    placement = words_placement(four_chips)
    lanes = cfg["users"] // 32
    assert lanes % placement.multiple == 0  # no pad copy at this size
    leaf = jax.ShapeDtypeStruct((lanes,), jnp.int32,
                                sharding=placement.sharding)
    compiled = jax.jit(fp.words_fn(program)).lower(*[leaf] * days).compile()
    m = compiled.memory_analysis()
    per_chip = lanes * 4 // len(four_chips)  # one leaf's quarter
    assert m.argument_size_in_bytes == days * per_chip
    assert m.output_size_in_bytes < 1.01 * 2 * per_chip  # still sharded
    need = m.argument_size_in_bytes + m.temp_size_in_bytes \
        + m.output_size_in_bytes
    assert need < 16e9
    assert "all-gather" not in compiled.as_text()  # no cross-chip traffic
