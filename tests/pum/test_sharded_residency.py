"""Fused evaluator selection by size, and leaves kept resident on the
devices under their pipeline's placement.

* The size rule (``backends.shards_over_devices``): a flush whose leaves
  outgrow one chip of a multi-device host goes to ``shard-words``; every
  other flush keeps the platform's choice.
* Residency: a cached leaf commits once, at the flush that seeds the
  cache, under the placement of the pipeline that reads it, and later
  flushes place nothing (``engine.leaf_bytes_placed``, ``flush.place``).
  On four forced host devices the bitmap-index query's leaves and outputs
  are split over all four (a subprocess: the flag must be set before JAX
  starts, and the test process keeps its one CPU device).
* The query's total is summed on each device: its AND bitmap is never an
  output, and one uint32 partial a device crosses back.
"""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import repro.pum as pum
from repro import backends
from repro.kernels import fused_program as fp

GIB = 1 << 30
V5E_LIMIT = int(15.75 * GIB)  # one v5e chip's bytes_limit


@pytest.mark.parametrize("devices, leaf_bytes, chip_bytes, sharded", [
    (4, 15 * GIB, V5E_LIMIT, True),        # 2^32 users: 16.1 GB of leaves
    (4, int(7.5 * GIB), V5E_LIMIT, True),  # 2^31 users: the compile refuses
    (4, int(3.75 * GIB), V5E_LIMIT, False),  # 2^30 users fit one chip
    (4, 4096, V5E_LIMIT, False),           # a small flush
    (2, 8 * GIB, V5E_LIMIT, True),
    (1, 15 * GIB, V5E_LIMIT, False),       # one device: nowhere to shard
    (1, 4096, V5E_LIMIT, False),
    (4, 15 * GIB, None, False),            # a host reporting no limit
    (8, 15 * GIB, None, False),
])
def test_size_rule(devices, leaf_bytes, chip_bytes, sharded):
    assert backends.shards_over_devices(devices, leaf_bytes,
                                        chip_bytes) is sharded


def _and_program(n_leaves=3):
    ops = [fp.FusedOp("and", (0, 1))]
    for i in range(2, n_leaves):
        ops.append(fp.FusedOp("and", (n_leaves + len(ops) - 1, i)))
    return fp.FusedProgram(width=32, n_inputs=n_leaves, ops=tuple(ops),
                           outputs=(n_leaves + len(ops) - 1,))


@pytest.mark.parametrize("memory, leaf_bytes, want", [
    ((4, V5E_LIMIT), 15 * GIB, "shard-words"),
    ((4, V5E_LIMIT), int(3.75 * GIB), "words-cpu"),
    ((1, V5E_LIMIT), 15 * GIB, "words-cpu"),
    ((1, None), 15 * GIB, "words-cpu"),    # this CPU host
    ((4, V5E_LIMIT), 0, "words-cpu"),      # leaf bytes not given
])
def test_get_pipeline_applies_the_size_rule(monkeypatch, memory,
                                            leaf_bytes, want):
    """On this one-CPU host the platform's choice is ``words-cpu``; the
    device memory the rule reads is stood in for, not the choice."""
    monkeypatch.setattr(backends, "device_memory", lambda: memory)
    fp._cached_pipeline.cache_clear()
    pipeline = fp.get_pipeline(_and_program(), leaf_bytes=leaf_bytes)
    assert hasattr(pipeline, "placement") is (want == "shard-words")
    # A backend named by the caller is never overruled.
    named = fp.get_pipeline(_and_program(), backend="words-cpu",
                            leaf_bytes=leaf_bytes)
    assert not hasattr(named, "placement")


def _bmi(dev, days):
    acc = dev.asarray(days[0])
    for d in range(1, days.shape[0]):
        acc = acc & days[d]
    return int(acc.popcount(width=64).to_numpy().sum())


def test_one_device_commits_at_the_seeding_flush(monkeypatch):
    """One device, jitted word pipeline: the flush that seeds the leaf
    cache places the leaves and commits them; the next places nothing.
    The NumPy short-circuit places nothing at all."""
    monkeypatch.setattr(fp, "_NP_CUTOFF_WIRE_OPS", 1 << 10)  # pin jitted
    rng = np.random.default_rng(17)
    days = rng.integers(0, 1 << 64, (4, 8192), dtype=np.uint64)
    want = int(np.bitwise_count(np.bitwise_and.reduce(days, axis=0)).sum())
    dev = pum.device(width=32, fuse=True)
    placed = []
    with pum.profile(dev) as tr:
        for _ in range(2):
            assert _bmi(dev, days) == want
            placed.append(dev.counters["engine.leaf_bytes_placed"])
    assert placed == [days.nbytes, days.nbytes]  # the second adds 0
    args = [a for name, *_, a in tr.events if name == "flush.place"]
    assert [a["bytes"] for a in args] == [days.nbytes, 0]
    assert [a["devices"] for a in args] == [1, 1]
    cache = dev.engine._leaf_cache
    assert all(e.dev is not None for e in cache._entries.values())
    dev.close()

    monkeypatch.setattr(fp, "_NP_CUTOFF_WIRE_OPS", 1 << 40)  # pin NumPy
    dev = pum.device(width=32, fuse=True)
    with pum.profile(dev):
        assert _bmi(dev, days) == want
    assert dev.counters["engine.leaf_bytes_placed"] == 0
    dev.close()


def test_four_devices_keep_the_sharded_leaves_resident():
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = \\
            "--xla_force_host_platform_device_count=4"
        import numpy as np
        import jax
        assert len(jax.devices()) == 4
        import repro.pum as pum
        from repro.core import realworld

        rng = np.random.default_rng(23)
        days = rng.integers(0, 1 << 64, (6, 4096), dtype=np.uint64)
        want = int(np.bitwise_count(
            np.bitwise_and.reduce(days, axis=0)).sum())
        dev = pum.device(width=32, leaf_cache_bytes=1 << 26)
        placed, place = [], []
        for _ in range(2):
            with pum.profile(dev) as tr:
                got, _, _ = realworld.bmi_active_users(dev, days,
                                                       verify=False)
            assert got == want, (got, want)
            c = dev.counters
            assert c["engine.flushes"] == 1
            assert c["engine.sums.device"] == 1  # once a query
            assert "engine.sums.host" not in c
            h = c.histogram("engine.flush_devices")
            assert h["min"] == h["max"] == 4  # outputs on all four
            placed.append(c["engine.leaf_bytes_placed"])
            place += [a for n, *_, a in tr.events if n == "flush.place"]
            spans = {n: a for n, *_, a in tr.events}
            assert spans["flush.materialize"]["n_outputs"] == 1  # no AND
            # 128 uint32 partials a device cross back, not 2^13 lanes.
            assert spans["flush.fetch"]["bytes"] == 4 * 128 * 4
            c.clear()

        assert placed == [days.nbytes, 0], placed
        assert [a["devices"] for a in place] == [4, 4]
        assert [a["bytes"] for a in place] == [days.nbytes, 0]
        entries = list(dev.engine._leaf_cache._entries.values())
        assert len(entries) == days.shape[0]
        for e in entries:
            assert len(e.dev.sharding.device_set) == 4
            assert not e.dev.sharding.is_fully_replicated

        # An uncached leaf (a strided view: its snapshot is private, so
        # the cache never sees it) is still placed, and counted.
        other = rng.integers(0, 1 << 64, 8192, dtype=np.uint64)[::2]
        with pum.profile(dev):
            acc = dev.asarray(days[0]) & days[1]
            got = (acc & other).to_numpy()
        np.testing.assert_array_equal(got, days[0] & days[1] & other)
        assert dev.counters["engine.leaf_bytes_placed"] == other.nbytes

        # Lanes past a multiple of the tile: the padding never counts.
        odd = rng.integers(0, 1 << 64, (6, 1000), dtype=np.uint64)
        odd[:, -1] = 2**64 - 1
        got, _, _ = realworld.bmi_active_users(dev, odd, verify=False)
        assert got == int(np.bitwise_count(
            np.bitwise_and.reduce(odd, axis=0)).sum())
        dev.close()
        print("OK")
    """)
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(repo, "src")
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, \
        f"STDOUT:{proc.stdout}\nSTDERR:{proc.stderr}"
    assert "OK" in proc.stdout
