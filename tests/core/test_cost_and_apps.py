"""Cost model, destruction, engine + real-world kernels."""

import numpy as np
import pytest

from repro.core.chip import PulsarChip
from repro.core.cost_model import (CostModel, MICROBENCHES,
                                   throughput_elems_per_s)
from repro.core.destruction import (destroy_bank_fracdram,
                                    destroy_bank_pulsar,
                                    destroy_bank_rowclone,
                                    fracdram_destruction_cost,
                                    plan_pulsar_cover,
                                    pulsar_destruction_cost,
                                    rowclone_destruction_cost)
from repro.core.geometry import DramGeometry
import repro.pum as pum
from repro.core.profiles import MFR_H, MFR_M
from repro.core.pulsar import PulsarExecutor
from repro.core import realworld

GEOM = DramGeometry(row_bits=256, rows_per_subarray=256, subarrays_per_bank=2,
                    banks=1, predecoder_widths=(2, 2, 2, 2))


def _chip(profile=MFR_H):
    chip = PulsarChip(GEOM, profile, seed=0)
    chip.decoder = chip.decoder.__class__(GEOM, profile, None)
    return chip


# --------------------------------------------------------------------- #
# Cost model <-> executor cross-check
# --------------------------------------------------------------------- #

@pytest.mark.parametrize("m,n_rg", [(3, 4), (3, 8), (3, 16), (5, 8), (7, 16)])
def test_maj_cost_matches_executed_trace(m, n_rg):
    chip = _chip()
    chip.stats.trace = []
    x = PulsarExecutor(chip, 0, 0)
    rng = np.random.default_rng(0)
    for i in range(m):
        chip.write_row(0, 200 + i, rng.integers(
            0, 2**32, GEOM.words_per_row, dtype=np.uint64).astype(np.uint32))
    base_lat = chip.stats.latency_ns
    base_seq = chip.stats.n_ops
    x.maj(240, [200 + i for i in range(m)], n_rg)
    executed_lat = chip.stats.latency_ns - base_lat
    executed_seq = chip.stats.n_ops - base_seq
    cm = CostModel(row_bits=GEOM.row_bits)
    cost = cm.maj_op(m, n_rg, frac_supported=True)
    assert cost.n_sequences == executed_seq
    assert cost.latency_ns == pytest.approx(executed_lat, rel=1e-9)


def test_fracdram_baseline_cost_shape():
    cm = CostModel()
    c = cm.fracdram_maj3()
    # 3 copy-ins + 1 frac + 1 APA + 1 copy-out
    assert c.n_sequences == 6
    assert c.latency_ns > 0


def test_tree_nodes():
    assert CostModel.tree_nodes(64, 2) == 63
    assert CostModel.tree_nodes(2, 2) == 1
    assert CostModel.tree_nodes(5, 5) == 1
    assert CostModel.tree_nodes(64, 4) == 21
    assert CostModel.tree_nodes(1, 2) == 0


def test_maj5_full_adder_cheaper_than_maj3():
    cm = CostModel()
    fa3 = cm.full_adder(3, 8)
    fa5 = cm.full_adder(5, 8)
    assert fa5.latency_ns < fa3.latency_ns  # 4 MAJ vs 6 MAJ


def test_microbench_costs_positive_and_ordered():
    cm = CostModel()
    for name in MICROBENCHES:
        c3 = cm.microbench(name, 3, 4, width=32)
        assert c3.latency_ns > 0
    # mul is the most expensive, and/or the cheapest arithmetic-free ones.
    assert (cm.microbench("mul", 3, 4).latency_ns
            > cm.microbench("add", 3, 4).latency_ns
            > cm.microbench("and", 3, 4).latency_ns)


def test_throughput_metric():
    cm = CostModel()
    c = cm.fracdram_maj3()
    full = throughput_elems_per_s(c, 65536, 1.0)
    half = throughput_elems_per_s(c, 65536, 0.5)
    assert full == pytest.approx(2 * half)


# --------------------------------------------------------------------- #
# Content destruction (Fig 19)
# --------------------------------------------------------------------- #

def test_pulsar_destruction_overwrites_everything():
    chip = _chip()
    rng = np.random.default_rng(3)
    for r in range(GEOM.rows_per_bank):
        chip.banks[0, r] = rng.integers(0, 2**32, GEOM.words_per_row,
                                        dtype=np.uint64).astype(np.uint32)
    rep = destroy_bank_pulsar(chip, 0, pattern=0)
    assert rep.rows_destroyed == GEOM.rows_per_bank
    assert (chip.banks[0] == 0).all()
    assert rep.latency_ns > 0


def test_destruction_speedup_ordering():
    """PULSAR > FracDRAM > RowClone in destruction speed (Fig 19)."""
    chip_p, chip_r, chip_f = _chip(), _chip(), _chip()
    rp = destroy_bank_pulsar(chip_p, 0)
    rr = destroy_bank_rowclone(chip_r, 0)
    rf = destroy_bank_fracdram(chip_f, 0)
    assert rp.latency_ns < rf.latency_ns < rr.latency_ns * 1.5
    assert rp.latency_ns < rr.latency_ns


def test_destruction_cost_model_scales():
    cm = CostModel(row_bits=65536)
    n_sa, rows_sa = 16, 512
    n_rows = n_sa * rows_sa
    p32 = pulsar_destruction_cost(cm, rows_sa, n_sa, 32)
    p4 = pulsar_destruction_cost(cm, rows_sa, n_sa, 4)
    rc = rowclone_destruction_cost(cm, n_rows)
    fr = fracdram_destruction_cost(cm, n_rows)
    assert p32.latency_ns < p4.latency_ns < rc.latency_ns
    # Paper: PULSAR up to 20.87x vs RowClone, 7.55x vs FracDRAM.
    speedup_rc = rc.latency_ns / p32.latency_ns
    speedup_fr = fr.latency_ns / p32.latency_ns
    assert 10 < speedup_rc < 40
    assert 4 < speedup_fr < 16


def test_plan_pulsar_cover_counts():
    blocks = plan_pulsar_cover(512, 16, 32)
    assert sum(blocks) == 512 * 16
    assert max(blocks) == 32


# --------------------------------------------------------------------- #
# Engine + real-world kernels (Fig 20)
# --------------------------------------------------------------------- #

def test_engine_dataplane_matches_numpy():
    eng = pum.device(mfr="M", width=16, backend="fast", fuse=False)
    rng = np.random.default_rng(0)
    a = rng.integers(0, 2**16, 512, dtype=np.uint64)
    b = rng.integers(1, 2**16, 512, dtype=np.uint64)
    x = eng.asarray(a)
    np.testing.assert_array_equal(np.asarray(x & b), a & b)
    np.testing.assert_array_equal(np.asarray(x + b), (a + b) & 0xFFFF)
    np.testing.assert_array_equal(np.asarray(x * b), (a * b) & 0xFFFF)
    np.testing.assert_array_equal(np.asarray(x // b), a // b)
    assert eng.stats.latency_ns > 0
    assert 0 < eng.stats.lane_efficiency <= 1


def test_engine_sim_backend_small():
    eng = pum.device(mfr="H", width=8, backend="sim")
    rng = np.random.default_rng(1)
    n = eng.engine._alu.words * 32
    a = rng.integers(0, 256, n, dtype=np.uint64)
    b = rng.integers(0, 256, n, dtype=np.uint64)
    x = eng.asarray(a)
    np.testing.assert_array_equal(np.asarray(x & b), a & b)
    np.testing.assert_array_equal(np.asarray(x + b), (a + b) & 0xFF)


def test_engine_pulsar_beats_fracdram_on_add():
    pulsar = pum.device(mfr="M", width=32, use_pulsar=True, fuse=False)
    frac = pum.device(mfr="M", width=32, use_pulsar=False, fuse=False)
    a = np.arange(65536, dtype=np.uint64)
    pulsar.asarray(a) + a
    frac.asarray(a) + a
    t_p = pulsar.stats.latency_ns / pulsar.stats.lane_efficiency
    t_f = frac.stats.latency_ns / frac.stats.lane_efficiency
    assert t_p < t_f  # the paper's headline performance claim


FUSE = [False, True]  # every app kernel runs on the fused path too (PR 3)


@pytest.mark.parametrize("fuse", FUSE)
def test_bmi(fuse):
    eng = pum.device(mfr="M", fuse=fuse)
    rng = np.random.default_rng(2)
    bitmaps = rng.integers(0, 2**64, (30, 128), dtype=np.uint64)
    got, pum_ms, cpu_ms = realworld.bmi_active_users(eng, bitmaps)
    assert pum_ms > 0 and cpu_ms >= 0


@pytest.mark.parametrize("fuse", FUSE)
def test_bitweaving(fuse):
    eng = pum.device(mfr="M", width=16, fuse=fuse)
    rng = np.random.default_rng(3)
    col = rng.integers(0, 1000, 4096, dtype=np.uint64)
    got, pum_ms, _ = realworld.bitweaving_scan(eng, col, 100, 500)
    assert got == int(((col >= 100) & (col <= 500)).sum())


@pytest.mark.parametrize("fuse", FUSE)
def test_bitweaving_boundary_ranges(fuse):
    """c1 == 0 must not underflow the strict-compare sentinel (2**64-1
    wrap) and a c2 at the width max must not overflow it out of width —
    both bounds short-circuit to trivially-true predicates."""
    eng = pum.device(mfr="M", width=16, fuse=fuse)
    rng = np.random.default_rng(9)
    col = rng.integers(0, 1 << 16, 2048, dtype=np.uint64)
    got, _, _ = realworld.bitweaving_scan(eng, col, 0, 500)
    assert got == int((col <= 500).sum())
    got, _, _ = realworld.bitweaving_scan(eng, col, 100, (1 << 16) - 1)
    assert got == int((col >= 100).sum())
    got, _, _ = realworld.bitweaving_scan(eng, col, 0, (1 << 16) - 1)
    assert got == col.size


@pytest.mark.parametrize("fuse", FUSE)
def test_triangle_count(fuse):
    eng = pum.device(mfr="M", fuse=fuse)
    rng = np.random.default_rng(4)
    n = 24
    adj = np.triu((rng.random((n, n)) < 0.3).astype(np.uint8), 1)
    adj = adj + adj.T
    got, pum_ms, _ = realworld.triangle_count(eng, adj)
    assert pum_ms > 0


@pytest.mark.parametrize("fuse", FUSE)
def test_knn(fuse):
    eng = pum.device(mfr="M", width=24, fuse=fuse)
    rng = np.random.default_rng(5)
    q = rng.integers(0, 256, (4, 16), dtype=np.int64)
    r = rng.integers(0, 256, (64, 16), dtype=np.int64)
    got, pum_ms, _ = realworld.knn_distances(eng, q, r)
    assert got.shape == (4,)


@pytest.mark.parametrize("fuse", FUSE)
def test_image_segmentation(fuse):
    eng = pum.device(mfr="M", width=16, fuse=fuse)
    rng = np.random.default_rng(6)
    img = rng.integers(0, 256, (32, 32), dtype=np.int64)
    colors = np.array([10, 90, 170, 250])
    labels, pum_ms, _ = realworld.image_segmentation(eng, img, colors)
    assert labels.max() <= 3


@pytest.mark.parametrize("fuse", FUSE)
def test_xnor_conv_cost_positive(fuse):
    eng = pum.device(mfr="M", fuse=fuse)
    ms = realworld.xnor_conv_cost(eng, 128, 128, 3, 3, 16, 16)
    assert ms > 0


def test_app_kernels_fused_matches_eager_results_and_stats():
    """The fuse=True routing (default for fig20/examples) must leave every
    kernel's result AND its cost-plane charges bit-identical to eager —
    the set intersections exercise the raw packed-bitmap path, KNN the
    fused mul, image segmentation the fused compare network."""
    rng = np.random.default_rng(7)

    def pair(**kw):
        return (pum.device(mfr="M", fuse=False, **kw),
                pum.device(mfr="M", fuse=True, **kw))

    bitmaps = rng.integers(0, 2**64, (12, 96), dtype=np.uint64)
    e, f = pair()
    r_e = realworld.bmi_active_users(e, bitmaps)
    r_f = realworld.bmi_active_users(f, bitmaps)
    assert r_e[0] == r_f[0] and r_e[1] == r_f[1] and e.stats == f.stats

    adj = np.triu((rng.random((16, 16)) < 0.4).astype(np.uint8), 1)
    adj = adj + adj.T
    e, f = pair()
    assert (realworld.kclique_star(e, adj, [(0, 1, 2), (3, 4, 5)])[0]
            == realworld.kclique_star(f, adj, [(0, 1, 2), (3, 4, 5)])[0])
    assert e.stats == f.stats

    q = rng.integers(0, 256, (3, 8), dtype=np.int64)
    r = rng.integers(0, 256, (32, 8), dtype=np.int64)
    e, f = pair(width=24)
    np.testing.assert_array_equal(realworld.knn_distances(e, q, r)[0],
                                  realworld.knn_distances(f, q, r)[0])
    assert e.stats == f.stats

    img = rng.integers(0, 256, (16, 16), dtype=np.int64)
    colors = np.array([15, 120, 240])
    e, f = pair(width=16)
    np.testing.assert_array_equal(
        realworld.image_segmentation(e, img, colors)[0],
        realworld.image_segmentation(f, img, colors)[0])
    assert e.stats == f.stats
