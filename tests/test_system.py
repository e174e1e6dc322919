"""End-to-end system behaviour tests (cross-layer integration)."""

import numpy as np

from repro.core import (MFR_H, MFR_M, DramGeometry, PulsarChip,
                        PulsarEngine, PulsarExecutor)
from repro.core.alu import BitSerialAlu
from repro.core.charact import default_db
from repro.pum import EngineConfig


def test_public_api_surface():
    import repro.core as core
    for name in core.__all__:
        assert hasattr(core, name), name


def test_paper_headline_claims_hold_in_sim():
    """The three headline claims, end to end on the shipped defaults:
    1) up to 32 simultaneous rows (Mfr H), 16 (Mfr M);
    2) replication raises MAJ3 success (FracDRAM -> PULSAR);
    3) PULSAR-configured engine is never slower than the FracDRAM-configured
       engine on any of the seven microbenchmarks (paper Fig 17)."""
    geom = DramGeometry(row_bits=256, rows_per_subarray=512,
                        subarrays_per_bank=2, banks=1)
    for profile, max_rows in ((MFR_H, 32), (MFR_M, 16)):
        chip = PulsarChip(geom, profile, seed=0)
        chip.decoder = chip.decoder.__class__(geom, profile, None)
        x = PulsarExecutor(chip, 0, 0)
        assert x.max_n_rg() == max_rows
    db = default_db()
    assert db.mean("H", 3, 32) > db.mean("H", 3, 4) + 0.1
    pulsar = PulsarEngine(EngineConfig(mfr="M", use_pulsar=True,
                                       fuse=False))
    frac = PulsarEngine(EngineConfig(mfr="M", use_pulsar=False,
                                     fuse=False))
    for kind, planes in (("reduce_and", 64), ("reduce_xor", 64),
                         ("add", None), ("mul", None), ("div", None)):
        _, _, sr_p, c_p = pulsar._cfg_for(kind, 32, planes)
        _, _, sr_f, c_f = frac._cfg_for(kind, 32, planes)
        assert c_p.latency_ns / sr_p <= c_f.latency_ns / sr_f * 1.0001, kind


def test_full_stack_compute_pipeline():
    """Host ints -> vertical layout -> staged MAJ programs on the chip ->
    arithmetic -> read back, with latency/energy accounted."""
    geom = DramGeometry(row_bits=128, rows_per_subarray=256,
                        subarrays_per_bank=1, banks=1,
                        predecoder_widths=(2, 2, 2, 2))
    chip = PulsarChip(geom, MFR_H, seed=0)
    chip.decoder = chip.decoder.__class__(geom, MFR_H, None)
    alu = BitSerialAlu(PulsarExecutor(chip, 0, 0), width=8)
    rng = np.random.default_rng(0)
    a = rng.integers(0, 100, 128, dtype=np.uint64)
    b = rng.integers(1, 100, 128, dtype=np.uint64)
    va, vb = alu.load(a), alu.load(b)
    s = alu.add(va, vb)
    m = alu.mul(va, vb)
    np.testing.assert_array_equal(alu.store(s), (a + b) & 0xFF)
    np.testing.assert_array_equal(alu.store(m), (a * b) & 0xFF)
    assert chip.stats.latency_ns > 0
    assert chip.stats.energy_j > 0
    assert chip.stats.n_acts > 100  # real command traffic happened
