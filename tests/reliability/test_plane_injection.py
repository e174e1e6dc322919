"""ReliabilityPlane: config validation, fault injection mechanics, the
vote/retry/escalate loop, and the end-to-end acceptance scenario — a real
application kernel stays bit-exact under injected variation."""

import numpy as np
import pytest

from repro import pum
from repro.core.profiles import PROFILES
from repro.core.realworld import bitweaving_scan
from repro.reliability import (FaultInjector, ReliabilityConfig,
                               ReliabilityPlane, calibrate, majority_vote)
from repro.reliability.plane import ReliabilityMap

PV_M = PROFILES["M"].process_variation


def tiny_map(**kw):
    args = dict(banks=4, n_subarrays=4, n_columns=64, n_patterns=4, seed=13)
    args.update(kw)
    return calibrate("M", **args)


# --------------------------------------------------------------------- #
# Config / plane construction


def test_reliability_config_validation():
    with pytest.raises(ValueError):
        ReliabilityConfig(votes=2)
    with pytest.raises(ValueError):
        ReliabilityConfig(votes=0)
    with pytest.raises(ValueError):
        ReliabilityConfig(max_attempts=0)
    with pytest.raises(ValueError):
        ReliabilityConfig(min_margin=0)
    with pytest.raises(ValueError):
        ReliabilityConfig(target_success=0.0)
    with pytest.raises(ValueError):
        ReliabilityConfig(flip_scale=-1.0)


def test_plane_requires_matching_map():
    m = tiny_map()
    with pytest.raises(ValueError, match="manufacturer"):
        ReliabilityPlane(ReliabilityConfig(map=m), mfr="H", counters=None)
    with pytest.raises(ValueError, match="must be a ReliabilityMap"):
        ReliabilityPlane(ReliabilityConfig(), mfr="M", counters=None)
    with pytest.raises(TypeError):
        ReliabilityPlane(object(), mfr="M", counters=None)


def test_inject_requires_fused_device():
    m = tiny_map()
    cfg = ReliabilityConfig(map=m, inject=True)
    with pytest.raises(ValueError, match="fuse"):
        pum.Device(mfr="M", banks=4, fuse=False, reliability=cfg)


def test_plane_loads_map_from_path(tmp_path):
    m = tiny_map()
    p = tmp_path / "m.npz"
    m.save(p)
    plane = ReliabilityPlane(ReliabilityConfig(map=str(p)), mfr="M",
                             counters=None)
    np.testing.assert_array_equal(plane.map.flip_p, m.flip_p)


# --------------------------------------------------------------------- #
# Vote + injector mechanics


def test_majority_vote_hand_built():
    reps = np.array([[0b0110, 0b0010, 0b0010]], np.uint64).reshape(3, 1)
    maj, corrected, weak = majority_vote(reps, width=4, min_margin=2)
    # Bit 2 disagrees 1-vs-2: margin |2*1-3| = 1 < 2 -> weak (and counted
    # as corrected, since a minority was outvoted).
    assert maj[0] == 0b0010
    assert corrected == 1 and weak == 1
    maj5, c5, w5 = majority_vote(np.array([[6, 2, 2, 2, 2]], np.uint64
                                          ).reshape(5, 1), 4, 2)
    assert maj5[0] == 2 and c5 == 1 and w5 == 0  # margin 3 at R=5: strong


def test_majority_vote_unanimous():
    reps = np.full((3, 8), 0xAB, np.uint64)
    maj, corrected, weak = majority_vote(reps, width=8, min_margin=2)
    np.testing.assert_array_equal(maj, reps[0])
    assert corrected == 0 and weak == 0


def test_fault_injector_lane_tiling_and_determinism():
    m = tiny_map(process_variation=PV_M * 4)
    idx = m.config_index(3, 4)
    inj = FaultInjector(m, idx, width=16, n_ops=2, steer=True)
    n = m.n_columns * 3 + 7  # spans four homes, last partial
    p = inj.lane_probs(n)
    assert p.shape == (n,) and (p >= 0).all() and (p <= 1).all()
    homes = m.home_order(idx)
    b, s = homes[1]  # second chunk maps to the second-best home
    col = m.n_columns + 5
    expect = 1.0 - (1.0 - float(m.flip_p[b, s, idx, 5])) ** 2
    assert p[col] == pytest.approx(expect, rel=1e-6)
    # Unsteered tiling follows natural (bank, subarray) order instead.
    nat = FaultInjector(m, idx, width=16, n_ops=2, steer=False)
    pn = nat.lane_probs(n)
    expect0 = 1.0 - (1.0 - float(m.flip_p[0, 1, idx, 5])) ** 2
    assert pn[col] == pytest.approx(expect0, rel=1e-6)
    # Seeded masks are reproducible, and bits stay inside the word.
    ones = np.full(n, 1.0)
    mask1, k1 = inj.sample_mask(np.random.default_rng([1, 2]), ones,
                                np.dtype(np.uint64))
    mask2, k2 = inj.sample_mask(np.random.default_rng([1, 2]), ones,
                                np.dtype(np.uint64))
    np.testing.assert_array_equal(mask1, mask2)
    assert k1 == k2 == n
    assert (mask1 < (1 << 16)).all()
    assert (np.bitwise_count(mask1) == 1).all()


def test_flip_scale_saturates_probability():
    """Scaling pushes every fallible column to certainty; perfectly stable
    columns (flip_p exactly 0) stay clean at any scale."""
    m = tiny_map(process_variation=PV_M * 4)
    idx = m.config_index(3, 4)
    n = m.n_columns * m.n_banks * m.n_subarrays
    base = FaultInjector(m, idx, width=16).lane_probs(n)
    p = FaultInjector(m, idx, width=16, flip_scale=1e16).lane_probs(n)
    assert (p >= base).all()
    assert (p[base > 1e-12] == 1.0).all() and (p == 1.0).any()


# --------------------------------------------------------------------- #
# Devices: planning only (inject=False) is bit-exact and count-free


def fused_device(**kw):
    args = dict(mfr="M", width=16, banks=4, fuse=True, seed=7)
    args.update(kw)
    return pum.Device(**args)


def run_kernel(dev, a, b):
    x = dev.asarray(a)
    y = dev.asarray(b)
    out = (x & y) ^ (x + y)
    lt = x < y
    dev.flush()
    return out.to_numpy(), lt.to_numpy()


def test_plan_only_is_bit_exact_and_silent():
    rng = np.random.default_rng(3)
    a = rng.integers(0, 1 << 16, 256, np.uint64)
    b = rng.integers(0, 1 << 16, 256, np.uint64)
    plain = fused_device()
    want = run_kernel(plain, a, b)
    dev = fused_device()
    dev.calibrate(n_subarrays=4, n_columns=64, n_patterns=4,
                  process_variation=PV_M * 3)
    assert dev.reliability is not None and not dev.reliability.inject
    got = run_kernel(dev, a, b)
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])
    counters = dev.counters.as_dict()["counters"]
    assert not any(k.startswith("reliability.") for k in counters)


def test_disabled_plane_leaves_engine_untouched():
    dev = fused_device()
    assert dev.reliability is None
    assert dev.engine.reliability is None


def test_variation_aware_planning_prefers_reliable_config():
    """At elevated variation the calibrated plane must not pick a config
    whose calibrated success is below an achievable target."""
    dev = fused_device()
    dev.calibrate(n_subarrays=4, n_columns=64, n_patterns=4,
                  process_variation=PV_M * 3, target_success=0.95)
    rel = dev.reliability
    m, n, sr, _ = dev.engine._cfg_for("and2", dev.width, None)
    achievable = max(rel.plan_success(mm, nn) or 0.0
                     for mm, nn in rel.map.configs)
    if achievable >= 0.95:
        assert sr >= 0.95
    # The chosen config's rate is the calibrated one when profiled.
    if rel.map.config_index(m, n) is not None:
        assert sr == rel.plan_success(m, n)


def test_bank_order_is_timing_symmetric():
    """Ranked bank placement reorders WHICH banks serve the batch, never
    the command timing — calibrated and plain devices charge identically."""
    plain = fused_device(controller="auto")
    dev = fused_device(controller="auto")
    dev.calibrate(n_subarrays=4, n_columns=64, n_patterns=4,
                  process_variation=PV_M * 3)
    order = dev.reliability.bank_order(4)
    assert sorted(order) == list(range(4))
    assert dev.engine._batch_for("and2", 3, 8) == \
        plain.engine._batch_for("and2", 3, 8)


def test_controller_rejects_bad_bank_order():
    dev = fused_device(controller="auto")
    ctrl = dev.engine.controller
    from repro.core import commands as cmds
    t = dev.engine.cost.t
    unit = [cmds.prog_write_row(0, 0, dev.engine.cost._wr_bursts, t)]
    with pytest.raises(ValueError):
        ctrl.batch_cost(unit, 2, bank_order=(0, 0))
    with pytest.raises(ValueError):
        ctrl.batch_cost(unit, 2, bank_order=(0, 99))


# --------------------------------------------------------------------- #
# Injection: vote correction, retries, escalation, oracle fallback


def calibrated_injecting_device(*, flip_scale, pv_scale=5.0, steer=False,
                                **policy):
    """A weak-lot chip (elevated variation, scaled flip probabilities) with
    steering OFF so the lanes actually land on fallible columns — with
    steering on, this workload's lanes fit entirely in strong subarrays and
    nothing injects (see test_steering_avoids_weak_columns)."""
    dev = fused_device()
    dev.calibrate(inject=True, n_subarrays=4, n_columns=64, n_patterns=4,
                  process_variation=PV_M * pv_scale, flip_scale=flip_scale,
                  steer=steer, **policy)
    return dev


def rel_counters(dev):
    c = dev.counters.as_dict()["counters"]
    return {k.split(".", 1)[1]: v for k, v in c.items()
            if k.startswith("reliability.")}


def test_injection_corrects_and_stays_bit_exact():
    rng = np.random.default_rng(11)
    a = rng.integers(0, 1 << 16, 512, np.uint64)
    b = rng.integers(0, 1 << 16, 512, np.uint64)
    want = run_kernel(fused_device(), a, b)
    dev = calibrated_injecting_device(flip_scale=40.0)
    got = run_kernel(dev, a, b)
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])
    c = rel_counters(dev)
    assert c["flushes"] >= 1
    assert c["injected_bits"] > 0
    assert c["corrected_bits"] > 0
    assert c.get("oracle_fallbacks", 0) == 0


def test_retry_escalation_bounded_and_counted():
    rng = np.random.default_rng(12)
    a = rng.integers(0, 1 << 16, 512, np.uint64)
    b = rng.integers(0, 1 << 16, 512, np.uint64)
    dev = calibrated_injecting_device(flip_scale=40.0, max_attempts=3)
    want = run_kernel(fused_device(), a, b)
    got = run_kernel(dev, a, b)
    np.testing.assert_array_equal(got[0], want[0])
    c = rel_counters(dev)
    # With votes=3 and min_margin=2 ANY injected flip forces a retry; the
    # retry escalates replication and votes and must stay within bounds.
    assert 1 <= c["retries"] <= (3 - 1) * c["flushes"]
    assert c["weak_bits"] > 0
    assert c.get("oracle_fallbacks", 0) == 0


def test_injection_runs_are_deterministic():
    rng = np.random.default_rng(13)
    a = rng.integers(0, 1 << 16, 256, np.uint64)
    b = rng.integers(0, 1 << 16, 256, np.uint64)
    runs = []
    for _ in range(2):
        dev = calibrated_injecting_device(flip_scale=40.0)
        out = run_kernel(dev, a, b)
        runs.append((out, rel_counters(dev)))
    np.testing.assert_array_equal(runs[0][0][0], runs[1][0][0])
    assert runs[0][1] == runs[1][1]


def test_oracle_fallback_is_last_resort_and_bit_exact():
    rng = np.random.default_rng(14)
    a = rng.integers(0, 1 << 16, 128, np.uint64)
    b = rng.integers(0, 1 << 16, 128, np.uint64)
    want = run_kernel(fused_device(), a, b)
    # A lot so weak that every vote attempt has sub-margin bits: the loop
    # exhausts max_attempts and degrades to the eager oracle — bit-exact.
    dev = calibrated_injecting_device(flip_scale=10.0, pv_scale=6.0,
                                      max_attempts=2)
    got = run_kernel(dev, a, b)
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])
    c = rel_counters(dev)
    assert c["oracle_fallbacks"] >= 1
    assert c["retries"] == (2 - 1) * c["flushes"]


def test_acceptance_bitweaving_scan_under_injection():
    """ISSUE acceptance: a realworld app kernel completes bit-exactly on an
    injecting device via vote correction + bounded retries. The kernel
    itself asserts PuM result == CPU oracle."""
    rng = np.random.default_rng(2026)
    column = rng.integers(0, 1 << 16, 1024, np.uint64)
    dev = calibrated_injecting_device(flip_scale=40.0)
    got, _, _ = bitweaving_scan(dev, column, 200, 40000)
    assert got == int(((column >= 200) & (column <= 40000)).sum())
    c = rel_counters(dev)
    assert c["injected_bits"] > 0
    assert c["corrected_bits"] > 0
    assert c.get("oracle_fallbacks", 0) == 0
    assert c.get("retries", 0) <= 2 * c["flushes"]


def test_steering_avoids_weak_columns():
    """Tentpole part 3: with map-guided steering the same workload on the
    same weak chip sees strictly fewer injected faults, because its lanes
    are placed on the strongest (bank, subarray) homes first."""
    rng = np.random.default_rng(14)
    a = rng.integers(0, 1 << 16, 128, np.uint64)
    b = rng.integers(0, 1 << 16, 128, np.uint64)
    injected = {}
    for steer in (True, False):
        dev = calibrated_injecting_device(flip_scale=40.0, steer=steer)
        run_kernel(dev, a, b)
        injected[steer] = rel_counters(dev).get("injected_bits", 0)
    assert injected[True] < injected[False]


def test_flush_span_reports_attempts(tmp_path):
    rng = np.random.default_rng(15)
    a = rng.integers(0, 1 << 16, 256, np.uint64)
    b = rng.integers(0, 1 << 16, 256, np.uint64)
    dev = calibrated_injecting_device(flip_scale=40.0)
    with pum.profile(path=str(tmp_path / "trace.json"), device=dev):
        run_kernel(dev, a, b)
    import json
    events = json.loads(
        (tmp_path / "trace.json").read_text())["traceEvents"]
    dispatch = [e for e in events if e.get("name") == "flush.dispatch"]
    assert dispatch and all("attempts" in e["args"] for e in dispatch)


# --------------------------------------------------------------------- #
# Device.calibrate plumbing


def test_device_calibrate_save_and_reuse(tmp_path):
    p = tmp_path / "chip.npz"
    dev = fused_device()
    rmap = dev.calibrate(attach=False, n_subarrays=4, n_columns=64,
                         n_patterns=4, save=p)
    assert dev.reliability is None  # attach=False leaves the device alone
    dev2 = pum.Device(mfr="M", width=16, banks=4, fuse=True, seed=7,
                      reliability=pum.ReliabilityConfig(map=str(p)))
    np.testing.assert_array_equal(dev2.reliability.map.flip_p, rmap.flip_p)


def test_calibrate_inject_on_eager_device_raises():
    dev = pum.Device(mfr="M", width=16, banks=4, fuse=False)
    with pytest.raises(ValueError, match="fuse"):
        dev.calibrate(inject=True, n_subarrays=4, n_columns=64,
                      n_patterns=4)
