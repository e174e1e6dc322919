"""Tuner determinism, selection behavior, drift detection, persistence."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import repro.pum as pum
from repro.autotune import (CostModel, DriftDetector, SearchSpace,
                            TunedPlan, Tuner, WorkloadProfile)

pytestmark = pytest.mark.autotune


def profile_of(**overrides):
    base = dict(ops=1600, flushes=16, ops_per_flush=100.0, lanes=4096.0,
                op_mix={"add": 0.5, "xor": 0.5}, raw_fraction=0.0,
                cache_hit_rate=0.9, width=32, word_bits=32)
    base.update(overrides)
    return WorkloadProfile(**base)


# -- selection behavior ------------------------------------------------- #


def test_no_signal_keeps_static_config():
    """A profile with no exploitable structure must return the baseline
    exactly — no gratuitous knob churn."""
    plan = Tuner().tune(profile_of(), pum.EngineConfig(width=32))
    assert plan.non_default(pum.EngineConfig(width=32)) == {}
    assert plan.score_s == plan.baseline_score_s


def test_raw_heavy_workload_selects_64bit_layout():
    """Raw uint64 bitmaps split 2 lanes/word on the 32-bit layout; the
    tuner should move them to unsplit 64-bit lanes."""
    cfg = pum.EngineConfig(width=32)
    plan = Tuner().tune(profile_of(raw_fraction=1.0, lanes=8192.0), cfg)
    nd = plan.non_default(cfg)
    assert nd.get("word_bits") == 64
    assert (plan.fused_backend, plan.word_bits) == ("words-cpu", 64)
    assert plan.score_s < plan.baseline_score_s


def test_threshold_choked_workload_selects_larger_threshold():
    """When most flushes were forced by the ops threshold, a larger
    threshold merges dispatches."""
    cfg = pum.EngineConfig(width=16, flush_threshold=64)
    plan = Tuner().tune(
        profile_of(ops_per_flush=64.0, autoflush_ops_fraction=0.95,
                   cache_hit_rate=0.9, lanes=2048.0, width=16), cfg)
    assert plan.flush_threshold > 64
    assert plan.score_s < plan.baseline_score_s


def test_controller_signal_selects_ref_and_lookahead():
    """Refresh/stall fractions reward REF postponing and deeper crossbar
    lookahead — but only on the auto-controller cost path."""
    prof = profile_of(refresh_fraction=0.3, stall_trrd_fraction=0.2,
                      stall_tfaw_fraction=0.1, lanes=65536.0,
                      ops_per_flush=1000.0)
    auto = pum.EngineConfig(width=32, controller="auto")
    plan = Tuner().tune(prof, auto)
    assert plan.ref_postponing == 8
    assert plan.cmd_buffer_lookahead == 32
    # Closed-form path: ref_postponing pinned to the config's value.
    plain = Tuner().tune(prof, pum.EngineConfig(width=32))
    assert plain.ref_postponing == 1


def test_leaf_upload_bound_raw_chain_recommends_eager():
    """A doctored BMI-shaped window — long raw AND chains over huge
    bitmaps whose staged leaf-snapshot bytes dominate the flush — must
    flip the recommendation off the fused pipeline: the leaf-upload term
    prices what the flush path actually moves, and eager streams
    operands in place without snapshotting. The same window with zero
    staged bytes (a warm leaf cache) keeps fused."""
    cfg = pum.EngineConfig(width=32, layout=64)
    shape = dict(ops=480, flushes=16, ops_per_flush=30.0,
                 lanes=2_097_152.0, op_mix={"and": 1.0},
                 raw_fraction=1.0, cache_hit_rate=1.0,
                 width=32, word_bits=64)
    cold = profile_of(**shape, leaf_bytes_per_flush=2e8,
                      leaf_cache_hit_rate=0.0)
    plan = Tuner().tune(cold, cfg)
    assert plan.fuse is False
    assert plan.score_s < plan.baseline_score_s
    # Round-trips keep the recommendation.
    assert TunedPlan.from_dict(plan.as_dict()).fuse is False
    # apply() carries it; EngineConfig stays valid.
    assert plan.apply(cfg).fuse is False
    warm = profile_of(**shape)
    assert Tuner().tune(warm, cfg).fuse is True


def test_candidates_respect_evaluator_layouts():
    from repro.backends import EVALUATORS, host_evaluators
    cfg = pum.EngineConfig(width=48)  # only the 64-bit layout fits
    cands = Tuner().candidates(cfg)
    assert cands
    for cand in cands:
        assert cand.word_bits == 64
        assert cand.fused_backend in host_evaluators()
        assert 64 in EVALUATORS[cand.fused_backend][1]
    # shard-words runs no 64-bit layout, even when asked for by name
    space = SearchSpace(backends=("shard-words", "words-cpu"))
    assert {c.fused_backend for c in Tuner(space=space).candidates(cfg)} \
        == {"words-cpu"}


def test_space_override_narrows_search():
    space = SearchSpace(backends=("words-cpu",), layouts=(32,),
                        flush_thresholds=(128,), cmd_buffer_lookahead=(4,))
    plan = Tuner(space=space).tune(profile_of(), pum.EngineConfig())
    # Baseline still wins scoring ties, but every non-baseline candidate
    # comes from the narrowed space.
    cands = Tuner(space=space).candidates(pum.EngineConfig())
    assert {c.fused_backend for c in cands} == {"words-cpu"}
    assert {c.flush_threshold for c in cands} == {128}
    assert isinstance(plan, TunedPlan)


# -- determinism -------------------------------------------------------- #

TUNE_SNIPPET = """
import json
from repro.autotune import Tuner, WorkloadProfile
from repro.pum import EngineConfig
prof = WorkloadProfile(ops=1600, flushes=16, ops_per_flush=100.0,
                       lanes=8192.0,
                       op_mix={"add": 0.25, "xor": 0.3, "mul": 0.2,
                               "and": 0.15, "divmod": 0.1},
                       raw_fraction=0.6, cache_hit_rate=0.8,
                       refresh_fraction=0.1, stall_trrd_fraction=0.05,
                       width=32, word_bits=32)
plan = Tuner().tune(prof, EngineConfig(width=32, controller="auto"))
print(json.dumps(plan.as_dict(), sort_keys=True))
"""


def run_in_subprocess(snippet, hashseed):
    env = dict(os.environ, PYTHONHASHSEED=str(hashseed))
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in ("src", env.get("PYTHONPATH", "")) if p)
    out = subprocess.run([sys.executable, "-c", snippet], env=env,
                         capture_output=True, text=True, check=True,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.dirname(os.path.abspath(__file__)))))
    return json.loads(out.stdout)


def test_same_profile_same_plan_across_processes():
    a = run_in_subprocess(TUNE_SNIPPET, hashseed=0)
    b = run_in_subprocess(TUNE_SNIPPET, hashseed=98765)
    assert a == b  # exact knob + score equality under different salts


def test_tune_is_deterministic_in_process():
    prof = profile_of(raw_fraction=0.7, lanes=16384.0)
    cfg = pum.EngineConfig(width=32)
    assert Tuner().tune(prof, cfg) == Tuner().tune(prof, cfg)


# -- cost model sanity -------------------------------------------------- #


def test_cost_model_terms_are_positive_and_additive():
    est = CostModel().estimate(profile_of(),
                               Tuner().candidates(pum.EngineConfig())[0])
    assert est.compute_s > 0 and est.memory_s > 0 and est.overhead_s > 0
    assert est.controller_s == 0.0  # no controller counters in profile
    assert est.total_s == pytest.approx(
        est.compute_s + est.memory_s + est.overhead_s + est.controller_s)
    assert set(est.as_dict()) == {"compute_s", "memory_s", "overhead_s",
                                  "controller_s", "total_s"}


def test_ref_vertical_oracle_never_wins():
    space = SearchSpace(backends=("words-cpu", "ref-vertical"))
    plan = Tuner(space=space).tune(profile_of(), pum.EngineConfig())
    assert plan.fused_backend != "ref-vertical"


# -- persistence -------------------------------------------------------- #


def test_plan_round_trips_json_and_npz(tmp_path):
    prof = profile_of(raw_fraction=1.0, lanes=8192.0)
    plan = Tuner().tune(prof, pum.EngineConfig(width=32))
    for name in ("plan.json", "plan.npz"):
        path = tmp_path / name
        plan.save(path)
        loaded = TunedPlan.load(path)
        assert loaded == plan
        assert loaded.profile == prof


def test_plan_schema_guard(tmp_path):
    plan = Tuner().tune(profile_of(), pum.EngineConfig())
    blob = plan.as_dict()
    blob["schema"] = "repro.autotune/999"
    with pytest.raises(ValueError, match="schema"):
        TunedPlan.from_dict(blob)


def test_apply_splits_execution_and_cost_plane_knobs():
    cfg = pum.EngineConfig(width=32, controller="auto")
    plan = TunedPlan(fused_backend="words-cpu", word_bits=64,
                     flush_threshold=4096, ref_postponing=8,
                     cmd_buffer_lookahead=32)
    exe = plan.apply(cfg)
    assert exe.fused_backend == "words-cpu"
    assert exe.resolved_layout().word_bits == 64
    assert exe.flush_threshold == 4096
    assert exe.cmd_buffer_lookahead == 32
    assert exe.ref_postponing == cfg.ref_postponing  # cost plane untouched
    full = plan.apply(cfg, cost_plane=True)
    assert full.ref_postponing == 8


# -- drift detection + online re-tune ----------------------------------- #


def test_doctored_profile_fires_drift_detector():
    base = profile_of()
    det = DriftDetector(base, threshold=0.5)
    assert not det.fired(base)
    assert det.drift(base) == 0.0
    # Doctor the profile: the workload flipped to raw bitmaps on 16x the
    # lanes — both features breach the threshold on their own.
    doctored = WorkloadProfile.from_dict(
        dict(base.as_dict(), raw_fraction=1.0, lanes=base.lanes * 16))
    assert det.drift(doctored) >= 1.0
    assert det.fired(doctored)
    # Op-mix rotation alone fires too (total-variation distance).
    remixed = WorkloadProfile.from_dict(
        dict(base.as_dict(), op_mix={"divmod": 1.0}))
    assert det.fired(remixed)


def test_drift_triggers_online_retune():
    """A doctored counter window must make the online autotuner re-tune:
    phase 1 tunes on small value-mode programs, phase 2 flips the
    workload to wide raw bitmaps, and the drift detector (not the
    explore cadence — set astronomically high) must fire the re-tune."""
    dev = pum.device(width=32, fuse=True, flush_threshold=8)
    from repro.telemetry import Tracer
    dev.engine.tracer = Tracer()
    dev.autotune(online=True, window_flushes=2, explore_every=10**6,
                 drift_threshold=0.5)
    rng = np.random.default_rng(0)

    def small(seed):
        x = dev.asarray(np.arange(256, dtype=np.uint64))
        ((x + seed) * x).to_numpy()

    def raw(seed):
        a = dev.asarray(rng.integers(0, 2**64, 8192, dtype=np.uint64))
        b = dev.asarray(rng.integers(0, 2**64, 8192, dtype=np.uint64))
        ((a & b) | (a ^ b)).to_numpy()

    for i in range(8):
        small(i)
    ot = dev.engine.autotuner
    assert ot is not None and ot.windows >= 1
    retunes_before = ot.retunes
    plan_before = ot.plan
    for i in range(12):
        raw(i)
    assert ot.retunes > retunes_before
    assert ot.plan is not None and ot.plan != plan_before
    # The raw regime moved the device onto unsplit 64-bit lanes.
    assert dev.config.resolved_layout().word_bits == 64
    dev.engine.tracer = None
    dev.close()


def test_online_window_accounting_and_guards():
    with pytest.raises(ValueError):
        pum.device(width=8, fuse=True).autotune(online=True,
                                                window_flushes=0)
    dev = pum.device(width=8, fuse=False)
    with pytest.raises(ValueError, match="fuse"):
        dev.autotune()
