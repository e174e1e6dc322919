"""Each cell, run end to end on the CPU at a tiny size: the program's
answers equal the plain NumPy reference, and the check that decides
``correct`` fails when the timed path is broken underneath or when the
control answers in the program's place.

The tiny size is a copy of the benchmark whose configuration files hold
small tables; the harness's look for a chip is replaced by JAX on the
CPU."""

import json
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from chipbench import control, harness, loadgen  # noqa: E402

TINY = {"bmi-appb-2p30": {"users": 1 << 16, "shard_users": 1 << 12,
                          "tenants": 16},
        "bitweaving-2p26": {"rows": 1 << 14}}
BENCH = harness.load_bench(ROOT)
CELLS = [w["name"] for w in BENCH["workloads"]]
SEED = 2**31 + 12345  # more than 32 signed bits hold


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """The root of a copy of the benchmark at the sizes of ``TINY``: its
    own BENCHMARK.json and configuration files, the rest linked."""
    root = tmp_path_factory.mktemp("tiny")
    (root / "configs").mkdir()
    bench = json.loads(json.dumps(BENCH))
    for entry in bench["configs"]:
        src = os.path.join(ROOT, entry["file"])
        with open(src, encoding="utf-8") as f:
            cfg = json.load(f)
        cfg.update(TINY[entry["name"]])
        entry["file"] = f"configs/{entry['name']}.json"
        (root / entry["file"]).write_text(json.dumps(cfg))
        os.symlink(os.path.splitext(src)[0] + ".py",
                   root / "configs" / f"{entry['name']}.py")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    os.symlink(os.path.join(ROOT, "chipbench"), root / "chipbench")
    return str(root)


@pytest.fixture
def cpu(monkeypatch):
    """JAX on the CPU stands in for the chip."""
    import jax
    monkeypatch.setattr(harness, "open_chip", lambda chips: jax)


def _run(root, workload, seed=SEED):
    return harness.run(workload, seed, 0.3, False, time.perf_counter(),
                       root=root)


def _cell(root, workload):
    return harness.load_cell(harness.load_bench(root), workload, root)


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(workload, tiny, cpu):
    r = _run(tiny, workload)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert r["checks"] == {"max_count_gap": {"value": 0, "limit": 0},
                           "unanswered": {"value": 0, "limit": 0}}
    assert list(r)[-1] == "checks"
    assert "setup_s" in r["metrics"] and len(r["metrics"]) >= 2
    assert all(m["value"] > 0 for m in r["metrics"].values())


def _altered(orig):
    def to_numpy(self):
        out = orig(self).copy()
        out.flat[0] ^= np.uint64(1)     # one answer altered at its source
        return out
    return to_numpy


def _half_left_out(orig):
    def to_numpy(self):
        out = orig(self).copy()
        flat = out.reshape(-1)
        h = flat.size // 2              # the rest stands in for the half
        flat[h:2 * h] = flat[:h]
        return out
    return to_numpy


@pytest.mark.parametrize("fault", [_altered, _half_left_out],
                         ids=["answer_altered", "half_left_out"])
@pytest.mark.parametrize("workload", CELLS)
def test_broken_path_is_not_correct(workload, fault, tiny, cpu, monkeypatch):
    from repro.pum.api import PumArray
    monkeypatch.setattr(PumArray, "to_numpy", fault(PumArray.to_numpy))
    r = _run(tiny, workload)
    assert r["correct"] is False and r["failed"] > 0
    assert r["checks"]["max_count_gap"]["value"] > 0


@pytest.mark.parametrize("workload", CELLS)
def test_control_fails_the_check(workload, tiny):
    cell = _cell(tiny, workload)
    for seed in (1, 2, SEED):
        line = control.control_checks(cell, seed, queries=20, seconds=0.3)
        assert line["fails"], line


def test_bitweaving_reference_is_the_plain_predicate(tiny):
    cell = _cell(tiny, "scan-range")
    data = cell.module.make_data(cell.config, loadgen.rng(7, loadgen.DATA))
    col = data["column"]
    queries = [{"bounds": (1, 65534)}, {"bounds": (5, 6)},
               {"bounds": (30000, 30001)}] + \
        loadgen.Traffic(cell.traffic, cell.config, 7).first(20)
    want = [int(((col >= c1) & (col <= c2)).sum())
            for c1, c2 in (q["bounds"] for q in queries)]
    assert cell.module.reference(cell.config, data, queries) == want


def test_bmi_reference_is_the_plain_and_popcount(tiny):
    cell = _cell(tiny, "bmi-tenants")
    data = cell.module.make_data(cell.config, loadgen.rng(7, loadgen.DATA))
    days = data["days"]
    bits = np.unpackbits(days.view(np.uint8), axis=1, bitorder="little")
    every = bits.all(axis=0)
    per_tenant = every.reshape(cell.config["tenants"], -1).sum(axis=1)
    queries = [{}] + [{"tenant": t} for t in range(cell.config["tenants"])]
    want = [int(every.sum())] + [int(x) for x in per_tenant]
    assert cell.module.reference(cell.config, data, queries) == want
