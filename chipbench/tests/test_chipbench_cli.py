"""The command refuses to run without the chip, and the traffic generator
gives the same queries for the same seed."""

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import loadgen  # noqa: E402


def _run(cwd, env):
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "bmi-full",
         "--seed", str(2**33 + 1), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=240)


def _prints_no_result(out: str) -> bool:
    for line in out.splitlines():
        try:
            json.loads(line)
        except ValueError:
            continue
        return False
    return True


def test_run_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = _run(ROOT, env)
    assert p.returncode != 0
    assert _prints_no_result(p.stdout)
    assert "TPU" in p.stderr


def test_run_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = _run(tmp_path, env)
    assert p.returncode != 0
    assert _prints_no_result(p.stdout)


CONFIG = {"tenants": 256}
ZIPF = {"loop": "open", "rate_per_s": 50.0,
        "params": {"tenant": {"dist": "zipf", "items": "tenants",
                              "theta": 0.99}}}
RANGE = {"loop": "closed",
         "params": {"bounds": {"dist": "ordered_pair", "low": 1,
                               "high": 65534}}}


@pytest.mark.parametrize("seed", [0, 12, 2**31 + 5, 2**40 + 3])
def test_same_seed_same_queries(seed):
    a = loadgen.Traffic(ZIPF, CONFIG, seed).arrivals(10.0)
    b = loadgen.Traffic(ZIPF, CONFIG, seed).arrivals(10.0)
    assert a == b
    c = loadgen.Traffic(RANGE, CONFIG, seed).first(50)
    assert c == loadgen.Traffic(RANGE, CONFIG, seed).first(50)
    assert all(1 <= lo < hi <= 65534 for lo, hi in
               (q["bounds"] for q in c))


def test_open_loop_offers_the_same_work_for_every_seed():
    runs = [loadgen.Traffic(ZIPF, CONFIG, s).arrivals(10.0)
            for s in (1, 2, 3)]
    assert {len(r) for r in runs} == {500}
    assert runs[0] != runs[1]
    for r in runs:
        assert r[0][0] == 0.0 and r[-1][0] < 10.0
        assert all(0 <= q["tenant"] < 256 for _, q in r)
    # The same set of gaps (the last one runs to the window's end), in
    # another order.
    gaps = [np.sort(np.diff([due for due, _ in r] + [10.0])) for r in runs]
    assert np.allclose(gaps[0], gaps[1]) and np.allclose(gaps[0], gaps[2])


def test_warmup_touches_every_tenant_twice():
    warm = loadgen.Traffic(ZIPF, CONFIG, 9).warmup()
    assert sorted(q["tenant"] for q in warm) == sorted(list(range(256)) * 2)
    assert len(loadgen.Traffic(RANGE, CONFIG, 9).warmup()) == 2


def test_bursts_keep_the_work_and_fall_in_the_on_time():
    bursty = dict(ZIPF, bursts={"period_s": 2.0, "on_share": 0.25})
    plain = loadgen.Traffic(ZIPF, CONFIG, 4).arrivals(10.0)
    runs = [loadgen.Traffic(bursty, CONFIG, s).arrivals(10.0)
            for s in (4, 5)]
    for r in runs:
        assert len(r) == len(plain) == 500
        due = np.array([d for d, _ in r])
        assert due[0] == 0.0 and due[-1] < 10.0
        assert np.all(np.diff(due) > 0)
        assert np.all(due % 2.0 < 0.5)      # only in each period's on time
        # Every period gets a burst: four times the rate, a quarter of
        # the time.
        assert set(np.floor(due / 2.0).astype(int)) == set(range(5))
    # The params are those the plain mix draws from the same seed.
    assert [q for _, q in runs[0]] == [q for _, q in plain]


def test_choice_draws_by_weight_and_warms_each_value():
    mix = {"loop": "closed",
           "params": {"op": {"dist": "choice", "values": ["read", "write"],
                             "weights": [3, 1]}}}
    ops = [q["op"] for q in loadgen.Traffic(mix, CONFIG, 6).first(4000)]
    assert 0.70 < ops.count("read") / len(ops) < 0.80
    warm = [q["op"] for q in loadgen.Traffic(mix, CONFIG, 6).warmup()]
    assert sorted(warm) == ["read", "read", "write", "write"]
    with pytest.raises(ValueError):
        loadgen.Traffic({"loop": "closed", "params": {"op": {
            "dist": "choice", "values": ["a"], "weights": [0]}}},
            CONFIG, 6)
