"""BENCHMARK.json keeps to the benchmark's contract, and every cell
resolves to its files by name."""

import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import harness  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}
BENCH = harness.load_bench(ROOT)


def _line(text):
    return isinstance(text, str) and 1 <= len(text) <= 200 \
        and "\n" not in text and "\t" not in text


def test_top_level_keys_and_size():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 << 10
    assert BENCH["command"][0] == "python3"
    assert all(_line(w) and not w.startswith("/") and ".." not in w
               for w in BENCH["command"])
    assert BENCH["paths"] == ["chipbench"]
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51


@pytest.mark.parametrize("section", sorted(KEYS))
def test_entries_keep_to_the_contract(section):
    entries = BENCH[section]
    assert entries
    names = [e["name"] for e in entries]
    assert len(set(names)) == len(names)
    for e in entries:
        extra = set(e) - KEYS[section] - {"workloads"}
        assert KEYS[section] <= set(e) and not extra, (e["name"], extra)
        assert NAME.match(e["name"])
        if "unit" in e:
            assert UNIT.match(e["unit"]) and e["better"] in ("lower",
                                                             "higher")
        if "why" in e:
            assert _line(e["why"])
    if section == "end_to_end":
        assert "setup_s" in names
        for e in entries:
            assert e["source"] in ("host_clock", "device_trace")
            assert 0.01 <= e["bound"] <= 0.25
    if section == "per_layer":
        e2e = {m["name"] for m in BENCH["end_to_end"]}
        for e in entries:
            assert e["source"] in ("device_trace", "program_span",
                                   "program_counter", "host_clock")
            assert e["moves"] in e2e and _line(e["layer"])


def test_every_metric_names_existing_cells():
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert set(m.get("workloads", cells)) <= cells


def test_configs_are_used_and_sources_differ():
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    assert len({c["source"] for c in BENCH["configs"]}) == len(used)
    assert len({c["file"] for c in BENCH["configs"]}) == len(used)
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)
    assert all(w["chips"] in (1, 4) for w in BENCH["workloads"])


@pytest.mark.parametrize("config", [c["name"] for c in BENCH["configs"]])
def test_config_file_states_its_cuts(config):
    entry = next(c for c in BENCH["configs"] if c["name"] == config)
    assert entry["file"].startswith("chipbench/configs/")
    with open(os.path.join(ROOT, entry["file"]), encoding="utf-8") as f:
        cfg = json.load(f)
    assert cfg["name"] == config and cfg["source"] == entry["source"]
    assert sorted(cfg["reduced"]) == sorted(entry["reduced"])
    assert cfg["guarantees"] and cfg["assumed"] and cfg["device"]


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_workload_resolves_to_its_files(workload):
    cell = harness.load_cell(BENCH, workload, root=ROOT)
    for fn in ("check", "make_data", "run_query", "query_bytes",
               "reference", "control"):
        assert callable(getattr(cell.module, fn))
    assert cell.traffic["loop"] in ("closed", "open")
    assert any(m["name"] == "setup_s" for m in cell.end_to_end)
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in cell.end_to_end:
        assert callable(harness.reader("end_to_end", m["name"], ROOT))
    for m in cell.per_layer:
        assert callable(harness.reader("layers", m["name"], ROOT))


def test_peaks_are_keyed_by_device_kind_with_a_source():
    from chipbench import peaks
    v5e = peaks.peak("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["source"]
    with pytest.raises(KeyError):
        peaks.peak("cpu")
