"""The reduction from a profiler trace to busy time, device operations
and labelled idle gaps: by hand on made-up intervals, and on a short
trace of the ``scan-range`` cell recorded on a TPU v5e."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import trace_reduce  # noqa: E402

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "scan-range.xplane.pb")

EVENTS = {
    "host": [["window", 0, 100], ["query", 10, 40], ["build", 12, 30],
             ["materialize", 30, 40], ["query", 50, 90]],
    "devices": {
        "/device:TPU:0": [["a", 20, 25], ["b", 24, 28], ["c", 35, 45],
                          ["d", 95, 105], ["e", -5, 2]],
        "/device:TPU:1": [["a", 0, 50]],
    },
}


def test_busy_is_the_union_inside_the_window():
    s = trace_reduce.summarize(EVENTS, n_chips=1)
    assert s["window_s"] == pytest.approx(100e-9)
    assert s["busy_s"] == pytest.approx(25e-9)  # 2 + 8 + 10 + 5
    assert dict(s["device_ops"]) == pytest.approx(
        {"a": 5e-9, "b": 4e-9, "c": 10e-9, "d": 5e-9, "e": 2e-9})


def test_idle_gaps_carry_the_innermost_host_span():
    s = trace_reduce.summarize(EVENTS, n_chips=1)
    idle = dict(s["idle_gaps"])
    assert idle == pytest.approx({"between queries": 18e-9,
                                  "query": 42e-9, "build": 10e-9,
                                  "materialize": 5e-9})
    assert sum(idle.values()) == pytest.approx(s["window_s"] - s["busy_s"])
    assert [k for k, _ in s["idle_gaps"]][0] == "query"  # largest first


def test_busy_is_averaged_over_the_chips_used():
    s = trace_reduce.summarize(EVENTS, n_chips=2)
    assert s["busy_s"] == pytest.approx((25e-9 + 50e-9) / 2)


def test_one_window_is_required():
    with pytest.raises(ValueError):
        trace_reduce.summarize({"host": [], "devices": EVENTS["devices"]})


def test_op_kind_drops_the_instruction_number():
    assert trace_reduce.op_kind(
        "%copy.63 = s32[32,256]{0,1} copy(s32[32,256]{1,0} %x)") == "copy"
    assert trace_reduce.op_kind(
        "%run_program_pallas.1 = s32[2] custom-call()") == \
        "run_program_pallas"


@pytest.fixture(scope="module")
def recorded():
    return trace_reduce.load(RECORDED)


def test_recorded_trace_loads_host_spans_and_device_ops(recorded):
    labels = {label for label, _, _ in recorded["host"]}
    assert labels == {"window", "query", "build", "materialize"}
    assert list(recorded["devices"]) == ["/device:TPU:0"]
    kinds = {op for op, _, _ in recorded["devices"]["/device:TPU:0"]}
    assert {"run_program_pallas", "bit_transpose32"} <= kinds


def test_recorded_trace_reduces_consistently(recorded):
    s = trace_reduce.summarize(recorded, n_chips=1)
    assert 0 < s["busy_s"] < s["window_s"]
    # As the run on the chip reduced it (two queries, 33.9 ms of device
    # work each).
    assert s["busy_s"] == pytest.approx(0.067750117, abs=1e-9)
    assert s["window_s"] == pytest.approx(10.145381634, abs=1e-9)
    idle = dict(s["idle_gaps"])
    assert set(idle) <= {"between queries", "query", "build", "materialize"}
    assert sum(idle.values()) == pytest.approx(
        s["window_s"] - s["busy_s"], rel=1e-9)
    # The device works while the host materializes; it idles while the
    # host records (build).
    assert idle["build"] > idle.get("materialize", 0)
    assert len(s["device_ops"]) <= 10
