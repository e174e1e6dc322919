"""The reduction of the ``scan-range`` trace recorded on a TPU v5e,
pinned number for number, so that a change to ``trace_reduce`` or to the
spans the program puts in the trace shows as a changed summary."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import trace_reduce  # noqa: E402

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "scan-range.xplane.pb")


def test_recorded_trace_summary_is_unchanged():
    recorded = trace_reduce.load(RECORDED)
    assert trace_reduce.summarize(recorded, n_chips=1) == {
        "busy_s": 0.067750117, "window_s": 10.145381634,
        "device_ops": [["copy", 0.021817485], ["reshape", 0.020561305],
                       ["bit_transpose32", 0.01409461],
                       ["slice_bitcast_fusion", 0.003246691],
                       ["pad_add_fusion", 0.003152731],
                       ["run_program_pallas", 0.002764487],
                       ["pad_bitcast_fusion", 0.001229475],
                       ["squeeze", 0.000883333]],
        "idle_gaps": [["build", 6.961030859], ["materialize", 2.928009347],
                      ["query", 0.109001946],
                      ["between queries", 0.079589365]],
        "query_spans": 2, "op_events": 48}
