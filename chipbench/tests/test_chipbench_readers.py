"""The readers of the flush-phase and compile metrics, on hand-built
windows: each sums its spans over the window and divides by the answered
queries, and reports nothing where nothing was recorded."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from chipbench import harness  # noqa: E402

SPANS = [  # (name, t0_ns, t1_ns, args): two flushes
    ("flush.record", 0, 1_000_000, {}),
    ("flush.optimize", 1_000_000, 1_100_000, {}),
    ("flush.leaf_upload", 1_100_000, 1_200_000, {}),
    ("flush.compile", 1_200_000, 1_300_000, {}),
    ("flush.dispatch", 1_300_000, 1_500_000, {}),
    ("flush.materialize", 1_500_000, 9_500_000, {}),
    ("flush.wait", 1_500_000, 3_500_000, {}),
    ("flush.fetch", 3_500_000, 7_500_000, {}),
    ("flush.unpack", 7_500_000, 9_500_000, {}),
    ("flush.wait", 10_000_000, 11_000_000, {}),
    ("flush.fetch", 11_000_000, 15_000_000, {}),
    ("flush.unpack", 15_000_000, 16_000_000, {}),
]


def _window(spans, n_answered=2):
    queries = [harness.Query({}, answer=1) for _ in range(n_answered)]
    return harness.Window(queries, 1.0, 1.0, spans=spans)


@pytest.mark.parametrize("name, per_query_ms", [
    ("wait_ms", (2.0 + 1.0) / 2),
    ("fetch_ms", (4.0 + 4.0) / 2),
    ("unpack_ms", (2.0 + 1.0) / 2),
    ("prepare_ms", (0.1 + 0.1 + 0.1 + 0.2) / 2),
])
def test_flush_phase_reader_sums_per_query(name, per_query_ms):
    read = harness.reader("layers", name)
    assert read(_window(SPANS)) == pytest.approx(per_query_ms)
    # Nothing to read: an untraced run, no answered query, or a program
    # without the span.
    assert read(_window(None)) is None
    assert read(_window(SPANS, n_answered=0)) is None
    assert read(_window([s for s in SPANS if s[0] == "flush.record"])) \
        is None


def test_compile_reader_reads_the_process_counter(monkeypatch):
    import repro.telemetry as telemetry
    from repro.telemetry import CounterBank
    read = harness.reader("layers", "compile_s")
    bank = CounterBank()
    monkeypatch.setattr(telemetry, "process_counters", lambda: bank)
    assert read(_window(SPANS)) is None  # nothing compiled
    bank.inc("compile.s", 12.5)
    bank.inc("compile.trace_s", 3.0)
    assert read(_window(SPANS)) == 12.5
    assert read(_window(None)) is None   # an untraced run
    # A program without the process counters reports nothing.
    monkeypatch.delattr(telemetry, "process_counters")
    assert read(_window(SPANS)) is None
