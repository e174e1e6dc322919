#!/usr/bin/env python3
"""Rate sweep of an open-loop cell: the highest rate it sustains.

    python3 chipbench/sweep.py --workload bmi-tenants --seed <n> \
        --seconds 10 --rates 60,80,100,120

One process sets the cell up once, then offers each rate in turn for
``--seconds`` (the cell's traffic with only the rate changed) and prints
one JSON line per rate: queries offered and answered, latency quartiles
and tail, how late the last query started, and the backlog at the close:
the queries due by the last arrival that had not yet been answered when
it arrived. A rate is sustained where that backlog stays a handful and
does not grow with the window. The cell's rate is then set, by hand, in
its traffic file at 4/5 of the highest rate sustained. Needs the chip,
like ``run.py``.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated queries per second")
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import numpy as np
    from chipbench import harness

    cell = harness.load_cell(harness.load_bench(), args.workload)
    if cell.traffic["loop"] != "open":
        print("sweep: the cell's traffic is not open loop", file=sys.stderr)
        return 2
    try:
        s = harness.Session(cell, args.seed, T0)
    except harness.NoChip as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 1
    for rate in (float(r) for r in args.rates.split(",")):
        arrivals = s.traffic.arrivals(args.seconds, rate)
        queries, length = s.open_loop(args.seconds, rate)
        last_due = arrivals[-1][0]
        backlog = sum(due + q.latency_s > last_due
                      for (due, _), q in zip(arrivals, queries)) - 1
        lat = np.array([q.latency_s for q in queries
                        if q.answer is not None]) * 1e3
        print(json.dumps({
            "rate_per_s": rate, "offered": len(arrivals),
            "answered": int(lat.size), "window_s": length,
            "ms_p25_p50_p75": np.percentile(lat, [25, 50, 75]).tolist(),
            "ms_p95": float(np.percentile(lat, 95)),
            "ms_p99": float(np.percentile(lat, 99)),
            "last_start_late_ms": 1e3 * queries[-1].late_s,
            "backlog_at_close": int(backlog)}), flush=True)
    s.release()
    return 0


if __name__ == "__main__":
    sys.exit(main())
