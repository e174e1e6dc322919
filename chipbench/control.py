#!/usr/bin/env python3
"""The control of a cell's correctness check: the reference with one
guarantee of the configuration broken, put in the program's place.

    python3 chipbench/control.py --workload <cell> --seeds 1,2,3 \
        [--queries N] [--seconds S]

For each seed it makes the cell's data, draws the queries a window would
send (``--queries`` of a closed-loop stream, or the arrivals of a
``--seconds`` open-loop window), answers them with the configuration's
``control`` and compares those answers with its ``reference`` exactly as
a run compares the program's. It prints one JSON line per seed with each
number compared beside its limit; the control is sound only if every
seed fails. It runs on the host (NumPy) and needs no chip; the benchmark's
own runs never run it.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def control_checks(cell, seed: int, queries: int, seconds: float) -> dict:
    from chipbench import harness, loadgen

    data = cell.module.make_data(cell.config, loadgen.rng(seed, loadgen.DATA))
    traffic = loadgen.Traffic(cell.traffic, cell.config, seed)
    params = (traffic.first(queries) if traffic.loop == "closed"
              else [p for _, p in traffic.arrivals(seconds)])
    refs = cell.module.reference(cell.config, data, params)
    answers = cell.module.control(cell.config, data, params)
    checks = harness.compare(answers, refs)
    return {"workload": cell.name, "seed": seed, "queries": len(params),
            "checks": checks, "fails": not harness.passes(checks)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--queries", type=int, default=100)
    ap.add_argument("--seconds", type=float, default=30.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from chipbench import harness

    cell = harness.load_cell(harness.load_bench(), args.workload)
    ok = True
    for seed in (int(s) for s in args.seeds.split(",")):
        line = control_checks(cell, seed, args.queries, args.seconds)
        ok &= line["fails"]
        print(json.dumps(line), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
