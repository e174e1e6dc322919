"""Benchmark aggregator: one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--only fig17] [--emit-dir DIR]

Prints ``name,us_per_call,derived`` CSV (one row per measurement).
``--emit-dir`` additionally writes the gated modules' rows as
``BENCH_*.json`` baselines (see benchmarks/common.py for the schema;
``tools/bench_compare.py`` diffs a fresh emit against the committed
copies at the repo root).
"""

from __future__ import annotations

import argparse
import importlib
import os
import sys
import traceback

from benchmarks import common
from repro.backends import use_compile_cache

# Modules with a recorded perf trajectory: their rows emit to these
# baseline files under --emit-dir (committed copies live at repo root).
BENCH_NAMES = {
    "kernel_bench": "BENCH_kernel.json",
    "bank_parallelism": "BENCH_bankpar.json",
    "reliability_sweep": "BENCH_reliability.json",
}

MODULES = [
    "fig03_fracdram_success",
    "fig04_process_variation",
    "fig05_majm_speedup_model",
    "table1_nrg_discovery",
    "fig11_input_replication",
    "fig14_maj3_success",
    "fig15_majm_success",
    "fig16_spatial_success",
    "fig17_microbenchmarks",
    "bank_parallelism",
    "fig18_nrg_sensitivity",
    "fig19_destruction",
    "fig20_realworld",
    "kernel_bench",
    "reliability_sweep",
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--emit-dir", default=None, metavar="DIR",
                    help="write BENCH_*.json baselines for the gated "
                         "modules into DIR")
    args = ap.parse_args()
    use_compile_cache()
    if args.emit_dir:
        os.makedirs(args.emit_dir, exist_ok=True)
    print("name,us_per_call,derived")
    failed = []
    for name in MODULES:
        if args.only and args.only not in name:
            continue
        try:
            mod = importlib.import_module(f"benchmarks.{name}")
            rows = mod.run()
            for bname, us, derived in rows:
                print(f"{bname},{us},\"{derived}\"", flush=True)
            if args.emit_dir and name in BENCH_NAMES:
                path = common.emit_bench_json(
                    name, rows, os.path.join(args.emit_dir,
                                             BENCH_NAMES[name]))
                print(f"# wrote {path}", file=sys.stderr)
            else:
                common.drain_counters()  # never leak across modules
        except Exception:  # noqa: BLE001 — keep the suite running
            failed.append(name)
            common.drain_counters()
            print(f"{name},-1,\"FAILED: "
                  f"{traceback.format_exc().splitlines()[-1]}\"", flush=True)
    if failed:
        print(f"# {len(failed)} module(s) failed: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
