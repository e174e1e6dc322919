#!/usr/bin/env python3
"""Chip benchmark of ``repro.pum``: one run of one cell of BENCHMARK.json.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Run it from the root of a checkout, on a machine whose JAX sees the TPU
chips the cell asks for; without them it exits non-zero and prints no
result. Set-up (data made from the seed, device, warm-up of every shape
the cell's traffic uses) counts as ``setup_s``; then the window runs for
``--seconds``. Every answer of the window is compared with the
configuration's NumPy reference afterwards. The last lines of standard
error give each number compared beside its limit; the last line of
standard output is the result as one JSON object. ``--trace 1`` runs the
window under the JAX profiler and reports the per-layer metrics instead
of the end-to-end ones. JAX's compilation cache is kept at
``$JAX_COMPILATION_CACHE_DIR``, else at ``.jax_cache`` in the checkout;
the trace goes to ``.chipbench_trace/<cell>`` in the checkout.
"""

import time

T0 = time.perf_counter()  # set-up is timed from here

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    try:
        from chipbench import harness
        import repro.pum  # noqa: F401  (the system under test)
    except ImportError as e:
        print(f"chipbench: cannot import the benchmark or the system "
              f"under test ({e}); run from the root of a checkout",
              file=sys.stderr)
        return 2
    try:
        result = harness.run(args.workload, args.seed, args.seconds,
                             bool(args.trace), T0)
    except harness.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 1
    harness.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
