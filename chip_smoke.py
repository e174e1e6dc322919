#!/usr/bin/env python3
"""Smoke run of the fused ``repro.pum`` dataplane on a TPU at deployment size.

    python chip_smoke.py [--seed N]     # one chip
    python chip_smoke.py --chips 4      # the four-chip shard-words path only

It drives the main path the way a user does: ``pum.device(...)``, the
Appendix B application kernels of ``repro.core.realworld``, one fused
program per flush. Every answer is checked against plain NumPy on data
made from ``--seed``; any mismatch or exception exits non-zero. Wall
times are printed for information only: they include host staging and,
on the first call, compilation.

One chip:
  (a) bitmap index: ``bmi_active_users`` over 30 daily bitmaps of 2**28
      users (960 MiB) on ``pum.device(width=32)``, three times (the first
      compiles, the later ones hit the pipeline and leaf caches);
  (a') the same query with ``fused_backend="words-cpu"`` pinned, which
      must run jitted on the chip (no flush computed in host NumPy);
  (b) range scan: ``bitweaving_scan`` over 2**26 rows of 16-bit values
      in 32-bit lanes.
Four chips (``--chips 4``): phase (a) through ``shard-words`` across the
four chips, whose output shards must sit on four distinct devices, whose
cached leaves must be committed split over the four, and whose second
run must place no leaf byte (the leaves stay resident); then the same
query through ``pallas-tpu`` on device 0; both counts must agree.

The script fails before any work unless JAX's first device is a TPU. The
last line of standard output is one JSON object naming the device, and
is printed only on success. ``JAX_COMPILATION_CACHE_DIR`` places the
compile cache; unset, it goes to ``.jax_cache`` in this checkout.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

DAYS = 30
USERS = 1 << 28          # bitmap-index users: 30 x 32 MiB of daily bitmaps
SCAN_ROWS = 1 << 26      # range-scan column rows
SCAN_BITS = 16           # value width of the scanned column
BMI_RUNS = 3
# Device settings of a deployment that keeps its bitmaps resident: the
# leaf cache holds all 30 days (960 MiB), and one query stays one flush.
DEVICE_KW = dict(leaf_cache_bytes=2 << 30, flush_memory_bytes=4 << 30)


class SmokeFailure(Exception):
    """A check of the smoke run failed."""


def fail(msg: str) -> None:
    raise SmokeFailure(msg)


def log(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def bmi_data(rng, np):
    """30 daily activity bitmaps (packed uint64, 2**28 users). A fixed
    eighth of the users is active every day; everyone else is active on
    a given day with probability 1/2, so the answer is about 2**25."""
    words = USERS // 64
    loyal = (rng.integers(0, 1 << 64, words, dtype=np.uint64)
             & rng.integers(0, 1 << 64, words, dtype=np.uint64)
             & rng.integers(0, 1 << 64, words, dtype=np.uint64))
    days = rng.integers(0, 1 << 64, (DAYS, words), dtype=np.uint64)
    days |= loyal
    want = int(np.bitwise_count(np.bitwise_and.reduce(days, axis=0)).sum())
    return days, want


def phase_stats(c) -> str:
    flushes = int(c.get("engine.flushes"))
    auto = {k.rsplit(".", 1)[-1]: int(v)
            for k, v in c.as_dict()["counters"].items()
            if k.startswith("engine.autoflush.")}
    hits = int(c.get("engine.leaf_cache.hits"))
    misses = int(c.get("engine.leaf_cache.misses"))
    return (f"flushes={flushes} autoflush={auto} leaf_cache_hits={hits} "
            f"leaf_cache_misses={misses}")


def flush_devices(counters) -> tuple[int, int]:
    """(min, max) devices the phase's flush outputs were split across;
    0 means a flush computed in host NumPy."""
    try:
        h = counters.histogram("engine.flush_devices")
    except KeyError:
        fail("no flush was recorded")
    return int(h["min"]), int(h["max"])


def peak_bytes(jax) -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def run_bmi(phase, pum, realworld, jax, np, days, want, runs=BMI_RUNS,
            **device_kw):
    """Run the bitmap-index query ``runs`` times on a fresh device and
    return (count, counters, evaluator name, leaf bytes placed by each
    run, the devices each committed leaf spans)."""
    dev = pum.device(width=32, **DEVICE_KW, **device_kw)
    if not dev.config.fuse:
        fail(f"{phase}: the device fell back to eager execution")
    from repro.backends import fused_evaluator
    name = fused_evaluator(dev.layout, leaf_bytes=days.nbytes,
                           pinned=dev.config.fused_backend)
    log(phase, f"evaluator={name} days={DAYS} users={USERS} "
               f"bitmaps_mib={days.nbytes >> 20}")
    got = None
    placed = []
    with pum.profile(dev):
        for i in range(runs):
            before = dev.counters.get("engine.leaf_bytes_placed")
            t0 = time.perf_counter()
            got, _, _ = realworld.bmi_active_users(dev, days, verify=True)
            wall = time.perf_counter() - t0
            if got != want:
                fail(f"{phase}: run {i + 1} counted {got} active users, "
                     f"NumPy counts {want}")
            placed.append(int(dev.counters.get("engine.leaf_bytes_placed")
                              - before))
            log(phase, f"run {i + 1}: active_users={got} (numpy {want}) "
                       f"wall_s={wall:.6f} leaf_bytes_placed={placed[-1]} "
                       f"({'first call' if i == 0 else 'warm'}, "
                       f"informational)")
    counters = dev.counters.snapshot()
    leaf_devices = [len(e.dev.sharding.device_set)
                    for e in dev.engine._leaf_cache._entries.values()
                    if e.dev is not None]
    dev.close()
    return got, counters, name, placed, leaf_devices


def one_chip(pum, realworld, jax, np, seed: int) -> None:
    rng = np.random.default_rng(seed)
    days, want = bmi_data(rng, np)

    # (a) the default evaluator: pallas-tpu on a TPU.
    _, c, name, _, _ = run_bmi("bmi", pum, realworld, jax, np, days, want)
    if name != "pallas-tpu":
        fail(f"bmi: fused_evaluator chose {name!r}, not 'pallas-tpu'")
    if c.get("engine.leaf_cache.hits") <= 0:
        fail("bmi: warm runs never hit the leaf cache")
    if c.get("engine.pipeline_cache.hit") <= 0:
        fail("bmi: warm runs never hit the pipeline cache")
    lo, hi = flush_devices(c)
    if lo < 1:
        fail("bmi: a flush ran on the host")
    log("bmi", phase_stats(c)
        + f" flush_devices={lo}..{hi} peak_bytes_in_use={peak_bytes(jax)}")

    # (a') the word evaluator pinned: jitted on the chip, never NumPy.
    _, c, name, _, _ = run_bmi("bmi-words", pum, realworld, jax, np, days,
                               want, runs=2, fused_backend="words-cpu")
    lo, hi = flush_devices(c)
    if lo < 1:
        fail(f"bmi-words: {name} computed a flush in host NumPy")
    log("bmi-words", phase_stats(c)
        + f" flush_devices={lo}..{hi} (jitted on the device) "
          f"peak_bytes_in_use={peak_bytes(jax)}")
    del days

    # (b) range scan over a 16-bit column in 32-bit lanes.
    column = rng.integers(0, 1 << SCAN_BITS, SCAN_ROWS, dtype=np.uint64)
    c1, c2 = sorted(int(v) for v in rng.integers(1, (1 << SCAN_BITS) - 1, 2))
    want = int(((column >= c1) & (column <= c2)).sum())
    dev = pum.device(width=SCAN_BITS, **DEVICE_KW)
    if not dev.config.fuse or dev.layout.word_bits != 32:
        fail("scan: expected a fused device on 32-bit lanes")
    from repro.backends import fused_evaluator
    name = fused_evaluator(dev.layout, leaf_bytes=column.nbytes)
    if name != "pallas-tpu":
        fail(f"scan: fused_evaluator chose {name!r}, not 'pallas-tpu'")
    log("scan", f"evaluator={name} rows={SCAN_ROWS} bits={SCAN_BITS} "
                f"range=[{c1}, {c2}]")
    with pum.profile(dev):
        for i in range(2):
            t0 = time.perf_counter()
            got, _, _ = realworld.bitweaving_scan(dev, column, c1, c2)
            wall = time.perf_counter() - t0
            if got != want:
                fail(f"scan: counted {got} rows, NumPy counts {want}")
            log("scan", f"run {i + 1}: rows_in_range={got} (numpy {want}) "
                        f"wall_s={wall:.6f} "
                        f"({'first call' if i == 0 else 'warm'}, "
                        f"informational)")
    c = dev.counters.snapshot()
    dev.close()
    lo, hi = flush_devices(c)
    if lo < 1:
        fail("scan: a flush ran on the host")
    log("scan", phase_stats(c)
        + f" flush_devices={lo}..{hi} peak_bytes_in_use={peak_bytes(jax)}")


def four_chips(pum, realworld, jax, np, seed: int) -> None:
    n_dev = len(jax.devices())
    if n_dev != 4:
        fail(f"--chips 4 needs four devices, JAX sees {n_dev}")
    rng = np.random.default_rng(seed)
    days, want = bmi_data(rng, np)
    got_sh, c, _, placed, leaf_devices = run_bmi(
        "bmi-shard", pum, realworld, jax, np, days, want, runs=2,
        fused_backend="shard-words")
    lo, hi = flush_devices(c)
    if lo != 4 or hi != 4:
        fail(f"bmi-shard: outputs split across {lo}..{hi} devices, not 4")
    if len(leaf_devices) != DAYS or set(leaf_devices) != {4}:
        fail(f"bmi-shard: committed leaves span {sorted(set(leaf_devices))}"
             f" devices ({len(leaf_devices)} of {DAYS} committed), not 4")
    if placed[1] != 0:
        fail(f"bmi-shard: the second run placed {placed[1]} leaf bytes; "
             f"the leaves should stay resident")
    log("bmi-shard", phase_stats(c)
        + f" output shards on {hi} distinct devices, {DAYS} leaves "
          f"resident on 4 devices, leaf bytes placed by run {placed}")
    got_p, c, name, _, _ = run_bmi("bmi-pallas", pum, realworld, jax, np,
                                   days, want, runs=1)
    if name != "pallas-tpu":
        fail(f"bmi-pallas: fused_evaluator chose {name!r}")
    if got_p != got_sh:
        fail(f"shard-words counted {got_sh}, pallas-tpu counted {got_p}")
    log("bmi-pallas", f"count {got_p} equals the shard-words count")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()
    try:
        import numpy as np
        import jax
        import repro.pum as pum
        from repro.backends import use_compile_cache
        from repro.core import realworld
    except ImportError as e:
        print(f"chip_smoke: cannot import the repro package ({e}); run "
              f"this script from the root of a checkout", file=sys.stderr)
        return 2
    platform = jax.devices()[0].platform
    if platform != "tpu":
        print(f"chip_smoke: no TPU is attached (JAX's first device is "
              f"{platform!r}); this run needs the chip", file=sys.stderr)
        return 1
    print(f"compile cache: {use_compile_cache()}", flush=True)
    try:
        if args.chips == 4:
            four_chips(pum, realworld, jax, np, args.seed)
        else:
            one_chip(pum, realworld, jax, np, args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    d = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
