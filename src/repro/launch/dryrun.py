"""Multi-pod dry-run (deliverable e): lower + compile EVERY
(architecture x input-shape) cell on the production meshes.

  python -m repro.launch.dryrun --arch qwen3-1.7b --shape train_4k
  python -m repro.launch.dryrun --all --multi-pod --out results/dryrun

For each cell: .lower() -> .compile() must succeed; we record
memory_analysis() (proves it fits), cost_analysis() (FLOPs/bytes for the
roofline), and the collective-byte census parsed from the post-SPMD HLO.

Run as a program, it forces 512 host devices (``XLA_FLAGS``) before JAX
starts its backend (the device count locks on first backend init).
Importing the module sets nothing, so tests and benches that import
``parse_collective_bytes`` see the real devices.
"""

import argparse
import dataclasses
import json
import os
import re
import time
import traceback

import jax

from repro.config.base import ARCH_IDS, LM_SHAPES, get_config, shapes_for
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import build_cell

# HLO collective ops whose operand bytes count toward the collective term.
COLLECTIVE_RE = re.compile(
    r"^\s*(?:\S+\s*=\s*)?"
    r"((?:[a-z0-9-]+)?(?:all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start|-done)?)"
    r"\(", re.MULTILINE)
SHAPE_RE = re.compile(r"(f32|bf16|f16|s32|u32|s8|u8|pred|s64|u64|f64)"
                      r"\[([0-9,]*)\]")

DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1,
               "u8": 1, "pred": 1, "s64": 8, "u64": 8, "f64": 8}

COLLECTIVE_FACTOR = {  # per-chip wire traffic multiplier on local bytes
    "all-reduce": 2.0,          # ring AR = RS + AG
    "all-gather": 1.0,
    "reduce-scatter": 1.0,
    "all-to-all": 1.0,
    "collective-permute": 1.0,
}


def parse_collective_bytes(hlo_text: str) -> dict:
    """Sum operand bytes of every collective in the (post-SPMD, per-device)
    HLO, split by whether the instruction sits in the entry computation or
    inside a while-loop body region.

    XLA's cost_analysis (and a naive text census) counts while bodies ONCE,
    not x trip-count — every lax.scan (layer stacks, chunked attention)
    under-reports. The roofline layer multiplies the 'region' bucket by the
    cell's dominant loop trip count (the layer scan).

    Returns {kind: bytes, 'total_weighted': ..., 'region_weighted': ...}.
    """
    out: dict[str, float] = {}
    weighted = 0.0
    region_weighted = 0.0
    in_region = False
    for line in hlo_text.splitlines():
        ls = line.strip()
        # Computation block headers: scan bodies/conditions are %region_*.
        if ls.endswith("{") and (ls.startswith("%") or
                                 ls.startswith("ENTRY")):
            in_region = ls.startswith("%region")
            continue
        m = re.search(
            r"=\s*(\S+?)\s+"
            r"((?:all-gather|all-reduce|reduce-scatter|all-to-all|"
            r"collective-permute)(?:-start)?)\(", ls)
        if not m:
            continue
        shape_str, kind = m.group(1), m.group(2)
        kind_base = kind.replace("-start", "")
        nbytes = 0
        for dt, dims in SHAPE_RE.findall(shape_str):
            n = 1
            if dims:
                for d in dims.split(","):
                    if d:
                        n *= int(d)
            nbytes += n * DTYPE_BYTES[dt]
        out[kind_base] = out.get(kind_base, 0) + nbytes
        w = COLLECTIVE_FACTOR[kind_base] * nbytes
        weighted += w
        if in_region:
            region_weighted += w
    out["total_weighted"] = weighted
    out["region_weighted"] = region_weighted
    return out


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             overrides: dict | None = None) -> dict:
    shape = LM_SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "2x16x16" if multi_pod else "16x16",
           "n_devices": mesh.size}
    t0 = time.time()
    with mesh:
        cell = build_cell(arch, shape, mesh, overrides=overrides)
        lowered = cell.lower()
        compiled = lowered.compile()
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        rec["lower_compile_s"] = round(time.time() - t0, 1)
        rec["memory"] = {
            k: int(getattr(mem, k))
            for k in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "generated_code_size_in_bytes")
            if hasattr(mem, k)}
        rec["flops"] = float(cost.get("flops", -1.0))
        rec["bytes_accessed"] = float(cost.get("bytes accessed", -1.0))
        rec["transcendentals"] = float(cost.get("transcendentals", 0.0))
        hlo = compiled.as_text()
        rec["collectives"] = parse_collective_bytes(hlo)
        rec["hlo_bytes"] = len(hlo)
    return rec


def _run_one_inline(arch: str, sname: str, multi_pod: bool,
                    out: str | None) -> dict:
    tag = f"{arch} x {sname} x {'2x16x16' if multi_pod else '16x16'}"
    try:
        rec = run_cell(arch, sname, multi_pod)
        rec["status"] = "ok"
        print(f"[dryrun] OK   {tag}: flops={rec['flops']:.3e} "
              f"argbytes={rec['memory'].get('argument_size_in_bytes', 0):.3e} "
              f"temp={rec['memory'].get('temp_size_in_bytes', 0):.3e} "
              f"coll={rec['collectives']['total_weighted']:.3e} "
              f"({rec['lower_compile_s']}s)", flush=True)
    except Exception as e:  # noqa: BLE001 — report, keep sweeping
        rec = {"arch": arch, "shape": sname,
               "mesh": "2x16x16" if multi_pod else "16x16",
               "status": "fail", "error": f"{type(e).__name__}: {e}",
               "traceback": traceback.format_exc()[-2000:]}
        print(f"[dryrun] FAIL {tag}: {rec['error']}", flush=True)
    if out:
        with open(out, "a") as f:
            f.write(json.dumps(rec) + "\n")
    jax.clear_caches()  # bound host RAM across the 80-cell sweep
    return rec


def _run_one_subprocess(arch: str, sname: str, multi_pod: bool, out: str,
                        timeout_s: int) -> dict:
    """Per-cell worker-process isolation: one OOM-killed or hung compile
    can't take down the sweep (same supervision posture as the trainer)."""
    import subprocess
    import sys
    tag = f"{arch} x {sname} x {'2x16x16' if multi_pod else '16x16'}"
    argv = [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
            "--shape", sname, "--out", out]
    if multi_pod:
        argv.append("--multi-pod")
    try:
        proc = subprocess.run(argv, timeout=timeout_s,
                              capture_output=True, text=True)
        if proc.returncode == 0:
            for line in proc.stdout.splitlines():
                if line.startswith("[dryrun] OK") or \
                        line.startswith("[dryrun] FAIL"):
                    print(line, flush=True)
            return {"status": "ok"}
        err = {"arch": arch, "shape": sname,
               "mesh": "2x16x16" if multi_pod else "16x16",
               "status": "fail",
               "error": f"worker exit {proc.returncode} "
                        f"(OOM-killed?): {proc.stdout[-300:]}"}
    except subprocess.TimeoutExpired:
        err = {"arch": arch, "shape": sname,
               "mesh": "2x16x16" if multi_pod else "16x16",
               "status": "fail", "error": f"timeout after {timeout_s}s"}
    print(f"[dryrun] FAIL {tag}: {err['error'][:160]}", flush=True)
    with open(out, "a") as f:
        f.write(json.dumps(err) + "\n")
    return err


def _done_cells(out: str | None) -> set:
    done = set()
    if out and os.path.exists(out):
        with open(out) as f:
            for line in f:
                r = json.loads(line)
                if r.get("status") == "ok":
                    done.add((r["arch"], r["shape"], r["mesh"]))
    return done


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(LM_SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default=None, help="JSONL output path")
    ap.add_argument("--isolate", action="store_true",
                    help="one worker subprocess per cell + resume")
    ap.add_argument("--cell-timeout", type=int, default=3600)
    args = ap.parse_args()

    cells: list[tuple[str, str]] = []
    if args.all:
        for arch in ARCH_IDS:
            for sname in shapes_for(arch):
                cells.append((arch, sname))
    else:
        if not args.arch or not args.shape:
            ap.error("--arch and --shape (or --all) required")
        cells = [(args.arch, args.shape)]

    meshes = [args.multi_pod]
    if args.both_meshes:
        meshes = [False, True]

    done = _done_cells(args.out) if args.isolate else set()
    results = []
    for multi_pod in meshes:
        mesh_name = "2x16x16" if multi_pod else "16x16"
        for arch, sname in cells:
            if (arch, sname, mesh_name) in done:
                print(f"[dryrun] SKIP {arch} x {sname} x {mesh_name} "
                      f"(already ok)", flush=True)
                continue
            if args.isolate:
                rec = _run_one_subprocess(arch, sname, multi_pod, args.out,
                                          args.cell_timeout)
            else:
                rec = _run_one_inline(arch, sname, multi_pod, args.out)
            results.append(rec)
    n_ok = sum(r["status"] == "ok" for r in results)
    print(f"[dryrun] {n_ok}/{len(results)} cells compiled "
          f"({len(done)} skipped as done)")
    if n_ok < len(results):
        raise SystemExit(1)


if __name__ == "__main__":
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=512")
    main()
