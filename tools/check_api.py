#!/usr/bin/env python3
"""Public-API snapshot test for ``repro.pum``.

The ``repro.pum`` surface is the repo's one stable contract: this script
compares the *actual* exports (module ``__all__`` + the public attribute
surface of ``PumArray``/``Device``/``EngineConfig`` + the fused
evaluators and eager dataplanes of ``repro.backends``) against the frozen
snapshot below and exits 1 on any drift — an accidentally-added export
fails CI just like a removed one.

Intentional surface changes update ``EXPECTED`` here (run with
``--print`` to emit the current surface) and ``docs/api.md`` together.

Usage: ``PYTHONPATH=src python tools/check_api.py [--print]``
"""

from __future__ import annotations

import sys

# The frozen public surface. Dunders are part of the contract: PumArray's
# operator set IS the API.
EXPECTED = {
    "repro.pum": [
        "CapturedProgram", "CounterBank", "Device",
        "EngineConfig", "EngineStats", "FlushHandle", "LAYOUT32",
        "LAYOUT64", "PlaneLayout", "PumArray",
        "ReliabilityConfig", "ReliabilityMap", "Tracer",
        "TunedPlan", "Tuner", "WorkloadProfile",
        "asarray", "calibrate", "default_device", "device", "get_layout",
        "profile",
    ],
    "PumArray": [
        "__add__", "__and__", "__array__", "__array_priority__",
        "__array_ufunc__", "__bool__", "__divmod__", "__eq__",
        "__floordiv__", "__ge__", "__getitem__", "__gt__", "__hash__",
        "__init__", "__le__", "__len__",
        "__lt__", "__mod__", "__mul__", "__ne__", "__or__", "__radd__",
        "__rand__", "__rdivmod__", "__repr__", "__rfloordiv__", "__rmod__",
        "__rmul__", "__ror__", "__rsub__", "__rxor__", "__sub__",
        "__xor__", "astype", "device", "dtype", "ndim", "popcount",
        "reduce_bits", "reshape", "shape", "size", "sum", "to_numpy",
    ],
    "Device": [
        "__enter__", "__exit__", "__init__", "__repr__", "asarray",
        "autotune", "calibrate", "capture", "charge", "client", "close",
        "config", "counters", "flush", "flush_async", "latency_ms",
        "layout", "reliability", "reset_counters", "reset_stats", "stats",
        "width",
    ],
    "EngineConfig": [
        "backend", "banks", "chained", "cmd_buffer_lookahead",
        "controller", "donate_leaves",
        "flush_memory_bytes", "flush_threshold", "fuse", "fused_backend",
        "layout", "leaf_cache_bytes", "mfr",
        "ref_postponing", "reliability", "row_bits",
        "seed", "success_db", "use_pulsar", "width",
    ],
    # The fused evaluators (each runs the 64-bit layout under its own
    # name where it runs it at all) and the eager dataplanes.
    "backends": ["fast", "pallas-tpu", "ref-vertical", "shard-words",
                 "sim", "words-cpu"],
}

_SKIP = {"__module__", "__qualname__", "__doc__", "__slots__", "__dict__",
         "__weakref__", "__dataclass_fields__", "__dataclass_params__",
         "__match_args__", "__annotations__", "__firstlineno__",
         "__static_attributes__", "__parameters__", "__orig_bases__",
         "__replace__"}


def _class_surface(cls) -> list[str]:
    """Names the class itself defines: public attributes plus dunders
    (the operator contract); single-underscore internals excluded."""
    return sorted(
        n for n in vars(cls)
        if n not in _SKIP
        and not (n.startswith("_") and not n.startswith("__")))


def actual_surface() -> dict[str, list[str]]:
    import repro.pum as pum
    from repro.backends import DATAPLANES, EVALUATORS

    missing = [n for n in pum.__all__ if not hasattr(pum, n)]
    if missing:
        raise AssertionError(f"__all__ names missing from module: {missing}")
    # Accidental exports: public module attributes beyond __all__
    # (submodules excluded — `import repro.pum.api` necessarily binds them).
    import types
    stray = sorted(
        n for n, v in vars(pum).items()
        if not n.startswith("_") and n not in pum.__all__
        and not isinstance(v, types.ModuleType))
    return {
        "repro.pum": sorted(pum.__all__) + [f"<stray:{n}>" for n in stray],
        "PumArray": _class_surface(pum.PumArray),
        "Device": _class_surface(pum.Device),
        "EngineConfig": sorted(
            f.name for f in
            __import__("dataclasses").fields(pum.EngineConfig)),
        "backends": sorted((*EVALUATORS, *DATAPLANES)),
    }


def main(argv: list[str] | None = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    got = actual_surface()
    if "--print" in argv:
        import pprint
        pprint.pprint(got)
        return 0
    failures = []
    for key, want in EXPECTED.items():
        have = got[key]
        if sorted(want) != have:
            extra = sorted(set(have) - set(want))
            lost = sorted(set(want) - set(have))
            failures.append(
                f"{key}: surface drift"
                + (f" — unexpected exports {extra}" if extra else "")
                + (f" — missing exports {lost}" if lost else ""))
    if failures:
        print("repro.pum public-API snapshot mismatch:", file=sys.stderr)
        for f in failures:
            print(f"  {f}", file=sys.stderr)
        print("(intentional? update tools/check_api.py EXPECTED and "
              "docs/api.md together; `--print` emits the current surface)",
              file=sys.stderr)
        return 1
    print(f"check_api: repro.pum surface OK "
          f"({sum(len(v) for v in got.values())} names)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
