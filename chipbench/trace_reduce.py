"""Reduce a JAX profiler trace of one benchmark window to device numbers.

The run wraps its window and each query in ``jax.profiler.TraceAnnotation``
spans named ``chipbench.<label>`` (``window``, ``query``, and a query's own
phases such as ``build`` and ``materialize``). From the ``.xplane.pb`` the
reduction keeps those host spans and the operations of each device plane
(``/device:TPU:<n>``, line ``XLA Ops``), and computes, inside the window:

* ``busy_s``: the union of the intervals in which an operation ran on a
  device, averaged over the chips the cell uses. Only the op line counts,
  so transfers between host and device are not busy time;
* ``window_s``: the length of the ``window`` span;
* ``device_ops``: seconds per kind of operation (the HLO instruction's
  name without its number: ``copy``, ``reshape``, ``bit_transpose32``,
  ``run_program_pallas``), the ten largest;
* ``idle_gaps``: the device's idle seconds, split by what the host was
  doing meanwhile: the innermost benchmark span then open, or
  ``between queries`` where none was. The ten largest.

``load`` reads the trace into plain lists and ``summarize`` works on
those, so the reduction can be checked on made-up intervals too.
"""

from __future__ import annotations

import glob
import os
import re

PREFIX = "chipbench."
OP_LINE = "XLA Ops"
IDLE_LABEL = "between queries"
_DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:(\d+)$")
_OP_NUMBER = re.compile(r"\.\d+$")


def op_kind(name: str) -> str:
    """``%copy.63 = s32[...] copy(...)`` -> ``copy``."""
    return _OP_NUMBER.sub("", name.split(" = ", 1)[0].lstrip("%"))


def find_xplane(trace_dir: str) -> str:
    """The single ``.xplane.pb`` a profiler session wrote under
    ``trace_dir``."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under "
                                f"{trace_dir}, found {len(paths)}")
    return paths[0]


def load(xplane_path: str) -> dict:
    """``{"host": [[label, t0_ns, t1_ns], ...], "devices": {plane:
    [[op, t0_ns, t1_ns], ...]}}``: the benchmark's host spans and the op
    line of every device plane."""
    from jax.profiler import ProfileData

    host, devices = [], {}
    for plane in ProfileData.from_file(xplane_path).planes:
        if _DEVICE_PLANE.match(plane.name):
            ops = []
            for line in plane.lines:
                if line.name == OP_LINE:
                    ops.extend([op_kind(e.name), int(e.start_ns),
                                int(e.start_ns + e.duration_ns)]
                               for e in line.events)
            devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        host.append([e.name[len(PREFIX):], int(e.start_ns),
                                     int(e.start_ns + e.duration_ns)])
    return {"host": host, "devices": devices}


def _union(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    """Disjoint sorted union of ``intervals`` clipped to ``[lo, hi]``."""
    out: list[list[int]] = []
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _segments(spans, lo: int, hi: int) -> list[tuple[int, int, str]]:
    """Cut ``[lo, hi]`` at every span boundary and label each piece with
    the innermost span open over it (spans of one thread nest)."""
    marks = []
    for label, a, b in spans:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            # At one instant, closes come before opens, and an outer span
            # opens before the inner one it holds.
            marks.append((b, 0, 0, label))
            marks.append((a, 1, -(b - a), label))
    marks.sort()
    out, stack, t = [], [], lo
    for when, kind, _, label in marks:
        if when > t:
            out.append((t, when, stack[-1] if stack else IDLE_LABEL))
            t = when
        if kind:
            stack.append(label)
        else:  # spans closing at one instant may come in any order
            del stack[len(stack) - 1 - stack[::-1].index(label)]
    if hi > t:
        out.append((t, hi, stack[-1] if stack else IDLE_LABEL))
    return out


def _idle_by_label(busy, segments) -> dict[str, int]:
    """Nanoseconds of each segment's label not covered by ``busy``."""
    out: dict[str, int] = {}
    i = 0
    for a, b, label in segments:
        free = b - a
        while i < len(busy) and busy[i][1] <= a:
            i += 1
        j = i
        while j < len(busy) and busy[j][0] < b:
            free -= min(b, busy[j][1]) - max(a, busy[j][0])
            j += 1
        if free:
            out[label] = out.get(label, 0) + free
    return out


def summarize(events: dict, n_chips: int = 1, top: int = 10) -> dict:
    """Device numbers of the one ``window`` span in ``events``."""
    windows = [(a, b) for label, a, b in events["host"] if label == "window"]
    if len(windows) != 1:
        raise ValueError(f"expected one window span, found {len(windows)}")
    lo, hi = windows[0]
    planes = sorted(events["devices"],
                    key=lambda p: int(_DEVICE_PLANE.match(p).group(1)))
    planes = planes[:n_chips]
    if not planes:
        raise ValueError("the trace holds no device plane")
    spans = [s for s in events["host"] if s[0] != "window"]
    segs = _segments(spans, lo, hi)
    busy_ns = 0
    ops: dict[str, int] = {}
    idle: dict[str, int] = {}
    for p in planes:
        evs = events["devices"][p]
        busy = _union(((a, b) for _, a, b in evs), lo, hi)
        busy_ns += sum(b - a for a, b in busy)
        for name, a, b in evs:
            d = min(b, hi) - max(a, lo)
            if d > 0:
                ops[name] = ops.get(name, 0) + d
        for label, ns in _idle_by_label(busy, segs).items():
            idle[label] = idle.get(label, 0) + ns
    n = len(planes)

    def top_list(d):
        ranked = sorted(d.items(), key=lambda kv: -kv[1])[:top]
        return [[k, v / n / 1e9] for k, v in ranked]

    return {"busy_s": busy_ns / n / 1e9, "window_s": (hi - lo) / 1e9,
            "device_ops": top_list(ops), "idle_gaps": top_list(idle),
            "query_spans": sum(s[0] == "query" for s in spans),
            "op_events": sum(len(events["devices"][p]) for p in planes)}
