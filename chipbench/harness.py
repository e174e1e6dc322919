"""The benchmark harness: runs one cell of ``BENCHMARK.json``.

It is driven by data. Everything that belongs to one configuration, one
traffic mix or one metric sits in a file of its own, found by the name
``BENCHMARK.json`` gives it:

* ``configs/<config>.json``: sizes, source, cuts, guarantees, device
  settings (the ``file`` of the configuration);
* ``configs/<config>.py``: the data made from the seed, the timed query
  through ``repro.pum``, the plain NumPy reference and the control;
* ``traffic/<mix>.json``: the mix, read by :mod:`chipbench.loadgen`;
* ``end_to_end/<metric>.py`` and ``layers/<metric>.py``: one reader per
  metric, ``read(window) -> number | None`` over a :class:`Window`.

A run: make the data and a device, warm every shape the traffic will use
(set-up), measure for ``seconds``, free the program's state, compare
every answer of the window with the reference, print the result.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import time
import traceback

import numpy as np

from chipbench import loadgen, peaks, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRACE_DIR = ".chipbench_trace"
# Answers are exact counts: the limit of each number compared is 0.
LIMITS = {"max_count_gap": 0, "unanswered": 0}


class NoChip(RuntimeError):
    """JAX finds no accelerator, or fewer chips than the cell asks for."""


def load_bench(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json"), encoding="utf-8") as f:
        return json.load(f)


def _one(entries: list, name: str, what: str) -> dict:
    hits = [e for e in entries if e["name"] == name]
    if len(hits) != 1:
        raise KeyError(f"BENCHMARK.json has no {what} named {name!r}")
    return hits[0]


def load_module(path: str):
    """Import a file of the benchmark by its path (names may hold '-')."""
    name = "chipbench_" + os.path.relpath(path, HERE).replace(os.sep, "_") \
        .replace("-", "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` with its files loaded."""
    name: str
    chips: int
    config: dict
    traffic: dict
    module: object
    end_to_end: list
    per_layer: list


def load_cell(bench: dict, workload: str, root: str = ROOT) -> Cell:
    wl = _one(bench["workloads"], workload, "workload")
    entry = _one(bench["configs"], wl["config"], "configuration")
    path = os.path.join(root, entry["file"])
    with open(path, encoding="utf-8") as f:
        config = json.load(f)
    module = load_module(os.path.splitext(path)[0] + ".py")
    module.check(config)
    with open(os.path.join(root, "chipbench", "traffic",
                           wl["traffic"] + ".json"), encoding="utf-8") as f:
        traffic = json.load(f)
    return Cell(workload, int(wl["chips"]), config, traffic, module,
                [m for m in bench["end_to_end"] if applies(m, workload)],
                [m for m in bench["per_layer"] if applies(m, workload)])


def reader(kind: str, name: str, root: str = ROOT):
    """The ``read`` function of ``chipbench/<kind>/<name>.py``."""
    return load_module(os.path.join(root, "chipbench", kind,
                                    name + ".py")).read


@dataclasses.dataclass
class Query:
    params: dict
    answer: int | None = None
    error: str | None = None
    latency_s: float = 0.0      # open loop: from the time it was due
    late_s: float = 0.0         # open loop: how late it started
    nbytes: int = 0


@dataclasses.dataclass
class Window:
    """What the metric readers read: the window's queries, its length,
    set-up, and (in a traced run) the program's spans and counters and
    the reduced device trace."""
    queries: list
    seconds: float
    setup_s: float
    spans: list | None = None
    counters: object = None
    trace: dict | None = None
    peak: dict | None = None

    @property
    def answered(self) -> list:
        return [q for q in self.queries if q.answer is not None]

    @property
    def n_queries(self) -> int:
        return len(self.answered)


def open_chip(chips: int):
    """JAX, once it finds ``chips`` TPU chips, with the persistent
    compilation cache on; :class:`NoChip` otherwise."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        raise NoChip(f"the cell needs {chips} TPU chip(s); JAX finds "
                     f"{len(devices)} {devices[0].platform!r} device(s)")
    from repro.backends import use_compile_cache
    use_compile_cache()
    # Cache every program, however fast it compiled, so that only a
    # checkout's first run compiles.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax


class Session:
    """A cell's device and data after set-up, ready to measure."""

    def __init__(self, cell: Cell, seed: int, t0: float):
        self.cell, self.seed, self.t0 = cell, seed, t0
        self.phases: dict[str, float] = {}
        t = time.perf_counter()
        jax = open_chip(cell.chips)
        import repro.pum as pum
        self.jax, self.pum = jax, pum
        self.kind = jax.devices()[0].device_kind
        t = self._phase("chip", t)
        mod = cell.module
        self.data = mod.make_data(cell.config,
                                  loadgen.rng(seed, loadgen.DATA))
        t = self._phase("data", t)
        self.dev = pum.device(**cell.config["device"])
        self.traffic = loadgen.Traffic(cell.traffic, cell.config, seed)
        self.tracing = False
        for i, params in enumerate(self.traffic.warmup()):
            q = self.query(params)
            if q.error is not None:
                raise RuntimeError(f"warm-up query {params} failed:\n"
                                   f"{q.error}")
            if i == 0:  # stages and uploads the data, loads or compiles
                t = self._phase("first query", t)
        self._phase("rest of warm-up", t)

    def _phase(self, name: str, since: float) -> float:
        now = time.perf_counter()
        self.phases[name] = now - since
        return now

    def mark(self, label: str):
        if self.tracing:
            return self.jax.profiler.TraceAnnotation(
                trace_reduce.PREFIX + label)
        return contextlib.nullcontext()

    def query(self, params: dict, due: float | None = None) -> Query:
        mod, cfg = self.cell.module, self.cell.config
        q = Query(params, nbytes=mod.query_bytes(cfg, params))
        t = time.perf_counter()
        try:
            with self.mark("query"):
                q.answer = int(mod.run_query(self.dev, self.data, params,
                                             self.mark))
        except Exception:  # a failed query is counted, not fatal
            q.error = traceback.format_exc()
            print(q.error, file=sys.stderr)
        done = time.perf_counter()
        q.latency_s = done - (t if due is None else due)
        q.late_s = 0.0 if due is None else max(0.0, t - due)
        return q

    def closed_loop(self, seconds: float) -> tuple[list, float]:
        out = []
        stream = self.traffic.stream()
        t_start = time.perf_counter()
        end = t_start + seconds
        while time.perf_counter() < end:
            out.append(self.query(next(stream)))
            if out[-1].error is not None:
                break
        return out, time.perf_counter() - t_start

    def open_loop(self, seconds: float, rate: float | None = None
                  ) -> tuple[list, float]:
        out = []
        arrivals = self.traffic.arrivals(seconds, rate)
        t_start = time.perf_counter()
        give_up = t_start + 2 * seconds
        for due, params in arrivals:
            t_due = t_start + due
            now = time.perf_counter()
            if now < t_due:
                time.sleep(t_due - now)
            elif now > give_up:  # hopelessly behind: the rest never ran
                out.append(Query(params, error="never ran: backlog"))
                continue
            out.append(self.query(params, due=t_due))
        return out, time.perf_counter() - t_start

    def measure(self, seconds: float, trace: bool = False,
                root: str = ROOT) -> Window:
        loop = (self.closed_loop if self.traffic.loop == "closed"
                else self.open_loop)
        trace_dir = os.path.join(root, TRACE_DIR, self.cell.name)
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = self.jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # Python calls: costly, not read
            opts.enable_hlo_proto = False
            self.jax.profiler.start_trace(trace_dir, profiler_options=opts)
            self.tracing = True
        profile = (self.pum.profile(self.dev) if trace
                   else contextlib.nullcontext())
        with profile as tracer:
            setup_s = time.perf_counter() - self.t0
            with self.mark("window"):
                queries, length = loop(seconds)
        w = Window(queries, length, setup_s)
        if trace:
            self.tracing = False
            self.jax.profiler.stop_trace()
            w.spans = tracer.events
            w.counters = self.dev.counters
            events = trace_reduce.load(trace_reduce.find_xplane(trace_dir))
            w.trace = trace_reduce.summarize(events, self.cell.chips)
            w.peak = peaks.peak(self.kind)
            # A profiler that dropped events would undercount busy time.
            print(f"trace: {w.trace['query_spans']} query spans for "
                  f"{len(queries)} queries, {w.trace['op_events']} device "
                  f"op events", file=sys.stderr)
        return w

    def device_info(self) -> dict:
        devices = self.jax.devices()
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices)
        return {"platform": devices[0].platform, "kind": self.kind,
                "count": len(devices), "memory_peak_bytes": int(peak)}

    def release(self) -> None:
        """Free the program's state before the reference runs."""
        self.dev.close()
        self.dev = None
        gc.collect()


def steadiness(queries: list) -> str:
    """Median latency of each quarter of the window, in the order the
    queries were sent, and the slowest query with its place in the
    window: a first quarter far above the others means that something
    still warmed up inside the window, a lone slow query a stall."""
    lat = [q.latency_s * 1e3 for q in queries if q.answer is not None]
    k = len(lat) // 4
    if not k:
        return f"window: {len(lat)} queries answered"
    quarters = [float(np.median(lat[i * k:(i + 1) * k])) for i in range(4)]
    worst = int(np.argmax(lat))
    return (f"window: {len(lat)} queries answered; median ms by quarter "
            + " ".join(f"{x:.3f}" for x in quarters)
            + f"; slowest {lat[worst]:.3f} ms, query {worst}")


def compare(answers: list, references: list) -> dict:
    """Each number compared, beside its limit."""
    gaps = [abs(a - r) for a, r in zip(answers, references)
            if a is not None]
    return {"max_count_gap": {"value": max(gaps, default=0),
                              "limit": LIMITS["max_count_gap"]},
            "unanswered": {"value": sum(a is None for a in answers),
                           "limit": LIMITS["unanswered"]}}


def passes(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())


def metrics(cell: Cell, w: Window, trace: bool, root: str = ROOT) -> dict:
    """The cell's end-to-end metrics (``trace`` off) or per-layer metrics
    (``trace`` on), each from its own reader; a reader that finds nothing
    to read leaves its metric out."""
    kind, entries = (("layers", cell.per_layer) if trace
                     else ("end_to_end", cell.end_to_end))
    out = {}
    for m in entries:
        value = reader(kind, m["name"], root)(w)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run(workload: str, seed: int, seconds: float, trace: bool, t0: float,
        root: str = ROOT) -> dict:
    """One run of one cell of the benchmark at ``root``; returns the
    result line as a dict."""
    cell = load_cell(load_bench(root), workload, root)
    s = Session(cell, seed, t0)
    w = s.measure(seconds, trace=trace, root=root)
    print("set-up seconds: " + ", ".join(
        f"{k} {v:.3f}" for k, v in s.phases.items()), file=sys.stderr)
    print(steadiness(w.queries), file=sys.stderr)
    device = s.device_info()
    s.release()
    queries = [q.params for q in w.queries]
    refs = cell.module.reference(cell.config, s.data, queries)
    checks = compare([q.answer for q in w.queries], refs)
    failed = sum(q.answer is None or q.answer != r
                 for q, r in zip(w.queries, refs))
    result = {"correct": bool(w.queries) and failed == 0 and passes(checks),
              "attempted": len(w.queries), "failed": failed,
              "metrics": metrics(cell, w, trace, root), "device": device}
    if trace:
        device["busy_s"] = w.trace["busy_s"]
        device["window_s"] = w.trace["window_s"]
        result["breakdown"] = {"device_ops": w.trace["device_ops"],
                               "idle_gaps": w.trace["idle_gaps"]}
    result["checks"] = checks
    return result


def emit(result: dict) -> None:
    """Checks as the last lines of standard error, then the result as the
    last line of standard output."""
    sys.stdout.flush()
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
