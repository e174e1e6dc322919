"""PumArray + Device: the ndarray-like operator frontend over the engine.

``PumArray`` is the one caller-visible value type: it wraps whichever
representation the engine produced (an eager ndarray, a pending
``LazyArray`` of the fused graph, or raw packed-bitmap words) behind
operator overloading, and materializes on demand (``to_numpy()`` /
``np.asarray``). ``Device`` owns the engine an array computes on; used as
a context manager it scopes the default device for :func:`asarray` and
auto-flushes pending work on exit.

>>> import numpy as np
>>> import repro.pum as pum
>>> with pum.device(width=8) as dev:
...     x = dev.asarray(np.array([3, 5, 250], np.uint64))
...     y = (x + 6) * x                  # records into the fused graph
>>> y.to_numpy()                         # flushed on scope exit
array([27, 55,  0], dtype=uint64)
>>> q, r = divmod(y, np.array([4, 7, 9], np.uint64))
>>> np.asarray(q), np.asarray(r)         # one restoring-division pass
(array([6, 7, 0], dtype=uint64), array([3, 6, 0], dtype=uint64))

Plane-wise operators (``&``/``|``/``^``) on out-of-width operands route
through the engine's raw packed-bitmap path (bit-exact on full uint64
words); arithmetic computes modulo ``2**width`` and rejects out-of-width
operands loudly in fused mode — exactly the :class:`PulsarEngine`
contract, now behind one type.
"""

from __future__ import annotations

import contextlib
import dataclasses

import numpy as np

from repro.core.engine import LazyArray, LazySum, _as_array
from repro.pum.config import EngineConfig

# Innermost active `with device(...)` last; module default built lazily.
_ACTIVE: list["Device"] = []
_DEFAULT: "Device | None" = None


class Device:
    """One PuM compute device: an engine plus its configuration.

    Construction goes through :class:`EngineConfig` (keyword overrides
    accepted), handed whole to the engine; the fused evaluator a flush
    runs on is ``repro.backends.fused_evaluator``'s choice. As a context
    manager the device becomes the scoped default for :func:`asarray`
    and flushes any pending fused graph on exit.
    """

    def __init__(self, config: EngineConfig | None = None, **overrides):
        if config is None:
            config = EngineConfig(**overrides)
        elif overrides:
            config = config.replace(**overrides)
        from repro.core.engine import PulsarEngine
        self.engine = PulsarEngine(config)
        self._scalars: dict[tuple, np.ndarray] = {}

    @property
    def config(self) -> EngineConfig:
        """The :class:`EngineConfig` this device's engine runs."""
        return self.engine.config

    # -- array construction / lifecycle -------------------------------- #

    def asarray(self, x) -> "PumArray":
        """Wrap ``x`` as a :class:`PumArray` on this device (no compute,
        no charge — arrays enter the dataplane when an op consumes them).
        """
        if isinstance(x, PumArray):
            return x if x.device is self else PumArray(self, x.to_numpy())
        return PumArray(self, np.asarray(x, np.uint64))

    def flush(self) -> None:
        """Materialize every pending fused op graph — all client
        contexts, parked retries, and in-flight async flushes (no-op when
        eager or empty; never touches the cost plane)."""
        self.engine.flush_all()

    def flush_async(self):
        """Compile + dispatch the calling context's pending graph off
        this thread (double-buffered: the caller stages the next flush
        while the worker dispatches the current one). Returns a
        :class:`~repro.core.engine.FlushHandle`; ``result()`` waits and
        re-raises a failed dispatch after parking the graph for retry,
        exactly like a failed synchronous flush."""
        return self.engine.flush_async()

    def capture(self, fn, name: str | None = None):
        """Capture ``fn(*PumArrays) -> PumArray(s)`` as a
        :class:`~repro.pum.capture.CapturedProgram`: first call per input
        shape records + compiles; later calls replay the compiled pipeline
        with zero re-recording (cost charges replay identically)."""
        from repro.pum.capture import CapturedProgram
        return CapturedProgram(self, fn, name=name)

    def client(self, name: str):
        """Scope ops to a named client context (``with dev.client("a"):``)
        — its own recording graph and stats shard, so N logical clients
        share the device without interleaving their programs."""
        return self.engine.client(name)

    def close(self) -> None:
        """Shut the async flush worker down (waits for in-flight
        dispatches); safe to call repeatedly, recreated lazily on the
        next ``flush_async``."""
        self.engine.close()

    def __enter__(self) -> "Device":
        _ACTIVE.append(self)
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        _ACTIVE.remove(self)
        if exc_type is None:
            self.flush()
        self.close()

    # -- cost plane ----------------------------------------------------- #

    @property
    def stats(self):
        """Accumulated :class:`~repro.core.engine.EngineStats` charges."""
        return self.engine.stats

    @property
    def counters(self):
        """The engine's telemetry :class:`~repro.telemetry.CounterBank`
        (flush/pipeline-cache/auto-flush counters — populated only while
        a tracer is attached, e.g. inside :func:`profile`; the
        ``reliability.*`` counters are recorded whenever the reliability
        plane is active)."""
        return self.engine.counters

    @property
    def reliability(self):
        """The engine's :class:`~repro.reliability.ReliabilityPlane`
        (None unless configured or :meth:`calibrate`-attached)."""
        return self.engine.reliability

    def calibrate(self, *, inject: bool = False, attach: bool = True,
                  n_subarrays: int = 4, n_columns: int = 256,
                  n_patterns: int = 8, configs=None,
                  process_variation: float | None = None,
                  seed: int | None = None, save=None, **policy):
        """Profile this device's simulated chip into a
        :class:`~repro.reliability.ReliabilityMap` and (by default) attach
        it: subsequent ops plan their fig-11 replication factor from the
        calibrated per-bank/per-subarray success rates and placement
        steers onto strong banks. With ``inject=True`` the flush-time
        fault-injection + replication-vote/retry loop also turns on
        (requires a fused device). Extra keyword ``policy`` fields
        (``votes``, ``max_attempts``, ``min_margin``, ``target_success``,
        ``steer``, ``flip_scale``, reliability ``seed``) go to the
        :class:`~repro.reliability.ReliabilityConfig`.

        Calibration is seeded from the device config (same device config
        => bit-identical map in any process); ``save=`` persists the map
        as ``.npz`` for reuse via
        ``ReliabilityConfig(map="path.npz")``. The default profile sizes
        are test-scale — production calibration passes larger
        ``n_subarrays``/``n_columns``/``n_patterns``. Returns the map.
        """
        from repro.reliability import (ReliabilityConfig, ReliabilityPlane,
                                       calibrate)
        cfg = self.config
        rmap = calibrate(
            cfg.mfr, banks=cfg.banks, n_subarrays=n_subarrays,
            n_columns=n_columns, n_patterns=n_patterns, configs=configs,
            seed=cfg.seed if seed is None else seed,
            process_variation=process_variation)
        if save is not None:
            rmap.save(save)
        if attach:
            if inject and not cfg.fuse:
                raise ValueError(
                    "reliability fault injection hooks the fused dispatch "
                    "path; this device runs eager (fuse=False)")
            rcfg = ReliabilityConfig(map=rmap, inject=inject, **policy)
            self.engine.reliability = ReliabilityPlane(
                rcfg, mfr=cfg.mfr, counters=self.engine.counters)
            # Planning/placement caches were computed without the map.
            self.engine._best_cfg_cache.clear()
            self.engine._batch_cache.clear()
            self.engine.config = cfg.replace(reliability=rcfg)
        return rmap

    def reset_stats(self) -> None:
        self.engine.reset_stats()

    def reset_counters(self) -> None:
        """Clear the telemetry :class:`~repro.telemetry.CounterBank` in
        place (the engine — and an attached reliability plane — keep
        writing into the same bank), starting a fresh measurement window
        without recreating the device. For overlapping windows on a live
        device prefer ``counters.snapshot()`` + ``counters.delta()``."""
        self.engine.counters.clear()

    # -- autotuning ----------------------------------------------------- #

    def autotune(self, profile=None, *, apply: bool = True,
                 cost_plane: bool = False, space=None, tuner=None,
                 online: bool = False, window_flushes: int = 16,
                 explore_every: int = 8, drift_threshold: float = 0.5,
                 save=None):
        """Tune this device's execution config from measured telemetry.

        ``profile`` is a :class:`~repro.autotune.WorkloadProfile` (or a
        counter window to extract one from); by default it is taken from
        the device's accumulated counters — run the workload under
        :func:`profile` first (engine counters populate only while a
        tracer is attached). The :class:`~repro.autotune.Tuner` searches
        the discrete config space and returns the frozen
        :class:`~repro.autotune.TunedPlan`; with ``apply=True`` (default)
        the plan's *execution* knobs — fused backend, plane layout,
        auto-flush bounds, crossbar lookahead — are applied live to this
        device. Execution knobs change only where/when programs run:
        outputs and ``EngineStats`` are bit-identical to the static
        config (pinned by tests/autotune). ``cost_plane=True``
        additionally applies the REF-postponing recommendation, which
        changes the *modeled* refresh schedule and therefore EngineStats
        — an explicit opt-in.

        ``online=True`` installs an
        :class:`~repro.autotune.OnlineAutotuner` on the engine: every
        ``window_flushes`` flushes it profiles the counter delta and
        re-tunes when the drift detector fires (exploit) or every
        ``explore_every`` windows (explore). ``save=`` persists the plan
        (``.json``/``.npz``, see ``TunedPlan.save``). Returns the plan
        (``None`` with ``online=True`` before the first window closes).
        """
        from repro.autotune import (OnlineAutotuner, Tuner,
                                    WorkloadProfile)
        if not self.config.fuse:
            raise ValueError(
                "autotune targets the fused execution pipeline; this "
                "device runs eager (fuse=False)")
        if tuner is None:
            tuner = Tuner(space=space, drift_threshold=drift_threshold)
        if online:
            self.engine.autotuner = OnlineAutotuner(
                self, tuner=tuner, window_flushes=window_flushes,
                explore_every=explore_every,
                drift_threshold=drift_threshold)
            if profile is None:
                return None  # first window closes at flush granularity
        if profile is None:
            profile = WorkloadProfile.from_device(self)
        elif not isinstance(profile, WorkloadProfile):
            profile = WorkloadProfile.from_counters(
                profile, width=self.config.width,
                word_bits=self.config.resolved_layout().word_bits)
        plan = tuner.tune(profile, self.config)
        if apply:
            self._apply_plan(plan, cost_plane=cost_plane)
        if online and self.engine.autotuner is not None:
            self.engine.autotuner.plan = plan
        if save is not None:
            plan.save(save)
        return plan

    def _apply_plan(self, plan, *, cost_plane: bool = False,
                    flush: bool = True) -> None:
        """Reconfigure the live engine to a ``TunedPlan`` (the
        ``calibrate()`` idiom: replace the engine's config, rebuild what
        derives from it, drop stale caches). With ``flush=True`` pending graphs
        flush first so backend/layout flips never split a recorded
        program across lane formats; the online autotuner calls with
        ``flush=False`` from inside the flush path and the
        backend/layout switch is then deferred while graphs are
        pending."""
        # A fuse flip cannot be applied to a live engine (it would
        # rebuild the whole execution pipeline mid-stream); the
        # recommendation stays on the returned plan for the caller to
        # construct a new device from.
        old = self.config
        plan = dataclasses.replace(plan, fuse=old.fuse)
        cfg = plan.apply(old, cost_plane=cost_plane)
        eng = self.engine
        if flush:
            eng.flush_all()
        with eng._lock:
            pending = bool(eng._inflight) or any(
                g is not None and getattr(g, "ops", None)
                for g in eng._slots.values())
            if pending:
                cfg = cfg.replace(fused_backend=old.fused_backend,
                                  layout=old.layout)
            if cost_plane and cfg.ref_postponing != old.ref_postponing \
                    and cfg.controller == "auto":
                from repro.controller import MemoryController
                from repro.core.cost_model import CostModel as _EngineCost
                eng.controller = MemoryController(
                    n_banks=cfg.banks, postponing=cfg.ref_postponing,
                    lookahead=cfg.cmd_buffer_lookahead)
                eng.cost = _EngineCost(row_bits=cfg.row_bits,
                                       controller=eng.controller)
            eng.config = cfg
            eng.layout = cfg.resolved_layout()
            # Planning/batch caches were computed under the old config.
            eng._best_cfg_cache.clear()
            eng._batch_cache.clear()

    @property
    def latency_ms(self) -> float:
        return self.engine.latency_ms

    @property
    def width(self) -> int:
        return self.config.width

    @property
    def layout(self):
        """The engine's :class:`~repro.kernels.plane_layout.PlaneLayout`
        (the lane word format fused programs compile against)."""
        return self.engine.layout

    def charge(self, kind: str, n_elems: int, width: int | None = None,
               n_planes: int | None = None) -> None:
        """Charge the cost plane for work the host performs on the PuM
        array's behalf (e.g. a popcount over raw 64-bit bitmap words that
        the dataplane computes host-side). Dataplane ops charge
        themselves — this is for explicitly modeled extra passes."""
        self.engine._charge(kind, n_elems, width=width, n_planes=n_planes)

    # -- op dispatch (PumArray operators land here) --------------------- #

    def _op(self, name: str, *operands):
        return getattr(self.engine, "_" + name)(*operands)

    def _broadcast_scalar(self, value, shape: tuple) -> np.ndarray:
        """One shared array per (scalar, shape): handing the engine the
        SAME object on every use lets the fused graph's id()-keyed leaf
        dedup hit, instead of snapshotting a fresh full-size leaf per op.
        Entries are O(1) read-only broadcast views (the engine copies at
        snapshot time anyway), so the bounded cache stays tiny."""
        key = (int(value), shape)
        arr = self._scalars.get(key)
        if arr is None:
            if len(self._scalars) >= 64:
                self._scalars.clear()
            arr = np.broadcast_to(np.uint64(value), shape)
            self._scalars[key] = arr
        return arr

    def __repr__(self) -> str:
        c = self.config
        mode = "fused" if c.fuse else "eager"
        return (f"Device({c.mfr}:{c.width}w:{c.banks}b, "
                f"backend={c.backend!r}, {mode})")


class PumArray:
    """ndarray-like handle for a value on a PuM device.

    Wraps eager ndarrays and pending fused-graph handles behind one type;
    operators record/execute through the owning device's engine and
    charge the cost plane exactly like the engine methods they replace.
    ``to_numpy()`` / ``np.asarray`` materialize (flushing the fused graph
    if pending); ``sum``/``reshape``/``astype`` materialize and return
    plain ndarrays.
    """

    __slots__ = ("_device", "_data")
    # Keep NumPy from consuming us element-wise: binary ops with ndarrays
    # return NotImplemented on the ndarray side and come back through our
    # reflected methods.
    __array_ufunc__ = None
    __array_priority__ = 1000

    def __init__(self, device: Device, data):
        self._device = device
        self._data = data

    # -- introspection -------------------------------------------------- #

    @property
    def device(self) -> Device:
        return self._device

    @property
    def shape(self) -> tuple:
        return self._data.shape

    @property
    def size(self) -> int:
        return self._data.size

    @property
    def ndim(self) -> int:
        return self._data.ndim

    @property
    def dtype(self):
        return np.dtype(np.uint64)

    def __len__(self) -> int:
        if not self.shape:
            raise TypeError("len() of unsized PumArray")
        return self.shape[0]

    def __getitem__(self, idx) -> "PumArray":
        """Basic (NumPy-style) indexing along the lane axes.

        Eager values slice to **views** (no copy, no charge — the lanes
        were already materialized); a pending fused-graph handle forces a
        materialize first (one flush), then slices: a slice is a host
        access pattern, not a dataplane op, so it cannot extend the
        recorded program. Integer indexing yields a 0-d PumArray (use
        ``int(x[i])`` / ``to_numpy()`` for a Python scalar)."""
        data = self._data
        if isinstance(data, LazyArray):
            data = data.materialize()
        out = data[idx]
        if not isinstance(out, np.ndarray):  # 0-d from integer indexing
            out = np.asarray(out, np.uint64)
        return PumArray(self._device, out)

    def __repr__(self) -> str:
        pending = getattr(self._data, "_value", self._data) is None
        state = "pending" if pending else "materialized"
        return f"PumArray(shape={self.shape}, {state}, on {self._device})"

    # -- materialization ------------------------------------------------ #

    def to_numpy(self) -> np.ndarray:
        """The value as a uint64 ndarray (flushes the fused graph if this
        handle is pending): the held value itself, not a copy."""
        return np.asarray(self._data, np.uint64)

    def __array__(self, dtype=None, copy=None):
        return _as_array(self.to_numpy(), dtype, copy)

    def sum(self, *args, **kw):
        """The total of the elements: the host's NumPy sum, except that
        ``sum()`` of a pending value on a fused device returns a pending
        0-d :class:`~repro.core.engine.LazySum`, which the flush computes
        on the device where it can (``PulsarEngine._sum``). Reading it
        gives the same ``np.uint64``; it reads the partial sums as an
        array of this device (``to_numpy``)."""
        if isinstance(self._data, LazyArray):
            parts = self._device.engine._sum(self._data, *args, **kw)
            if parts is not None:
                return LazySum(PumArray(self._device, parts))
        return self.to_numpy().sum(*args, **kw)

    def reshape(self, *shape, **kw) -> np.ndarray:
        return self.to_numpy().reshape(*shape, **kw)

    def astype(self, dtype, **kw) -> np.ndarray:
        return self.to_numpy().astype(dtype, **kw)

    # -- operator frontend ---------------------------------------------- #

    def _operand(self, other):
        """Unwrap/conform the second operand: same-device PumArrays pass
        their underlying handle through (extending the fused graph);
        foreign-device arrays materialize; scalars broadcast to this
        array's shape so the op stays fusable."""
        if isinstance(other, PumArray):
            return other._data if other._device is self._device \
                else other.to_numpy()
        arr = np.asarray(other, np.uint64)
        if arr.ndim == 0 and self.shape:
            arr = self._device._broadcast_scalar(arr[()], self.shape)
        return arr

    def _binop(self, name: str, other, reflect: bool = False):
        a, b = self._data, self._operand(other)
        if reflect:
            a, b = b, a
        return PumArray(self._device, self._device._op(name, a, b))

    def __and__(self, other):
        return self._binop("and", other)

    def __rand__(self, other):
        return self._binop("and", other, reflect=True)

    def __or__(self, other):
        return self._binop("or", other)

    def __ror__(self, other):
        return self._binop("or", other, reflect=True)

    def __xor__(self, other):
        return self._binop("xor", other)

    def __rxor__(self, other):
        return self._binop("xor", other, reflect=True)

    def __add__(self, other):
        return self._binop("add", other)

    def __radd__(self, other):
        return self._binop("add", other, reflect=True)

    def __sub__(self, other):
        return self._binop("sub", other)

    def __rsub__(self, other):
        return self._binop("sub", other, reflect=True)

    def __mul__(self, other):
        return self._binop("mul", other)

    def __rmul__(self, other):
        return self._binop("mul", other, reflect=True)

    def __floordiv__(self, other):
        return self._binop("div", other)

    def __rfloordiv__(self, other):
        return self._binop("div", other, reflect=True)

    def __mod__(self, other):
        return self._binop("mod", other)

    def __rmod__(self, other):
        return self._binop("mod", other, reflect=True)

    def __divmod__(self, other):
        """(quotient, remainder) sharing ONE restoring-division pass (the
        fused-ISA ``divmod`` tuple op; one cost-plane division charge)."""
        q, r = self._device._op("divmod", self._data,
                                self._operand(other))
        return PumArray(self._device, q), PumArray(self._device, r)

    def __rdivmod__(self, other):
        q, r = self._device._op("divmod", self._operand(other),
                                self._data)
        return PumArray(self._device, q), PumArray(self._device, r)

    def __lt__(self, other):
        """Unsigned ``self < other`` per lane -> 0/1 PumArray."""
        return self._binop("less_than", other)

    def __gt__(self, other):
        return self._binop("less_than", other, reflect=True)

    def _not(self, bit: "PumArray") -> "PumArray":
        ones = self._device._broadcast_scalar(1, bit.shape)
        return PumArray(self._device,
                        self._device._op("xor", bit._data, ones))

    def __le__(self, other):
        """``self <= other`` == NOT(other < self): one compare + one
        plane XOR (both charged — that is what the DRAM would run)."""
        return self._not(self.__gt__(other))

    def __ge__(self, other):
        return self._not(self.__lt__(other))

    def popcount(self, width: int | None = None) -> "PumArray":
        """Per-element set-bit count over ``width`` planes (device width
        by default)."""
        return PumArray(self._device,
                        self._device._op("popcount", self._data, width))

    def reduce_bits(self, kind: str, width: int | None = None
                    ) -> "PumArray":
        """Per-element AND/OR/XOR reduction across the element's bits."""
        return PumArray(self._device,
                        self._device._op("reduce_bits", self._data, kind,
                                         width))

    # -- ndarray comparison/truth semantics (values, not identity) ------ #

    def __eq__(self, other):
        return self.to_numpy() == np.asarray(other)

    def __ne__(self, other):
        return self.to_numpy() != np.asarray(other)

    __hash__ = None  # unhashable, like ndarray

    def __bool__(self):
        return bool(self.to_numpy())


# --------------------------------------------------------------------- #
# Module-level device scoping
# --------------------------------------------------------------------- #


def device(config: EngineConfig | None = None, **overrides) -> Device:
    """Build a :class:`Device` from an :class:`EngineConfig` (or keyword
    overrides of the defaults). Use as a context manager to scope it as
    the default device and auto-flush on exit::

        with pum.device(mfr="M", width=32, controller="auto") as dev:
            y = dev.asarray(x) + x2
    """
    return Device(config, **overrides)


def default_device() -> Device:
    """The innermost active ``with device(...)`` scope, else a process-wide
    default ``Device(EngineConfig())`` built on first use."""
    global _DEFAULT
    if _ACTIVE:
        return _ACTIVE[-1]
    if _DEFAULT is None:
        _DEFAULT = Device(EngineConfig())
    return _DEFAULT


def asarray(x, device: Device | None = None) -> PumArray:
    """Wrap ``x`` as a :class:`PumArray` on ``device`` (default: the
    scoped/default device)."""
    return (device or default_device()).asarray(x)


@contextlib.contextmanager
def profile(device: Device | None = None, path: str | None = None):
    """Trace one device's fused flushes for the duration of the block.

    Attaches a fresh :class:`~repro.telemetry.Tracer` to ``device`` (the
    scoped/default device when omitted), flushes any still-pending graph
    on exit so the trace is complete, then detaches. Yields the tracer;
    with ``path`` the Chrome trace-event JSON (plus the device's counters)
    is written there on exit — open it in Perfetto or ``chrome://tracing``.

        with pum.profile(path="trace.json") as tr:
            y = pum.asarray(x) + x2
        print(tr.span_names())   # flush.record ... flush.unpack

    Profiling is observational only: results, ``Device.stats`` and the
    scheduled command streams are bit-identical with or without it
    (tested in tests/telemetry)."""
    from repro.telemetry import Tracer

    dev = device if device is not None else default_device()
    tracer = Tracer()
    prev = dev.engine.tracer
    dev.engine.tracer = tracer
    try:
        yield tracer
    finally:
        try:
            dev.flush()  # complete the trace: pending graphs span-ify
        finally:
            dev.engine.tracer = prev
            if path is not None:
                tracer.export(path, counters=dev.engine.counters)
