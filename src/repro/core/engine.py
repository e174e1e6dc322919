"""PulsarEngine — the PuM compute engine behind the ``repro.pum`` API.

The public way to use this system is :mod:`repro.pum` (``PumArray``
operator frontend + ``Device``/``EngineConfig`` + the backend registry);
``PulsarEngine``'s dataplane *method* surface (``add``/``and_``/…) is kept
as a thin compat shim that emits ``DeprecationWarning`` and delegates to
the private implementations the new API calls directly. Construction,
``stats``/``reset_stats``, ``flush`` and the cost-plane helpers
(``op_effective_ns``) are NOT deprecated — ``Device`` wraps them.

Two coupled planes:
  * dataplane: bit-exact results. ``backend="fast"`` computes on packed
    NumPy words via the same bit-plane algorithms (vectorized, scales to
    millions of elements; the TPU-accelerated variant of these inner loops is
    kernels/ — same algorithms, Pallas-tiled). ``backend="sim"`` routes every
    operation through the DRAM chip model + command programs (bit-exact AND
    cycle-exact; used by tests and small demos).
  * cost plane: every op is priced by the closed-form cost model with the
    paper's methodology (per-op best-throughput N_RG, stable-lane efficiency,
    optional multi-bank parallelism) so application benchmarks (Fig 20)
    report PuM latencies regardless of dataplane backend.

Fused execution (``fuse=True``, backend="fast" only): dataplane ops record
into a lazy op graph and return ``LazyArray`` handles; ``flush()`` (or any
value access) compiles the whole graph into ONE jit'd bit-plane pipeline
(kernels/fused_program.py) — on TPU operands transpose to vertical layout
once, the Pallas program runs fused, outputs transpose back once; on CPU
the same program fuses in the word domain (transposes cancel, so they are
elided — same semantics, validated in tests). This mirrors in
silicon what PULSAR's chained staging does in the DRAM command stream
(§5.2): batch the op sequence, pay the staging cost once. The *cost plane
is invariant*: every op is charged at record time exactly as in eager mode,
so EngineStats (and fig17/fig20 numbers) are identical in both modes.

The whole integer op set is in the fused ISA — including ``mul``
(shift-add over the add plane) and ``div``/``mod`` (restoring division
over the add/sub planes) — so complete workloads compile to one trace.
Before compilation the recorded graph is normalized (CSE + dead-node
pruning, ``fused_program.optimize_program``); auto-flush thresholds
(``flush_threshold`` recorded ops / ``flush_memory_bytes`` estimated graph
bytes) bound graph growth for long-running callers. Only the sim backend
stays eager-only.

Width semantics: fused arithmetic computes modulo 2**width (the vertical
layout holds ``width`` planes); arithmetic operands with bits at or above
``width`` are rejected at record time rather than silently truncated,
because eager ops compute on raw uint64 values. The *plane-wise* ops
(``and_``/``or_``/``xor``) instead switch to a raw packed-bitmap mode on
out-of-width operands: each 64-bit word reinterprets onto the plane
layout's lanes (two 32-bit lanes per word on the 32-bit layout, the word
itself on the 64-bit layout — bit-exact for bitwise ops at any value
range; this is what realworld's packed-bitmap kernels route through),
and the lanes are re-joined at materialization. Cost charging is
identical either way: ops are priced on the caller-visible element count
before the dataplane splits lanes.

Plane layouts: the lane word format is an explicit
:class:`~repro.kernels.plane_layout.PlaneLayout` (default: the narrowest
canonical layout holding ``width`` — 32-bit up to width 32, 64-bit
above). The fused pipeline, leaf snapshots and the raw lane split all
derive from it, and evaluator selection filters the backend registry by
it — width-64 fused execution is the 64-bit layout plus the additively
registered ``*-64`` evaluators, not a special case. ``fused_backend``
pins a specific registered fused evaluator by name (e.g. the
multi-device ``"shard-words"`` pipeline).
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import threading
import time
import warnings
import weakref
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from repro.backends import get_backend, select_backend
from repro.core.charact import SuccessRateDb, default_db
from repro.core.cost_model import CostModel, OpCost, ZERO
from repro.core.geometry import PAPER_MODULE
from repro.core.profiles import PROFILES
from repro.kernels import fused_program as _fused
from repro.kernels.fused_program import (FusedOp, FusedProgram, get_pipeline,
                                         optimize_program,
                                         with_fault_injection)
from repro.kernels.plane_layout import (PlaneLayout, get_layout,
                                        layout_for_width)
from repro.telemetry import NULL_TRACER, CounterBank


def _warn_deprecated(method: str, replacement: str) -> None:
    """One-line compat-shim warning: the PulsarEngine op methods survive
    for out-of-tree callers, but in-repo code goes through repro.pum."""
    warnings.warn(
        f"PulsarEngine.{method}() is deprecated; use {replacement} "
        f"(repro.pum — migration table in docs/api.md)",
        DeprecationWarning, stacklevel=3)


@dataclasses.dataclass
class EngineStats:
    """Accumulated cost-plane charges for one engine session.

    Units: ``latency_ns`` and ``refresh_stall_ns`` in nanoseconds,
    ``energy_j`` in joules (per-command energies derive from pJ-scale
    DDR4 IDD figures in the cost model), ``n_sequences`` counts
    row-activation command sequences, ``lane_efficiency`` is the minimum
    success rate (0..1] over the ops used. Charges accrue at op-issue
    time in both eager and fused modes (fused ``flush()`` never touches
    this object), so the two modes are stats-identical by construction.
    """
    latency_ns: float = 0.0
    energy_j: float = 0.0
    n_sequences: int = 0
    lane_efficiency: float = 1.0  # min success rate over ops used
    refresh_stall_ns: float = 0.0  # controller-modeled REF interference

    def as_dict(self) -> dict:
        """Plain-JSON snapshot with explicit units in the key names — the
        same schema telemetry JSON (``BENCH_*.json``) embeds."""
        return {
            "latency_ns": self.latency_ns,
            "energy_j": self.energy_j,
            "n_sequences": self.n_sequences,
            "lane_efficiency": self.lane_efficiency,
            "refresh_stall_ns": self.refresh_stall_ns,
        }

    def __repr__(self) -> str:
        # Defined in the body so @dataclass keeps it (units explicit:
        # the raw ns/J floats render unreadably at DRAM scales).
        return (f"EngineStats(latency={self.latency_ns:,.1f} ns, "
                f"energy={self.energy_j * 1e6:,.3f} uJ, "
                f"sequences={self.n_sequences:,}, "
                f"lane_efficiency={self.lane_efficiency:.4f}, "
                f"refresh_stall={self.refresh_stall_ns:,.1f} ns)")

    def charge(self, cost: OpCost, n_vec_rows: int, banks: int,
               success: float, batch=None) -> None:
        if batch is None:
            # Legacy closed-form divide: ideal bank-level parallelism.
            eff_rows = -(-n_vec_rows // banks)
            self.latency_ns += cost.latency_ns * eff_rows
        else:
            # Controller-scheduled pricing: the measured bank-parallel
            # speedup (tFAW/tRRD/bus-limited, <= banks) and the steady-state
            # refresh slowdown replace the ideal divide.
            speedup = max(1.0, batch.parallel_speedup)
            base = max(cost.latency_ns * n_vec_rows / speedup,
                       cost.latency_ns * (-(-n_vec_rows // banks)))
            total = base * batch.refresh_factor
            self.latency_ns += total
            self.refresh_stall_ns += total - base
        self.energy_j += cost.energy_j * n_vec_rows
        self.n_sequences += cost.n_sequences * n_vec_rows
        self.lane_efficiency = min(self.lane_efficiency, success)


class FlushHandle:
    """Future-like handle for one :meth:`PulsarEngine.flush_async`.

    ``result()`` blocks until the dispatched graph(s) materialize (after
    which every LazyArray the flush covered holds its value) and re-raises
    the flush error on failure — a failed async flush parks its graph for
    retry exactly like a failed synchronous ``flush()``, so a later
    ``flush()``/``materialize()`` recovers the pending handles."""

    __slots__ = ("_future",)

    def __init__(self, future=None):
        self._future = future  # None => the flush had nothing to dispatch

    def done(self) -> bool:
        return self._future is None or self._future.done()

    def result(self, timeout: float | None = None) -> None:
        """Wait for the dispatch; re-raises the flush failure, if any."""
        if self._future is not None:
            self._future.result(timeout)

    def exception(self, timeout: float | None = None):
        if self._future is None:
            return None
        return self._future.exception(timeout)

    def __repr__(self) -> str:
        state = "done" if self.done() else "in-flight"
        return f"FlushHandle({state})"


class LazyArray:
    """Handle for a value pending in the engine's fused op graph.

    Behaves like a read-only array: ``np.asarray`` (or ``materialize()``)
    triggers ``engine.flush()`` on first access. Feeding it back into engine
    ops extends the graph instead of materializing.
    """

    __slots__ = ("_engine", "_graph", "_op_idx", "shape", "__weakref__",
                 "_value")

    def __init__(self, engine: "PulsarEngine", graph: "_OpGraph",
                 op_idx: int, shape: tuple):
        self._engine = engine
        self._graph = graph
        self._op_idx = op_idx
        self.shape = shape
        self._value: np.ndarray | None = None

    @property
    def size(self) -> int:
        n = 1
        for d in self.shape:
            n *= d
        return n

    @property
    def ndim(self) -> int:
        return len(self.shape)

    @property
    def dtype(self):
        return np.dtype(np.uint64)

    def materialize(self) -> np.ndarray:
        if self._value is None:
            g, eng = self._graph, self._engine
            if g is not None and eng is not None:
                # Route to the owning graph: it may belong to another
                # client context, sit on the retry list after a failed
                # flush, or be in flight on the async flush worker — the
                # engine dispatches or waits as appropriate.
                eng._materialize_graph(g)
            elif eng is not None:
                eng.flush()
        if self._value is None:
            raise RuntimeError(
                "LazyArray failed to materialize: the engine flush that "
                "should have produced it did not complete")
        return self._value

    def __array__(self, dtype=None, copy=None):
        v = self.materialize()
        return v.astype(dtype) if dtype is not None else v

    # ndarray conveniences the app kernels lean on: each materializes
    # (flushing the graph) and delegates — results are plain ndarrays.
    def sum(self, *args, **kw):
        return self.materialize().sum(*args, **kw)

    def reshape(self, *shape, **kw) -> np.ndarray:
        return self.materialize().reshape(*shape, **kw)

    def astype(self, dtype, **kw) -> np.ndarray:
        return self.materialize().astype(dtype, **kw)

    # ndarray comparison/truth semantics, not object identity: code ported
    # from eager mode must not silently get `False` from `t1 == t2`.
    def __eq__(self, other):
        return self.materialize() == np.asarray(other)

    def __ne__(self, other):
        return self.materialize() != np.asarray(other)

    __hash__ = None  # unhashable, like ndarray

    def __bool__(self):
        return bool(self.materialize())

    def __repr__(self) -> str:
        state = "pending" if self._value is None else "materialized"
        return f"LazyArray(shape={self.shape}, {state})"


def _DEAD_REF():  # weakref stand-in for ops that must never be outputs
    return None


def _split_devices(out) -> int:
    """Devices a pipeline output is split across: 0 for a host NumPy
    array, 1 for a single-device or replicated device array."""
    sharding = getattr(out, "sharding", None)
    if sharding is None:
        return 0
    return 1 if sharding.is_fully_replicated else len(sharding.device_set)


def _stage_wire(flat, pad: int, layout: PlaneLayout,
                copy: bool = False) -> np.ndarray:
    """Flat lane array -> padded int32 wire array with AT MOST one host
    copy: the pad tail and the lane-dtype conversion fuse into a single
    allocation (NumPy converts during the assignment), and an in-dtype
    unpadded input stages as a pure view unless ``copy`` forces private
    memory (required when ``flat`` still aliases a caller buffer)."""
    if pad:
        out = np.zeros(flat.size + pad, layout.np_dtype)
        out[:flat.size] = flat
        return layout.to_wire(out)
    if flat.dtype != layout.np_dtype:
        return layout.to_wire(flat.astype(layout.np_dtype))
    if copy:
        flat = flat.copy()
    return layout.to_wire(flat)


class _LeafCacheEntry:
    """One cached leaf upload: the private padded host wire plus (lazily)
    its committed device buffer. ``fp`` is the 257-sample content
    fingerprint taken when the source buffer was registered — a lookup
    only hits while the caller's memory still matches it."""

    __slots__ = ("key", "fp", "wire", "dev", "nbytes")

    def __init__(self, key, fp, wire):
        self.key = key
        self.fp = fp
        self.wire = wire        # private padded int32 host wire
        self.dev = None         # committed jax buffer (lazy, non-donating)
        self.nbytes = wire.nbytes


class _LeafCache:
    """Fingerprint-keyed cache of staged leaf uploads (the device-resident
    leaf cache). Keyed on the *caller buffer* — (data pointer, byte size,
    layout, raw mode) — and guarded by the same sampled content
    fingerprint as the graph's leaf dedup, so repeated flushes over the
    same operands (ServeEngine stop predicates, pum_database scans, the
    BMI/k-clique AND-chains) stage zero bytes and re-upload nothing: the
    entry's host wire is private (inserted from a record-time snapshot)
    and its device buffer commits once and survives across flushes and
    ``CapturedProgram`` replays.

    LRU-bounded by ``capacity`` bytes of host wire (the device mirror is
    counted implicitly — it exists only for entries hot enough to hit a
    jitted pipeline). Thread-safe behind its own lock: record-side
    lookups run under the engine lock, but staging/dispatch
    (``_prepare_graph``/``_run_staged``) runs outside it.

    Donation policy: a donating flush never passes a cached buffer to the
    trace — it serves the private host wire (jax device-puts and donates
    a *fresh* buffer) and drops the entry's device residency, so donated
    buffers are evicted and cached ones are never donated."""

    def __init__(self, capacity: int):
        self.capacity = capacity
        self._lock = threading.Lock()
        self._entries: collections.OrderedDict = collections.OrderedDict()
        self._bytes = 0

    def __len__(self) -> int:
        return len(self._entries)

    @property
    def nbytes(self) -> int:
        return self._bytes

    def lookup(self, key, fp) -> "_LeafCacheEntry | None":
        with self._lock:
            e = self._entries.get(key)
            if e is not None and np.array_equal(e.fp, fp):
                self._entries.move_to_end(key)
                return e
            return None

    def insert(self, key, fp, wire) -> tuple["_LeafCacheEntry | None", int]:
        """Cache ``wire`` (a private buffer) under ``key``; returns
        ``(entry, n_evicted)``. Oversized singletons are not cached."""
        if wire.nbytes > self.capacity:
            return None, 0
        entry = _LeafCacheEntry(key, fp, wire)
        evicted = 0
        with self._lock:
            old = self._entries.pop(key, None)
            if old is not None:
                self._bytes -= old.nbytes
            self._entries[key] = entry
            self._bytes += entry.nbytes
            while self._bytes > self.capacity and len(self._entries) > 1:
                _, dead = self._entries.popitem(last=False)
                self._bytes -= dead.nbytes
                evicted += 1
        return entry, evicted

    def device_buffer(self, entry: "_LeafCacheEntry"):
        """The entry's committed device buffer (uploads once, lazily)."""
        dev = entry.dev
        if dev is None:
            import jax.numpy as jnp
            dev = jnp.asarray(entry.wire)
            with self._lock:
                if entry.dev is None:
                    entry.dev = dev
                else:       # another flush won the commit race
                    dev = entry.dev
        return dev

    def drop_device(self, entry: "_LeafCacheEntry") -> None:
        """Release device residency (donating flushes: the trace consumes
        a fresh buffer, so any committed mirror is stale weight)."""
        with self._lock:
            entry.dev = None

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._bytes = 0


class _Leaf:
    """One registered operand of an op graph.

    Exactly one staging source is set:

    * ``entry`` — a leaf-cache entry whose fingerprint matched at record
      time: flush stages the cached wire (or its committed device
      buffer) and the record-time ``.copy()`` is elided entirely;
    * ``wire`` — the record-time snapshot, already in padded wire form
      (one fused pad+convert copy when the operand aliased caller
      memory; a zero-copy view when ``ravel()`` already privatized it).
    """

    __slots__ = ("wire", "entry", "nbytes")

    def __init__(self, wire=None, entry=None, nbytes=0):
        self.wire = wire
        self.entry = entry
        self.nbytes = nbytes


# The 257-point fingerprint sample grid per lane count: every graph at a
# given lane count shares one read-only index array — rebuilding it per
# flush (np.linspace + astype) is measurable against small programs.
_FP_IDX_CACHE: dict[int, np.ndarray] = {}


def _fp_indices(n: int) -> np.ndarray:
    idx = _FP_IDX_CACHE.get(n)
    if idx is None:
        if len(_FP_IDX_CACHE) >= 1024:  # unbounded lane-count churn guard
            _FP_IDX_CACHE.clear()
        idx = np.linspace(0, n - 1, min(n, 257)).astype(np.int64)
        idx.setflags(write=False)
        _FP_IDX_CACHE[n] = idx
    return idx


class _OpGraph:
    """Recording buffer for one fused program: leaf operand arrays plus the
    op list, with weakrefs to the handed-out LazyArrays (ops whose handle
    died unreferenced are dead code — never materialized).

    ``raw=True`` marks a packed-bitmap graph: plane-wise ops on raw uint64
    words, each reinterpreted as ``layout.raw_lanes_per_word`` dataplane
    lanes (two 32-bit lanes on the 32-bit layout, one full-width lane on
    the 64-bit layout; ``n`` counts lanes, width is the layout's word
    size). A graph is entirely raw or entirely value-mode; the engine
    flushes at mode boundaries."""

    def __init__(self, n: int, width: int, layout: PlaneLayout,
                 raw: bool = False, cache: "_LeafCache | None" = None):
        self.n = n                      # dataplane lane count (all values)
        self.width = width
        self.layout = layout
        self.raw = raw
        self.cache = cache              # engine's leaf cache (may be None)
        self.leaves: list[_Leaf] = []
        self._leaf_ids: dict[int, int] = {}
        self._pins: list[np.ndarray] = []  # keep id() keys alive (below)
        self._fps: list[np.ndarray] = []   # content fingerprints (below)
        self._fp_idx = _fp_indices(n)
        self._pad = (-n) % 32  # every pipeline tiles lanes in groups of 32
        self.elided_bytes = 0  # snapshot copies skipped (cache hit / view)
        self.cache_evictions = 0
        self.ops: list[tuple[str, tuple, int]] = []  # (opcode, args, param)
        self.results: list = []         # weakref per op
        # perf_counter_ns at first recorded op — set only when a tracer is
        # attached, so flush() can emit the "flush.record" span.
        self.t_start: int | None = None
        # Flush lifecycle (guarded by the engine lock): "recording" in a
        # client context's slot, "queued" parked on the retry list after a
        # failed flush, "flushing" detached and being dispatched (``done``
        # is then an Event concurrent materializers wait on), "done".
        self.state: str = "recording"
        self.done: threading.Event | None = None

    def leaf_id(self, arr: np.ndarray) -> tuple[str, int]:
        """Register an operand under the copy-on-write snapshot contract
        (mod the layout word — the pipeline keeps planes[:width]): the
        graph must not alias caller buffers, or mutations between record
        and flush would silently diverge from eager results. Re-feeding
        the same array object dedups to one pipeline input, guarded by a
        sampled content fingerprint so an in-place mutation between two
        recorded uses registers a fresh leaf instead of reusing the stale
        snapshot. (The guard samples 257 positions; a mutation confined
        to unsampled elements can still alias — call flush() before
        mutating operands in place.)

        The record-time ``.copy()`` is taken only when it is needed:

        * the engine's leaf cache holds an entry for this buffer whose
          fingerprint still matches -> stage straight from the cache,
          copy nothing;
        * ``ravel()`` already privatized the memory (non-contiguous
          operand, e.g. a broadcast scalar) -> the private flat array IS
          the snapshot;
        * otherwise the operand aliases caller memory -> snapshot now
          (directly into padded wire form, one fused copy) and seed the
          cache so the NEXT flush over this buffer stages zero bytes.
        """
        key = id(arr)
        rav = arr.ravel()
        flat = rav
        if self.raw:  # reinterpret uint64 words as layout lanes
            flat = self.layout.raw_lanes(rav)
        idx = self._leaf_ids.get(key)
        if idx is not None and np.array_equal(flat[self._fp_idx],
                                              self._fps[idx]):
            return ("leaf", idx)
        # Width guard is value-mode only: raw lanes carry full words and
        # the raw graph width is the word size, so the scan could never
        # fire there.
        if not self.raw and self.width < 64 and flat.size \
                and int(flat.max()) >> self.width:
            # Loud, not silent: eager ops compute on raw uint64 values
            # (realworld's packed-bitmap kernels rely on that), so
            # truncating here would quietly change their answers.
            raise ValueError(
                f"fused dataplane computes modulo 2**{self.width}; an "
                f"operand has bits at or above bit {self.width} — mask "
                f"inputs to the engine width or use fuse=False")
        i = len(self.leaves)
        self._leaf_ids[key] = i  # latest content owns the dedup slot
        fp = flat[self._fp_idx]  # fancy indexing: always a private copy
        nbytes = flat.size * self.layout.nbytes_per_word
        # ``ravel()`` returns a view (base set) iff the flat memory still
        # belongs to the caller; a fresh copy (base None) is private.
        shared = rav.base is not None or rav is arr
        ckey = entry = None
        if shared and self.cache is not None and flat.size:
            ckey = (flat.__array_interface__["data"][0], flat.nbytes,
                    self.layout.name, self.raw)
            entry = self.cache.lookup(ckey, fp)
        if entry is not None:
            self.elided_bytes += nbytes          # record-time cache hit
            self.leaves.append(_Leaf(entry=entry, nbytes=nbytes))
        else:
            wire = _stage_wire(flat, self._pad, self.layout, copy=shared)
            if wire.base is not None and not shared:
                self.elided_bytes += nbytes      # staged as a pure view
            if ckey is not None:                 # seed for the next flush
                entry, ev = self.cache.insert(ckey, fp, wire)
                self.cache_evictions += ev
            self.leaves.append(_Leaf(wire=wire, entry=None, nbytes=nbytes))
        self._fps.append(fp)
        # Pin the original: the id() dedup key is only valid while the
        # caller's array stays alive.
        self._pins.append(arr)
        return ("leaf", i)

    def stage_leaf(self, li: int) -> np.ndarray:
        """The padded int32 host wire for leaf ``li`` (zero-copy: either
        the record-time snapshot or the cached upload's host wire)."""
        leaf = self.leaves[li]
        e = leaf.entry
        return leaf.wire if e is None else e.wire

    def add_op(self, opcode: str, args: tuple, param: int,
               out: "LazyArray", internal: bool = False) -> int:
        self.ops.append((opcode, args, param))
        # Internal ops (tuple values feeding selectors) record a dead ref:
        # they can never be materialized as a program output.
        self.results.append(_DEAD_REF if internal else weakref.ref(out))
        return len(self.ops) - 1


class PulsarEngine:
    """Bulk bitwise/bit-serial integer SIMD on (simulated) PuM DRAM.

    Dataplane values are unsigned integers carried in uint64 ndarrays;
    arithmetic ops (``add``/``sub``/``mul``/``div``/``mod``/``less_than``/
    ``popcount``/``reduce_bits``) compute modulo ``2**width``. The cost
    plane prices every op in nanoseconds/joules via the paper-calibrated
    ``CostModel`` (``stats.latency_ns`` / ``stats.energy_j``), independent
    of which dataplane backend produced the values.

    With ``fuse=True`` ops return :class:`LazyArray` handles and execute
    as one compiled program per :meth:`flush` — bit-exact and
    stats-identical to eager, including division by zero. The public way
    in is :mod:`repro.pum` (div-by-zero yields 0, as in eager NumPy; a
    ``divmod`` shares one restoring-division pass):

    >>> import numpy as np
    >>> import repro.pum as pum
    >>> with pum.device(mfr="M", width=16, fuse=True) as dev:
    ...     q, r = divmod(dev.asarray(np.array([1000, 7], np.uint64)),
    ...                   np.array([6, 0], np.uint64))
    >>> np.asarray(q)
    array([166,   0], dtype=uint64)
    >>> int(r.to_numpy()[0])
    4
    >>> with pum.device(width=16, fuse=False) as dev2:   # eager twin
    ...     _ = divmod(dev2.asarray(np.array([1000, 7], np.uint64)),
    ...                np.array([6, 0], np.uint64))
    >>> dev.stats == dev2.stats          # identical cost-plane charges
    True

    ``flush_threshold`` (recorded ops) and ``flush_memory_bytes``
    (estimated graph footprint) auto-flush oversized graphs; pass ``None``
    to disable either bound. ``donate_leaves=True`` donates the fused
    pipeline's leaf device buffers to the compiled trace (cuts peak
    memory; bit-exactness unaffected — the engine's snapshots live on the
    host). The ``backend`` name resolves through the ``repro.backends``
    registry (capability ``"eager"``): ``"fast"`` computes on packed
    NumPy words, ``"sim"`` routes through the bit-exact chip model.
    """

    def __init__(self, mfr: str = "M", width: int = 32,
                 row_bits: int = 65536, banks: int = 16,
                 backend: str = "fast",
                 success_db: SuccessRateDb | None = None,
                 use_pulsar: bool = True, chained: bool = False,
                 controller=None, seed: int = 0, fuse: bool = False,
                 flush_threshold: int | None = 1024,
                 flush_memory_bytes: int | None = 1 << 30,
                 donate_leaves: bool = False, layout=None,
                 fused_backend: str | None = None,
                 ref_postponing: int = 1, reliability=None,
                 cmd_buffer_lookahead: int = 8,
                 leaf_cache_bytes: int | None = 1 << 26):
        self.profile = PROFILES[mfr]
        self.mfr = mfr
        self.width = width
        self.row_bits = row_bits
        self.banks = banks
        self.backend = backend
        self.seed = seed
        self.use_pulsar = use_pulsar  # False => FracDRAM baseline costs
        self.chained = chained and use_pulsar  # chained-staging (§Perf P4)
        # Plane layout: the lane word format of the fused dataplane.
        # Default: the narrowest canonical layout holding `width` bits
        # (width <= 32 keeps the exact pre-layout 32-bit behavior).
        self.layout = (layout_for_width(width) if layout is None
                       else get_layout(layout))
        if width > self.layout.word_bits:
            raise ValueError(
                f"width {width} does not fit the {self.layout.word_bits}"
                f"-bit plane layout {self.layout.name!r}")
        # controller="auto" builds a MemoryController over `banks` banks;
        # None keeps the legacy closed-form bank divide (reproduces the
        # pre-controller numbers exactly). `ref_postponing` batches up to
        # N REF commands into one rank lockout (JEDEC allows 8) — longer
        # but rarer refresh windows, priced by batch_cost.
        if not 1 <= ref_postponing <= 8:
            raise ValueError(
                f"ref_postponing must be in [1, 8] (JEDEC allows "
                f"postponing up to 8 REFs), got {ref_postponing}")
        if ref_postponing != 1 and controller != "auto":
            # Loud, not silently inert: the closed-form path never models
            # refresh, and a prebuilt controller carries its own policy.
            raise ValueError(
                "ref_postponing requires controller='auto' (with "
                "controller=None refresh is not modeled; a prebuilt "
                "MemoryController sets postponing= itself)")
        if cmd_buffer_lookahead < 1:
            raise ValueError(f"cmd_buffer_lookahead must be >= 1, got "
                             f"{cmd_buffer_lookahead}")
        if controller == "auto":
            from repro.controller import MemoryController
            controller = MemoryController(n_banks=banks,
                                          postponing=ref_postponing,
                                          lookahead=cmd_buffer_lookahead)
        self.controller = controller
        self.ref_postponing = ref_postponing
        # Crossbar command-buffer depth for concurrent-stream scheduling;
        # execution-only (never priced by the single-stream cost plane).
        self.cmd_buffer_lookahead = cmd_buffer_lookahead
        self.cost = CostModel(row_bits=row_bits, controller=controller)
        self.db = success_db or default_db()
        # Concurrency state: one recording slot + one EngineStats shard
        # per client context (a thread, or a named ``client()`` scope).
        # The RLock guards all record-side mutation (slots, shards, cost
        # caches, retry list); compiled-pipeline dispatch runs outside it.
        self._lock = threading.RLock()
        self._local = threading.local()
        self._slots: dict[tuple, _OpGraph] = {}
        self._stats_shards: dict[tuple, EngineStats] = {}
        self._retry: list[_OpGraph] = []       # failed flushes, FIFO
        self._inflight: dict[int, object] = {}  # id(graph) -> Future
        self._executor: ThreadPoolExecutor | None = None
        # Double-buffered async flush: at most 2 staged dispatches in
        # flight — the caller stages flush k+1 while the worker runs k.
        self._async_slots = threading.BoundedSemaphore(2)
        self._best_cfg_cache: dict[int, tuple[int, int, float]] = {}
        self._batch_cache: dict[tuple, object] = {}
        # Eager-dataplane backend by registry lookup: the builder returns
        # None for the packed-NumPy word dataplane or an ALU-protocol
        # object (see repro.backends.BackendSpec) to route ops through.
        spec = get_backend(backend)
        if "eager" not in spec.capabilities:
            raise ValueError(
                f"backend {backend!r} has no eager dataplane "
                f"(capabilities: {sorted(spec.capabilities)})")
        if width > spec.max_width:
            raise ValueError(
                f"backend {backend!r} supports width <= {spec.max_width}, "
                f"got {width}")
        if not spec.available():
            raise ValueError(f"backend {backend!r} is registered but not "
                             f"available on this host")
        self._alu = spec.builder(self)
        if fuse and self._alu is not None:
            raise ValueError(
                f"fuse=True requires an eager word-dataplane backend "
                f"(builder returns None, e.g. 'fast'); backend "
                f"{backend!r} routes ops through an ALU and stays "
                f"per-op")
        if fused_backend is not None:
            fspec = get_backend(fused_backend)
            if "fused" not in fspec.capabilities:
                raise ValueError(
                    f"fused_backend {fused_backend!r} has no fused "
                    f"evaluator (capabilities: "
                    f"{sorted(fspec.capabilities)})")
            if width > fspec.max_width \
                    or self.layout.word_bits not in fspec.layouts:
                raise ValueError(
                    f"fused_backend {fused_backend!r} covers width <= "
                    f"{fspec.max_width} on layouts "
                    f"{sorted(fspec.layouts)}; engine is width {width} "
                    f"on the {self.layout.word_bits}-bit layout")
        elif fuse:
            # Layout capability query (replaces the old hardwired
            # `width > 32` guard): some registered fused evaluator must
            # cover this width on this plane layout. pum.Device falls
            # back to eager automatically when nothing does.
            try:
                select_backend(require="fused", width=width,
                               layout=self.layout)
            except LookupError as e:
                raise ValueError(
                    f"no registered fused evaluator covers width {width} "
                    f"on the {self.layout.word_bits}-bit plane layout "
                    f"({e}); use fuse=False or register_backend() one"
                ) from None
        if flush_threshold is not None and flush_threshold < 1:
            raise ValueError("flush_threshold must be >= 1 or None")
        if leaf_cache_bytes is not None and leaf_cache_bytes < 0:
            raise ValueError(
                f"leaf_cache_bytes must be >= 0 or None (0/None disables "
                f"the leaf cache), got {leaf_cache_bytes}")
        self.fuse = fuse
        self.fused_backend = fused_backend
        self.flush_threshold = flush_threshold
        self.flush_memory_bytes = flush_memory_bytes
        self.donate_leaves = donate_leaves
        # Device-resident leaf cache: staged leaf uploads keyed on the
        # caller's buffer + content fingerprint, shared across all client
        # contexts of this engine (one cache per device). 0/None disables.
        self.leaf_cache_bytes = leaf_cache_bytes or 0
        self._leaf_cache = (_LeafCache(leaf_cache_bytes)
                            if leaf_cache_bytes else None)
        # Telemetry: counters always exist (cheap dict, written only while
        # a tracer is attached); ``tracer`` is None until someone opts in
        # (pum.profile(), ServeEngine(telemetry=True)) — the disabled path
        # is a single `is None` check per flush, nothing per op.
        self.counters = CounterBank()
        self.tracer = None
        # Autotuner hook: None (default) costs one `is None` check per
        # flush; Device.autotune(online=True) installs an
        # repro.autotune.OnlineAutotuner whose on_flush() closes the
        # measure->decide->apply loop at flush granularity.
        self.autotuner = None
        # Reliability plane: calibrated-map planning/placement plus the
        # flush-time injection + vote/retry loop (repro.reliability). None
        # (default) keeps every path exactly as before — the enabled check
        # is a single `is None` per flush, like the tracer.
        self.reliability = None
        if reliability is not None:
            from repro.reliability import ReliabilityPlane
            self.reliability = ReliabilityPlane(
                reliability, mfr=mfr, counters=self.counters)
            if self.reliability.inject and not fuse:
                raise ValueError(
                    "reliability fault injection hooks the fused dispatch "
                    "path; it requires fuse=True (eager ops never run the "
                    "vote/retry loop)")

    # ------------------------------------------------------------------ #
    # Client contexts (per-thread / named recording slots + stats shards)
    # ------------------------------------------------------------------ #

    def _ctx_key(self) -> tuple:
        name = getattr(self._local, "client", None)
        if name is not None:
            return ("client", name)
        return ("thread", threading.get_ident())

    @contextlib.contextmanager
    def client(self, name: str):
        """Scope ops to a named client context.

        Inside the scope, recorded ops go to the context's own graph slot
        and cost charges to its own stats shard — so N logical clients can
        share one engine (from any threads) without interleaving their
        programs. Without a ``client()`` scope the calling thread is its
        own implicit context."""
        prev = getattr(self._local, "client", None)
        self._local.client = str(name)
        try:
            yield self
        finally:
            self._local.client = prev

    @property
    def _graph(self) -> "_OpGraph | None":
        """The current client context's recording graph (or None)."""
        return self._slots.get(self._ctx_key())

    @_graph.setter
    def _graph(self, g: "_OpGraph | None") -> None:
        key = self._ctx_key()
        if g is None:
            self._slots.pop(key, None)
        else:
            self._slots[key] = g

    def _stats_shard(self) -> EngineStats:
        s = self._stats_shards.get(self._ctx_key())
        if s is None:
            s = self._stats_shards[self._ctx_key()] = EngineStats()
        return s

    @property
    def stats(self) -> EngineStats:
        """Merged cost-plane charges across every client context.

        Per-context shards merge in sorted-key order, so the totals are
        identical no matter which thread/arbitration interleaving produced
        the charges (float addition is order-sensitive; the merge order is
        canonical). With a single context this is bit-identical to the
        pre-concurrency accumulator."""
        with self._lock:
            out = EngineStats()
            for key in sorted(self._stats_shards, key=str):
                s = self._stats_shards[key]
                out.latency_ns += s.latency_ns
                out.energy_j += s.energy_j
                out.n_sequences += s.n_sequences
                out.lane_efficiency = min(out.lane_efficiency,
                                          s.lane_efficiency)
                out.refresh_stall_ns += s.refresh_stall_ns
            return out

    # ------------------------------------------------------------------ #
    # Cost plumbing
    # ------------------------------------------------------------------ #

    def _kind_cost(self, kind: str, m: int, n_rg: int, w: int,
                   n_planes: int | None, n_rg3: int | None = None) -> OpCost:
        fs = self.profile.frac_supported
        ps = "pow2" if self.use_pulsar else "max"
        kw = dict(frac_supported=fs, plan_style=ps)
        ckw = dict(kw, chained=self.chained)
        c = self.cost
        if kind in ("and2", "or2"):
            return c.logic2(min(3, m), n_rg, **kw)
        if kind == "xor2":
            return c.xor2(min(3, m), n_rg, **kw)
        if kind == "add" or kind == "sub":
            return c.add(w, m, n_rg, n_rg3, **ckw)
        if kind == "mul":
            return c.mul(w, m, n_rg, n_rg3, **ckw)
        if kind == "div":
            return c.div(w, m, n_rg, n_rg3, **ckw)
        if kind in ("reduce_and", "reduce_or"):
            return c.reduce_tree(n_planes or w, m, n_rg, **ckw)
        if kind == "reduce_xor":
            return c.xor_reduce(n_planes or w, m, n_rg, **ckw)
        if kind == "popcount":
            out_w = max(1, (n_planes or w).bit_length())
            return (n_planes or w) * out_w * c.full_adder(m, n_rg, n_rg3,
                                                          **ckw)
        if kind == "compare":
            return c.add(w + 1, m, n_rg, n_rg3, **ckw)
        if kind in ("load", "store"):
            return (c.write_row() if kind == "load" else c.read_row()) * (2 * w)
        raise KeyError(kind)

    _ARITH = ("add", "sub", "mul", "div", "popcount", "compare")

    def _cfg_for(self, kind: str, w: int, n_planes: int | None
                 ) -> tuple[int, int, float, OpCost]:
        """Best (maj_fan_in, n_rg[, n_rg3]) for this op kind: minimizes
        latency / success_rate — the paper's per-op configuration search
        ("we choose the N_RG that produces the highest throughput").
        Arithmetic kinds search MAJ3/MAJ5 sub-op configs independently."""
        if not self.use_pulsar:
            # FracDRAM baseline: MAJ3 on 4-row activation only.
            sr = self.db.mean(self.mfr, 3, 4)
            return 3, 4, sr, self._kind_cost(kind, 3, 4, w, n_planes, 4)
        key = (kind, w, n_planes)
        if key not in self._best_cfg_cache:
            prof = self.profile
            cap = prof.max_simul_rows
            pows = [n for n in (4, 8, 16, 32) if n <= cap]
            rel = self.reliability

            def sr_of(m, n):
                if n < m:
                    return 0.0
                if rel is not None:
                    # Variation-aware planning: the calibrated map's
                    # (steering-weighted) rate for profiled configs; the
                    # global DB covers the rest.
                    s = rel.plan_success(m, n)
                    if s is not None:
                        return s
                return self.db.mean(self.mfr, m, n, plan_style="pow2")

            candidates: list[tuple[int, int, int | None]] = []
            if kind in self._ARITH:
                for n3 in pows:                       # MAJ3-only FA
                    candidates.append((3, n3, None))
                if prof.max_maj_fan_in >= 5:
                    for n5 in pows:
                        for n3 in pows:
                            if n5 >= 5:
                                candidates.append((5, n5, n3))
            else:
                m = 3
                while m <= min(prof.max_maj_fan_in, cap):
                    for n in pows:
                        if n >= m:
                            candidates.append((m, n, None))
                    m += 2
            best = None
            best_ok = None  # reliability: best config MEETING the target
            target = (rel.config.target_success if rel is not None else None)
            for m, n, n3 in candidates:
                sr = sr_of(m, n)
                if n3 is not None:
                    sr = min(sr, sr_of(3, n3))
                if sr <= 1e-3:
                    continue
                cost = self._kind_cost(kind, m, n, w, n_planes, n3)
                eff = cost.latency_ns / sr
                if best is None or eff < best[0]:
                    best = (eff, m, n, sr, cost)
                if target is not None and sr >= target \
                        and (best_ok is None or eff < best_ok[0]):
                    best_ok = (eff, m, n, sr, cost)
            assert best is not None, f"no viable config for {kind}"
            # Per-op replication choice (Fig 11): prefer the fastest config
            # whose calibrated success meets the reliability target; only
            # when none does fall back to raw throughput (the vote/retry
            # loop then carries the correction burden).
            self._best_cfg_cache[key] = (best_ok or best)[1:]
        return self._best_cfg_cache[key]

    def _n_vec_rows(self, n_elems: int) -> int:
        return -(-n_elems // self.row_bits)

    def _batch_for(self, kind: str, m: int, n_rg: int):
        """Controller-measured bank-batch cost for this op's dominant
        primitive (the MAJ unit for compute kinds, the full-row transfer
        program for load/store), cached per configuration."""
        if kind in ("load", "store"):
            key = ("io", kind)
        else:
            key = ("maj", m, n_rg, self.chained)
        if key not in self._batch_cache:
            from repro.core import commands as cmds
            t = self.cost.t
            if kind == "load":
                unit = [cmds.prog_write_row(0, 0, self.cost._wr_bursts, t)]
            elif kind == "store":
                unit = [cmds.prog_read_row(0, 0, self.cost._wr_bursts, t)]
            else:
                unit = self.cost.maj_unit_programs(
                    m, n_rg, frac_supported=self.profile.frac_supported,
                    plan_style="pow2" if self.use_pulsar else "max",
                    # Chained staging keeps one input resident per MAJ, so
                    # measure bank contention on the thinner command stream.
                    resident_inputs=1 if self.chained else 0)
            order = (tuple(self.reliability.bank_order(self.banks))
                     if self.reliability is not None else None)
            self._batch_cache[key] = self.controller.batch_cost(
                unit, self.banks, bank_order=order)
        return self._batch_cache[key]

    def _charge(self, kind: str, n_elems: int, width: int | None = None,
                n_planes: int | None = None) -> None:
        with self._lock:
            log = getattr(self._local, "charge_log", None)
            if log is not None:
                # Program capture records the charge recipe so replays
                # price identically to the uncaptured path.
                log.append((kind, n_elems, width, n_planes))
            w = width or self.width
            m, n, sr, cost = self._cfg_for(kind, w, n_planes)
            if self.reliability is not None:
                # The flush-time vote loop injects at the worst config used.
                self.reliability.note_op(m, n, sr)
            batch = (self._batch_for(kind, m, n)
                     if self.controller is not None else None)
            self._stats_shard().charge(cost, self._n_vec_rows(n_elems),
                                       self.banks, sr, batch)

    def _replay_charges(self, recipe) -> None:
        """Re-apply a captured charge recipe (one replayed program)."""
        for kind, n_elems, width, n_planes in recipe:
            self._charge(kind, n_elems, width, n_planes)

    def op_effective_ns(self, kind: str, width: int | None = None,
                        n_planes: int | None = None
                        ) -> tuple[float, float, int, int]:
        """Amortized per-vector-row latency of one op at this engine's bank
        count: ``(latency_ns, success_rate, maj_fan_in, n_rg)``.  With a
        controller the latency is priced through the scheduled bank batch
        (tFAW/tRRD-limited speedup + refresh factor); without one it is the
        closed-form single-bank latency divided by ``banks``."""
        w = width or self.width
        m, n, sr, cost = self._cfg_for(kind, w, n_planes)
        if self.controller is None:
            return cost.latency_ns / self.banks, sr, m, n
        b = self._batch_for(kind, m, n)
        eff = (cost.latency_ns / max(1.0, b.parallel_speedup)
               * b.refresh_factor)
        return eff, sr, m, n

    # ------------------------------------------------------------------ #
    # Dataplane ops (fast backend: NumPy; sim backend: chip model;
    # fuse=True: record into the lazy op graph, execute at flush())
    # ------------------------------------------------------------------ #

    def _mask(self, w: int) -> np.uint64:
        return np.uint64((1 << w) - 1)

    def _coerce(self, x):
        """Engine-op operand: LazyArrays pass through while pending (so the
        graph extends); everything else becomes a uint64 ndarray."""
        if isinstance(x, LazyArray):
            return x if x._value is None else x._value
        return np.asarray(x, np.uint64)

    def _force(self, x) -> np.ndarray:
        return x.materialize() if isinstance(x, LazyArray) else x

    def _can_fuse(self, *operands) -> bool:
        if not self.fuse:
            return False
        shape = operands[0].shape
        return all(x.shape == shape for x in operands[1:])

    def _is_raw_operand(self, x) -> bool:
        """Does this operand carry bits at or above the engine width?
        (Pending raw-graph handles count; pending value-mode handles are
        in-width by construction.)"""
        if isinstance(x, LazyArray):
            if x._value is None:
                return x._graph is not None and x._graph.raw
            x = x._value
        return bool(self.width < 64 and x.size
                    and int(x.max()) >> self.width)

    def _use_raw(self, operands: tuple) -> bool:
        """Plane-wise ops route through the raw packed-bitmap graph when
        any operand is out of width (bit-exact: bitwise ops reinterpret
        cleanly onto the layout's lanes — two 32-bit lanes per word on
        the 32-bit layout, the word itself on the 64-bit one) or when a
        raw graph of the same lane count is already open (in-width words
        join it losslessly — their high bits are zero)."""
        g = self._graph
        if g is not None and g.raw \
                and g.n == self.layout.raw_lanes_per_word \
                * operands[0].size:
            return True
        return any(self._is_raw_operand(x) for x in operands)

    def _record(self, opcode: str, operands: tuple, param: int = 0,
                raw: bool = False, defer_flush: bool = False,
                internal: bool = False) -> LazyArray:
        """Append one op to the lazy graph (starting/flushing as needed)
        and hand back its LazyArray.

        ``defer_flush`` skips the auto-flush threshold check so a multi-op
        lowering (divmod -> selectors) records atomically — a flush
        between the tuple op and its selector would try to materialize a
        tuple value. ``internal=True`` marks an op that must never be a
        program output (its handle only carries the op index for selector
        args): it records a dead weakref so flush() can't see it live."""
        shape = operands[0].shape
        lanes_per_word = self.layout.raw_lanes_per_word if raw else 1
        n = operands[0].size * lanes_per_word  # dataplane lanes
        g = self._graph
        if g is not None and (g.n != n or g.raw != raw):
            if self.tracer is not None:
                self.counters.inc("engine.autoflush.mode_boundary")
            self.flush()  # one program = one lane count and one mode
        # Cross-context materialization (a pending lazy of ANOTHER graph
        # entering as a leaf) may dispatch a flush, so resolve operands
        # before taking the lock for this context's graph mutation.
        # A pending raw popcount also materializes before further use:
        # its lanes are per-lane partial counts that only become the
        # caller-visible word count at the materialize fold, so in-graph
        # consumers would see the packed halves instead of the sum.
        def _needs_fold(x):
            return (x._graph.raw
                    and x._graph.layout.raw_lanes_per_word == 2
                    and x._graph.ops[x._op_idx][0] == "popcount")

        resolved = [x.materialize() if isinstance(x, LazyArray)
                    and (not (x._value is None and x._graph is not None
                              and x._graph is self._graph)
                         or _needs_fold(x))
                    else x for x in operands]
        with self._lock:
            g = self._graph
            if g is None:
                g = self._graph = _OpGraph(
                    n, self.layout.word_bits if raw else self.width,
                    self.layout, raw=raw, cache=self._leaf_cache)
                if self.tracer is not None:
                    g.t_start = time.perf_counter_ns()
            if self.tracer is not None:
                self.counters.inc("engine.ops_recorded")
                self.counters.inc(f"engine.op.{opcode}")
                if raw:
                    self.counters.inc("engine.raw_ops")
            args = []
            for x in resolved:
                if isinstance(x, LazyArray) and x._value is None \
                        and x._graph is g:
                    args.append(("op", x._op_idx))
                else:
                    # Plain array or an already-materialized lazy —
                    # enters as a leaf.
                    arr = x.materialize() if isinstance(x, LazyArray) else x
                    args.append(g.leaf_id(arr))
            out = LazyArray(self, g, len(g.ops), shape)
            g.add_op(opcode, tuple(args), param, out, internal=internal)
            reason = None
            if not defer_flush \
                    and not getattr(self._local, "no_autoflush", False):
                reason = self._graph_over_threshold(g)
                if reason and self.tracer is not None:
                    self.counters.inc(f"engine.autoflush.{reason}")
        if reason:
            self.flush()  # auto-flush: `out` is live, materializes
        return out

    def _graph_over_threshold(self, g: _OpGraph) -> str | None:
        """Auto-flush policy: graph-size (recorded ops), estimated
        memory (one layout word per lane per held value: leaf snapshots
        plus the pipeline's per-op intermediates), and the Pallas
        kernel's VMEM block (every plane of one tile of every leaf and
        output; ops bound the outputs). The VMEM bound is always on, so
        no flush can build a program the TPU kernel cannot compile.
        Returns the trigger name ("ops"/"memory"/"vmem", doubling as the
        telemetry counter suffix) or None when the graph may keep
        growing."""
        if self.flush_threshold is not None \
                and len(g.ops) >= self.flush_threshold:
            return "ops"
        if self.flush_memory_bytes is not None:
            est = g.layout.nbytes_per_word * g.n \
                * (len(g.leaves) + len(g.ops))
            if est >= self.flush_memory_bytes:
                return "memory"
        if _fused.block_bytes(len(g.leaves) + len(g.ops), g.width) \
                >= _fused.VMEM_BLOCK_BUDGET:
            return "vmem"
        return None

    def flush(self) -> None:
        """Materialize the pending op graph through the fused bit-plane
        pipeline (one transpose in, one fused program, one transpose out).
        The recorded graph is normalized first (CSE + dead-node pruning,
        ``fused_program.optimize_program``) — results and EngineStats are
        unaffected, only redundant dataplane work is dropped. No-op when
        nothing is pending; never touches the cost plane — every op was
        charged at record time.

        Drains, in order: graphs parked by earlier failed flushes (the
        retry list), then the calling context's own pending graph. A
        failure parks the graph back on the retry list (never into a
        recording slot, so the restore cannot interleave with another
        client's in-flight record) and re-raises."""
        while True:
            g = self._take_next(self._ctx_key())
            if g is None:
                return
            self._dispatch_graph(g)

    def flush_all(self) -> None:
        """Flush every client context's pending graph, drain the retry
        list, and wait out in-flight async flushes (``Device.flush`` /
        clean ``with`` exit). Failures propagate like :meth:`flush`."""
        while True:
            with self._lock:
                futs = list(self._inflight.values())
            for f in futs:
                f.result()
            g = self._take_next(None)
            if g is None:
                with self._lock:
                    # An entry whose future resolved is stale (its
                    # registration raced the worker's pop) — drop it
                    # instead of spinning on it.
                    for k, f in list(self._inflight.items()):
                        if f.done():
                            del self._inflight[k]
                    if not self._inflight:
                        return
                continue
            self._dispatch_graph(g)

    def flush_async(self) -> FlushHandle:
        """Compile + dispatch the pending graph off the calling thread.

        The record-side half (dead-code scan, program normalization, leaf
        wire staging) runs on the caller — so at most two flushes are ever
        staged at once (double buffering: the caller stages flush k+1
        while the worker dispatches k; a third call blocks). The compile/
        dispatch/materialize half runs on the engine's single flush worker
        thread. Returns a :class:`FlushHandle`; ``result()`` re-raises a
        failed dispatch after parking the graph for retry exactly like a
        failed synchronous flush."""
        batch: list[_OpGraph] = []
        with self._lock:
            while self._retry:
                batch.append(self._begin_flush(self._retry.pop(0)))
            g = self._slots.pop(self._ctx_key(), None)
            if g is not None and g.ops:
                batch.append(self._begin_flush(g))
        if not batch:
            return FlushHandle(None)
        staged = []
        try:
            for g in batch:
                staged.append((g, self._prepare_graph(g)))
        except BaseException:
            # Nothing reached the worker yet: park the whole batch, in
            # order, so a later flush/materialize retries it.
            with self._lock:
                self._park_graphs(batch)
            raise
        self._async_slots.acquire()
        try:
            fut = self._ensure_executor().submit(self._async_run, staged)
        except BaseException:
            self._async_slots.release()
            with self._lock:
                self._park_graphs([g for g, _ in staged])
            raise
        with self._lock:
            for g, _ in staged:
                self._inflight[id(g)] = fut
        if fut.done():
            # The worker can drain _async_run before the entries above
            # land (its per-graph pops find nothing) — drop them here so
            # flush_all never waits on an already-finished dispatch.
            with self._lock:
                for g, _ in staged:
                    self._inflight.pop(id(g), None)
        if self.tracer is not None:
            self.counters.inc("engine.flush_async")
        return FlushHandle(fut)

    def close(self) -> None:
        """Shut the async flush worker down (waits for in-flight
        dispatches). Safe to call repeatedly; the worker is recreated
        lazily if ``flush_async`` is used again."""
        with self._lock:
            ex, self._executor = self._executor, None
        if ex is not None:
            ex.shutdown(wait=True)

    # -- flush plumbing -------------------------------------------------- #

    def _begin_flush(self, g: _OpGraph) -> _OpGraph:
        """Transition a detached graph to the flushing state (lock held)."""
        g.state = "flushing"
        g.done = threading.Event()
        return g

    def _park_graphs(self, graphs) -> None:
        """Park failed/abandoned flushes for retry (lock held): FIFO on
        the retry list, never back into a recording slot — restoring into
        a slot could interleave with that client's in-flight record."""
        for g in graphs:
            g.state = "queued"
            self._retry.append(g)
            if g.done is not None:
                g.done.set()

    def _take_next(self, key) -> "_OpGraph | None":
        """Pop the next graph to dispatch: retries first, then ``key``'s
        slot (or any slot when ``key`` is None, for flush_all)."""
        with self._lock:
            if self._retry:
                return self._begin_flush(self._retry.pop(0))
            if key is None:
                for k in list(self._slots):
                    return self._begin_flush(self._slots.pop(k))
                return None
            g = self._slots.pop(key, None)
            return None if g is None else self._begin_flush(g)

    def _ensure_executor(self) -> ThreadPoolExecutor:
        with self._lock:
            if self._executor is None:
                self._executor = ThreadPoolExecutor(
                    max_workers=1, thread_name_prefix="pum-flush")
            return self._executor

    def _async_run(self, staged) -> None:
        """Worker-side half of flush_async: dispatch each staged graph."""
        try:
            for g, st in staged:
                try:
                    if st is not None:
                        self._run_staged(g, st)
                    with self._lock:
                        g.state = "done"
                except BaseException:
                    with self._lock:
                        self._park_graphs([g])
                    raise
                finally:
                    if g.done is not None:
                        g.done.set()
                    with self._lock:
                        self._inflight.pop(id(g), None)
        finally:
            self._async_slots.release()

    def _dispatch_graph(self, g: _OpGraph) -> None:
        """Prepare + dispatch one detached graph on the calling thread."""
        try:
            st = self._prepare_graph(g)
            if st is not None:
                self._run_staged(g, st)
            with self._lock:
                g.state = "done"
        except BaseException:
            # Keep pending handles recoverable after a transient failure
            # (interrupt, backend OOM): park the graph so a later
            # flush/materialize retries instead of orphaning them.
            with self._lock:
                self._park_graphs([g])
            raise
        finally:
            if g.done is not None:
                g.done.set()

    def _materialize_graph(self, g: _OpGraph) -> None:
        """Make ``g``'s live handles hold values, wherever ``g`` is in the
        flush lifecycle: still recording (any context's slot), parked for
        retry, in flight on the async worker (wait on it), or done."""
        fut = None
        with self._lock:
            st = g.state
            if st == "recording":
                for k, v in list(self._slots.items()):
                    if v is g:
                        del self._slots[k]
                        break
                self._begin_flush(g)
            elif st == "queued":
                self._retry.remove(g)
                self._begin_flush(g)
            elif st == "flushing":
                fut = self._inflight.get(id(g))
        if st in ("recording", "queued"):
            self._dispatch_graph(g)
        elif st == "flushing":
            # Another thread is dispatching this graph (sync or async):
            # wait for it; if it failed and parked the graph, retry here.
            if fut is not None:
                fut.result()
            elif g.done is not None:
                g.done.wait()
            if g.state == "queued":
                self._materialize_graph(g)
        # st == "done": values are set (or the flush had no live outputs).

    def _prepare_graph(self, g: _OpGraph):
        """Record-side half of a flush: dead-code scan, program build +
        normalization, leaf wire staging. Returns None when nothing in the
        graph is live (nothing to dispatch)."""
        if not g.ops:
            return None
        tr = NULL_TRACER if self.tracer is None else self.tracer
        if g.t_start is not None:
            # The record phase ran between first op and now; stamp it as a
            # span from the graph's own start time.
            tr.add_span("flush.record", g.t_start, time.perf_counter_ns(),
                        n_ops=len(g.ops), n_leaves=len(g.leaves),
                        raw=g.raw)
        live = [wr() for wr in g.results]
        # Materialize ops whose handle is still referenced; handles that
        # died unreferenced are dead code (their cost was still charged,
        # as in eager mode, but no dataplane work remains).
        out_idx = [i for i, lz in enumerate(live) if lz is not None]
        if not out_idx:
            return None
        n_leaves = len(g.leaves)

        def vid(tag):  # combined id space: leaves first, then ops
            return tag[1] if tag[0] == "leaf" else n_leaves + tag[1]

        with tr.span("flush.optimize", n_ops_in=len(g.ops)) as sp_opt:
            program = FusedProgram(
                width=g.width, n_inputs=n_leaves,
                ops=tuple(FusedOp(opcode, tuple(vid(a) for a in args),
                                  param)
                          for opcode, args, param in g.ops),
                outputs=tuple(n_leaves + i for i in out_idx),
                layout=g.layout)
            program, out_pos, leaf_map = optimize_program(program)
            sp_opt.args["n_ops_out"] = len(program.ops)
        with tr.span("flush.leaf_upload", n_leaves=len(leaf_map)) as sp_up:
            # Leaves are already padded wire (or leaf-cache entries) —
            # staging moves no bytes; cache entries resolve to committed
            # device buffers at dispatch (_run_staged).
            staged_b = skipped_b = hits = 0
            leaves = []
            for li in leaf_map:
                leaf = g.leaves[li]
                if leaf.entry is not None:
                    hits += 1
                    skipped_b += leaf.entry.nbytes
                    leaves.append(leaf.entry)
                else:
                    staged_b += leaf.wire.nbytes
                    leaves.append(leaf.wire)
            if self.tracer is not None:
                sp_up.args["bytes_staged"] = staged_b
                sp_up.args["bytes_skipped"] = skipped_b
                c = self.counters
                if hits:
                    c.inc("engine.leaf_cache.hits", hits)
                if len(leaf_map) - hits:
                    c.inc("engine.leaf_cache.misses", len(leaf_map) - hits)
                if g.cache_evictions:
                    c.inc("engine.leaf_cache.evictions", g.cache_evictions)
                    g.cache_evictions = 0
                if g.elided_bytes:
                    c.inc("engine.snapshot_bytes_elided", g.elided_bytes)
                    g.elided_bytes = 0
                if staged_b:
                    c.inc("engine.leaf_bytes_staged", staged_b)
        return (program, out_pos, live, out_idx, leaves)

    def _run_staged(self, g: _OpGraph, staged) -> None:
        """Dispatch-side half of a flush: compile, run, materialize."""
        program, out_pos, live, out_idx, leaves = staged
        tr = NULL_TRACER if self.tracer is None else self.tracer
        with tr.span("flush.compile") as sp_c:
            if self.tracer is not None:
                misses0 = _fused._cached_pipeline.cache_info().misses
            pipeline = get_pipeline(program, donate=self.donate_leaves,
                                    backend=self.fused_backend)
            if self.tracer is not None:
                hit = (_fused._cached_pipeline.cache_info().misses
                       == misses0)
                self.counters.inc("engine.pipeline_cache.hit" if hit
                                  else "engine.pipeline_cache.miss")
                sp_c.args["cache"] = "hit" if hit else "miss"
        leaves = self._resolve_cached_leaves(g, pipeline, leaves)
        rel = self.reliability
        with tr.span("flush.dispatch", n_ops=len(program.ops),
                     n_lanes=g.n) as sp_d:
            if rel is not None and rel.inject:
                # Fault-injection hook: the pipeline runs once clean
                # (the eager oracle), then the reliability plane votes
                # over map-driven faulty replicas, retrying/escalating
                # on weak margins (repro.reliability.plane).
                voted = with_fault_injection(
                    pipeline,
                    lambda o: rel.correct(o, program, g.n, span=sp_d))
                outs = voted(*leaves)
            else:
                outs = pipeline(*leaves)
        with tr.span("flush.materialize", n_outputs=len(out_idx)):
            for i, pos in zip(out_idx, out_pos):
                lz = live[i]
                lanes = g.layout.from_wire(outs[pos])[:g.n]
                if g.raw:  # re-join the lanes of each caller uint64 word
                    val = g.layout.join_raw(lanes)
                    if g.ops[i][0] == "popcount" \
                            and g.layout.raw_lanes_per_word == 2:
                        # A raw popcount's lanes hold per-lane partial
                        # counts: the word's count is their SUM (the
                        # adder tree's final fold), not a bit-join.
                        val = ((val >> np.uint64(32))
                               + (val & np.uint64(0xFFFFFFFF)))
                else:
                    val = lanes.astype(np.uint64)
                lz._value = val.reshape(lz.shape)
                # A materialized handle never needs the graph again — drop
                # the references so surviving handles don't pin the leaf
                # snapshots (or the engine) for their lifetime.
                lz._graph = None
                lz._engine = None
        if self.tracer is not None:
            self.counters.inc("engine.flushes")
            self.counters.observe("engine.flush_lanes", g.n)
            self.counters.observe("engine.flush_ops", len(program.ops))
            self.counters.observe("engine.flush_devices",
                                  _split_devices(outs[0]))
        if self.autotuner is not None:
            # Per-flush decision point: the online autotuner counts
            # windows / takes counter deltas here (reentrancy-guarded on
            # its side — a re-tune's own flushes never recurse).
            self.autotuner.on_flush(self)

    def _resolve_cached_leaves(self, g: _OpGraph, pipeline, leaves) -> list:
        """Resolve staged leaf-cache entries against the compiled pipeline:

        * non-donating jitted pipelines (``pipeline.wants_device`` says
          the program is big enough to leave the NumPy short-circuit)
          get the entry's committed device buffer — repeat flushes
          re-upload nothing;
        * everything else gets the entry's private host wire; a donating
          flush additionally drops the entry's device residency (the
          trace device-puts and donates a FRESH buffer — cached buffers
          are never donated, donated ones are never cached).
        """
        if not any(isinstance(x, _LeafCacheEntry) for x in leaves):
            return leaves
        cache = self._leaf_cache
        wants = getattr(pipeline, "wants_device", None)
        wire_words = (g.n + g._pad) * g.layout.wire_words_per_lane
        use_dev = (not self.donate_leaves and wants is not None
                   and wants(wire_words))
        out = []
        for x in leaves:
            if isinstance(x, _LeafCacheEntry):
                if use_dev:
                    out.append(cache.device_buffer(x))
                else:
                    if self.donate_leaves:
                        cache.drop_device(x)
                    out.append(x.wire)
            else:
                out.append(x)
        return out

    _PLANEWISE = frozenset({"and", "or", "xor"})

    def _binary(self, kind: str, opcode: str, a, b, np_fn):
        """kind prices the op (cost plane); opcode names it in the fused
        ISA and the sim-backend ALU dispatch."""
        a, b = self._coerce(a), self._coerce(b)
        self._charge(kind, a.size)
        if self._can_fuse(a, b):
            if opcode in self._PLANEWISE and self._use_raw((a, b)):
                return self._record(opcode, (a, b), raw=True)
            return self._record(opcode, (a, b))
        return self._run2(opcode, self._force(a), self._force(b), np_fn)

    # -- private implementations (the repro.pum bridge) ----------------- #

    def _and(self, a, b):
        return self._binary("and2", "and", a, b, lambda x, y: x & y)

    def _or(self, a, b):
        return self._binary("or2", "or", a, b, lambda x, y: x | y)

    def _xor(self, a, b):
        return self._binary("xor2", "xor", a, b, lambda x, y: x ^ y)

    def _add(self, a, b):
        return self._binary("add", "add", a, b,
                            lambda x, y: (x + y) & self._mask(self.width))

    def _sub(self, a, b):
        return self._binary("add", "sub", a, b,
                            lambda x, y: (x - y) & self._mask(self.width))

    def _mul(self, a, b):
        return self._binary("mul", "mul", a, b,
                            lambda x, y: (x * y) & self._mask(self.width))

    def _divpart(self, a, b, which: str):
        """div or mod: ONE restoring-division charge; in fused mode the op
        lowers to the shared ``divmod`` tuple op plus a selector, so
        ``a // b`` and ``a % b`` of the same operands CSE into one divider
        pass at flush."""
        a, b = self._coerce(a), self._coerce(b)
        self._charge("div", a.size)
        if self._can_fuse(a, b):
            pair = self._record("divmod", (a, b), defer_flush=True,
                                internal=True)
            return self._record("fst" if which == "div" else "snd", (pair,))
        with np.errstate(divide="ignore", invalid="ignore"):
            fn = (lambda x, y: x // y) if which == "div" \
                else (lambda x, y: x % y)
            return self._run2(which, self._force(a), self._force(b), fn)

    def _div(self, a, b):
        return self._divpart(a, b, "div")

    def _mod(self, a, b):
        return self._divpart(a, b, "mod")

    def _divmod(self, a, b):
        """(quotient, remainder) for ONE division charge: the restoring
        divider produces both in the same pass (fused: one ``divmod``
        tuple op + two selectors; eager: one charge, two NumPy ops)."""
        a, b = self._coerce(a), self._coerce(b)
        self._charge("div", a.size)
        if self._can_fuse(a, b):
            pair = self._record("divmod", (a, b), defer_flush=True,
                                internal=True)
            q = self._record("fst", (pair,), defer_flush=True)
            r = self._record("snd", (pair,))
            return q, r
        with np.errstate(divide="ignore", invalid="ignore"):
            af, bf = self._force(a), self._force(b)
            if self._alu is not None and af.size <= self._alu.words * 32:
                # One restoring-division pass on the sim ALU yields both.
                # The ALU's divider assumes nonzero divisors; mask those
                # lanes to 0 to keep the engine-wide x//0 == x%0 == 0
                # contract (unsigned NumPy semantics) on every backend.
                va, vb = self._alu_load2(af, bf)
                vq, vr = self._alu.div(va, vb)
                zero = bf == 0
                out = (np.where(zero, np.uint64(0),
                                self._alu_store(vq, af)),
                       np.where(zero, np.uint64(0),
                                self._alu_store(vr, af)))
                for v in (vq, vr, va, vb):
                    self._alu.free(v)  # return the subarray rows
                return out
            return (af // bf, af % bf)

    def _less_than(self, a, b):
        a, b = self._coerce(a), self._coerce(b)
        self._charge("compare", a.size)
        if self._can_fuse(a, b):
            return self._record("less", (a, b))
        return (self._force(a) < self._force(b)).astype(np.uint64)

    def _popcount(self, a, width: int | None = None):
        a = self._coerce(a)
        w = width or self.width
        self._charge("popcount", a.size, n_planes=w)
        if self._can_fuse(a):
            # Raw packed-bitmap graphs keep popcount planewise on the
            # 64-bit words (the evaluators' adder tree counts the whole
            # word), joining the pending raw program instead of forcing
            # a mode-boundary flush that would materialize the operand.
            if self._use_raw((a,)):
                return self._record("popcount", (a,), raw=True)
            return self._record("popcount", (a,))
        return _vec_popcount(self._force(a))

    def _reduce_bits(self, a, kind: str, width: int | None = None):
        a = self._coerce(a)
        w = width or self.width
        self._charge(f"reduce_{kind}", a.size, n_planes=w)
        if self._can_fuse(a):
            return self._record(f"reduce_{kind}", (a,),
                                param=w if kind == "and" else 0)
        a = self._force(a)
        if kind == "and":
            return (a == self._mask(w)).astype(np.uint64)
        if kind == "or":
            return (a != 0).astype(np.uint64)
        pc = _vec_popcount(a)
        return pc & np.uint64(1)

    # -- deprecated compat shim (the pre-repro.pum method surface) ------ #
    # Each method is a one-line delegate that warns once per call site;
    # semantics are identical to the private implementations above.

    def and_(self, a, b):
        """Deprecated: use ``&`` on :class:`repro.pum.PumArray`."""
        _warn_deprecated("and_", "PumArray.__and__ (a & b)")
        return self._and(a, b)

    def or_(self, a, b):
        """Deprecated: use ``|`` on :class:`repro.pum.PumArray`."""
        _warn_deprecated("or_", "PumArray.__or__ (a | b)")
        return self._or(a, b)

    def xor(self, a, b):
        """Deprecated: use ``^`` on :class:`repro.pum.PumArray`."""
        _warn_deprecated("xor", "PumArray.__xor__ (a ^ b)")
        return self._xor(a, b)

    def add(self, a, b):
        """Deprecated: use ``+`` on :class:`repro.pum.PumArray`."""
        _warn_deprecated("add", "PumArray.__add__ (a + b)")
        return self._add(a, b)

    def sub(self, a, b):
        """Deprecated: use ``-`` on :class:`repro.pum.PumArray`."""
        _warn_deprecated("sub", "PumArray.__sub__ (a - b)")
        return self._sub(a, b)

    def mul(self, a, b):
        """Deprecated: use ``*`` on :class:`repro.pum.PumArray`."""
        _warn_deprecated("mul", "PumArray.__mul__ (a * b)")
        return self._mul(a, b)

    def div(self, a, b):
        """Deprecated: use ``//`` on :class:`repro.pum.PumArray`.
        Unsigned floor division; lanes dividing by zero yield 0 (the
        NumPy unsigned semantics, preserved bit-exactly when fused)."""
        _warn_deprecated("div", "PumArray.__floordiv__ (a // b)")
        return self._div(a, b)

    def mod(self, a, b):
        """Deprecated: use ``%`` on :class:`repro.pum.PumArray`.
        Unsigned remainder, priced as one division (the restoring divider
        computes the remainder alongside the quotient); lanes with a zero
        divisor yield 0."""
        _warn_deprecated("mod", "PumArray.__mod__ (a % b)")
        return self._mod(a, b)

    def divmod(self, a, b):
        """Deprecated: use ``divmod()`` on :class:`repro.pum.PumArray`."""
        _warn_deprecated("divmod", "PumArray.__divmod__ (divmod(a, b))")
        return self._divmod(a, b)

    def less_than(self, a, b):
        """Deprecated: use ``<`` on :class:`repro.pum.PumArray`."""
        _warn_deprecated("less_than", "PumArray.__lt__ (a < b)")
        return self._less_than(a, b)

    def popcount(self, a, width: int | None = None):
        """Deprecated: use :meth:`repro.pum.PumArray.popcount`."""
        _warn_deprecated("popcount", "PumArray.popcount()")
        return self._popcount(a, width)

    def reduce_bits(self, a, kind: str, width: int | None = None):
        """Deprecated: use :meth:`repro.pum.PumArray.reduce_bits`.
        Per-element AND/OR/XOR reduction across the element's bits."""
        _warn_deprecated("reduce_bits", "PumArray.reduce_bits(kind)")
        return self._reduce_bits(a, kind, width)

    def _alu_load2(self, a: np.ndarray, b: np.ndarray):
        """Both operands into sim-ALU vertical registers (one row budget:
        ``alu.words * 32`` lanes — callers guard the size)."""
        alu = self._alu
        return (alu.load(a.ravel()[: alu.words * 32]),
                alu.load(b.ravel()[: alu.words * 32]))

    def _alu_store(self, vec, like: np.ndarray) -> np.ndarray:
        """Read a sim-ALU register back into ``like``'s size and shape."""
        return self._alu.store(vec)[: like.size].reshape(like.shape)

    def _run2(self, name, a, b, np_fn):
        if self._alu is not None and a.size <= self._alu.words * 32:
            alu = self._alu
            va, vb = self._alu_load2(a, b)
            fn = {"and": alu.and_, "or": alu.or_, "xor": alu.xor,
                  "add": alu.add, "sub": alu.sub, "mul": alu.mul}.get(name)
            if fn is None and name in ("div", "mod"):
                # Zero-divisor lanes yield 0 on every backend (the ALU's
                # restoring divider assumes b != 0 elementwise).
                q, r = alu.div(va, vb)
                out = self._alu_store(q if name == "div" else r, a)
                out = np.where(b == 0, np.uint64(0), out)
                vecs = (q, r, va, vb)
            else:
                res = fn(va, vb)
                out = self._alu_store(res, a)
                vecs = (res, va, vb)
            for v in vecs:  # return the subarray rows to the pool: the
                alu.free(v)  # engine owns no Vec past the op
            return out
        return np_fn(a, b)

    # ------------------------------------------------------------------ #

    @property
    def latency_ms(self) -> float:
        return self.stats.latency_ns * 1e-6

    def reset_stats(self) -> None:
        with self._lock:
            self._stats_shards.clear()


_M1 = np.uint64(0x5555555555555555)
_M2 = np.uint64(0x3333333333333333)
_M4 = np.uint64(0x0F0F0F0F0F0F0F0F)
_H01 = np.uint64(0x0101010101010101)


def _vec_popcount(a: np.ndarray) -> np.ndarray:
    """Fixed-iteration SWAR popcount (Hacker's Delight 5-2): 12 vector ops
    regardless of data, replacing the data-dependent shift loop."""
    a = np.asarray(a, np.uint64).copy()
    a -= (a >> np.uint64(1)) & _M1
    a = (a & _M2) + ((a >> np.uint64(2)) & _M2)
    a = (a + (a >> np.uint64(4))) & _M4
    return (a * _H01) >> np.uint64(56)
