"""EngineConfig — the frozen configuration object behind ``pum.device``.

One immutable dataclass configures a device: every knob is named here,
validated once (``__post_init__``), and handed whole to the
:class:`~repro.core.engine.PulsarEngine` a :class:`~repro.pum.Device`
owns. ``dataclasses.replace`` derives variants (the idiom the benchmarks
use for PULSAR-vs-FracDRAM pairs).
"""

from __future__ import annotations

import dataclasses
from typing import Any

from repro.backends import DATAPLANES, fused_evaluator


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Complete configuration of one PuM device.

    Fields mirror the modeled hardware (``mfr``/``width``/``row_bits``/
    ``banks``), the cost plane (``use_pulsar``/``chained``/``controller``)
    and the execution pipeline (``backend``/``fuse``/auto-flush bounds/
    ``donate_leaves``). ``fuse`` defaults to **True**: the fused lazy
    pipeline is the production path (bit-exact and stats-identical to
    eager — set ``fuse=False`` to force per-op eager execution).

    * ``backend`` — the eager dataplane: ``"fast"`` (packed NumPy words)
      or ``"sim"`` (bit-exact chip model; per-op by construction, so a
      ``sim`` config always carries ``fuse=False``).
    * ``layout`` — plane-layout word bits of the fused dataplane (32 or
      64, or a ``repro.kernels.plane_layout.PlaneLayout``). ``None``
      derives the narrowest canonical layout holding ``width`` (32-bit
      up to width 32, 64-bit above); pass 64 explicitly to run narrow
      values on 64-bit lanes (e.g. to keep raw uint64 bitmaps unsplit).
    * ``fused_backend`` — pin a fused evaluator of
      ``repro.backends.EVALUATORS`` by name (e.g. ``"shard-words"``, the
      multi-device word-axis pipeline); it must run the resolved layout.
      ``None`` leaves the choice to ``repro.backends.fused_evaluator``.
    * ``controller`` — ``None`` (closed-form bank divide), ``"auto"``
      (build a ``MemoryController``), or a controller instance.
    * ``ref_postponing`` — REF commands batched per rank lockout by the
      ``"auto"`` controller's refresher (1..8; JEDEC allows postponing up
      to 8): longer but rarer refresh windows, priced by ``batch_cost``.
      A value other than 1 needs ``controller="auto"``.
    * ``cmd_buffer_lookahead`` — per-bank command-buffer depth of the
      concurrent-client crossbar (LiteDRAM's ``cmd_buffer_depth``): how
      many pending sequences each bank machine may hold when scheduling
      concurrent streams. Threaded into the ``"auto"`` controller (its
      ``schedule_concurrent`` default); purely an execution knob — the
      single-stream cost plane never consults it.
    * ``donate_leaves`` — donate leaf device buffers to the fused trace
      (``jax.jit(..., donate_argnums=...)``): XLA may reuse them for
      intermediates, cutting pipeline peak memory. Results are
      bit-identical either way.
    * ``leaf_cache_bytes`` — byte budget of the per-device leaf cache
      (staged wire snapshots keyed by buffer pointer + content
      fingerprint, re-served across flushes and capture replays; see
      docs/execution-pipeline.md "Flush-path memory traffic"). ``0`` or
      ``None`` disables the cache. Results and ``EngineStats`` are
      bit-identical either way.
    * ``success_db`` — optional ``SuccessRateDb`` override for the
      characterization data (tests/sensitivity sweeps).
    * ``reliability`` — ``None`` (default: every path unchanged), or a
      ``repro.reliability.ReliabilityConfig`` / calibrated
      ``ReliabilityMap`` (= config with defaults): the engine plans
      replication per op from the map, steers placement onto strong
      banks/subarrays, and — when the config sets ``inject=True`` — runs
      the flush-time fault-injection + replication-vote/retry loop
      (requires ``fuse=True``; see docs/reliability.md).
    """

    mfr: str = "M"
    width: int = 32
    row_bits: int = 65536
    banks: int = 16
    backend: str = "fast"
    use_pulsar: bool = True
    chained: bool = False
    controller: Any = None
    seed: int = 0
    fuse: bool = True
    flush_threshold: int | None = 1024
    flush_memory_bytes: int | None = 1 << 30
    donate_leaves: bool = False
    leaf_cache_bytes: int | None = 1 << 26
    success_db: Any = None
    layout: Any = None
    fused_backend: str | None = None
    ref_postponing: int = 1
    reliability: Any = None
    cmd_buffer_lookahead: int = 8

    def __post_init__(self):
        if self.backend not in DATAPLANES:
            raise ValueError(f"backend must be 'fast' or 'sim', got "
                             f"{self.backend!r}")
        if self.backend == "sim" and self.fuse:
            # The chip model has no word dataplane to fuse over.
            object.__setattr__(self, "fuse", False)
        if not 1 <= self.width <= 64:
            raise ValueError(f"width must be in [1, 64], got {self.width}")
        if self.flush_threshold is not None and self.flush_threshold < 1:
            raise ValueError("flush_threshold must be >= 1 or None")
        if self.cmd_buffer_lookahead < 1:
            raise ValueError("cmd_buffer_lookahead must be >= 1 (each "
                             "bank machine holds at least one sequence)")
        if self.leaf_cache_bytes is not None and self.leaf_cache_bytes < 0:
            raise ValueError("leaf_cache_bytes must be >= 0 or None")
        if not 1 <= self.ref_postponing <= 8:
            raise ValueError("ref_postponing must be in [1, 8] (JEDEC "
                             "allows postponing up to 8 REFs)")
        if self.ref_postponing != 1 and self.controller != "auto":
            # Loud, not silently inert: the closed-form path never models
            # refresh, and a prebuilt controller carries its own policy.
            raise ValueError(
                "ref_postponing requires controller='auto' (with "
                "controller=None refresh is not modeled; a prebuilt "
                "MemoryController sets postponing= itself)")
        layout = self.resolved_layout()
        if layout.word_bits < self.width:
            raise ValueError(
                f"width {self.width} does not fit the "
                f"{layout.word_bits}-bit plane layout")
        if self.fused_backend is not None:
            fused_evaluator(layout, pinned=self.fused_backend)
        if getattr(self.reliability, "inject", False) and not self.fuse:
            raise ValueError(
                "reliability fault injection hooks the fused dispatch "
                "path; it requires fuse=True (eager ops never run the "
                "vote/retry loop)")

    def resolved_layout(self):
        """The :class:`~repro.kernels.plane_layout.PlaneLayout` this
        config runs on (``layout`` resolved, or derived from ``width``)."""
        from repro.kernels.plane_layout import get_layout, layout_for_width
        if self.layout is None:
            return layout_for_width(self.width)
        return get_layout(self.layout)

    def replace(self, **changes) -> "EngineConfig":
        """A copy with ``changes`` applied (``dataclasses.replace``)."""
        return dataclasses.replace(self, **changes)
