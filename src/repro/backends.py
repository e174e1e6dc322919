"""Pluggable backend registry: capability lookup for dataplane evaluators.

Before this module existed, backend selection was hard-coded: the engine
branched on ``backend == "sim"`` to build the chip-model ALU, and
``kernels/fused_program.py`` branched on ``jax.default_backend() == "tpu"``
to pick the Pallas vertical evaluator over the word-domain one. Adding a
new evaluator (a width-64 plane backend, a multi-device sharded pipeline)
meant editing both call sites.

Now every evaluator is a registered :class:`BackendSpec` and the call
sites *look capabilities up*:

* the engine resolves its ``backend=`` name to an **eager dataplane**
  builder (capability ``"eager"``), which returns either ``None`` (compute
  on packed NumPy words — the ``"fast"`` default) or an ALU-protocol
  object (the bit-exact ``"sim"`` chip model);
* the fused pipeline resolves a :class:`FusedProgram` to a **fused
  evaluator** (capability ``"fused"``) by :func:`select_backend` — the
  highest-priority available backend whose ``max_width`` covers the
  program and whose declared ``layouts`` include the program's plane
  layout (the lane word format, see ``repro.kernels.plane_layout``).

A future backend is an additive ``register_backend(...)`` call — no
engine or compiler edits; the width-64 evaluators and the multi-device
``shard-words`` pipeline below are exactly that. The full contract (builder signatures per
capability) is documented in ``docs/api.md``; ``repro.pum`` re-exports
the registry functions as the public surface.

This module is intentionally dependency-free (no repro imports at module
level): builders import their implementation lazily so the registry can
be imported from anywhere in the stack without cycles.
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Callable


@dataclasses.dataclass(frozen=True)
class BackendSpec:
    """One registered backend.

    ``builder`` signature depends on capability:

    * ``"eager"`` backends: ``builder(engine) -> alu | None`` — called at
      ``PulsarEngine`` construction. Return ``None`` for the packed-NumPy
      word dataplane, or an object with the ``BitSerialAlu`` protocol
      (``words``, ``load``/``store``, ``and_``/``or_``/``xor``/``add``/
      ``sub``/``mul``/``div``) to route small operands through it.
    * ``"fused"`` backends: ``builder(program, interpret=..., donate=...)
      -> fn(*leaves) -> tuple(outs)`` — called (and cached) per program
      structure by ``fused_program.get_pipeline``. Leaves/outputs are flat
      int32 arrays of packed horizontal words.

    ``available`` gates automatic selection (e.g. the Pallas evaluator is
    only auto-selected on a TPU host); an unavailable backend can still be
    requested by name. ``max_width`` bounds the element width the backend
    can evaluate; ``layouts`` declares the plane-layout word sizes (32/64
    — see ``repro.kernels.plane_layout``) its pipelines consume;
    ``priority`` breaks ties (higher wins).
    """
    name: str
    builder: Callable[..., Any]
    capabilities: frozenset[str]
    max_width: int = 32
    priority: int = 0
    available: Callable[[], bool] = lambda: True
    layouts: frozenset[int] = frozenset({32})


_REGISTRY: dict[str, BackendSpec] = {}


def register_backend(name: str, builder: Callable[..., Any], *,
                     capabilities=("fused",), max_width: int = 32,
                     priority: int = 0,
                     available: Callable[[], bool] | None = None,
                     layouts=(32,)) -> BackendSpec:
    """Register (or replace) a backend under ``name`` and return its spec.

    Re-registering an existing name replaces it — callers own their
    namespace; the built-in names are ``fast``, ``sim``, ``words-cpu``,
    ``pallas-tpu``, ``ref-vertical``, their ``-64`` layout variants and
    the multi-device ``shard-words`` pipeline.
    """
    spec = BackendSpec(name=name, builder=builder,
                       capabilities=frozenset(capabilities),
                       max_width=max_width, priority=priority,
                       available=available or (lambda: True),
                       layouts=frozenset(int(b) for b in layouts))
    _REGISTRY[name] = spec
    return spec


def unregister_backend(name: str) -> None:
    """Remove a registered backend (mainly for tests)."""
    _REGISTRY.pop(name, None)


def get_backend(name: str) -> BackendSpec:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown backend {name!r}; registered: "
            f"{sorted(_REGISTRY)} (register_backend() adds new ones)"
        ) from None


def available_backends(capability: str | None = None) -> tuple[str, ...]:
    """Names of registered backends, optionally filtered by capability
    (registration order; includes unavailable ones — availability is a
    host property, registration is not)."""
    return tuple(n for n, s in _REGISTRY.items()
                 if capability is None or capability in s.capabilities)


# Selection overrides: capability -> pinned backend name. Consulted by
# select_backend before the priority scan — the autotuner's hook for
# steering callers that reach capability lookup without a Device (e.g.
# fused_program.get_pipeline with backend=None). An override only wins
# when its spec actually satisfies the query's capability/width/layout
# constraints; otherwise the normal lookup proceeds, so a pinned name
# can never produce a pipeline the program cannot run on.
_SELECTION_OVERRIDE: dict[str, str] = {}


def set_selection_override(capability: str, name: str | None) -> None:
    """Pin (or with ``None`` unpin) the backend ``select_backend``
    returns for single-capability ``capability`` queries. The pinned
    backend is validated against each query's width/layout constraints
    and skipped when it cannot satisfy them. Prefer the scoped
    :func:`selection_override` context manager."""
    if name is None:
        _SELECTION_OVERRIDE.pop(capability, None)
    else:
        get_backend(name)  # loud on unknown names
        _SELECTION_OVERRIDE[capability] = name


def get_selection_override(capability: str) -> str | None:
    """The currently pinned backend name for ``capability`` (or None)."""
    return _SELECTION_OVERRIDE.get(capability)


@contextlib.contextmanager
def selection_override(capability: str, name: str | None):
    """Scoped :func:`set_selection_override`: pin ``name`` for the
    duration of the block, restoring the previous pin on exit. The
    ``TunedPlan.selection_override()`` entry point."""
    prev = _SELECTION_OVERRIDE.get(capability)
    set_selection_override(capability, name)
    try:
        yield
    finally:
        set_selection_override(capability, prev)


def select_backend(*, require, width: int | None = None,
                   layout=None) -> BackendSpec:
    """Capability lookup: the highest-priority *available* backend whose
    capabilities cover ``require``, whose ``max_width`` covers ``width``,
    and whose declared ``layouts`` include ``layout`` (a word-bit count
    or a ``PlaneLayout``; ``None`` skips the filter). A
    :func:`set_selection_override` pin for the capability takes
    precedence when it satisfies the same constraints. Raises
    ``LookupError`` when nothing matches."""
    need = frozenset((require,) if isinstance(require, str) else require)
    wb = getattr(layout, "word_bits", layout)
    if len(need) == 1:
        pinned = _SELECTION_OVERRIDE.get(next(iter(need)))
        if pinned is not None:
            spec = _REGISTRY.get(pinned)
            if spec is not None and need <= spec.capabilities \
                    and (width is None or spec.max_width >= width) \
                    and (wb is None or wb in spec.layouts):
                return spec
    best: BackendSpec | None = None
    for spec in _REGISTRY.values():
        if not need <= spec.capabilities:
            continue
        if width is not None and spec.max_width < width:
            continue
        if wb is not None and wb not in spec.layouts:
            continue
        if not spec.available():
            continue
        if best is None or spec.priority > best.priority:
            best = spec
    if best is None:
        raise LookupError(
            f"no available backend with capabilities {sorted(need)}"
            + (f" at width {width}" if width is not None else "")
            + (f" on the {wb}-bit plane layout" if wb is not None else "")
            + f"; registered: {sorted(_REGISTRY)}")
    return best


# --------------------------------------------------------------------- #
# Built-in backends. Builders import lazily: the registry stays
# import-cycle-free and costs nothing until a backend is actually used.
# --------------------------------------------------------------------- #


def _build_fast_dataplane(engine) -> None:
    """Packed-NumPy word dataplane: the engine computes ops directly on
    uint64 ndarrays (and fuses through the lazy op graph when asked)."""
    return None


def _build_sim_dataplane(engine):
    """Bit-exact chip-model dataplane: a small simulated DRAM region with
    the dual-rail bit-serial ALU on top (cycle-exact command accounting)."""
    from repro.core.alu import BitSerialAlu
    from repro.core.chip import PulsarChip
    from repro.core.geometry import DramGeometry
    from repro.core.pulsar import PulsarExecutor
    geom = DramGeometry(row_bits=min(engine.row_bits, 2048),
                        rows_per_subarray=512, subarrays_per_bank=2,
                        banks=2)
    chip = PulsarChip(geom, engine.profile, seed=engine.seed)
    chip.decoder = chip.decoder.__class__(geom, engine.profile, None)
    return BitSerialAlu(PulsarExecutor(chip, 0, 0), width=engine.width)


def _build_words_pipeline(program, interpret: bool = False,
                          donate: bool = False):
    from repro.kernels import fused_program
    return fused_program.build_words_pipeline(program, donate=donate)


def _build_pallas_pipeline(program, interpret: bool = False,
                           donate: bool = False):
    from repro.kernels import fused_program
    return fused_program.build_vertical_pipeline(
        program, use_pallas=True, interpret=interpret, donate=donate)


def _build_ref_vertical_pipeline(program, interpret: bool = False,
                                 donate: bool = False):
    from repro.kernels import fused_program
    return fused_program.build_vertical_pipeline(
        program, use_pallas=False, interpret=interpret, donate=donate)


def _build_sharded_words_pipeline(program, interpret: bool = False,
                                  donate: bool = False):
    from repro.kernels import fused_program
    return fused_program.build_sharded_words_pipeline(program,
                                                      donate=donate)


def on_tpu() -> bool:
    """The one TPU-detection rule: gates Pallas auto-selection here and
    the Pallas dispatch of the kernel wrappers in kernels/ops.py."""
    import jax
    return jax.default_backend() == "tpu"


def use_compile_cache() -> str:
    """Give JAX's persistent compilation cache a fixed directory and
    return it. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has
    already read it and nothing changes. Otherwise the cache goes to
    ``.jax_cache`` at the root of this checkout: the directory is part of
    what a cached program is found by, so it never depends on a temp
    directory, a pid or the time. Called by entry points (the chip smoke
    run, the benchmark CLI), never on import."""
    import os
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        path = os.path.join(root, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def multi_device() -> bool:
    """Gates auto-selection of the sharded word pipeline: with one local
    device the plain word evaluator is the same computation minus the
    placement overhead."""
    import jax
    return len(jax.devices()) > 1


register_backend("fast", _build_fast_dataplane,
                 capabilities=("eager",), max_width=64, priority=10,
                 layouts=(32, 64))
register_backend("sim", _build_sim_dataplane,
                 capabilities=("eager", "sim"), max_width=64,
                 layouts=(32, 64))
register_backend("words-cpu", _build_words_pipeline,
                 capabilities=("fused",), max_width=32, priority=10)
register_backend("pallas-tpu", _build_pallas_pipeline,
                 capabilities=("fused", "vertical"), max_width=32,
                 priority=20, available=on_tpu)
# The vertical jnp oracle: never auto-selected (it exists to validate the
# other two), but requestable by name — get_pipeline(force_vertical=True).
register_backend("ref-vertical", _build_ref_vertical_pipeline,
                 capabilities=("fused", "vertical", "debug"), max_width=32,
                 priority=-10, available=lambda: False)

# 64-bit plane-layout evaluators: the SAME builders, registered
# additively over the wider layout — the registry extension story the
# module docstring promises. The engine reaches them whenever its layout
# is 64-bit (explicit EngineConfig.layout=64 or any width > 32).
register_backend("words-cpu-64", _build_words_pipeline,
                 capabilities=("fused",), max_width=64, priority=10,
                 layouts=(64,))
register_backend("pallas-tpu-64", _build_pallas_pipeline,
                 capabilities=("fused", "vertical"), max_width=64,
                 priority=20, available=on_tpu, layouts=(64,))
register_backend("ref-vertical-64", _build_ref_vertical_pipeline,
                 capabilities=("fused", "vertical", "debug"), max_width=64,
                 priority=-10, available=lambda: False, layouts=(64,))

# Multi-device sharded word pipeline: partitions the program's word axis
# across jax.devices() (jax.sharding mesh placement). Auto-selected only
# on multi-device hosts (beats words-cpu, loses to single-chip Pallas);
# always requestable by name (EngineConfig.fused_backend="shard-words").
register_backend("shard-words", _build_sharded_words_pipeline,
                 capabilities=("fused", "sharded"), max_width=32,
                 priority=15, available=multi_device)
