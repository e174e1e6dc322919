"""The fused evaluators, and the one rule that chooses among them.

A flush compiles its program into one pipeline, built by one of four
evaluators (:data:`EVALUATORS`), each running the plane layouts
(``repro.kernels.plane_layout``) it lists:

* ``words-cpu`` — the horizontal word-domain evaluator (32 and 64 bits);
* ``pallas-tpu`` — the Pallas vertical bit-plane kernel (32 and 64 bits);
* ``shard-words`` — the word evaluator with its word axis split over
  the host's devices (32 bits);
* ``ref-vertical`` — the vertical jnp oracle the other two vertical
  paths are checked against (32 and 64 bits), run only by name.

:func:`fused_evaluator` makes the choice for every caller (the engine's
flush, ``fused_program.get_pipeline``, the autotuner, ``chip_smoke.py``).
The eager dataplane is not chosen here: ``EngineConfig.backend`` is
``"fast"`` (packed NumPy words) or ``"sim"`` (the bit-exact chip model,
:func:`build_sim_dataplane`).

The module imports nothing of ``repro`` at module level: builders import
their implementation lazily, so it can be imported from anywhere in the
stack without cycles.
"""

from __future__ import annotations

import functools


# The eager dataplanes an ``EngineConfig.backend`` names: packed NumPy
# words, or the chip model (build_sim_dataplane).
DATAPLANES = ("fast", "sim")


def build_sim_dataplane(config):
    """Bit-exact chip-model dataplane: a small simulated DRAM region with
    the dual-rail bit-serial ALU on top (cycle-exact command accounting),
    for an ``EngineConfig`` with ``backend="sim"``."""
    from repro.core.alu import BitSerialAlu
    from repro.core.chip import PulsarChip
    from repro.core.geometry import DramGeometry
    from repro.core.profiles import PROFILES
    from repro.core.pulsar import PulsarExecutor
    geom = DramGeometry(row_bits=min(config.row_bits, 2048),
                        rows_per_subarray=512, subarrays_per_bank=2,
                        banks=2)
    profile = PROFILES[config.mfr]
    chip = PulsarChip(geom, profile, seed=config.seed)
    chip.decoder = chip.decoder.__class__(geom, profile, None)
    return BitSerialAlu(PulsarExecutor(chip, 0, 0), width=config.width)


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


# name -> (builder(program, interpret=, donate=) -> fn(*leaves) -> outs,
#          the plane-layout word bits it runs)
EVALUATORS = {
    "words-cpu": (_build_words_pipeline, (32, 64)),
    "pallas-tpu": (_build_pallas_pipeline, (32, 64)),
    "shard-words": (_build_sharded_words_pipeline, (32,)),
    "ref-vertical": (_build_ref_vertical_pipeline, (32, 64)),
}


def fused_evaluator(layout, leaf_bytes: int = 0,
                    pinned: str | None = None) -> str:
    """The evaluator a flush on ``layout`` (word bits, or a
    ``PlaneLayout``) runs on.

    * A ``pinned`` name wins; it raises ``ValueError`` where that
      evaluator does not run the layout.
    * A 32-bit flush whose one-chip plan outgrows one device of a
      multi-device host (:func:`shards_over_devices` on ``leaf_bytes``)
      goes to ``shard-words``.
    * Otherwise a TPU runs ``pallas-tpu``; off a TPU, a multi-device
      host runs ``shard-words`` at 32 bits and ``words-cpu`` elsewhere.

    ``ref-vertical`` is only ever run by name.
    """
    wb = getattr(layout, "word_bits", layout)
    if pinned is not None:
        if pinned not in EVALUATORS:
            raise ValueError(f"unknown fused evaluator {pinned!r}; one of "
                             f"{sorted(EVALUATORS)}")
        if wb not in EVALUATORS[pinned][1]:
            raise ValueError(f"fused evaluator {pinned!r} does not run the "
                             f"{wb}-bit plane layout")
        return pinned
    if wb == 32 and leaf_bytes:
        devices, chip_bytes = device_memory()
        if shards_over_devices(devices, leaf_bytes, chip_bytes):
            return "shard-words"
    if on_tpu():
        return "pallas-tpu"
    return "shard-words" if wb == 32 and multi_device() else "words-cpu"


def host_evaluators() -> tuple[str, ...]:
    """The evaluators this host can run unpinned, the autotuner's
    candidates: ``words-cpu`` everywhere (the word path runs jitted on
    any platform), ``pallas-tpu`` on a TPU, ``shard-words`` on a
    multi-device host. ``ref-vertical``, the test oracle, is never among
    them."""
    return (("words-cpu",) + (("pallas-tpu",) if on_tpu() else ())
            + (("shard-words",) if multi_device() else ()))


def on_tpu() -> bool:
    """The one TPU-detection rule: gates the choice of ``pallas-tpu`` here
    and the Pallas dispatch of the kernel wrappers in kernels/ops.py."""
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
    """Gates the choice of the sharded word pipeline: with one local
    device the plain word evaluator is the same computation minus the
    placement overhead."""
    import jax
    return len(jax.devices()) > 1


# What the one-chip ``pallas-tpu`` plan of a flush takes per byte of its
# leaves: the leaves, their bit-plane stack and the outputs. The TPU
# compiler plans 8.25 GiB for the whole-table bitmap query's 3.75 GiB of
# leaves (2.2x) and 16.25 GiB for 7.5 GiB (2.17x); 2.25 is the larger
# ratio rounded up to a quarter (chipbench/tests/test_chipbench_fit.py).
ONE_CHIP_PLAN_FACTOR = 2.25


def shards_over_devices(devices: int, leaf_bytes: int,
                        chip_bytes: int | None) -> bool:
    """The size rule of :func:`fused_evaluator`: a flush goes to
    ``shard-words`` when the host has more than one device and the
    one-chip plan of its leaves (``leaf_bytes`` x
    :data:`ONE_CHIP_PLAN_FACTOR`) exceeds one device's memory limit
    ``chip_bytes``. A host whose devices report no limit (the CPU) keeps
    the platform's choice."""
    return (devices > 1 and chip_bytes is not None
            and leaf_bytes * ONE_CHIP_PLAN_FACTOR > chip_bytes)


@functools.lru_cache(maxsize=1)
def device_memory() -> tuple[int, int | None]:
    """(local device count, the first device's ``bytes_limit``, or None
    where the backend reports no memory statistics)."""
    import jax
    devices = jax.devices()
    stats = devices[0].memory_stats() or {}
    return len(devices), stats.get("bytes_limit")
