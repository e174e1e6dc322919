"""Activation sharding-constraint helpers that degrade gracefully.

``maybe_shard(x, *axes)`` applies a with_sharding_constraint when the
surrounding (abstract) mesh actually has the named axes — so model code can
carry production constraints (EP dispatch buffers, logits vocab sharding)
while the same code runs unconstrained on a single CPU device in tests.
"""

from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as P


def _current_axes() -> tuple[str, ...]:
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return ()
    return tuple(mesh.axis_names)


def gather_layer_params(cfg, lp):
    """Constrain one scanned layer slice to its TP-only sharding (drop the
    FSDP data-axis factor). Inside a lax.scan body this forces GSPMD to
    slice-then-gather each layer's weights per iteration, instead of
    all-gathering the whole stacked [L, ...] tensor before the loop (which
    is what blows temp memory to ~model-size on big models)."""
    from repro.distributed.sharding import param_pspec  # lazy: no cycle
    axes = _current_axes()
    if "model" not in axes:
        return lp
    mesh = jax.sharding.get_abstract_mesh()
    msize = dict(zip(mesh.axis_names, mesh.axis_sizes))["model"]

    def one(path, leaf):
        parts = [str(getattr(p, "key", getattr(p, "idx", p))) for p in path]
        spec = param_pspec(cfg, "/".join(parts), leaf.shape, msize,
                           dsize=1, fsdp=False)
        return jax.lax.with_sharding_constraint(leaf, spec)

    return jax.tree_util.tree_map_with_path(one, lp)


def maybe_shard(x: jax.Array, *spec) -> jax.Array:
    """spec entries: axis name, tuple of axis names, or None. Entries whose
    axes are absent from the current mesh collapse to None."""
    axes = _current_axes()
    if not axes:
        return x

    def ok(entry):
        if entry is None:
            return None
        if isinstance(entry, tuple):
            kept = tuple(a for a in entry if a in axes)
            return kept if kept else None
        return entry if entry in axes else None

    cleaned = [ok(e) for e in spec]
    if all(c is None for c in cleaned):
        return x
    return jax.lax.with_sharding_constraint(x, P(*cleaned))
