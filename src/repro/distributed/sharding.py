"""Sharding rules: DP / TP / EP / SP over the (pod, data, model) mesh.

Name-based rules map parameter paths to PartitionSpecs:
  * vocab (embedding/unembedding)      -> model
  * attention heads (q and kv)         -> model when divisible, else
    replicated (decided per-arch; uneven shards are avoided by construction)
  * FFN hidden                          -> model (all assigned d_ff are
    divisible by 16)
  * MoE experts                         -> model (EP: 64/16, 160/16)
  * MLA latent up-projections (heads)   -> model
  * SSM projections                     -> replicated in the baseline
    (mixed-boundary channel packing; lifted in the §Perf pass)
  * batch                               -> (pod?, data)
  * everything 1-D (norms, biases)      -> replicated

Optimizer states mirror their parameters (same tree structure).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.launch.mesh import data_axes, model_axis_size


def _shardable(dim: int, size: int) -> bool:
    return size > 1 and dim % size == 0


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def param_pspec(cfg, path_s: str, shape: tuple[int, ...], msize: int,
                dsize: int = 1, fsdp: bool = False,
                daxes: tuple[str, ...] = ("data",)) -> P:
    """PartitionSpec for one parameter. ``shape`` may include a leading
    stacked-layer dim (never sharded); rules index from the trailing dims.

    ``fsdp``: additionally shard a second (non-TP) dim over the data axes —
    ZeRO-3 via GSPMD: the compiler inserts per-layer weight all-gathers and
    gradient reduce-scatters. Used for training (and for serving models
    whose TP-only shards exceed HBM)."""
    none = P()
    dax = daxes if len(daxes) > 1 else daxes[0]

    def fs(dim_size):
        """data-axis entry for an fsdp-shardable dim."""
        return dax if fsdp and _shardable(dim_size, dsize) else None

    def spec_trailing(*trailing):
        pad = len(shape) - len(trailing)
        return P(*([None] * pad + list(trailing)))

    name = path_s.rsplit("/", 1)[-1]
    if len(shape) <= 1:
        return none
    # --- embeddings ---
    if name in ("embedding", "unembed"):
        if _shardable(shape[0], msize):
            return P("model", fs(shape[1]))
        return none
    # --- attention (GQA) ---
    if name == "wq" or name in ("wk", "wv"):
        h = shape[-2]
        if _shardable(h, msize):
            return spec_trailing(fs(shape[-3]), "model", None)
        return spec_trailing(fs(shape[-3]), None, None)
    if name == "wo":
        h = shape[-3]
        if _shardable(h, msize):
            return spec_trailing("model", None, fs(shape[-1]))
        return spec_trailing(None, None, fs(shape[-1]))
    if name in ("bq", "bk", "bv"):
        h = shape[-2]
        return (spec_trailing("model", None)
                if _shardable(h, msize) else none)
    # --- MLA ---
    if name in ("w_uq", "w_uk", "w_uv"):
        h = shape[-2]
        return (spec_trailing(fs(shape[-3]), "model", None)
                if _shardable(h, msize)
                else spec_trailing(fs(shape[-3]), None, None))
    if name in ("w_dq", "w_dkv", "w_kr"):
        return spec_trailing(fs(shape[-2]), None)
    # --- MoE ---
    if "moe" in path_s and name in ("w_gate", "w_up", "w_down"):
        e = shape[-3]
        if _shardable(e, msize):
            return spec_trailing("model", fs(shape[-2]), None)
        return spec_trailing(None, fs(shape[-2]), None)
    if name == "router":
        return none
    # --- dense MLP / shared experts ---
    if name in ("w_gate", "w_up"):
        f = shape[-1]
        return (spec_trailing(fs(shape[-2]), "model")
                if _shardable(f, msize)
                else spec_trailing(fs(shape[-2]), None))
    if name == "w_down":
        f = shape[-2]
        return (spec_trailing("model", fs(shape[-1]))
                if _shardable(f, msize)
                else spec_trailing(None, fs(shape[-1])))
    # --- SSM: TP-replicated in baseline; FSDP on d_model/d_inner dims ---
    if name in ("in_proj", "out_proj"):
        return spec_trailing(fs(shape[-2]), None)
    if name == "conv_w":
        return none
    return none


def param_shardings(cfg, mesh: Mesh, params_shape: Any, fsdp: bool = False,
                    tp: bool = True):
    """NamedSharding pytree for a params (or optimizer-state) shape tree.

    ``tp=False`` (small-model serving): weights replicate (the embedding /
    unembedding keep vocab TP — they are the one big matmul) and the model
    axis carries SEQUENCE parallelism instead — this removes the per-layer
    FFN all-reduce entirely (§Perf H1 iteration 2)."""
    msize = model_axis_size(mesh)
    daxes = data_axes(mesh)
    dsize = int(np.prod([mesh.shape[a] for a in daxes])) if daxes else 1

    def one(path, leaf):
        name = _path_str(path)
        if not tp and name.rsplit("/", 1)[-1] not in ("embedding", "unembed"):
            return NamedSharding(mesh, P())
        spec = param_pspec(cfg, name, leaf.shape, msize,
                           dsize=dsize, fsdp=fsdp, daxes=daxes)
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(one, params_shape)


def batch_pspec(mesh: Mesh) -> P:
    axes = data_axes(mesh)
    return P(axes if len(axes) > 1 else axes[0])


def batch_shardings(mesh: Mesh, batch_shape: Any, *, batch_divisible: bool
                    = True):
    """Shard the leading (batch) dim of every batch leaf over (pod, data);
    falls back to replication when the batch is too small (long_500k B=1,
    where sequence sharding takes over via activation constraints)."""
    daxes = data_axes(mesh)
    dsize = int(np.prod([mesh.shape[a] for a in daxes]))

    def one(leaf):
        if leaf.shape and leaf.shape[0] % dsize == 0:
            return NamedSharding(mesh, batch_pspec(mesh))
        return NamedSharding(mesh, P())

    return jax.tree.map(one, batch_shape)


def cache_shardings(cfg, mesh: Mesh, cache_shape: Any,
                    tp_threshold_bytes: float = 256e6):
    """Decode caches: batch -> data axes; kv-head dim -> model when it
    divides; seq (ring) dim -> model for B=1 long-context cells (SP).

    ``tp_threshold_bytes``: model-axis sharding of the KV head/head_dim is
    a MEMORY measure, but it back-propagates into the attention compute and
    (when only head_dim divides) forces partial-sum all-reduces per
    attention block — observed to make hymba's 32k prefill 128x
    collective-bound (§Perf H1). So it is applied only when the
    batch-sharded leaf exceeds this per-device size."""
    daxes = data_axes(mesh)
    dsize = int(np.prod([mesh.shape[a] for a in daxes]))
    msize = model_axis_size(mesh)
    dspec = daxes if len(daxes) > 1 else daxes[0]

    def one(path, leaf):
        s = leaf.shape
        p = _path_str(path)
        name = p.rsplit("/", 1)[-1]
        base_rank = {"k": 4, "v": 4, "c_kv": 3, "k_rope": 3,
                     "conv": 3, "state": 4}.get(name, len(s))
        lead = [None] * (len(s) - base_rank)  # stacked-layer dims: unsharded
        bi = len(s) - base_rank               # batch-dim index
        batch_ok = s and s[bi] % dsize == 0
        bdim = dspec if batch_ok else None
        nbytes = float(np.prod(s)) * leaf.dtype.itemsize
        per_dev = nbytes / (dsize if batch_ok else 1)
        if name in ("k", "v"):
            # [*, B, C, Hkv, dh]: prefer kv-head TP; fall back to head_dim
            # TP (partial-sum attention); SP on the ring for B=1 cells.
            need_tp = per_dev > tp_threshold_bytes
            hdim = ("model" if need_tp and _shardable(s[bi + 2], msize)
                    else None)
            ddim = ("model" if need_tp and hdim is None
                    and _shardable(s[bi + 3], msize) else None)
            cdim = (dspec if not batch_ok and _shardable(s[bi + 1], dsize)
                    else None)
            return NamedSharding(mesh, P(*lead, bdim, cdim, hdim, ddim))
        if name in ("c_kv", "k_rope"):
            # [*, B, C, R]: flash-decoding layout — the cache SEQUENCE
            # shards over `model`, so absorbed-MLA scores compute locally
            # per seq-shard and only [B, H, R] partials cross the wire.
            # (R-dim TP was 700x worse: the score contraction over a
            # sharded R made XLA all-gather the whole cache — §Perf H3.)
            cdim = None
            if _shardable(s[bi + 1], msize):
                cdim = "model"
            elif not batch_ok and _shardable(s[bi + 1], dsize):
                cdim = dspec
            return NamedSharding(mesh, P(*lead, bdim, cdim, None))
        return NamedSharding(
            mesh, P(*lead, bdim, *([None] * (base_rank - 1))))

    return jax.tree_util.tree_map_with_path(one, cache_shape)


def replicated(mesh: Mesh, tree: Any):
    return jax.tree.map(lambda _: NamedSharding(mesh, P()), tree)


# --------------------------------------------------------------------- #
# PuM word-axis sharding (the fused dataplane's `shard-words` backend)
# --------------------------------------------------------------------- #


def words_mesh(devices=None) -> Mesh:
    """1-D ``("words",)`` mesh over the local devices: the PuM fused
    dataplane is elementwise across packed words, so the word axis is the
    one natural partition dimension (every device runs the same fused
    program on its slice, no collectives)."""
    devices = jax.devices() if devices is None else list(devices)
    return Mesh(np.array(devices), ("words",))


def words_sharding(mesh: Mesh) -> NamedSharding:
    """Leading-axis sharding of a flat packed-word array over ``mesh``."""
    return NamedSharding(mesh, P("words"))


@dataclasses.dataclass(frozen=True)
class WordsPlacement:
    """Where the ``shard-words`` pipeline keeps a leaf: contiguous word
    ranges over the ``("words",)`` mesh, the leaf padded with zero words
    to ``multiple`` (32 words a device, so every shard is whole lane
    groups). The leaf cache commits its entries through :meth:`put`, and
    the pipeline places host leaves the same way, so both agree on one
    placement."""
    sharding: NamedSharding
    multiple: int

    @property
    def devices(self) -> int:
        return self.sharding.mesh.size

    def holds(self, x) -> bool:
        """``x`` is already committed under this placement."""
        return isinstance(x, jax.Array) and x.sharding == self.sharding

    def put(self, wire) -> jax.Array:
        """Commit one flat wire array: pad it (one host copy, only when
        its length is not a multiple), then ``device_put`` each device's
        contiguous slice."""
        pad = (-wire.shape[0]) % self.multiple
        if pad:
            wire = np.pad(np.asarray(wire), (0, pad))
        return jax.device_put(wire, self.sharding)


def words_placement(devices=None) -> WordsPlacement:
    """The placement of ``shard-words`` over ``devices`` (default: every
    local device)."""
    sharding = words_sharding(words_mesh(devices))
    return WordsPlacement(sharding, 32 * sharding.mesh.size)
