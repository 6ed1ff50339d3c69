"""Sharding rules: param-name-driven PartitionSpecs with divisibility guards.

Tensor-parallel (Megatron-style) layout over the ``model`` mesh axis,
data-parallel batches over ``data`` (and ``pod`` when the multi-pod mesh is
active — except in the party-to-pod CELU protocol, where ``pod`` carries the
two parties; see core/pod_protocol.py).

Every rule checks divisibility against the actual mesh axis size and falls
back to replication — e.g. GQA archs with n_kv ∈ {5, 8} < 16 replicate the
KV projections (exactly what production Llama-GQA TP does), hymba's 25 query
heads replicate while its d_ff=5504=16·344 shards, and so on.  This keeps
every (arch × mesh) combination lowerable without per-arch special cases.

Name-based rules (leaf key -> which logical dim shards over ``model``):

  embed        (V, d)        -> V          head       (d, V)   -> V
  wq           (d, H, hd)    -> H          wo   (H, hd, d)     -> H
  wk/wv        (d, Kv, hd)   -> Kv         mlp wg/wu  (d, f)   -> f
  mlp wd       (f, d)        -> f          moe  (E, d, f)      -> f ("tp") or E ("ep")
  mamba in_proj(d, 2di)      -> 2di        mamba out_proj (di, d) -> di
  xlstm w_x    (d, 4d)       -> 4d         norms/bias/scalars  -> replicate

Scanned tower stacks carry a leading layer axis (detected via a SequenceKey
in the tree path — stages are list entries), shifting every dim index by 1.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _axis_size(mesh: Mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, (tuple, list)):
        return int(np.prod([mesh.shape.get(a, 1) for a in axis]))
    return int(mesh.shape.get(axis, 1))


def _leaf_name(path) -> str:
    for p in reversed(path):
        if isinstance(p, jax.tree_util.DictKey):
            return str(p.key)
    return ""


def _is_scanned(path) -> bool:
    return any(isinstance(p, jax.tree_util.SequenceKey) for p in path)


# rule: name -> (shard_dim_from_end or from_start, ...) handled explicitly
def _param_spec(path, leaf, mesh: Mesh, model_axis: str,
                moe_sharding: str, fsdp_axis: Optional[str]) -> P:
    name = _leaf_name(path)
    msize = _axis_size(mesh, model_axis)
    fsize = _axis_size(mesh, fsdp_axis) if fsdp_axis else 1
    nd = leaf.ndim
    off = 1 if _is_scanned(path) else 0

    def _add_fsdp(parts: list) -> list:
        """ZeRO-3-style second axis: shard the largest remaining divisible
        dim over the data axis (weights all-gather before use; needed for
        the ≥30B archs to fit v5e HBM — see DESIGN §4)."""
        if not fsdp_axis or fsize == 1 or leaf.size < 1 << 20:
            return parts
        cands = sorted(
            (i for i in range(off, nd)
             if parts[i] is None and leaf.shape[i] % fsize == 0
             and leaf.shape[i] >= fsize),
            key=lambda i: -leaf.shape[i])
        if cands:
            parts[cands[0]] = fsdp_axis
        return parts

    def _model_dim(*dims: int) -> Optional[int]:
        """First candidate dim divisible by the model-axis size."""
        for dim in dims:
            if dim < nd and msize > 1 and leaf.shape[dim] % msize == 0 \
                    and leaf.shape[dim] >= msize:
                return dim
        return None

    # which dims to try sharding over `model`, by param name
    if name == "embed":
        cand = (off + 0,)
    elif name == "head":
        cand = (off + 1,)
    elif name == "wq":
        # (d, H, hd): shard heads only.  Sharding head_dim instead would
        # make every attention score a partial sum all-reduced over `model`
        # (measured: 8 GB/step extra collectives on smollm) — replicating,
        # as Megatron does for non-divisible head counts, is strictly better.
        cand = (off + 1,)
    elif name in ("wk", "wv"):
        cand = (off + 1,)
    elif name == "wo":
        cand = (off + 0,)
    elif name in ("wg", "wu"):
        if nd - off == 3:                 # MoE (E, d, f)
            cand = (off + 0,) if moe_sharding == "ep" else (off + 2,)
        else:
            cand = (off + 1,)
    elif name == "wd":
        if nd - off == 3:                 # MoE (E, f, d)
            cand = (off + 0,) if moe_sharding == "ep" else (off + 1,)
        else:
            cand = (off + 0,)
    elif name in ("in_proj", "w_x"):
        cand = (off + 1,)
    elif name == "out_proj":
        cand = (off + 0,)
    elif name in ("proj", "proj1", "proj2", "fuse_proj"):
        cand = (off + 1,)
    else:
        # norms, biases, routers, conv, ssm/xlstm small tensors, scalars
        return P()

    parts: list = [None] * nd
    dim = _model_dim(*cand)
    if dim is not None:
        parts[dim] = model_axis
    return P(*_add_fsdp(parts))


def params_pspecs(params, mesh: Mesh, *, model_axis: str = "model",
                  moe_sharding: str = "tp", fsdp_axis: Optional[str] = None):
    """Pytree of PartitionSpecs matching ``params``.

    ``fsdp_axis``: additionally shard big params over the data axis
    (ZeRO-3-style) — required for the ≥30B archs to fit v5e HBM."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    specs = [_param_spec(path, leaf, mesh, model_axis, moe_sharding,
                         fsdp_axis)
             for path, leaf in flat]
    return jax.tree_util.tree_unflatten(treedef, specs)


def batch_pspec(shape, mesh: Mesh, *, data_axes=("data",),
                model_axis: str = "model") -> P:
    """Shard an input batch leaf: batch dim over the data axes if divisible,
    else (decode with tiny batch) shard the next-largest dim — the
    sequence/capacity dim — over data, else replicate."""
    dsize = _axis_size(mesh, tuple(data_axes))
    ax = tuple(data_axes) if len(data_axes) > 1 else data_axes[0]
    nd = len(shape)
    if nd >= 1 and shape[0] % dsize == 0 and shape[0] >= dsize:
        return P(*((ax,) + (None,) * (nd - 1)))
    if nd >= 2 and shape[1] % dsize == 0 and shape[1] >= dsize:
        return P(*((None, ax) + (None,) * (nd - 2)))
    return P()


def tree_pspecs(tree, mesh: Mesh, *, data_axes=("data",)):
    """Batch-like pytrees (batches, caches, workset buffers)."""
    return jax.tree_util.tree_map(
        lambda leaf: batch_pspec(leaf.shape, mesh, data_axes=data_axes), tree)


def workset_pspecs(table, mesh: Mesh, *, data_axes=("data",)):
    """Ring-buffer tables (``core.workset``): every buf leaf carries a
    leading W slot axis — shard the per-instance batch dim (dim 1) over
    data, never the ring axis (a draw reads ONE slot; sharding W would
    turn every gather into a cross-device fetch).  This covers the
    quantized leaves transparently: ``QuantLeaf``/``Quant4Leaf`` codes
    (W, B, F or packed nibbles) and their (W, B, 1) scales shard B the
    same way, so an int4 ring shards identically to the fp32 ring it
    replaces.  Clock vectors (W,) and scalars replicate."""
    dsize = _axis_size(mesh, tuple(data_axes))
    ax = tuple(data_axes) if len(data_axes) > 1 else data_axes[0]

    def spec(leaf) -> P:
        nd = leaf.ndim
        if nd >= 2 and leaf.shape[1] % dsize == 0 and leaf.shape[1] >= dsize:
            return P(*((None, ax) + (None,) * (nd - 2)))
        return P()

    return jax.tree_util.tree_map(spec, table)


def opt_state_pspecs(opt_state, mesh: Mesh, *, data_axes=("data",)):
    """ZeRO-1-style specs for optimizer state, covering the quantized
    layouts (``optim.quantized``): a ``QuantAccum``'s int8 codes (R, C)
    and (R, 1) master scales shard the padded row dim over data (R is a
    multiple of the fused kernel's ROWS tiling, so it divides the usual
    data-axis sizes and every shard keeps whole requant rows — the
    row-max scale never crosses a device); fp32/bf16 accumulators shard
    their leading dim when divisible (the rule dryrun's ZeRO-1 path
    derives from ``params_pspecs``); SM3's factored row/col vectors,
    step counters, and other 1-D/scalar state replicate."""
    dsize = _axis_size(mesh, tuple(data_axes))
    ax = tuple(data_axes) if len(data_axes) > 1 else data_axes[0]

    def spec(leaf) -> P:
        nd = leaf.ndim
        if nd >= 2 and leaf.shape[0] % dsize == 0 and leaf.shape[0] >= dsize:
            return P(*((ax,) + (None,) * (nd - 1)))
        return P()

    return jax.tree_util.tree_map(spec, opt_state)


def _cache_spec(path, leaf, mesh: Mesh, data_axes, model_axis: str) -> P:
    """KV/state cache leaves: stacked (L, B, cap, Kv, hd) etc.  Shard batch
    over data if divisible; shard Kv/heads over model if divisible; for
    B=1 long-context decode, shard the capacity dim over data instead."""
    name = _leaf_name(path)
    dsize = _axis_size(mesh, tuple(data_axes))
    msize = _axis_size(mesh, model_axis)
    ax = tuple(data_axes) if len(data_axes) > 1 else data_axes[0]
    nd = leaf.ndim
    parts: list = [None] * nd
    if name in ("k", "v"):          # (L, B, cap, Kv, hd)
        if nd >= 5:
            if leaf.shape[1] % dsize == 0:
                parts[1] = ax
            elif leaf.shape[2] % dsize == 0:
                parts[2] = ax
            if leaf.shape[3] % msize == 0:
                parts[3] = model_axis
            elif parts[2] is None and leaf.shape[2] % msize == 0:
                # GQA kv ∈ {5, 8} < 16 can't shard heads — shard the cache
                # sequence dim over `model` instead (partial-softmax decode,
                # flash-decoding style; XLA inserts the psum combine).
                parts[2] = model_axis
    elif name in ("h", "C", "n", "c", "m", "conv"):   # ssm / xlstm states
        if nd >= 2 and leaf.shape[1] % dsize == 0:
            parts[1] = ax
    return P(*parts)


def cache_pspecs(cache, mesh: Mesh, *, data_axes=("data",),
                 model_axis: str = "model"):
    flat, treedef = jax.tree_util.tree_flatten_with_path(cache)
    specs = [_cache_spec(p, l, mesh, data_axes, model_axis) for p, l in flat]
    return jax.tree_util.tree_unflatten(treedef, specs)


def make_sharding(mesh: Mesh, pspec_tree):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), pspec_tree,
        is_leaf=lambda x: isinstance(x, P))
