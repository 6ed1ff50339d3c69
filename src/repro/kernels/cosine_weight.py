"""Fused staleness-weighting kernel (paper Algorithm 2 hot path).

The naive composition (row-cosine, threshold, broadcast-multiply into ∇Z)
makes three HBM round-trips over the (B, F) statistics.  The kernels here
and in ``fused_sample.py`` fuse reduction + threshold + scale into one
VMEM pass over the operands.

Layout decisions for TPU:
  * rows (instances) on the sublane axis, features on the lane axis — the
    row-reduction is a lane reduction, natively supported by the VPU;
  * the feature axis is a grid dimension.  A DLRM cut row (256 floats in
    the paper) is one tile; a split-LLM row (F = S * d, ~half a million
    floats) is cut into lane-aligned tiles of at most ``BLOCK_ELEMS``
    elements per block, and the three row sums accumulate over the tiles
    in a VMEM scratch before the threshold is applied;
  * every per-row result (weights, scales) is a ``(rows, 1)`` column
    block, which Mosaic lays out natively; the ops wrappers reshape the
    weights to (B,);
  * scalars (the threshold) live in SMEM;
  * fp32 accumulation regardless of input dtype (bf16 inputs upcast in
    VMEM).

Inputs of any rank are flattened to (B, F) by the ops.py wrapper.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import feature_tile, pallas_call, smem_scalar

EPS = 1e-12
BLOCK_B = 128


def row_sums_scratch(rows: int):
    """VMEM accumulator of the three row sums (a·z, a·a, z·z)."""
    return pltpu.VMEM((3, rows, 1), jnp.float32)


def accumulate(acc_ref, k, a, z):
    """Add one feature tile's row sums into the scratch (k == 0 starts
    the row)."""
    sums = (jnp.sum(a * z, axis=1, keepdims=True),
            jnp.sum(a * a, axis=1, keepdims=True),
            jnp.sum(z * z, axis=1, keepdims=True))

    @pl.when(k == 0)
    def _():
        for j, s in enumerate(sums):
            acc_ref[j] = s

    @pl.when(k > 0)
    def _():
        for j, s in enumerate(sums):
            acc_ref[j] = acc_ref[j] + s


def row_weights(acc_ref, thresh):
    """Completed row sums -> (rows, 1) cosines floored at ``thresh``."""
    den = jnp.sqrt(acc_ref[1] * acc_ref[2])
    w = acc_ref[0] / jnp.maximum(den, EPS)
    return jnp.where(w < thresh, 0.0, w)


def _kernel_weights_only(thresh_ref, a_ref, s_ref, w_ref, acc_ref):
    k = pl.program_id(1)
    accumulate(acc_ref, k, a_ref[...].astype(jnp.float32),
               s_ref[...].astype(jnp.float32))

    @pl.when(k == pl.num_programs(1) - 1)
    def _():
        w_ref[...] = row_weights(acc_ref, thresh_ref[0])


@jax.jit
def cosine_weights_2d(ad_hoc, stale, cos_xi):
    """Weights-only variant: loads 2 (B, F) operands, writes only the
    (B, 1) weights — for the label party's InsWeight, where no cotangent
    scale follows (the weighted loss drives the backward pass instead)."""
    B, F = ad_hoc.shape
    bb = min(BLOCK_B, B)
    assert B % bb == 0, (B, bb)
    tf = feature_tile(F, bb)
    thresh = jnp.asarray([cos_xi], jnp.float32)
    return pallas_call(
        _kernel_weights_only,
        grid=(B // bb, F // tf),
        in_specs=[
            smem_scalar(),
            pl.BlockSpec((bb, tf), lambda i, k: (i, k)),
            pl.BlockSpec((bb, tf), lambda i, k: (i, k)),
        ],
        out_specs=pl.BlockSpec((bb, 1), lambda i, k: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, 1), jnp.float32),
        scratch_shapes=[row_sums_scratch(bb)],
        name="cosine_weights",
    )(thresh, ad_hoc, stale)


@jax.jit
def cosine_weight_2d(ad_hoc, stale, dz, cos_xi):
    """ad_hoc, stale, dz: (B, F).  -> (weights (B, 1) f32, weighted dz in
    dz's dtype).  The fused-sample kernel over a one-slot ring: the same
    two-phase body (row sums over the feature tiles, then the scale)."""
    from .fused_sample import fused_sample_2d
    w, out = fused_sample_2d(jnp.zeros((1,), jnp.int32), ad_hoc,
                             stale[None], dz[None], cos_xi)
    return w, out.astype(dz.dtype)

