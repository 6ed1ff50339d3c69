"""Fused workset-sample kernel: gather-from-ring → dequantize → row-cosine
→ threshold → cotangent-scale in ONE VMEM pass (the local-update hot path,
paper Algorithm 2 over the §3.1 cache).

The unfused composition materializes a full-precision copy of the sampled
ring entry in HBM (``tree_map(lambda b: b[slot], buf)``) and then the
weighting kernel re-reads it — two-plus HBM passes over the cut
statistics, all at fp32.  This kernel reads the sampled rows STRAIGHT out
of the (possibly int8-at-rest) ring and writes only the weights and the
weighted cotangent: one pass, and with the quantized cache over ~4x fewer
bytes.  It runs ``n_local x K`` times per communication round — the
dominant on-device loop once the wire is compressed and pipelined.

Layout decisions for TPU:
  * the dynamic ring slot rides in as a SCALAR-PREFETCH operand
    (``pltpu.PrefetchScalarGridSpec``): the BlockSpec index maps consume it
    before the body runs, so only the selected slot's blocks are ever
    DMA'd — the gather happens at the block-fetch level, no HBM-side entry
    copy exists;
  * rows (instances) on the sublane axis, the flattened feature dim on the
    lane axis, cut into lane-aligned feature tiles
    (``kernels.feature_tile``).  The grid is (row blocks, phase,
    feature tiles): phase 0 accumulates the three row sums over the tiles
    of a ∗ z, phase 1 walks the ∇Z tiles and writes the weighted
    cotangent once the row's weight is known.  Index maps hold the block
    a phase does not need, so no operand is fetched twice.  A row that is
    one tile (the paper's DLRM rows) runs both phases in one step;
  * the int8 cache's one-fp32-scale-per-row is a (rows, 1) column block
    ((W, B, 1) at rest) and dequantizes as a lane broadcast;
  * fp32 compute regardless of storage dtype (int8/bf16 upcast in VMEM);
    the fp32-ring variant reproduces ``kernels.ref`` bit-for-bit when a
    row is one tile (same reduction over the same blocks — the golden
    traces pin this through the engine).  Rows cut into several tiles
    sum the per-tile partial sums, which reassociates the float32 row
    reduction.

Oracles: ``kernels.ref.fused_sample_ref`` / ``fused_sample_q8_ref`` /
``fused_sample_q4_ref``.  B not divisible by BLOCK_B falls back to the
reference composition in the engine (same rule as the weighting kernel).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import LANES, feature_tile, pallas_call, smem_scalar
from .cosine_weight import (BLOCK_B, accumulate, row_sums_scratch,
                            row_weights)


def _decode_f32(z_ref):
    return z_ref[...].astype(jnp.float32)


def _decode_q8(q_ref, s_ref):
    return q_ref[...].astype(jnp.float32) * s_ref[...]


def unpack4(packed):
    """(rows, P) packed uint8 -> (rows, 2P) fp32 int4 codes, in VMEM.

    The at-rest nibble layout (``core.workset.pack_nibbles``) packs each
    group of 256 codes into 128 bytes: byte j holds code j in its low
    nibble and code j + 128 in its high nibble (a shorter last group
    splits its codes in halves the same way).  Unpacking is then two
    nibble extractions and lane-aligned concatenation.  The bytes are
    widened to int32 first: the VPU has no 8-bit arithmetic."""
    w = packed.astype(jnp.int32)
    lo = ((w & 0xF) - 8).astype(jnp.float32)
    hi = ((w >> 4) - 8).astype(jnp.float32)
    P = packed.shape[1]
    parts = []
    for g in range(0, P, LANES):
        e = min(g + LANES, P)
        parts += [lo[:, g:e], hi[:, g:e]]
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)


def _decode_q4(q_ref, s_ref):
    return unpack4(q_ref[...]) * s_ref[...]


def _sample_kernel(decode, n_ring: int, two_pass: bool):
    """Kernel over the (row block, phase, feature tile) grid.  ``decode``
    turns one ring operand's refs (``n_ring`` of them) into fp32 rows."""
    def kernel(slot_ref, thresh_ref, a_ref, *refs):
        del slot_ref                         # consumed by the index maps
        z_refs, dz_refs = refs[:n_ring], refs[n_ring:2 * n_ring]
        w_ref, out_ref, acc_ref = refs[2 * n_ring:]
        p, k = pl.program_id(1), pl.program_id(2)

        @pl.when(p == 0)
        def _():
            accumulate(acc_ref, k, a_ref[...].astype(jnp.float32),
                       decode(*z_refs))

        @pl.when(p == int(two_pass))         # the row sums are complete
        def _():
            w = row_weights(acc_ref, thresh_ref[0])
            w_ref[...] = w
            out_ref[...] = decode(*dz_refs) * w
    return kernel


def _sample(decode, slot, ad_hoc, z_ops, dz_ops, cos_xi, *, tile: int,
            packed: bool = False, name: str):
    """pallas_call plumbing shared by the ring variants.

    ``z_ops`` / ``dz_ops``: the ring's arrays — (W, B, Fs) codes or
    values, plus a (W, B, 1) scale column for quantized rings.  ``tile``
    is the feature tile in STORED elements (packed bytes for int4, where
    one byte holds two codes)."""
    B, F = ad_hoc.shape
    Fs = z_ops[0].shape[2]
    bb = min(BLOCK_B, B)
    assert B % bb == 0, (B, bb)
    nk = Fs // tile
    two_pass = nk > 1
    ta = 2 * tile if packed else tile        # ad_hoc / cotangent tile

    # phase 0 walks a/z over the tiles and holds dz at tile 0; phase 1
    # holds a/z at the last tile and walks dz (each block fetched once)
    def first(p, k):
        return k + p * (nk - 1 - k)

    def second(p, k):
        return k * p

    def ring_specs(ops, tiles):
        specs = [pl.BlockSpec((None, bb, tile),
                              lambda i, p, k, s: (s[0], i, tiles(p, k)))]
        if len(ops) == 2:                    # (W, B, 1) row scales
            specs.append(pl.BlockSpec((None, bb, 1),
                                      lambda i, p, k, s: (s[0], i, 0)))
        return specs

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B // bb, 2 if two_pass else 1, nk),
        in_specs=[smem_scalar(),
                  pl.BlockSpec((bb, ta),
                               lambda i, p, k, s: (i, first(p, k)))]
        + ring_specs(z_ops, first) + ring_specs(dz_ops, second),
        out_specs=[
            pl.BlockSpec((bb, 1), lambda i, p, k, s: (i, 0)),
            pl.BlockSpec((bb, ta), lambda i, p, k, s: (i, second(p, k))),
        ],
        scratch_shapes=[row_sums_scratch(bb)],
    )
    thresh = jnp.asarray([cos_xi], jnp.float32)
    return pallas_call(
        _sample_kernel(decode, len(z_ops), two_pass),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, F), jnp.float32),
        ],
        name=name,
    )(slot, thresh, ad_hoc, *z_ops, *dz_ops)


@jax.jit
def fused_sample_2d(slot, ad_hoc, z_ring, dz_ring, cos_xi):
    """Full-precision ring.  slot: (1,) int32; ad_hoc: (B, F); z_ring /
    dz_ring: (W, B, F).  -> (weights (B, 1) f32, weighted cotangent
    (B, F) f32) for the entry at ``slot``."""
    B, F = ad_hoc.shape
    return _sample(_decode_f32, slot, ad_hoc, (z_ring,), (dz_ring,),
                   cos_xi, tile=feature_tile(F, min(BLOCK_B, B)),
                   name="fused_sample")


@jax.jit
def fused_sample_q8_2d(slot, ad_hoc, zq, zscale, dzq, dzscale, cos_xi):
    """int8-at-rest ring.  zq / dzq: (W, B, F) int8 codes, zscale /
    dzscale: (W, B, 1) fp32 per-row scales.  Same contract as
    :func:`fused_sample_2d`; dequantization happens in VMEM."""
    B, F = ad_hoc.shape
    return _sample(_decode_q8, slot, ad_hoc, (zq, zscale), (dzq, dzscale),
                   cos_xi, tile=feature_tile(F, min(BLOCK_B, B)),
                   name="fused_sample_q8")


@jax.jit
def fused_sample_q4_2d(slot, ad_hoc, zq, zscale, dzq, dzscale, cos_xi):
    """int4 nibble-packed ring.  zq / dzq: (W, B, F // 2) packed uint8
    (F even — the storage codec pads odd rows; the caller pads ``ad_hoc``
    to match), zscale / dzscale: (W, B, 1) fp32 per-row scales.  Same
    contract as :func:`fused_sample_2d`; unpack + dequant happen in VMEM
    so the packed bytes are the only HBM ring traffic."""
    W, B, P = zq.shape
    assert ad_hoc.shape == (B, 2 * P), (ad_hoc.shape, B, P)
    return _sample(_decode_q4, slot, ad_hoc, (zq, zscale), (dzq, dzscale),
                   cos_xi, tile=feature_tile(P, min(BLOCK_B, B), width=2),
                   packed=True, name="fused_sample_q4")


# --------------------------------------------------------------------------
# Gather → dequant only (no weighting): the serving decode-activation read.
# Same scalar-prefetch gather as the sample kernels — only the selected
# slot's blocks are DMA'd out of the quantized ring — but the body is the
# bare dequant: serving consumes the cached cross-party activation as-is
# (there is no ad-hoc statistic to cosine-weight against at decode time).
# --------------------------------------------------------------------------
def _dequant_kernel(decode):
    def kernel(slot_ref, q_ref, s_ref, out_ref):
        del slot_ref                         # consumed by the index maps
        out_ref[...] = decode(q_ref, s_ref)
    return kernel


def _dequant(decode, slot, zq, zscale, *, width: int, name: str):
    """(W, B, Fs) codes + (W, B, 1) scales -> (B, width * Fs) fp32 rows of
    the entry at ``slot``, tiled over rows and features."""
    W, B, Fs = zq.shape
    bb = min(BLOCK_B, B)
    assert B % bb == 0, (B, bb)
    tile = feature_tile(Fs, bb, width)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B // bb, Fs // tile),
        in_specs=[
            pl.BlockSpec((None, bb, tile), lambda i, k, s: (s[0], i, k)),
            pl.BlockSpec((None, bb, 1), lambda i, k, s: (s[0], i, 0)),
        ],
        out_specs=pl.BlockSpec((bb, width * tile), lambda i, k, s: (i, k)),
    )
    return pallas_call(
        _dequant_kernel(decode),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, width * Fs), jnp.float32),
        name=name,
    )(slot, zq, zscale)


@jax.jit
def fused_dequant_q8_2d(slot, zq, zscale):
    """Gather + dequantize ONE int8 ring entry.  slot: (1,) int32; zq:
    (W, B, F) int8 codes, zscale: (W, B, 1) fp32 per-row scales.
    -> (B, F) fp32 rows of the entry at ``slot``; no full-precision ring
    copy ever exists in HBM."""
    return _dequant(_decode_q8, slot, zq, zscale, width=1,
                    name="fused_dequant_q8")


@jax.jit
def fused_dequant_q4_2d(slot, zq, zscale):
    """Gather + unpack + dequantize ONE int4 nibble-packed ring entry.
    zq: (W, B, F // 2) packed uint8, zscale: (W, B, 1) fp32 row scales.
    -> (B, F) fp32 (F = 2 * packed width; the caller slices any pad
    column)."""
    return _dequant(_decode_q4, slot, zq, zscale, width=2,
                    name="fused_dequant_q4")
