"""Public jit'd wrappers for the Pallas kernels.

Each kernel compiles with Mosaic when the calling program is lowered for
a TPU and runs in the Pallas interpreter when it is lowered for the CPU
(``kernels.pallas_call``); there is nothing to switch by hand.  Per-row
results leave the kernels as (B, 1) columns; the wrappers hand callers
(B,) weights.  Quantized rings keep their row scales as (W, B, 1)
columns at rest, the layout the kernels read.
"""
from __future__ import annotations

import jax.numpy as jnp

from . import cosine_weight as _cw
from . import flash_attention as _fa
from . import fused_adagrad as _ag
from . import fused_sample as _fs
from . import quantize as _qz


def _slot1(slot):
    """Scalar slot index -> the (1,) int32 scalar-prefetch operand."""
    return jnp.asarray(slot, jnp.int32).reshape((1,))


def cosine_weight(ad_hoc, stale, cos_xi):
    """Algorithm-2 InsWeight: -> (B,) float32 weights (weights-only kernel:
    no cotangent operand/result moves through VMEM)."""
    B = ad_hoc.shape[0]
    w = _cw.cosine_weights_2d(ad_hoc.reshape(B, -1), stale.reshape(B, -1),
                              jnp.float32(cos_xi))
    return w.reshape(B)


def weighted_cotangent(ad_hoc, stale, dz, cos_xi):
    """Fused InsWeight + weights ⊙ ∇Z.  -> (weights (B,), weighted dz)."""
    B = ad_hoc.shape[0]
    shape = dz.shape
    w, out = _cw.cosine_weight_2d(ad_hoc.reshape(B, -1),
                                  stale.reshape(B, -1), dz.reshape(B, -1),
                                  jnp.float32(cos_xi))
    return w.reshape(B), out.reshape(shape)


def fused_gather_weight(slot, ad_hoc, z_ring, dz_ring, cos_xi):
    """Fused workset sample over a full-precision (fp32/bf16) ring:
    gather slot → row-cosine vs ad_hoc → threshold → cotangent scale in
    one VMEM pass.  slot: scalar int32; ad_hoc: (B, ...); z_ring/dz_ring:
    (W,) + ad_hoc.shape.  -> (weights (B,) f32, weighted cotangent f32 in
    ad_hoc's shape)."""
    B = ad_hoc.shape[0]
    W = z_ring.shape[0]
    w, cot = _fs.fused_sample_2d(_slot1(slot), ad_hoc.reshape(B, -1),
                                 z_ring.reshape(W, B, -1),
                                 dz_ring.reshape(W, B, -1),
                                 jnp.float32(cos_xi))
    return w.reshape(B), cot.reshape(ad_hoc.shape)


def fused_gather_weight_q8(slot, ad_hoc, zq, zscale, dzq, dzscale, cos_xi):
    """Fused workset sample over the int8-at-rest ring (gather → dequant →
    cosine → threshold → cotangent scale, one VMEM pass).  zq/dzq:
    (W, B, F) int8, zscale/dzscale: (W, B, 1) fp32 row scales."""
    B = ad_hoc.shape[0]
    w, cot = _fs.fused_sample_q8_2d(_slot1(slot), ad_hoc.reshape(B, -1),
                                    zq, zscale, dzq, dzscale,
                                    jnp.float32(cos_xi))
    return w.reshape(B), cot.reshape(ad_hoc.shape)


def fused_gather_weight_q4(slot, ad_hoc, zq, zscale, dzq, dzscale, cos_xi):
    """Fused workset sample over the int4 nibble-packed ring (gather →
    unpack → dequant → cosine → threshold → cotangent scale, one VMEM
    pass — the packed bytes are the only HBM ring read).  zq/dzq:
    (W, B, ceil(F/2)) packed uint8, zscale/dzscale: (W, B, 1) fp32 row
    scales.  Odd F: the storage codec's pad nibble decodes to zero, so
    the wrapper zero-pads ``ad_hoc`` to the packed width and slices the
    pad column off the cotangent."""
    B = ad_hoc.shape[0]
    a2d = ad_hoc.reshape(B, -1).astype(jnp.float32)
    F = a2d.shape[1]
    Fp = 2 * zq.shape[2]
    if Fp != F:                      # odd row width: one pad column
        a2d = jnp.pad(a2d, ((0, 0), (0, Fp - F)))
    w, cot = _fs.fused_sample_q4_2d(_slot1(slot), a2d, zq, zscale,
                                    dzq, dzscale, jnp.float32(cos_xi))
    return w.reshape(B), cot[:, :F].reshape(ad_hoc.shape)


def fused_gather_dequant_q8(slot, zq, zscale):
    """Gather + dequantize one int8 ring entry (the serving decode-cache
    read: the cached cross-party activation comes straight out of the
    quantized ring, no weighting).  zq: (W, B, F) int8, zscale: (W, B, 1)
    fp32 row scales.  -> (B, F) fp32."""
    return _fs.fused_dequant_q8_2d(_slot1(slot), zq, zscale)


def fused_gather_dequant_q4(slot, zq, zscale, width: int):
    """Gather + unpack + dequantize one int4 nibble-packed ring entry.
    zq: (W, B, ceil(F/2)) packed uint8, zscale: (W, B, 1) fp32 row
    scales, width: the true row width F (the pad nibble of odd rows is
    sliced off).  -> (B, F) fp32."""
    out = _fs.fused_dequant_q4_2d(_slot1(slot), zq, zscale)
    return out[:, :width]


def quantize_stochastic(x, u, levels):
    """Fused per-row absmax-scale stochastic-rounding quantizer.

    x: (T, L) value rows, u: (T, L) uniforms in [0, 1), levels: max code
    magnitude (127 = int8, 7 = int4).  -> (codes int8 (T, L), fp32 scales
    (T, 1)); bit-exact with ``kernels.ref.quantize_sr_ref``."""
    return _qz.quantize_sr_2d(x, u, levels)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0):
    """(B, S, H, hd) x3 -> (B, S, H, hd); kv pre-repeated to H heads."""
    return _fa.flash_attention(q, k, v, causal=causal, window=window)


def fused_adagrad(grad, accum, lr, eps):
    """-> (update fp32, new_accum fp32)."""
    return _ag.fused_adagrad(grad, accum, lr, eps)


def fused_adagrad_q8(grad2d, accum_q, accum_scale, u, lr, eps):
    """int8-at-rest AdaGrad step (dequant → accumulate → scale → requant
    in one VMEM pass; the fp32 accumulator never exists in HBM).
    grad2d/u: (R, C) fp32 in the optimizer's padded tiling, accum_q:
    (R, C) int8 codes, accum_scale: (R, 1) fp32 master scales.
    -> (update fp32, new codes int8, new scales)."""
    return _ag.fused_adagrad_q8(grad2d, accum_q, accum_scale, u, lr, eps)


def flash_attention_trainable(q, k, v, *, causal: bool = True,
                              window: int = 0):
    """Differentiable flash attention (custom VJP: FlashAttention-2
    backward kernels — dq / dkv recompute score tiles, never materialize
    the softmax)."""
    from .flash_attention_bwd import flash_attention_vjp
    return flash_attention_vjp(q, k, v, causal, window)
