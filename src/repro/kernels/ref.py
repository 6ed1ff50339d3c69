"""Pure-jnp oracles for every Pallas kernel (the allclose targets).

Each function is the mathematically-plain composition that the fused kernel
must reproduce; tests sweep shapes/dtypes and assert the kernels (run by
the Pallas interpreter on the CPU) against these.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

EPS = 1e-12


def cosine_weight_ref(ad_hoc, stale, cos_xi: float):
    """Per-row cosine over flattened non-batch dims, floored at cos_xi.

    -> (B,) float32 weights (Algorithm 2 InsWeight)."""
    B = ad_hoc.shape[0]
    a = ad_hoc.reshape(B, -1).astype(jnp.float32)
    b = stale.reshape(B, -1).astype(jnp.float32)
    num = jnp.sum(a * b, axis=1)
    den = jnp.sqrt(jnp.sum(a * a, axis=1) * jnp.sum(b * b, axis=1))
    w = num / jnp.maximum(den, EPS)
    return jnp.where(w < cos_xi, 0.0, w)


def weighted_cotangent_ref(ad_hoc, stale, dz, cos_xi: float):
    """Fused InsWeight + weights ⊙ ∇Z (the full Algorithm-2 line 7-8 hot
    path): -> weighted cotangent, same shape/dtype as dz."""
    w = cosine_weight_ref(ad_hoc, stale, cos_xi)
    w = w.reshape((w.shape[0],) + (1,) * (dz.ndim - 1))
    return (dz.astype(jnp.float32) * w).astype(dz.dtype)


def fused_sample_ref(slot, ad_hoc, z_ring, dz_ring, cos_xi: float):
    """Gather-from-ring + InsWeight + cotangent scale over a
    full-precision ring (the fused-sample kernel's oracle).

    slot: scalar int; ad_hoc (B, ...); z_ring / dz_ring (W,) + ad_hoc
    shape.  -> (weights (B,) f32, weighted cotangent f32, ad_hoc's
    shape)."""
    B = ad_hoc.shape[0]
    z = z_ring[slot].reshape(B, -1)
    dz = dz_ring[slot].reshape(B, -1).astype(jnp.float32)
    w = cosine_weight_ref(ad_hoc.reshape(B, -1), z, cos_xi)
    return w, (dz * w[:, None]).reshape(ad_hoc.shape)


def fused_sample_q8_ref(slot, ad_hoc, zq, zscale, dzq, dzscale,
                        cos_xi: float):
    """int8-ring oracle: dequantize the sampled rows (codes * per-row
    (W, B, 1) scale), then the fp32 composition of
    :func:`fused_sample_ref`."""
    z = zq[slot].astype(jnp.float32) * zscale[slot]
    dz = dzq[slot].astype(jnp.float32) * dzscale[slot]
    B = ad_hoc.shape[0]
    w = cosine_weight_ref(ad_hoc.reshape(B, -1), z, cos_xi)
    return w, (dz * w[:, None]).reshape(ad_hoc.shape)


def fused_sample_q4_ref(slot, ad_hoc, zq, zscale, dzq, dzscale,
                        cos_xi: float):
    """int4 nibble-packed ring oracle: unpack the sampled rows' packed
    bytes (two signed codes per byte, ``workset.pack_nibbles`` layout),
    dequantize by the per-row scale, then the fp32 composition of
    :func:`fused_sample_ref`.  The pad nibble (odd row widths) decodes to
    an exact zero, so keeping it in the reductions is harmless; the
    cotangent is sliced back to ad_hoc's width."""
    from ..core.workset import unpack_nibbles
    B = ad_hoc.shape[0]
    a2d = ad_hoc.reshape(B, -1).astype(jnp.float32)
    F = a2d.shape[1]
    Fp = 2 * zq.shape[2]
    if Fp != F:
        a2d = jnp.pad(a2d, ((0, 0), (0, Fp - F)))
    z = unpack_nibbles(zq[slot]).astype(jnp.float32) * zscale[slot]
    dz = unpack_nibbles(dzq[slot]).astype(jnp.float32) * dzscale[slot]
    w = cosine_weight_ref(a2d, z, cos_xi)
    return w, (dz * w[:, None])[:, :F].reshape(ad_hoc.shape)


def fused_dequant_q8_ref(slot, zq, zscale):
    """Gather + dequant oracle over the int8 ring (the serving
    decode-cache read): codes * per-row scale at ``slot``.  -> (B, F)
    fp32."""
    return zq[slot].astype(jnp.float32) * zscale[slot]


def fused_dequant_q4_ref(slot, zq, zscale, width: int):
    """Gather + unpack + dequant oracle over the int4 nibble-packed ring;
    the pad nibble (odd widths) is sliced off.  -> (B, width) fp32."""
    from ..core.workset import unpack_nibbles
    out = unpack_nibbles(zq[slot]).astype(jnp.float32) * zscale[slot]
    return out[:, :width]


def quantize_sr_ref(x, u, levels):
    """Per-tile absmax scale + stochastic rounding to signed integer codes
    (the compressed-wire encode hot path).

    x, u: (T, L) — T quantization tiles of L values each, u ~ U[0, 1).
    -> (codes int8 (T, L), scales fp32 (T, 1)); decode is codes * scales.
    ``floor(x/s + u)`` is unbiased: E[codes * s] == x."""
    x = x.astype(jnp.float32)
    u = u.astype(jnp.float32)
    levels = jnp.float32(levels)
    amax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    scale = jnp.maximum(amax, EPS) / levels
    q = jnp.clip(jnp.floor(x / scale + u), -levels, levels)
    return q.astype(jnp.int8), scale


def flash_attention_ref(q, k, v, *, causal: bool = True, window: int = 0):
    """Dense softmax attention oracle.  q,k,v: (B, S, H, hd) (GQA: kv heads
    already repeated).  fp32 softmax internals."""
    B, S, H, hd = q.shape
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    scores = scores / jnp.sqrt(jnp.float32(hd))
    pos = jnp.arange(S)
    d = pos[:, None] - pos[None, :]
    mask = jnp.ones((S, S), bool)
    if causal:
        mask &= d >= 0
    if window:
        mask &= d < window
    scores = jnp.where(mask[None, None], scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", w.astype(v.dtype), v)


def fused_adagrad_ref(grad, accum, lr: float, eps: float):
    """AdaGrad accumulate + scaled update.  -> (update, new_accum)."""
    g = grad.astype(jnp.float32)
    a_new = accum + g * g
    return -lr * g / (jnp.sqrt(a_new) + eps), a_new


def fused_adagrad_q8_ref(grad2d, accum_q, accum_scale, u, lr: float,
                         eps: float):
    """int8-at-rest AdaGrad oracle.  Codes live in SQRT-space (stored
    accumulator value = (code * scale)², the resolution concentrated
    where AdaGrad's 1/sqrt step needs it): dequantize r = codes * scale,
    accumulate r' = sqrt(r² + g²), emit the update, re-derive the row
    scale from the new row max and stochastically requantize
    (``floor(r'/s + u)``, unbiased in r'; codes clipped to [0, 127] —
    the accumulator is non-negative).  grad2d/u: (R, C) fp32; accum_q:
    (R, C) int8; accum_scale: (R, 1) fp32.
    -> (update, new codes, new scales)."""
    g = grad2d.astype(jnp.float32)
    r = accum_q.astype(jnp.float32) * accum_scale
    r_new = jnp.sqrt(r * r + g * g)
    upd = -lr * g / (r_new + eps)
    s_new = jnp.maximum(jnp.max(r_new, axis=1, keepdims=True), EPS) / 127.0
    codes = jnp.clip(jnp.floor(r_new / s_new + u.astype(jnp.float32)),
                     0.0, 127.0).astype(jnp.int8)
    return upd, codes, s_new
