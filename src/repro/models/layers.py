"""Core transformer layers: RMSNorm, RoPE, GQA attention, gated MLP.

Pure functions over param pytrees.  Attention supports:
  * full-sequence causal self-attention (optionally sliding-window),
  * blockwise (flash-style, online-softmax) attention for long sequences —
    this doubles as the pure-jnp oracle for ``kernels/flash_attention.py``,
  * cross-attention to a memory,
  * single-token decode against a (ring-buffer) KV cache.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from .initializers import PARAM_DTYPE, dense_init, ones_init, zeros_init

# Sequences longer than this use the blockwise path (keeps peak memory of
# the lowered HLO O(S * block) instead of O(S^2)).
BLOCKWISE_THRESHOLD = 2048
Q_BLOCK = 512
KV_BLOCK = 1024
NEG_INF = -1e30

# ---------------------------------------------------------------------------
# Activation-sharding hints.  GSPMD's sharding propagation through while
# bodies (the blockwise-attention and layer scans) can drop the batch
# sharding of loop-local tensors, silently replicating multi-GB score tiles
# (observed on the 16x16 dry-run).  The launch layer installs the data-axis
# names here; ``shard_batch_dim`` then pins dim0 of key activations.  No-op
# outside the dry-run/launch context.
_BATCH_AXES = None
_BATCH_AXES_SIZE = 1
_VOCAB_AXIS = None
_VOCAB_AXIS_SIZE = 1


def set_batch_axes(axes, size: int = 1, vocab_axis=None, vocab_size: int = 1):
    """axes: mesh axis names carrying the batch dim (or None to disable);
    size: their product (passed in so this module never inspects meshes).
    vocab_axis/vocab_size: mesh axis sharding the logits' vocab dim."""
    global _BATCH_AXES, _BATCH_AXES_SIZE, _VOCAB_AXIS, _VOCAB_AXIS_SIZE
    _BATCH_AXES = tuple(axes) if axes else None
    _BATCH_AXES_SIZE = size if axes else 1
    _VOCAB_AXIS = vocab_axis
    _VOCAB_AXIS_SIZE = vocab_size if vocab_axis else 1


def shard_logits(x):
    """Pin (batch, ..., vocab) sharding on the logits tensor so the loss
    never replicates the vocab dim (12.6 GB/device measured otherwise)."""
    if x.ndim == 0:
        return x
    from jax.sharding import PartitionSpec as _P
    parts = [None] * x.ndim
    if _BATCH_AXES is not None and _BATCH_AXES_SIZE > 1 \
            and x.shape[0] % _BATCH_AXES_SIZE == 0 \
            and x.shape[0] >= _BATCH_AXES_SIZE:
        parts[0] = _BATCH_AXES if len(_BATCH_AXES) > 1 else _BATCH_AXES[0]
    if _VOCAB_AXIS is not None and _VOCAB_AXIS_SIZE > 1 \
            and x.shape[-1] % _VOCAB_AXIS_SIZE == 0:
        parts[-1] = _VOCAB_AXIS
    if all(p is None for p in parts):
        return x
    return jax.lax.with_sharding_constraint(x, _P(*parts))


def shard_batch_dim(x):
    if _BATCH_AXES is None or x.ndim == 0 or _BATCH_AXES_SIZE <= 1:
        return x
    if x.shape[0] % _BATCH_AXES_SIZE != 0 or x.shape[0] < _BATCH_AXES_SIZE:
        return x
    from jax.sharding import PartitionSpec as _P
    ax = _BATCH_AXES if len(_BATCH_AXES) > 1 else _BATCH_AXES[0]
    spec = _P(*((ax,) + (None,) * (x.ndim - 1)))
    return jax.lax.with_sharding_constraint(x, spec)


# --------------------------------------------------------------------------
# RMSNorm
# --------------------------------------------------------------------------
def rmsnorm_init(d: int):
    return {"scale": ones_init((d,))}


def rmsnorm(params, x, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)).astype(x.dtype)


# --------------------------------------------------------------------------
# RoPE
# --------------------------------------------------------------------------
def rope(x, positions, theta: float = 10000.0):
    """Apply rotary embedding.  x: (B, S, H, hd); positions: (S,) or (B, S)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    if positions.ndim == 1:
        ang = positions.astype(jnp.float32)[None, :, None] * freqs[None, None, :]
        ang = ang[:, :, None, :]                      # (1, S, 1, half)
    else:
        ang = positions.astype(jnp.float32)[:, :, None] * freqs[None, None, :]
        ang = ang[:, :, None, :]                      # (B, S, 1, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin],
                          axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# Attention
# --------------------------------------------------------------------------
class AttnParams(NamedTuple):
    pass  # (documentation only; params are plain dicts)


def attention_init(rng, d_model: int, n_heads: int, n_kv: int, head_dim: int,
                   *, kv_input_dim: Optional[int] = None,
                   qkv_bias: bool = False, qk_norm: bool = False):
    kd = kv_input_dim or d_model
    ks = jax.random.split(rng, 4)
    p = {
        "wq": dense_init(ks[0], d_model, n_heads * head_dim)
              .reshape(d_model, n_heads, head_dim),
        "wk": dense_init(ks[1], kd, n_kv * head_dim).reshape(kd, n_kv, head_dim),
        "wv": dense_init(ks[2], kd, n_kv * head_dim).reshape(kd, n_kv, head_dim),
        "wo": dense_init(ks[3], n_heads * head_dim, d_model)
              .reshape(n_heads, head_dim, d_model),
    }
    if qkv_bias:
        p["bq"] = zeros_init((n_heads, head_dim))
        p["bk"] = zeros_init((n_kv, head_dim))
        p["bv"] = zeros_init((n_kv, head_dim))
    if qk_norm:
        p["q_norm"] = rmsnorm_init(head_dim)
        p["k_norm"] = rmsnorm_init(head_dim)
    return p


def qk_normed(params, q, k, eps: float):
    """Per-head RMSNorm of q and k (``q_norm``/``k_norm``), where the
    attention has it; applied before RoPE."""
    if "q_norm" not in params:
        return q, k
    return rmsnorm(params["q_norm"], q, eps), rmsnorm(params["k_norm"], k, eps)


def _project_qkv(params, x, kv_src):
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    k = jnp.einsum("bsd,dhk->bshk", kv_src, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", kv_src, params["wv"])
    if "bq" in params:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    return q, k, v


def _repeat_kv(k, n_heads):
    """(B, S, Kv, hd) -> (B, S, H, hd) by repeating each KV group."""
    n_kv = k.shape[2]
    if n_kv == n_heads:
        return k
    return jnp.repeat(k, n_heads // n_kv, axis=2)


def _sdpa(q, k, v, mask):
    """q: (B,Q,H,hd) k,v: (B,K,H,hd); mask broadcastable to (B,H,Q,K)."""
    hd = q.shape[-1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    scores = scores / jnp.sqrt(jnp.float32(hd))
    scores = jnp.where(mask, scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", w.astype(v.dtype), v)


def _causal_mask(q_pos, k_pos, window: int):
    """bool (..., Q, K): True where key visible to query."""
    d = q_pos[..., :, None] - k_pos[..., None, :]
    m = d >= 0
    if window:
        m &= d < window
    return m


def _blockwise_sdpa(q, k, v, q_pos, k_pos, *, causal: bool, window: int):
    """Flash-style online-softmax attention, O(S * KV_BLOCK) memory.

    q: (B,Q,H,hd), k/v: (B,K,H,hd).  Also serves as the Pallas oracle.
    """
    B, Q, H, hd = q.shape
    K = k.shape[1]
    qb = min(Q_BLOCK, Q)
    kb = min(KV_BLOCK, K)
    n_qb, n_kb = Q // qb, K // kb
    assert Q % qb == 0 and K % kb == 0, (Q, K)
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))

    # remat: backward RECOMPUTES the per-block scores instead of saving all
    # (n_qb * n_kb) score tiles as scan residuals (measured 290 GB/device on
    # smollm train_4k without it) — this IS the flash-attention backward.
    @jax.checkpoint
    def q_step(_, qi):
        qs = shard_batch_dim(
            jax.lax.dynamic_slice_in_dim(q, qi * qb, qb, axis=1))
        qp = jax.lax.dynamic_slice_in_dim(q_pos, qi * qb, qb, axis=0)

        def kv_step(carry, ki):
            acc, m, l = carry
            ks = shard_batch_dim(
                jax.lax.dynamic_slice_in_dim(k, ki * kb, kb, axis=1))
            vs = shard_batch_dim(
                jax.lax.dynamic_slice_in_dim(v, ki * kb, kb, axis=1))
            kp = jax.lax.dynamic_slice_in_dim(k_pos, ki * kb, kb, axis=0)
            s = jnp.einsum("bqhd,bkhd->bhqk", qs, ks).astype(jnp.float32)
            s = s * scale
            if causal or window:
                msk = _causal_mask(qp, kp, window) if causal else (
                    (qp[:, None] - kp[None, :]) < window)
                s = jnp.where(msk[None, None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p, vs.astype(jnp.float32))
            return (acc, m_new, l_new), None

        init = (shard_batch_dim(jnp.zeros((B, H, qb, hd), jnp.float32)),
                shard_batch_dim(jnp.full((B, H, qb), NEG_INF, jnp.float32)),
                shard_batch_dim(jnp.zeros((B, H, qb), jnp.float32)))
        (acc, m, l), _ = jax.lax.scan(kv_step, init, jnp.arange(n_kb))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return None, out.transpose(0, 2, 1, 3)  # (B, qb, H, hd)

    _, outs = jax.lax.scan(q_step, None, jnp.arange(n_qb))
    # outs: (n_qb, B, qb, H, hd) -> (B, Q, H, hd)
    return outs.transpose(1, 0, 2, 3, 4).reshape(B, Q, H, hd).astype(q.dtype)


def attention_apply(params, x, *, positions, theta: float = 10000.0,
                    causal: bool = True, window: int = 0,
                    memory=None, memory_positions=None,
                    use_rope: bool = True, eps: float = 1e-5):
    """Full-sequence attention.  If ``memory`` is given -> cross-attention
    (no mask, no rope on memory unless memory_positions given)."""
    n_heads = params["wq"].shape[1]
    kv_src = memory if memory is not None else x
    q, k, v = _project_qkv(params, x, kv_src)
    q, k = qk_normed(params, q, k, eps)
    S = x.shape[1]
    if use_rope:
        q = rope(q, positions, theta)
        if memory is None:
            k = rope(k, positions, theta)
        elif memory_positions is not None:
            k = rope(k, memory_positions, theta)
    k = _repeat_kv(k, n_heads)
    v = _repeat_kv(v, n_heads)
    K = k.shape[1]

    if memory is not None:
        mask = jnp.ones((1, 1, S, K), bool)
        out = _sdpa(q, k, v, mask)
    elif max(S, K) > BLOCKWISE_THRESHOLD:
        k_pos = positions if positions.ndim == 1 else positions[0]
        out = _blockwise_sdpa(q, k, v, k_pos, k_pos,
                              causal=causal, window=window)
    else:
        p = positions if positions.ndim == 1 else positions[0]
        mask = _causal_mask(p, p, window)[None, None] if causal else \
            jnp.ones((1, 1, S, K), bool)
        out = _sdpa(q, k, v, mask)
    return jnp.einsum("bqhd,hdo->bqo", out, params["wo"])


# ---- decode with ring-buffer KV cache -------------------------------------
def make_kv_cache(batch: int, capacity: int, n_kv: int, head_dim: int,
                  dtype=PARAM_DTYPE):
    return {
        "k": jnp.zeros((batch, capacity, n_kv, head_dim), dtype),
        "v": jnp.zeros((batch, capacity, n_kv, head_dim), dtype),
        # absolute position held in each slot; very negative = empty
        "slot_pos": jnp.full((capacity,), -(2 ** 30), jnp.int32),
    }


def attention_decode(params, x, cache, pos, *, theta: float = 10000.0,
                     window: int = 0, use_rope: bool = True,
                     eps: float = 1e-5):
    """One-token decode.  x: (B, 1, d); pos: scalar int32 absolute position.

    The cache is a ring buffer of ``capacity`` slots (capacity == window for
    sliding-window archs, == max context otherwise).  Returns (out, cache).
    """
    n_heads = params["wq"].shape[1]
    q, k_new, v_new = _project_qkv(params, x, x)
    q, k_new = qk_normed(params, q, k_new, eps)
    pos_arr = jnp.reshape(pos, (1,))
    if use_rope:
        q = rope(q, pos_arr, theta)
        k_new = rope(k_new, pos_arr, theta)
    cap = cache["k"].shape[1]
    slot = jnp.mod(pos, cap)
    k_cache = jax.lax.dynamic_update_slice_in_dim(cache["k"], k_new, slot, 1)
    v_cache = jax.lax.dynamic_update_slice_in_dim(cache["v"], v_new, slot, 1)
    slot_pos = jax.lax.dynamic_update_slice_in_dim(
        cache["slot_pos"], pos_arr, slot, 0)

    k = _repeat_kv(k_cache, n_heads)
    v = _repeat_kv(v_cache, n_heads)
    dist = pos - slot_pos                                  # (cap,)
    valid = dist >= 0
    if window:
        valid &= dist < window
    mask = valid[None, None, None, :]                      # (1,1,1,cap)
    out = _sdpa(q, k, v, mask)
    out = jnp.einsum("bqhd,hdo->bqo", out, params["wo"])
    return out, {"k": k_cache, "v": v_cache, "slot_pos": slot_pos}


def cross_attention_decode(params, x, memory_kv, *, theta=10000.0):
    """Decode-time cross attention against precomputed memory K/V.

    memory_kv: dict {"k","v"}: (B, S_mem, Kv, hd) (already projected)."""
    n_heads = params["wq"].shape[1]
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    if "bq" in params:
        q = q + params["bq"]
    k = _repeat_kv(memory_kv["k"], n_heads)
    v = _repeat_kv(memory_kv["v"], n_heads)
    mask = jnp.ones((1, 1, 1, k.shape[1]), bool)
    out = _sdpa(q, k, v, mask)
    return jnp.einsum("bqhd,hdo->bqo", out, params["wo"])


def project_memory_kv(params, memory):
    """Precompute cross-attention K/V for decode."""
    k = jnp.einsum("bsd,dhk->bshk", memory, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", memory, params["wv"])
    if "bk" in params:
        k = k + params["bk"]
        v = v + params["bv"]
    return {"k": k, "v": v}


# --------------------------------------------------------------------------
# Gated MLP (llama-style)
# --------------------------------------------------------------------------
def mlp_init(rng, d_model: int, d_ff: int):
    ks = jax.random.split(rng, 3)
    return {
        "wg": dense_init(ks[0], d_model, d_ff),
        "wu": dense_init(ks[1], d_model, d_ff),
        "wd": dense_init(ks[2], d_ff, d_model),
    }


def mlp_apply(params, x):
    g = jax.nn.silu(jnp.einsum("bsd,df->bsf", x, params["wg"])
                    .astype(jnp.float32)).astype(x.dtype)
    u = jnp.einsum("bsd,df->bsf", x, params["wu"])
    return jnp.einsum("bsf,fd->bsd", g * u, params["wd"])
