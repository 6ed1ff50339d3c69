"""Flash attention BACKWARD Pallas kernels + custom-VJP wrapper.

Forward (flash_attention.py) re-exported here with an LSE output; backward
is the standard two-kernel FlashAttention-2 scheme:

  dkv kernel: grid over KV tiles; for each (BLOCK_K, hd) tile, loop the
    query blocks, recompute p = exp(s - lse), accumulate
       dv += pᵀ do
       dp  = do vᵀ ;  ds = p (dp - D)        (D = rowsum(do ∘ o))
       dk += dsᵀ q
  dq kernel: grid over Q tiles; loop KV blocks, accumulate dq += ds k.

All matmuls are MXU-shaped (BLOCK × hd / BLOCK × BLOCK); the softmax is
never materialized beyond one (BLOCK_Q, BLOCK_K) tile in VMEM; causal /
sliding-window masking mirrors the forward with the same block-skipping
bounds.  fp32 accumulation throughout.

``flash_attention_vjp`` is a jax.custom_vjp function validated against
``jax.grad`` of the pure-jnp oracle in tests.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import pallas_call

NEG_INF = -1e30
BLOCK_Q = 256
BLOCK_K = 256


# --------------------------------------------------------------------------
# forward with LSE residual
# --------------------------------------------------------------------------
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_k: int,
                causal: bool, window: int, seq_len: int):
    qi = pl.program_id(1)
    bq, hd = q_ref.shape
    q = q_ref[...].astype(jnp.float32)
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))
    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)[:, 0]
    n_kb = seq_len // block_k
    hi = jnp.minimum((qi * bq + bq + block_k - 1) // block_k, n_kb) \
        if causal else n_kb
    lo = jnp.maximum((qi * bq - window) // block_k, 0) if window else 0

    def body(ki, carry):
        acc, m, l = carry
        ks = k_ref[pl.ds(ki * block_k, block_k), :].astype(jnp.float32)
        vs = v_ref[pl.ds(ki * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, ks, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_k), 1)[0]
        d = q_pos[:, None] - k_pos[None, :]
        mask = jnp.ones_like(s, jnp.bool_)
        if causal:
            mask &= d >= 0
        if window:
            mask &= d < window
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=1)
        acc = acc * corr[:, None] + jax.lax.dot_general(
            p, vs, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return acc, m_new, l_new

    init = (jnp.zeros((bq, hd), jnp.float32),
            jnp.full((bq,), NEG_INF, jnp.float32),
            jnp.zeros((bq,), jnp.float32))
    acc, m, l = jax.lax.fori_loop(lo, hi, body, init)
    l_safe = jnp.maximum(l, 1e-30)
    o_ref[...] = (acc / l_safe[:, None]).astype(o_ref.dtype)
    lse_ref[...] = (m + jnp.log(l_safe))[:, None]     # (bq, 1) column


# --------------------------------------------------------------------------
# backward kernels
# --------------------------------------------------------------------------
def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, *, block_q: int, causal: bool, window: int,
                seq_len: int):
    ki = pl.program_id(1)
    bk, hd = k_ref.shape
    k = k_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0)[:, 0]
    n_qb = seq_len // block_q
    # causal: only query blocks at/after this kv block see it
    lo = (ki * bk) // block_q if causal else 0
    # window: query blocks beyond k_pos + window see nothing
    hi = jnp.minimum((ki * bk + window + block_q - 1) // block_q + 1,
                     n_qb) if window else n_qb

    def body(qi, carry):
        dk, dv = carry
        qs = q_ref[pl.ds(qi * block_q, block_q), :].astype(jnp.float32)
        dos = do_ref[pl.ds(qi * block_q, block_q), :].astype(jnp.float32)
        lse = lse_ref[pl.ds(qi * block_q, block_q), :]       # (bq_, 1)
        delta = delta_ref[pl.ds(qi * block_q, block_q), :]
        s = jax.lax.dot_general(qs, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, 1), 0)[:, 0]
        d = q_pos[:, None] - k_pos[None, :]
        mask = jnp.ones_like(s, jnp.bool_)
        if causal:
            mask &= d >= 0
        if window:
            mask &= d < window
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)           # (bq_, bk)
        dv_new = dv + jax.lax.dot_general(
            p, dos, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(dos, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dk_new = dk + jax.lax.dot_general(
            ds, qs, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return dk_new, dv_new

    init = (jnp.zeros((bk, hd), jnp.float32),
            jnp.zeros((bk, hd), jnp.float32))
    dk, dv = jax.lax.fori_loop(lo, hi, body, init)
    dk_ref[...] = dk.astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               *, block_k: int, causal: bool, window: int, seq_len: int):
    qi = pl.program_id(1)
    bq, hd = q_ref.shape
    q = q_ref[...].astype(jnp.float32)
    do = do_ref[...].astype(jnp.float32)
    lse = lse_ref[...]
    delta = delta_ref[...]
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))
    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)[:, 0]
    n_kb = seq_len // block_k
    hi = jnp.minimum((qi * bq + bq + block_k - 1) // block_k, n_kb) \
        if causal else n_kb
    lo = jnp.maximum((qi * bq - window) // block_k, 0) if window else 0

    def body(ki, dq):
        ks = k_ref[pl.ds(ki * block_k, block_k), :].astype(jnp.float32)
        vs = v_ref[pl.ds(ki * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, ks, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_k), 1)[0]
        d = q_pos[:, None] - k_pos[None, :]
        mask = jnp.ones_like(s, jnp.bool_)
        if causal:
            mask &= d >= 0
        if window:
            mask &= d < window
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        dp = jax.lax.dot_general(do, vs, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        return dq + jax.lax.dot_general(
            ds, ks, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    dq = jax.lax.fori_loop(lo, hi, body, jnp.zeros((bq, hd), jnp.float32))
    dq_ref[...] = dq.astype(dq_ref.dtype)


# --------------------------------------------------------------------------
# custom-vjp wrapper (folded (B*H, S, hd) layout like the forward)
# --------------------------------------------------------------------------
def _fold(x):
    B, S, H, hd = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B * H, S, hd)


def _unfold(x, B, H):
    BH, S, hd = x.shape
    return x.reshape(B, H, S, hd).transpose(0, 2, 1, 3)


def _fwd(q, k, v, causal, window):
    B, S, H, hd = q.shape
    bq = min(BLOCK_Q, S)
    bk = min(BLOCK_K, S)
    qf, kf, vf = _fold(q), _fold(k), _fold(v)
    kernel = functools.partial(_fwd_kernel, block_k=bk, causal=causal,
                               window=window, seq_len=S)
    o, lse = pallas_call(
        kernel,
        grid=(B * H, S // bq),
        in_specs=[pl.BlockSpec((None, bq, hd), lambda b, i: (b, i, 0)),
                  pl.BlockSpec((None, S, hd), lambda b, i: (b, 0, 0)),
                  pl.BlockSpec((None, S, hd), lambda b, i: (b, 0, 0))],
        out_specs=[pl.BlockSpec((None, bq, hd), lambda b, i: (b, i, 0)),
                   pl.BlockSpec((None, bq, 1), lambda b, i: (b, i, 0))],
        out_shape=[jax.ShapeDtypeStruct((B * H, S, hd), q.dtype),
                   jax.ShapeDtypeStruct((B * H, S, 1), jnp.float32)],
        name="flash_attention_fwd",
    )(qf, kf, vf)
    return o, lse


def _bwd(q, k, v, o, lse, do, causal, window):
    B, S, H, hd = q.shape
    bq = min(BLOCK_Q, S)
    bk = min(BLOCK_K, S)
    qf, kf, vf = _fold(q), _fold(k), _fold(v)
    of, dof = _fold(o), _fold(do)
    delta = jnp.sum(of.astype(jnp.float32) * dof.astype(jnp.float32),
                    axis=-1, keepdims=True)        # (BH, S, 1)

    dkv = pallas_call(
        functools.partial(_dkv_kernel, block_q=bq, causal=causal,
                          window=window, seq_len=S),
        grid=(B * H, S // bk),
        in_specs=[pl.BlockSpec((None, S, hd), lambda b, i: (b, 0, 0)),
                  pl.BlockSpec((None, bk, hd), lambda b, i: (b, i, 0)),
                  pl.BlockSpec((None, bk, hd), lambda b, i: (b, i, 0)),
                  pl.BlockSpec((None, S, hd), lambda b, i: (b, 0, 0)),
                  pl.BlockSpec((None, S, 1), lambda b, i: (b, 0, 0)),
                  pl.BlockSpec((None, S, 1), lambda b, i: (b, 0, 0))],
        out_specs=[pl.BlockSpec((None, bk, hd), lambda b, i: (b, i, 0)),
                   pl.BlockSpec((None, bk, hd), lambda b, i: (b, i, 0))],
        out_shape=[jax.ShapeDtypeStruct((B * H, S, hd), q.dtype),
                   jax.ShapeDtypeStruct((B * H, S, hd), q.dtype)],
        name="flash_attention_dkv",
    )(qf, kf, vf, dof, lse, delta)
    dk, dv = dkv

    dq = pallas_call(
        functools.partial(_dq_kernel, block_k=bk, causal=causal,
                          window=window, seq_len=S),
        grid=(B * H, S // bq),
        in_specs=[pl.BlockSpec((None, bq, hd), lambda b, i: (b, i, 0)),
                  pl.BlockSpec((None, S, hd), lambda b, i: (b, 0, 0)),
                  pl.BlockSpec((None, S, hd), lambda b, i: (b, 0, 0)),
                  pl.BlockSpec((None, bq, hd), lambda b, i: (b, i, 0)),
                  pl.BlockSpec((None, bq, 1), lambda b, i: (b, i, 0)),
                  pl.BlockSpec((None, bq, 1), lambda b, i: (b, i, 0))],
        out_specs=pl.BlockSpec((None, bq, hd), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, hd), q.dtype),
        name="flash_attention_dq",
    )(qf, kf, vf, dof, lse, delta)
    return (_unfold(dq, B, H), _unfold(dk, B, H), _unfold(dv, B, H))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_attention_vjp(q, k, v, causal: bool = True, window: int = 0):
    o, _ = _fwd(q, k, v, causal, window)
    return _unfold(o, q.shape[0], q.shape[2])


def _vjp_fwd(q, k, v, causal, window):
    o, lse = _fwd(q, k, v, causal, window)
    return _unfold(o, q.shape[0], q.shape[2]), (q, k, v, o, lse)


def _vjp_bwd(causal, window, res, g):
    q, k, v, of, lse = res
    o = _unfold(of, q.shape[0], q.shape[2])
    return _bwd(q, k, v, o, lse, g, causal, window)


flash_attention_vjp.defvjp(_vjp_fwd, _vjp_bwd)
