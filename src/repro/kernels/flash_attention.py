"""Flash attention (forward) Pallas kernel for the tower hot-spot.

Online-softmax blockwise attention with explicit VMEM tiling:

  grid = (B * H, S / BLOCK_Q); each step owns one (BLOCK_Q, hd) query tile
  and loops the KV sequence in (BLOCK_K, hd) tiles with running
  (max, sum, acc) statistics — the classic flash recurrence, laid out for
  the MXU: both matmuls are (BLOCK_Q, hd) x (hd, BLOCK_K) and
  (BLOCK_Q, BLOCK_K) x (BLOCK_K, hd) with hd, BLOCK_* multiples of 128.

Supports causal and sliding-window masking; GQA is handled by the ops.py
wrapper (kv heads repeated before the call — regrouping inside the kernel
would only save HBM for the K/V streams, noted as a future optimization).

Causal block skipping: for query tile qi, KV tiles with ki > qi are fully
masked — the kernel loop bound is ``qi + 1`` in the causal case, halving the
work (and for sliding windows the lower bound skips tiles left of the
window).  This is the TPU analogue of the CUDA kernel's early-exit.
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


def _kernel(q_ref, k_ref, v_ref, o_ref, *, block_k: int, causal: bool,
            window: int, seq_len: int):
    qi = pl.program_id(1)
    bq = q_ref.shape[0]
    hd = q_ref.shape[1]
    q = q_ref[...].astype(jnp.float32)            # (BQ, hd)
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))
    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)[:, 0]

    n_kb = seq_len // block_k
    if causal:
        # tiles strictly right of the diagonal contribute nothing
        hi = jnp.minimum((qi * bq + bq + block_k - 1) // block_k, n_kb)
    else:
        hi = n_kb
    if window:
        lo = jnp.maximum((qi * bq - window) // block_k, 0)
    else:
        lo = 0

    def body(ki, carry):
        acc, m, l = carry
        ks = k_ref[pl.ds(ki * block_k, block_k), :].astype(jnp.float32)
        vs = v_ref[pl.ds(ki * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, ks, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_k), 1)[0]
        d = q_pos[:, None] - k_pos[None, :]
        mask = jnp.ones_like(s, dtype=jnp.bool_)
        if causal:
            mask &= d >= 0
        if window:
            mask &= d < window
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=1)
        acc_new = acc * corr[:, None] + jax.lax.dot_general(
            p, vs, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return acc_new, m_new, l_new

    init = (jnp.zeros((bq, hd), jnp.float32),
            jnp.full((bq,), NEG_INF, jnp.float32),
            jnp.zeros((bq,), jnp.float32))
    acc, m, l = jax.lax.fori_loop(lo, hi, body, init)
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0):
    """q, k, v: (B, S, H, hd) (kv already repeated to H).  -> (B, S, H, hd).

    S must be a multiple of BLOCK_Q/BLOCK_K (pad upstream if not).
    """
    B, S, H, hd = q.shape
    bq = min(BLOCK_Q, S)
    bk = min(BLOCK_K, S)
    assert S % bq == 0 and S % bk == 0, (S, bq, bk)

    # (B, S, H, hd) -> (B*H, S, hd): head-major grid, seq contiguous per step
    def fold(x):
        return x.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    qf, kf, vf = fold(q), fold(k), fold(v)

    kernel = functools.partial(_kernel, block_k=bk, causal=causal,
                               window=window, seq_len=S)
    out = pallas_call(
        kernel,
        grid=(B * H, S // bq),
        in_specs=[
            pl.BlockSpec((None, bq, hd), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, S, hd), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, S, hd), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, bq, hd), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, hd), q.dtype),
        name="flash_attention",
    )(qf, kf, vf)
    return out.reshape(B, H, S, hd).transpose(0, 2, 1, 3)
