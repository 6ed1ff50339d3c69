"""Fused AdaGrad kernels: accumulate + rsqrt-scale in one VMEM pass.

The unfused optimizer reads grad, reads accum, writes accum, reads accum
again, writes update — with XLA usually fusing *some* of it but still
materializing the fp32 accumulator twice.  The kernel does

    a' = a + g²;  u = -lr * g / (sqrt(a') + eps)

with one load of (g, a) and one store of (u, a') per element — the memory-
bound optimum (3 streams in, 2 out → 2 in, 2 out).

Tiling: inputs are flattened and padded to (N/BLOCK, BLOCK) with BLOCK=1024
lanes — pure element-wise VPU work, no MXU, no cross-lane traffic.

``fused_adagrad_q8`` is the int8-at-rest variant (8-bit-optimizer style:
int8 codes + one fp32 master scale per row): dequantize the stored
accumulator, accumulate g², emit the update, re-derive the row scale
from the new row max, and stochastically requantize — all in the same
single VMEM pass, so the fp32 accumulator NEVER exists in HBM.  Codes
live in SQRT-space: the kernel already computes ``r = sqrt(a')`` for the
update, and quantizing r instead of a squares the representable dynamic
range ((1/127)² ≈ 6e-5 of the row max instead of 1/127) — the nonuniform
trick 8-bit optimizers use, with the resolution exactly where AdaGrad's
1/r step needs it.  The accumulator is non-negative and row-monotone, so
codes are in [0, 127] and the row scale only grows; stochastic rounding
(``floor(r/s + u)``, unbiased in r) keeps sub-LSB increments from
silently stalling.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import pallas_call, smem_scalar

BLOCK = 1024
ROWS = 8
Q8_LEVELS = 127.0
EPS_SCALE = 1e-12


def _kernel(g_ref, a_ref, hyp_ref, u_ref, a_out_ref):
    g = g_ref[...].astype(jnp.float32)
    a = a_ref[...]
    lr = hyp_ref[0]
    eps = hyp_ref[1]
    a_new = a + g * g
    u_ref[...] = -lr * g / (jnp.sqrt(a_new) + eps)
    a_out_ref[...] = a_new


@jax.jit
def fused_adagrad(grad, accum, lr, eps):
    """grad: any shape/dtype; accum: same shape fp32.
    -> (update fp32, new_accum fp32), same shape as grad."""
    shape = grad.shape
    n = grad.size
    cols = min(BLOCK, max(n, 1))
    rows_per_block = ROWS
    n_pad = ((n + cols - 1) // cols) * cols
    n_rows = n_pad // cols
    n_rows_pad = ((n_rows + rows_per_block - 1) // rows_per_block) \
        * rows_per_block

    g = jnp.zeros((n_rows_pad * cols,), jnp.float32).at[:n].set(
        grad.reshape(-1).astype(jnp.float32)).reshape(n_rows_pad, cols)
    a = jnp.zeros((n_rows_pad * cols,), jnp.float32).at[:n].set(
        accum.reshape(-1)).reshape(n_rows_pad, cols)
    hyp = jnp.asarray([lr, eps], jnp.float32)

    u, a_new = pallas_call(
        _kernel,
        grid=(n_rows_pad // rows_per_block,),
        in_specs=[
            pl.BlockSpec((rows_per_block, cols), lambda i: (i, 0)),
            pl.BlockSpec((rows_per_block, cols), lambda i: (i, 0)),
            smem_scalar(),
        ],
        out_specs=[
            pl.BlockSpec((rows_per_block, cols), lambda i: (i, 0)),
            pl.BlockSpec((rows_per_block, cols), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_rows_pad, cols), jnp.float32),
            jax.ShapeDtypeStruct((n_rows_pad, cols), jnp.float32),
        ],
        name="fused_adagrad",
    )(g, a, hyp)
    return (u.reshape(-1)[:n].reshape(shape),
            a_new.reshape(-1)[:n].reshape(shape))


def _kernel_q8(g_ref, q_ref, s_ref, u_ref, hyp_ref, upd_ref, q_out_ref,
               s_out_ref):
    g = g_ref[...].astype(jnp.float32)
    r = q_ref[...].astype(jnp.float32) * s_ref[...]     # dequant sqrt-accum
    lr = hyp_ref[0]
    eps = hyp_ref[1]
    r_new = jnp.sqrt(r * r + g * g)                      # accumulate
    upd_ref[...] = -lr * g / (r_new + eps)               # scale
    s_new = jnp.maximum(jnp.max(r_new, axis=1, keepdims=True),
                        EPS_SCALE) / Q8_LEVELS
    codes = jnp.floor(r_new / s_new + u_ref[...])        # requant (SR)
    q_out_ref[...] = jnp.clip(codes, 0.0, Q8_LEVELS).astype(jnp.int8)
    s_out_ref[...] = s_new


@jax.jit
def fused_adagrad_q8(grad2d, accum_q, accum_scale, u, lr, eps):
    """int8-at-rest AdaGrad step over the kernel's native tiling.

    grad2d: (R, C) fp32 with R % ROWS == 0 (the optimizer pads once at
    init and keeps the layout); accum_q: (R, C) int8 sqrt-space codes in
    [0, 127] (accumulator value = (code * scale)²); accum_scale: (R, 1)
    fp32 per-row master scales; u: (R, C) uniforms in [0, 1) for the
    requant stochastic rounding.
    -> (update fp32 (R, C), new codes int8, new scales (R, 1))."""
    R, C = grad2d.shape
    assert R % ROWS == 0, (R, ROWS)
    hyp = jnp.asarray([lr, eps], jnp.float32)
    return pallas_call(
        _kernel_q8,
        grid=(R // ROWS,),
        in_specs=[
            pl.BlockSpec((ROWS, C), lambda i: (i, 0)),
            pl.BlockSpec((ROWS, C), lambda i: (i, 0)),
            pl.BlockSpec((ROWS, 1), lambda i: (i, 0)),
            pl.BlockSpec((ROWS, C), lambda i: (i, 0)),
            smem_scalar(),
        ],
        out_specs=[
            pl.BlockSpec((ROWS, C), lambda i: (i, 0)),
            pl.BlockSpec((ROWS, C), lambda i: (i, 0)),
            pl.BlockSpec((ROWS, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((R, C), jnp.float32),
            jax.ShapeDtypeStruct((R, C), jnp.int8),
            jax.ShapeDtypeStruct((R, 1), jnp.float32),
        ],
        name="fused_adagrad_q8",
    )(grad2d.astype(jnp.float32), accum_q, accum_scale,
      u.astype(jnp.float32), hyp)
