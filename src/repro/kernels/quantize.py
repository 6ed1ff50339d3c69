"""Fused stochastic-rounding quantize-with-scale kernel (the compressed
wire's and the quantized cache's encode hot path).

The naive composition (per-tile absmax reduction, scale division, add
uniform noise, floor, clip, narrow) makes three HBM round-trips over the
(T, L) value tiles.  This kernel fuses all of it: each grid step loads a
(BLOCK_T, tile) block of value rows plus the matching pre-drawn uniforms,
reduces the per-row absmax on the VPU, and writes the int8 codes and the
(BLOCK_T, 1) fp32 scales.

Layout decisions for TPU:
  * quantization rows on the sublane axis, the L values of a row on the
    lane axis — the absmax is a lane reduction, natively supported;
  * a long row (a split-LLM cut row, L = S * d) is cut into lane-aligned
    feature tiles (``kernels.feature_tile``): the grid is (row
    blocks, phase, tiles), phase 0 folds each tile's absmax into a VMEM
    scratch, phase 1 encodes the tiles against the finished row scale.
    A row that is one tile runs both phases in one step.  The max is
    exact in any order, so tiling changes no bit of the result;
  * the uniform noise is an OPERAND, not in-kernel PRNG: the caller draws
    it with ``jax.random`` so the kernel is a deterministic function of
    (x, u) and bit-exact against the pure-jnp oracle
    (``kernels.ref.quantize_sr_ref``) — the parity tests rely on this;
  * ``levels`` rides in as an SMEM scalar (127 for int8, 7 for int4), so
    one compiled kernel serves every bit width;
  * fp32 scale math regardless of input dtype (bf16 upcast in VMEM).

Callers flatten/pad to (T, L) rows (see ``core.compression`` and
``core.workset``); T not divisible by BLOCK_T falls back to the reference
there.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import feature_tile, pallas_call, smem_scalar

EPS = 1e-12
BLOCK_T = 128


def _kernel(two_pass: bool):
    def kernel(levels_ref, x_ref, u_ref, q_ref, s_ref, amax_ref):
        p, k = pl.program_id(1), pl.program_id(2)

        @pl.when(p == 0)
        def _():
            m = jnp.max(jnp.abs(x_ref[...].astype(jnp.float32)), axis=1,
                        keepdims=True)          # lane reduction
            amax_ref[...] = jnp.where(k == 0, m,
                                      jnp.maximum(amax_ref[...], m))

        @pl.when(p == int(two_pass))           # the row absmax is complete
        def _():
            levels = levels_ref[0]
            scale = jnp.maximum(amax_ref[...], EPS) / levels
            x = x_ref[...].astype(jnp.float32)
            q = jnp.floor(x / scale + u_ref[...].astype(jnp.float32))
            q_ref[...] = jnp.clip(q, -levels, levels).astype(jnp.int8)
            s_ref[...] = scale
    return kernel


@jax.jit
def quantize_sr_2d(x, u, levels):
    """x: (T, L) values, u: (T, L) uniforms in [0, 1), levels: scalar max
    code magnitude.  -> (codes int8 (T, L), scales fp32 (T, 1))."""
    T, L = x.shape
    bt = min(BLOCK_T, T)
    assert T % bt == 0, (T, bt)
    tile = feature_tile(L, bt)
    nk = L // tile
    two_pass = nk > 1
    lv = jnp.asarray([levels], jnp.float32)
    return pallas_call(
        _kernel(two_pass),
        grid=(T // bt, 2 if two_pass else 1, nk),
        in_specs=[
            smem_scalar(),
            pl.BlockSpec((bt, tile), lambda i, p, k: (i, k)),
            # the uniforms are read by the encode phase only
            pl.BlockSpec((bt, tile), lambda i, p, k: (i, k * p)),
        ],
        out_specs=[
            pl.BlockSpec((bt, tile), lambda i, p, k: (i, k * p)),
            pl.BlockSpec((bt, 1), lambda i, p, k: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, L), jnp.int8),
            jax.ShapeDtypeStruct((T, 1), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bt, 1), jnp.float32)],
        name="quantize_sr",
    )(lv, x, u)
