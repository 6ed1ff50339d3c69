"""Pallas TPU kernels for the compute hot-spots.

  cosine_weight   -- fused Algorithm-2 staleness weighting (VPU, one pass)
  fused_sample    -- gather-from-ring + dequant + weighting (the cache read)
  quantize        -- stochastic-rounding quantizer (cache and wire encode)
  flash_attention -- blockwise online-softmax attention (MXU tiles)
  fused_adagrad   -- optimizer accumulate+scale (memory-bound optimum)

Each has a jit'd wrapper in ops.py and a pure-jnp oracle in ref.py.
Every kernel is built through :func:`pallas_call`, which compiles with
Mosaic when the program is lowered for a TPU and runs the Pallas
interpreter when it is lowered for the CPU (the tests).
"""
from __future__ import annotations

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_ELEMS = 128 * 1024     # fp32 elements per (rows, feature-tile) block
LANES = 128


def feature_tile(F: int, rows: int, width: int = 1) -> int:
    """Feature-tile length for a (rows, F) operand.

    ``width`` is how many values one stored element expands to in VMEM
    (2 for nibble-packed int4).  The whole row when ``rows * F * width``
    fits one block (or F cannot be cut on lane boundaries); otherwise the
    largest multiple of 128 lanes that divides F and fits."""
    limit = max(LANES, BLOCK_ELEMS // (rows * width))
    if F <= limit or F % LANES:
        return F
    t = limit // LANES * LANES
    while F % t:
        t -= LANES
    return t


def smem_scalar():
    """BlockSpec of a (1,) scalar operand held in SMEM."""
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def pallas_call(kernel, **kwargs):
    """``pl.pallas_call`` whose mode follows the lowering platform.

    The choice is made per lowering (``jax.lax.platform_dependent``), so
    a program lowered for a TPU contains only the Mosaic kernel and one
    lowered for the CPU only the interpreter: no caller can select the
    interpreter on a chip, and a compile for a described TPU topology
    from a CPU host gets the Mosaic kernel too."""
    def call(*args):
        return jax.lax.platform_dependent(
            *args,
            tpu=pl.pallas_call(kernel, interpret=False, **kwargs),
            cpu=pl.pallas_call(kernel, interpret=True, **kwargs))
    return call
