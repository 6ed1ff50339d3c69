"""Precision policies for the plain references.

The reference computes in float32 with matmuls at ``HIGHEST`` precision
and stores parameters as the configuration states.  The control, which
proves the comparison can fail, is the same reference one precision step
below what the configuration states (``bfloat16`` below float32,
``float8`` e4m3 below bfloat16, with one scale per tensor as float8
training keeps it).  A policy rounds the inputs of every
matmul, the activations between layers, and the parameters as they are
stored after each optimizer step; the optimizer's own arithmetic stays
float32, as a mixed-precision trainer keeps it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def round_e4m3(x):
    """Round float32 values to the nearest float8 e4m3 value (3 mantissa
    bits, largest finite 448), in float32 arithmetic, so it runs on any
    backend."""
    x = x.astype(jnp.float32)
    _, e = jnp.frexp(x)                  # x = m * 2**e, 0.5 <= |m| < 1
    e = jnp.maximum(e, -5)               # subnormals: fixed 2**-9 spacing
    q = jnp.round(jnp.ldexp(x, 4 - e)) * jnp.ldexp(jnp.float32(1), e - 4)
    return jnp.clip(q, -448.0, 448.0)


def round_e4m3_scaled(x):
    """Round to float8 e4m3 with one scale per tensor: the tensor's
    largest magnitude maps to e4m3's largest finite value (448), as
    float8 training scales a tensor before it casts it, so that small
    tensors such as gradients keep their values instead of flushing to
    zero.  A derivative passes through the rounding unchanged, as
    through a cast (``round`` itself has none)."""
    x = x.astype(jnp.float32)
    amax = jax.lax.stop_gradient(jnp.max(jnp.abs(x)))
    s = jnp.where(amax > 0, amax / 448.0, 1.0)
    return x + jax.lax.stop_gradient(round_e4m3(x / s) * s - x)


@jax.custom_vjp
def round_grad_e4m3_scaled(x):
    """The identity, whose derivative rounds the cotangent to float8
    e4m3 with one scale per tensor: put on a matmul's output, it makes
    both matmuls of its backward pass take float8 operands, as float8
    training computes gradients."""
    return x


def _rg_fwd(x):
    return x, None


def _rg_bwd(_, ct):
    return (round_e4m3_scaled(ct).astype(ct.dtype),)


round_grad_e4m3_scaled.defvjp(_rg_fwd, _rg_bwd)


def round_bf16(x):
    """Round float32 values to the nearest bfloat16 (ties to even), by
    their bits.  A pair of converts, float32 to bfloat16 and back, would
    do the same, but XLA may drop such a pair as excess precision (it
    does on the TPU), and the reference would then keep float32 where
    the configuration stores bfloat16."""
    b = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    b = (b + jnp.uint32(0x7FFF) + ((b >> 16) & jnp.uint32(1))) \
        & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(b, jnp.float32)


def _round(x, dtype: str):
    if dtype == "float32":
        return x
    if dtype == "bfloat16":
        return round_bf16(x)
    return round_e4m3_scaled(x)


class Policy:
    """``compute``: the precision of matmul inputs and activations;
    ``store``: the precision parameters are kept in between steps (held
    in float32 arrays, rounded)."""

    def __init__(self, compute: str, store: str = ""):
        store = store or compute
        for d in (compute, store):
            if d not in PRECISIONS:
                raise ValueError(f"no precision {d!r}")
        self.compute, self.storage = compute, store
        self.act = jnp.float32 if compute == "float32" else jnp.bfloat16

    def cast(self, x):
        """A value as this policy computes with it."""
        if self.compute == "float8":
            return round_e4m3_scaled(x).astype(jnp.bfloat16)
        return x.astype(self.act)

    def store(self, p):
        """A float32 parameter rounded as this policy stores it."""
        return _round(p, self.storage)

    def dot(self, spec: str, a, b):
        out = jnp.einsum(spec, self.cast(a), self.cast(b),
                         precision=HIGHEST, preferred_element_type=self.act)
        if self.compute == "float8":
            out = round_grad_e4m3_scaled(out)
        return out


PRECISIONS = ("float32", "bfloat16", "float8")
BELOW = {"float32": "bfloat16", "bfloat16": "float8"}


def reference(cfg) -> Policy:
    """The reference: float32 arithmetic at ``HIGHEST``, parameters
    stored as the configuration states (``param_dtype``)."""
    return Policy("float32", cfg["param_dtype"])


def control(cfg) -> Policy:
    """The control: each precision the configuration states taken one
    step down: its matmuls' operand precision (``matmul_precision``; a
    float32 matmul at the TPU's default precision rounds its operands to
    bfloat16) and its parameters' storage (``param_dtype``)."""
    return Policy(BELOW[cfg["matmul_precision"]], BELOW[cfg["param_dtype"]])
