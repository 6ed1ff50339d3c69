"""Optimizers as pure pytree transforms (no optax offline).

``Optimizer(init, update, update_rows=None)``:
  * ``init(params) -> opt_state``
  * ``update(grads, opt_state, params) -> (updates, opt_state)``; updates are
    ADDED to params by ``apply_updates``.
  * ``update_rows(grads, opt_state, params, rows, scale=None) -> (params,
    opt_state)``: the same step, update applied, where some leaves are
    tables updated at the rows a batch touched (below); None where the
    optimizer has no such step.

Accumulators are kept in fp32 regardless of the (bf16) param dtype — the
standard mixed-precision discipline.  The paper trains with AdaGrad
(Duchi et al.), which is the default throughout.

``adagrad(..., use_pallas=True)`` routes the element-wise accumulate+scale
through the fused Pallas kernel (kernels/fused_adagrad.py) — one VMEM pass
over (grad, accum, param) instead of three HBM round-trips.

``adagrad(..., state_dtype=...)`` selects the AT-REST accumulator storage:
"float32" (default, bit-identical to before), "bfloat16", or "int8"
(8-bit-optimizer style codes + per-row fp32 master scale, fused
dequant→accumulate→scale→requant kernel).  ``make_optimizer("sm3", ...)``
is the factored O(r + c) accumulator.  See ``optim.quantized``.

**Row update.**  The float32-state ``adagrad`` (without ``use_pallas``)
also has ``update_rows``.  A leaf whose ``rows`` entry is an int array
``(F, B)`` is a table ``(F, V, ...)`` whose gradient comes row-compact,
``(F, B, ...)``: row ``j`` of field ``f`` is the gradient of
``table[f, rows[f, j]]``; ids ``>= V`` pad and write nothing.  It gathers
``a`` and ``p`` at those rows, applies the one elementwise AdaGrad formula
(and ``scale``, the engine's mask and damping, to the update) and scatters
both back; other leaves take the dense step.  AdaGrad has no decay, so a
row with zero gradient moves neither ``p`` nor ``a``: the rows left out
are exactly what the dense step leaves unchanged, bit for bit.  The engine
engages it where the task declares its tables (``core/rows.py``) and the
optimizer has ``update_rows``; Adam, SGD, SM3, int8 or bfloat16 state and
``use_pallas`` have none, and keep the dense step.
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp


class Optimizer(NamedTuple):
    init: Callable
    update: Callable
    update_rows: Optional[Callable] = None


def _zeros_like_f32(params):
    return jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params)


OPT_STATE_DTYPES = ("float32", "bfloat16", "int8")


def _adagrad_step(g, a, lr, eps):
    """The elementwise AdaGrad step: -> (update, new accumulator)."""
    gf = g.astype(jnp.float32)
    a_new = a + gf * gf
    return (-lr * gf / (jnp.sqrt(a_new) + eps)), a_new


def _apply(p, u):
    return (p.astype(jnp.float32) + u).astype(p.dtype)


def adagrad(lr: float, eps: float = 1e-10, *,
            use_pallas: bool = False,
            state_dtype: str = "float32") -> Optimizer:
    if state_dtype not in OPT_STATE_DTYPES:
        raise ValueError(f"state_dtype must be one of {OPT_STATE_DTYPES}, "
                         f"got {state_dtype!r}")
    if state_dtype != "float32":
        from .quantized import adagrad_quantized
        return adagrad_quantized(lr, eps, state_dtype=state_dtype,
                                 use_pallas=use_pallas)

    def init(params):
        return {"accum": _zeros_like_f32(params)}

    def update(grads, state, params=None):
        if use_pallas:
            from ..kernels import ops as kops

            def one(g, a):
                return kops.fused_adagrad(g, a, lr, eps)
            out = jax.tree_util.tree_map(one, grads, state["accum"])
            upd = jax.tree_util.tree_map(lambda o: o[0], out,
                                         is_leaf=lambda x: isinstance(x, tuple))
            acc = jax.tree_util.tree_map(lambda o: o[1], out,
                                         is_leaf=lambda x: isinstance(x, tuple))
            return upd, {"accum": acc}

        def one(g, a):
            return _adagrad_step(g, a, lr, eps)
        flat = jax.tree_util.tree_map(one, grads, state["accum"])
        upd = jax.tree_util.tree_map(lambda o: o[0], flat,
                                     is_leaf=lambda x: isinstance(x, tuple))
        acc = jax.tree_util.tree_map(lambda o: o[1], flat,
                                     is_leaf=lambda x: isinstance(x, tuple))
        return upd, {"accum": acc}

    def field(f, pa, g, r, scale):
        """Field ``f`` of a table and its accumulator, stepped at the rows
        ``r[f]``.  A field is a leading-dim slice, so the scatter is over
        one index: the TPU then writes the table in its own layout, where
        a (field, row) index would make it relayout the whole table."""
        p, a = pa
        take = lambda x: jax.lax.dynamic_index_in_dim(x, f, 0, False)
        pf, af, rf = take(p), take(a), take(r)
        u, a_r = _adagrad_step(
            take(g), af.at[rf].get(mode="fill", fill_value=0), lr, eps)
        p_r = _apply(pf.at[rf].get(mode="fill", fill_value=0),
                     u if scale is None else u * scale)

        def put(x, xf, v):
            xf = xf.at[rf].set(v, mode="drop", unique_indices=True,
                               indices_are_sorted=True)
            return jax.lax.dynamic_update_index_in_dim(x, xf, f, 0)
        return put(p, pf, p_r), put(a, af, a_r)

    def update_rows(grads, state, params, rows, scale=None):
        """``update`` + ``apply_updates`` (update times ``scale`` when
        given), with the leaves that ``rows`` names stepped at those rows
        only (module docstring)."""
        def one(p, g, a, r):
            if r is None:
                u, a = _adagrad_step(g, a, lr, eps)
                return _apply(p, u if scale is None else u * scale), a
            return jax.lax.fori_loop(
                0, r.shape[0], lambda f, pa: field(f, pa, g, r, scale),
                (p, a))
        flat = jax.tree_util.tree_map(one, params, grads, state["accum"],
                                      rows)
        new_p = jax.tree_util.tree_map(lambda o: o[0], flat,
                                       is_leaf=lambda x: isinstance(x, tuple))
        acc = jax.tree_util.tree_map(lambda o: o[1], flat,
                                     is_leaf=lambda x: isinstance(x, tuple))
        return new_p, {"accum": acc}

    return Optimizer(init, update, None if use_pallas else update_rows)


def sgd(lr: float, momentum: float = 0.0) -> Optimizer:
    def init(params):
        if momentum:
            return {"mom": _zeros_like_f32(params)}
        return {}

    def update(grads, state, params=None):
        if momentum:
            mom = jax.tree_util.tree_map(
                lambda m, g: momentum * m + g.astype(jnp.float32),
                state["mom"], grads)
            upd = jax.tree_util.tree_map(lambda m: -lr * m, mom)
            return upd, {"mom": mom}
        upd = jax.tree_util.tree_map(
            lambda g: -lr * g.astype(jnp.float32), grads)
        return upd, state

    return Optimizer(init, update)


def adam(lr: float, b1: float = 0.9, b2: float = 0.999,
         eps: float = 1e-8) -> Optimizer:
    def init(params):
        return {"m": _zeros_like_f32(params), "v": _zeros_like_f32(params),
                "t": jnp.int32(0)}

    def update(grads, state, params=None):
        t = state["t"] + 1
        m = jax.tree_util.tree_map(
            lambda m_, g: b1 * m_ + (1 - b1) * g.astype(jnp.float32),
            state["m"], grads)
        v = jax.tree_util.tree_map(
            lambda v_, g: b2 * v_ + (1 - b2) * jnp.square(
                g.astype(jnp.float32)), state["v"], grads)
        bc1 = 1 - b1 ** t.astype(jnp.float32)
        bc2 = 1 - b2 ** t.astype(jnp.float32)
        upd = jax.tree_util.tree_map(
            lambda m_, v_: -lr * (m_ / bc1) / (jnp.sqrt(v_ / bc2) + eps),
            m, v)
        return upd, {"m": m, "v": v, "t": t}

    return Optimizer(init, update)


def apply_updates(params, updates):
    return jax.tree_util.tree_map(_apply, params, updates)


def make_optimizer(name: str, lr: float, **kw) -> Optimizer:
    from .quantized import sm3
    return {"adagrad": adagrad, "sgd": sgd, "adam": adam,
            "sm3": sm3}[name](lr, **kw)
