"""Mixture-of-Experts FFN: dropless grouped dispatch over the experts this
chip holds.

Routing is over all ``n_experts`` at the router's published width, top-k
per token:

  * ``softmax``: gates are the softmax of the top-k logits (a Switch-style
    load-balance loss, ``router_aux_coef``, is added where it is set);
  * ``sigmoid``: ``s = sigmoid(x @ W_router)``, the top-k of
    ``s + expert_bias`` (the bias selects and is not differentiated), gates
    ``s[top-k] / (sum s[top-k] + 1e-6)``.  No auxiliary loss.

The layer holds ``n_held`` experts from ``held_offset`` on (all of them by
default), as one chip of an expert-parallel group holds its share.  Gates
are normalised over each token's full top-k; the layer computes the held
experts' part of the output, and what absent experts would add is left
out.  Dispatch has no capacity: each held (token, slot) assignment is
moved to its expert's block of the grouped matmuls' input, the three
expert matmuls run as grouped matmuls (``jax.lax.ragged_dot``), and each
result is moved back to its (token, slot) and summed over the slots by
gate.  The moves are row gathers both ways (``_move``), so neither
direction scatter-adds.  A shared expert, where the config has one, runs
on every token.

A grouped matmul's time follows the row tiles its groups visit: the
TPU's kernel visits each tile once per group it holds rows of, so a
tile that a group boundary cuts is visited twice, and an empty group
visits none.  Which rows are held, and how they split among the held
experts, follow the router, and training moves both: with the absent
experts' part left out, the gradient raises the held experts' scores.
So that a chip's work does not follow the router, each held expert's
block is padded with zero rows to a multiple of ``GROUP_ROWS`` (the
kernel's row tile), and the last block runs on to the end of a static
``B*S*k + H*GROUP_ROWS`` rows: the tiles visited are then the same for
every routing.  The padding is used where it adds at most a quarter to
the rows (:func:`group_align`); elsewhere blocks are not padded and the
last still covers every row.  Padding rows are zeros in and nothing they
compute is moved back, so no number depends on it.

Spans: ``moe`` around the layer, ``moe_experts`` around the grouped
matmuls alone.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..configs.base import MoEConfig
from .initializers import PARAM_DTYPE, dense_init
from .layers import mlp_init, mlp_apply

MOE_SCOPE = "moe"
EXPERTS_SCOPE = "moe_experts"
GROUP_ROWS = 512          # the TPU grouped matmul's row tile


def group_align(rows: int, H: int) -> int:
    """The multiple each held expert's block is padded to, for ``rows``
    (token, slot) assignments over ``H`` held experts: ``GROUP_ROWS``
    where the padding adds at most a quarter to the rows, else 1."""
    return GROUP_ROWS if H * GROUP_ROWS <= rows // 4 else 1


def slot_count(rows: int, H: int, align: int) -> int:
    """Rows of the grouped matmuls' input: room for every assignment
    with each of the ``H`` blocks padded to a multiple of ``align``."""
    return -(-(rows + H * (align - 1)) // align) * align


def layout(key, H: int, align: int, n_slots: int):
    """Where each (token, slot) row goes in the grouped matmuls' input.

    key: (N,) the row's local expert, ``H`` where it is not held.
    Returns ``dest`` (N,): the row's slot, ``n_slots`` where not held;
    ``src`` (n_slots,): the row in each slot, N where the slot is
    padding; ``sizes`` (H,): each group's rows.  Expert e's rows fill
    the start of its block in their order, each block a multiple of
    ``align`` long; the last block runs on to ``n_slots``.  Sorts,
    compares and gathers only, so its cost does not follow the keys."""
    N = key.shape[0]
    order = jnp.argsort(key, stable=True)           # by sort -> row
    rank = jnp.argsort(order)                       # row -> by sort
    counts = jnp.sum(key[:, None] == jnp.arange(H + 1)[None, :], axis=0,
                     dtype=jnp.int32)
    zero = jnp.zeros((1,), jnp.int32)
    first = jnp.concatenate([zero, jnp.cumsum(counts)])        # by sort
    block = -(-counts[:H] // align) * align
    start = jnp.concatenate([zero, jnp.cumsum(block)])         # by slot
    dest = jnp.where(key < H, start[key] + rank - first[key], n_slots)
    slot = jnp.arange(n_slots)
    g = jnp.sum(slot[:, None] >= start[None, 1:H], axis=1)     # its group
    r = slot - start[g]
    src = jnp.where(r < counts[g],
                    order[jnp.minimum(first[g] + r, N - 1)], N)
    sizes = block.at[H - 1].add(n_slots - start[H])
    return dest.astype(jnp.int32), src.astype(jnp.int32), sizes


@jax.custom_vjp
def _move(x, src, dest):
    """Rows of ``x`` to new places: row i of the result is ``x[src[i]]``,
    zeros where ``src[i]`` is past ``x``'s rows.  ``dest`` is the way
    back (``x``'s row j lands at ``dest[j]``, past the result's rows
    where it lands nowhere), so the gradient is a gather too."""
    return jnp.take(x, src, axis=0, mode="fill", fill_value=0)


def _move_fwd(x, src, dest):
    return _move(x, src, dest), (src, dest)


def _move_bwd(res, g):
    src, dest = res
    return jnp.take(g, dest, axis=0, mode="fill", fill_value=0), None, None


_move.defvjp(_move_fwd, _move_bwd)


def moe_init(rng, d_model: int, d_ff: int, cfg: MoEConfig):
    ks = jax.random.split(rng, 6)
    f = cfg.d_expert or d_ff
    H = cfg.held
    p = {
        "router": dense_init(ks[0], d_model, cfg.n_experts),
        "wg": jax.vmap(lambda k: dense_init(k, d_model, f))(
            jax.random.split(ks[1], H)),
        "wu": jax.vmap(lambda k: dense_init(k, d_model, f))(
            jax.random.split(ks[2], H)),
        "wd": jax.vmap(lambda k: dense_init(k, f, d_model))(
            jax.random.split(ks[3], H)),
    }
    if cfg.router == "sigmoid":
        p["expert_bias"] = (jax.random.normal(ks[5], (cfg.n_experts,))
                            * 0.01).astype(PARAM_DTYPE)
    if cfg.n_shared:
        p["shared"] = mlp_init(ks[4], d_model, d_ff * cfg.n_shared)
    return p


def route(params, x2, cfg: MoEConfig):
    """x2: (T, d) -> (router logits (T, E) float32, expert ids (T, k),
    gates (T, k) float32)."""
    logits = jnp.einsum("td,de->te", x2, params["router"],
                        preferred_element_type=jnp.float32)
    if cfg.router == "sigmoid":
        s = jax.nn.sigmoid(logits)
        sel = s + params["expert_bias"].astype(jnp.float32)
        _, idx = jax.lax.top_k(sel, cfg.top_k)
        # s at the top-k, picked by a one-hot product (no gather, so the
        # gradient is no scatter)
        pick = jax.nn.one_hot(idx, cfg.n_experts, dtype=jnp.float32)
        g = jnp.sum(s[:, None, :] * pick, axis=-1)
        g = g / (jnp.sum(g, axis=-1, keepdims=True) + 1e-6)
        return logits, idx, g
    top, idx = jax.lax.top_k(logits, cfg.top_k)
    return logits, idx, jax.nn.softmax(top, axis=-1)


def _aux_loss(logits, idx, cfg: MoEConfig):
    """Switch-style load balance over all experts (softmax routing)."""
    if cfg.router != "softmax" or not cfg.router_aux_coef:
        return 0.0
    probs = jax.nn.softmax(logits, axis=-1)
    frac_tokens = jnp.mean(
        jax.nn.one_hot(idx, cfg.n_experts, dtype=jnp.float32), axis=(0, 1))
    aux = cfg.n_experts * jnp.sum(frac_tokens * jnp.mean(probs, axis=0))
    return cfg.router_aux_coef * aux


def moe_apply(params, x, cfg: MoEConfig):
    """x: (B, S, d) -> (y, aux_loss, expert_rows): ``expert_rows`` counts
    the (token, slot) assignments the held experts computed."""
    with jax.named_scope(MOE_SCOPE):
        B, S, d = x.shape
        T, k, H = B * S, cfg.top_k, cfg.held
        x2 = x.reshape(T, d)
        logits, idx, gates = route(params, x2, cfg)

        N = T * k
        e = idx.reshape(-1) - cfg.held_offset         # (T*k,) local ids
        held = (e >= 0) & (e < H)
        align = group_align(N, H)
        n_slots = slot_count(N, H, align)
        dest, src, sizes = layout(jnp.where(held, e, H), H, align, n_slots)
        rows = jnp.broadcast_to(x2[:, None], (T, k, d)).reshape(N, d)
        xs = _move(rows, src, dest)                   # (n_slots, d)
        with jax.named_scope(EXPERTS_SCOPE):
            hg = jax.lax.ragged_dot(xs, params["wg"], sizes)
            hu = jax.lax.ragged_dot(xs, params["wu"], sizes)
            h = jax.nn.silu(hg.astype(jnp.float32)).astype(x.dtype) * hu
            out = jax.lax.ragged_dot(h, params["wd"], sizes)
        out = _move(out, dest, src).astype(jnp.float32)   # zeros: not held
        y = jnp.sum((out * gates.reshape(-1)[:, None]).reshape(T, k, d),
                    axis=1)
        y = y.astype(x.dtype).reshape(B, S, d)
        if "shared" in params:
            y = y + mlp_apply(params["shared"], x)
        return y, _aux_loss(logits, idx, cfg), jnp.sum(held,
                                                       dtype=jnp.int32)
