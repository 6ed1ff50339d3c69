"""Plain reference of the CELU-VFL training round (arXiv:2207.14628,
Algorithms 1 and 2), two parties, sequential schedule (depth 0).

One round:

1. exchange: Party A's forward gives Z on its batch; Party B's mean loss
   gives the loss and the gradients for its parameters and for Z (dZ);
   Party A's backward with dZ gives its gradients.  Both parties take an
   AdaGrad step (accumulator starts at 0, ``p -= lr * g / (sqrt(acc) +
   eps)`` after ``acc += g * g``).
2. insert: each party's workset ring stores (Z, dZ, its own rows) at slot
   ``round mod W``, stamped with the round and a use count of 0.
3. ``n_local`` local updates (0 for vanilla): a round-robin cursor (never
   reset, advanced once per draw) picks slot ``cursor mod W``.  The slot
   is usable if it was inserted within the last W rounds and used fewer
   than R times; an unusable draw does nothing.  Party A recomputes Z on
   the slot's rows, weights each row by the cosine of the new and the
   cached Z (zero below cos xi), backpropagates the weighted cached dZ
   and steps.  Party B differentiates its mean loss at the cached Z to
   get an ad-hoc dZ, weights each row by its cosine with the cached dZ
   (zero below cos xi), and steps on the weighted mean loss.  Vanilla
   does no weighting and no local updates.

The ring here holds exact float32 values, so a program that keeps a
quantized ring differs from this by its rounding.  Everything runs in
the precision ``Policy`` given (``float32`` for the reference itself).
"""
from __future__ import annotations

import functools
import math
from typing import Callable, Dict, List

import jax
import jax.numpy as jnp

EPS_COS = 1e-12


def _cos_weights(a, b, cos_xi):
    B = a.shape[0]
    a = a.reshape(B, -1).astype(jnp.float32)
    b = b.reshape(B, -1).astype(jnp.float32)
    num = jnp.sum(a * b, axis=1)
    den = jnp.sqrt(jnp.sum(a * a, axis=1) * jnp.sum(b * b, axis=1))
    w = num / jnp.maximum(den, EPS_COS)
    return jnp.where(w < cos_xi, 0.0, w)


def _adagrad(P, lr, eps=1e-10):
    def step(params, acc, grads):
        acc = jax.tree_util.tree_map(
            lambda a, g: a + g.astype(jnp.float32) ** 2, acc, grads)
        params = jax.tree_util.tree_map(
            lambda p, g, a: P.store(p - lr * g.astype(jnp.float32)
                                    / (jnp.sqrt(a) + eps)),
            params, grads, acc)
        return params, acc
    return step


def run(forward_a: Callable, loss_b: Callable, params: Dict, batches: List,
        job: Dict, P, *, rows: int = 0) -> Dict:
    """Run ``len(batches)`` rounds from ``params`` ({"a", "b"}, float32).

    ``rows`` > 0 keeps only the first ``rows`` rows of every batch (the
    half-batch fault).  -> {"losses": [...], "grad1": the root of each
    AdaGrad accumulator's sum after round 1, party A's leaves then B's,
    "z1", "dz1": round 1's exchanged Z and dZ, "params": final}.  Each step updates its parameters and accumulators
    in place (donated), so a table-sized reference fits beside the
    caller's initial weights."""
    W, R, lr = job["W"], job["R"], job["lr"]
    celu = job["protocol"] == "celu"
    n_local = R if celu else 0
    cos_xi = math.cos(math.radians(job["xi"]))
    opt = _adagrad(P, lr)

    def cut(tree):
        return jax.tree_util.tree_map(lambda x: x[:rows], tree) if rows \
            else tree

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
    def exchange(pa, pb, acc_a, acc_b, ba, bb):
        z, vjp_a = jax.vjp(lambda p: forward_a(p, ba, P), pa)
        loss, (gb, dz) = jax.value_and_grad(
            lambda p, z: jnp.mean(loss_b(p, z, bb, P)), argnums=(0, 1))(
                pb, z)
        (ga,) = vjp_a(dz.astype(z.dtype))
        pa, acc_a = opt(pa, acc_a, ga)
        pb, acc_b = opt(pb, acc_b, gb)
        return loss, pa, pb, acc_a, acc_b, z.astype(jnp.float32), \
            dz.astype(jnp.float32)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def local_a(pa, acc, ba, z, dz):
        z_new, vjp_a = jax.vjp(lambda p: forward_a(p, ba, P), pa)
        w = _cos_weights(z_new, z, cos_xi)
        cot = dz * w.reshape((-1,) + (1,) * (dz.ndim - 1))
        (g,) = vjp_a(cot.astype(z_new.dtype))
        return opt(pa, acc, g)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def local_b(pb, acc, bb, z, dz):
        dz_new = jax.grad(lambda z: jnp.mean(loss_b(pb, z, bb, P)))(z)
        w = _cos_weights(dz_new, dz, cos_xi)
        g = jax.grad(lambda p: jnp.mean(w * loss_b(p, z, bb, P)))(pb)
        return opt(pb, acc, g)

    zeros = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda x: jnp.zeros(x.shape, jnp.float32), t)
    copy = lambda t: jax.tree_util.tree_map(jnp.copy, t)  # noqa: E731
    pa, pb = copy(params["a"]), copy(params["b"])
    acc_a, acc_b = zeros(pa), zeros(pb)
    ring = [None] * W            # (z, dz, batch_a, batch_b, round, uses)
    cursor, losses, grad1 = 0, [], None
    for t, (_, ba, bb) in enumerate(batches):
        ba, bb = cut(ba), cut(bb)
        loss, pa, pb, acc_a, acc_b, z, dz = exchange(pa, pb, acc_a, acc_b,
                                                     ba, bb)
        losses.append(float(loss))
        ring[t % W] = [z, dz, ba, bb, t, 0]
        for _ in range(n_local):
            slot = cursor % W
            cursor += 1
            e = ring[slot]
            if e is None or e[4] < t + 1 - W or e[5] >= R:
                continue
            e[5] += 1
            pa, acc_a = local_a(pa, acc_a, e[2], e[0], e[1])
            pb, acc_b = local_b(pb, acc_b, e[3], e[0], e[1])
        if t == 0:
            grad1 = _root_sums(jax.tree_util.tree_leaves(acc_a)
                               + jax.tree_util.tree_leaves(acc_b))
            z1, dz1 = z, dz
    return {"losses": losses, "grad1": grad1, "z1": z1, "dz1": dz1,
            "params": {"a": pa, "b": pb}}


@jax.jit
def _root_sums(leaves):
    return [jnp.sqrt(jnp.sum(x)) for x in leaves]
