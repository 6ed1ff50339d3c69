"""Deep-learning recommendation models — the paper's own workloads (§5.1).

Two DLRMs over vertically-partitioned categorical fields:

  * **WDL** (Wide & Deep): each party embeds its fields; Party A's deep MLP
    emits ``Z_A`` (dim 256, the paper's exchanged dimensionality); Party B
    fuses ``[Z_A ‖ Z_B]`` through the top MLP and adds its own wide (linear)
    term.
  * **DSSM**: two symmetric towers; the "top model" is the scaled dot
    interaction between the tower embeddings (owned by Party B).

Both expose the :class:`repro.core.protocol.VFLTask` interface with a
logistic per-instance loss, plus ``predict_logits`` for AUC evaluation.
Each declares its embedding tables (``core.rows``): a party's field ids
index its tower's ``embed`` (and B's ``wide``) and nothing else, so the
engine updates only the rows a batch touches.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Sequence

import jax
import jax.numpy as jnp

from ..core.protocol import VFLTask
from ..core.rows import RowTables, Tables
from .initializers import dense_init, zeros_init


@dataclass(frozen=True)
class DLRMConfig:
    model: str                  # wdl | dssm
    fields_a: int
    fields_b: int
    vocab: int = 1024
    embed_dim: int = 16
    z_dim: int = 256            # paper: output dimensionality of Z_A = 256
    hidden: Sequence[int] = (512, 256)


# --------------------------------------------------------------------------
def _mlp_init(rng, dims):
    ks = jax.random.split(rng, len(dims) - 1)
    return [{"w": dense_init(k, i, o, jnp.float32), "b": zeros_init((o,),
                                                                    jnp.float32)}
            for k, i, o in zip(ks, dims[:-1], dims[1:])]


def _mlp(params, x, final_act: bool = False):
    for i, layer in enumerate(params):
        x = x @ layer["w"] + layer["b"]
        if i < len(params) - 1 or final_act:
            x = jax.nn.relu(x)
    return x


def _tower_init(rng, cfg: DLRMConfig, n_fields: int, out_dim: int):
    ke, km = jax.random.split(rng)
    emb = jax.random.normal(ke, (n_fields, cfg.vocab, cfg.embed_dim),
                            jnp.float32) * 0.01
    dims = [n_fields * cfg.embed_dim, *cfg.hidden, out_dim]
    return {"embed": emb, "mlp": _mlp_init(km, dims)}


def _tower(params, x_fields):
    """x_fields: (B, F) int32 -> (B, out_dim)."""
    B, F = x_fields.shape
    f_idx = jnp.arange(F)
    e = params["embed"][f_idx[None, :], x_fields]    # (B, F, E)
    return _mlp(params["mlp"], e.reshape(B, -1))


# --------------------------------------------------------------------------
# WDL
# --------------------------------------------------------------------------
def wdl_init(rng, cfg: DLRMConfig):
    ka, kb, kt, kw = jax.random.split(rng, 4)
    return {
        "a": {"tower": _tower_init(ka, cfg, cfg.fields_a, cfg.z_dim)},
        "b": {"tower": _tower_init(kb, cfg, cfg.fields_b, cfg.z_dim),
              "top": _mlp_init(kt, [2 * cfg.z_dim, cfg.hidden[-1], 1]),
              "wide": jax.random.normal(
                  kw, (cfg.fields_b, cfg.vocab), jnp.float32) * 0.01,
              "bias": zeros_init((), jnp.float32)},
    }


def _wdl_task(cfg: DLRMConfig) -> VFLTask:
    def forward_a(pa, batch_a):
        return _tower(pa["tower"], batch_a["x_a"])

    def loss_b(pb, z_a, batch_b):
        z_b = _tower(pb["tower"], batch_b["x_b"])
        h = jnp.concatenate([z_a.astype(jnp.float32), z_b], axis=-1)
        logit = _mlp(pb["top"], h)[:, 0]
        F = batch_b["x_b"].shape[1]
        wide = pb["wide"][jnp.arange(F)[None, :], batch_b["x_b"]].sum(axis=1)
        logit = logit + wide + pb["bias"]
        y = batch_b["y"]
        li = jnp.maximum(logit, 0) - logit * y + jnp.log1p(
            jnp.exp(-jnp.abs(logit)))
        return li, jnp.float32(0.0)

    return VFLTask(forward_a, loss_b, RowTables(
        a=Tables("x_a", ("tower/embed",)),
        b=Tables("x_b", ("tower/embed", "wide"))))


def wdl_predict(params, cfg: DLRMConfig, batch_a, batch_b):
    z_a = _tower(params["a"]["tower"], batch_a["x_a"])
    z_b = _tower(params["b"]["tower"], batch_b["x_b"])
    h = jnp.concatenate([z_a, z_b], axis=-1)
    logit = _mlp(params["b"]["top"], h)[:, 0]
    F = batch_b["x_b"].shape[1]
    wide = params["b"]["wide"][jnp.arange(F)[None, :],
                               batch_b["x_b"]].sum(axis=1)
    return logit + wide + params["b"]["bias"]


# --------------------------------------------------------------------------
# DSSM
# --------------------------------------------------------------------------
def dssm_init(rng, cfg: DLRMConfig):
    ka, kb = jax.random.split(rng)
    return {
        "a": {"tower": _tower_init(ka, cfg, cfg.fields_a, cfg.z_dim)},
        "b": {"tower": _tower_init(kb, cfg, cfg.fields_b, cfg.z_dim),
              "scale": jnp.float32(1.0), "bias": zeros_init((), jnp.float32)},
    }


def _dssm_logit(pb, z_a, z_b):
    # smooth normalization: sqrt(|x|^2 + eps) — NOT max(norm, eps), whose
    # gradient is 0 * d(sqrt)/dx = NaN at x = 0 (zero vectors occur for
    # round-robin "bubble" workset entries)
    def nrm(x):
        return x * jax.lax.rsqrt(
            jnp.sum(x * x, axis=-1, keepdims=True) + 1e-12)
    za = nrm(z_a.astype(jnp.float32))
    zb = nrm(z_b)
    return pb["scale"] * 10.0 * jnp.sum(za * zb, axis=-1) + pb["bias"]


def _dssm_task(cfg: DLRMConfig) -> VFLTask:
    def forward_a(pa, batch_a):
        return _tower(pa["tower"], batch_a["x_a"])

    def loss_b(pb, z_a, batch_b):
        z_b = _tower(pb["tower"], batch_b["x_b"])
        logit = _dssm_logit(pb, z_a, z_b)
        y = batch_b["y"]
        li = jnp.maximum(logit, 0) - logit * y + jnp.log1p(
            jnp.exp(-jnp.abs(logit)))
        return li, jnp.float32(0.0)

    return VFLTask(forward_a, loss_b, RowTables(
        a=Tables("x_a", ("tower/embed",)), b=Tables("x_b", ("tower/embed",))))


def dssm_predict(params, cfg: DLRMConfig, batch_a, batch_b):
    z_a = _tower(params["a"]["tower"], batch_a["x_a"])
    z_b = _tower(params["b"]["tower"], batch_b["x_b"])
    return _dssm_logit(params["b"], z_a, z_b)


# --------------------------------------------------------------------------
def make_dlrm(cfg: DLRMConfig):
    """-> (init_fn, task, predict_fn)."""
    if cfg.model == "wdl":
        return wdl_init, _wdl_task(cfg), wdl_predict
    if cfg.model == "dssm":
        return dssm_init, _dssm_task(cfg), dssm_predict
    raise ValueError(cfg.model)


def auc(logits, labels) -> float:
    """Rank-based AUC (ties handled by average rank)."""
    import numpy as np
    s = np.asarray(logits, np.float64)
    y = np.asarray(labels)
    order = np.argsort(s)
    ranks = np.empty_like(order, dtype=np.float64)
    ranks[order] = np.arange(1, len(s) + 1)
    # average ranks for ties
    ss = s[order]
    i = 0
    while i < len(ss):
        j = i
        while j + 1 < len(ss) and ss[j + 1] == ss[i]:
            j += 1
        if j > i:
            ranks[order[i:j + 1]] = (i + j) / 2.0 + 1.0
        i = j + 1
    n_pos = float(y.sum())
    n_neg = float(len(y) - n_pos)
    if n_pos == 0 or n_neg == 0:
        return 0.5
    return float((ranks[y > 0.5].sum() - n_pos * (n_pos + 1) / 2)
                 / (n_pos * n_neg))
