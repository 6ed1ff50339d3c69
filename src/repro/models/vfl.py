"""VFL split model: Party A bottom tower, Party B bottom+top towers.

See DESIGN §3.  The split keeps the paper's information-flow discipline at
the module boundary: ``forward_a`` only touches Party A params/features and
produces the exchanged activation ``Z_A``; ``loss_b`` consumes ``Z_A`` as an
explicit argument so the protocol layer can take ``∇Z_A = ∂loss/∂Z_A``
without ever handing Party B's params (or labels) to Party A.

Families:
  text  (dense/moe/hybrid/ssm): Party A has a token-aligned auxiliary
        feature stream; fusion = add (projected).
  vlm   : Party A = vision owner (patch embeddings -> projector);
          fusion = cross-attention (every ``cross_attn_every``-th layer).
  audio : Party A = audio encoder over frame embeddings;
          fusion = per-layer cross-attention in the decoder.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig, ShapeConfig
from .initializers import PARAM_DTYPE, dense_init, embed_init
from . import layers as L
from .backbone import (Ctx, tower_apply, tower_decode, tower_init,
                       tower_make_cache, tower_prefill, tower_stages)


# --------------------------------------------------------------------------
def _role(cfg: ArchConfig) -> str:
    return {"vlm": "vlm", "audio": "audio_dec"}.get(cfg.family, "text")


def stages_a(cfg: ArchConfig):
    if cfg.family == "vlm":
        return []                       # projector only
    if cfg.family == "audio":
        return tower_stages(cfg, cfg.vfl_split.layers_a, "enc")
    return tower_stages(cfg, cfg.vfl_split.layers_a, "text")


# With per-layer kinds, each tower is its slice of the published layer
# sequence: A's tower the first ``layers_a`` layers, B's bottom the next
# ``layers_b``, its top the ``layers_top`` after them.
def stages_b(cfg: ArchConfig):
    role = _role(cfg)
    return tower_stages(cfg, cfg.vfl_split.layers_b, role,
                        first=cfg.vfl_split.layers_a)


def stages_top(cfg: ArchConfig):
    role = _role(cfg)
    s = cfg.vfl_split
    return tower_stages(cfg, s.layers_top, role,
                        first=s.layers_a + s.layers_b)


# --------------------------------------------------------------------------
# Init
# --------------------------------------------------------------------------
def init_party_a(rng, cfg: ArchConfig):
    ks = jax.random.split(rng, 4)
    if cfg.family == "vlm":
        return {"proj1": dense_init(ks[0], cfg.d_frontend, cfg.d_model),
                "proj2": dense_init(ks[1], cfg.d_model, cfg.d_model)}
    if cfg.family == "audio":
        return {"proj": dense_init(ks[0], cfg.d_frontend, cfg.d_model),
                "tower": tower_init(ks[1], cfg, stages_a(cfg)),
                "ln": L.rmsnorm_init(cfg.d_model)}
    vocab_a = ((cfg.aux_vocab_size + 255) // 256) * 256
    return {"embed": embed_init(ks[0], vocab_a, cfg.d_model),
            "tower": tower_init(ks[1], cfg, stages_a(cfg))}


def init_party_b(rng, cfg: ArchConfig):
    ks = jax.random.split(rng, 6)
    p = {"embed": embed_init(ks[0], cfg.padded_vocab, cfg.d_model),
         "bottom": tower_init(ks[1], cfg, stages_b(cfg)),
         "top": tower_init(ks[2], cfg, stages_top(cfg)),
         "ln_f": L.rmsnorm_init(cfg.d_model),
         "head": dense_init(ks[3], cfg.d_model, cfg.padded_vocab)}
    if cfg.vfl_split.fusion == "add":
        p["fuse_proj"] = dense_init(ks[4], cfg.d_model, cfg.d_model)
    return p


def init_all(rng, cfg: ArchConfig):
    ka, kb = jax.random.split(rng)
    return {"a": init_party_a(ka, cfg), "b": init_party_b(kb, cfg)}


# --------------------------------------------------------------------------
# Party A forward
# --------------------------------------------------------------------------
def forward_a(params_a, cfg: ArchConfig, batch: Dict[str, Any],
              train: bool = False, remat: bool = True, counts: bool = False):
    """-> Z_A.  text: (B,S,d); vlm: (B,P,d); audio: (B,S_a,d).  With
    ``counts`` (text families) -> (Z_A, expert rows computed)."""
    if cfg.family == "vlm":
        h = jax.nn.silu(jnp.einsum(
            "bpf,fd->bpd", batch["patches"].astype(PARAM_DTYPE),
            params_a["proj1"]).astype(jnp.float32)).astype(PARAM_DTYPE)
        return jnp.einsum("bpd,de->bpe", h, params_a["proj2"])
    if cfg.family == "audio":
        x = jnp.einsum("bsf,fd->bsd", batch["frames"].astype(PARAM_DTYPE),
                       params_a["proj"])
        S = x.shape[1]
        ctx = Ctx(cfg, positions=jnp.arange(S, dtype=jnp.int32),
                  causal=False, train=train, remat=remat,
                  window=cfg.sliding_window)
        x, _ = tower_apply(params_a["tower"], x, cfg, stages_a(cfg), ctx)
        return L.rmsnorm(params_a["ln"], x, cfg.norm_eps)
    x = params_a["embed"][batch["tokens_a"]]
    S = x.shape[1]
    ctx = Ctx(cfg, positions=jnp.arange(S, dtype=jnp.int32), train=train,
              remat=remat, window=cfg.sliding_window)
    x, _, *rows = tower_apply(params_a["tower"], x, cfg, stages_a(cfg), ctx,
                              counts=counts)
    return (x, rows[0]) if counts else x


# --------------------------------------------------------------------------
# Party B forward / loss
# --------------------------------------------------------------------------
def _logits(h, params_b, cfg: ArchConfig):
    h = L.rmsnorm(params_b["ln_f"], h, cfg.norm_eps)
    logits = jnp.einsum("bsd,dv->bsv", h, params_b["head"]).astype(jnp.float32)
    if cfg.padded_vocab != cfg.vocab_size:
        pad = cfg.padded_vocab - cfg.vocab_size
        mask = jnp.concatenate([jnp.zeros((cfg.vocab_size,), jnp.float32),
                                jnp.full((pad,), -1e30, jnp.float32)])
        logits = logits + mask
    return L.shard_logits(logits)


def forward_b(params_b, cfg: ArchConfig, z_a, batch: Dict[str, Any],
              train: bool = False, remat: bool = True, counts: bool = False):
    """-> (logits, aux), and with ``counts`` the expert rows computed
    after them.  z_a enters via the fusion declared by the split."""
    x = params_b["embed"][batch["tokens"]]
    S = x.shape[1]
    pos = jnp.arange(S, dtype=jnp.int32)
    fusion = cfg.vfl_split.fusion
    mem = z_a if fusion == "cross_attn" else None
    ctx = Ctx(cfg, positions=pos, memory=mem, train=train, remat=remat,
              window=cfg.sliding_window)
    x, aux1, *r1 = tower_apply(params_b["bottom"], x, cfg, stages_b(cfg),
                               ctx, counts=counts)
    if fusion == "add":
        x = x + jnp.einsum("bsd,de->bse", z_a, params_b["fuse_proj"])
    x, aux2, *r2 = tower_apply(params_b["top"], x, cfg, stages_top(cfg), ctx,
                               counts=counts)
    out = (_logits(x, params_b, cfg), aux1 + aux2)
    return out + (r1[0] + r2[0],) if counts else out


def per_instance_loss(params_b, cfg: ArchConfig, z_a, batch,
                      train: bool = True, remat: bool = True,
                      counts: bool = False):
    """Cross-entropy per instance (B,) + aux scalar — Party B's objective
    (with ``counts``, + the expert rows computed)."""
    logits, aux, *rows = forward_b(params_b, cfg, z_a, batch, train=train,
                                   remat=remat, counts=counts)
    labels = batch["labels"]
    # Sharding-friendly cross-entropy: logsumexp + one-hot-reduction both
    # lower to vocab-dim-local reductions + psum when the vocab is sharded
    # over `model` (take_along_axis would all-gather the logits instead).
    lse = jax.nn.logsumexp(logits, axis=-1)
    onehot = labels[..., None] == jnp.arange(logits.shape[-1],
                                             dtype=labels.dtype)
    label_logit = jnp.sum(jnp.where(onehot, logits, 0.0), axis=-1)
    nll = lse - label_logit
    return (jnp.mean(nll, axis=-1), aux) + tuple(rows)


def joint_loss(params, cfg: ArchConfig, batch, train: bool = True):
    """Vanilla VFL objective (both parties in one program)."""
    z_a = forward_a(params["a"], cfg, batch, train=train)
    li, aux = per_instance_loss(params["b"], cfg, z_a, batch, train=train)
    return jnp.mean(li) + aux


# --------------------------------------------------------------------------
# Serving (co-served split model; party boundary = module boundary)
# --------------------------------------------------------------------------
def serve_capacity(cfg: ArchConfig, seq_len: int) -> int:
    return min(seq_len, cfg.sliding_window) if cfg.sliding_window else seq_len


def make_serve_cache(cfg: ArchConfig, batch: int, seq_len: int,
                     memory_len: int = 0):
    cap = serve_capacity(cfg, seq_len)
    cache = {
        "b": tower_make_cache(cfg, stages_b(cfg), batch, cap, memory_len),
        "top": tower_make_cache(cfg, stages_top(cfg), batch, cap, memory_len),
    }
    if cfg.family not in ("vlm", "audio"):
        cache["a"] = tower_make_cache(cfg, stages_a(cfg), batch, cap)
    return cache


def prefill_a(params_a, cfg: ArchConfig, batch, total_len: int = 0):
    """Party A's half of prefill -> (z_a, cache_a).

    z_a is the activation that crosses the party boundary (the ONLY thing
    Party B may see); cache_a is Party A's private decode KV state (None
    for cross-attn families, whose memory crosses once at prefill and is
    cached inside Party B's towers)."""
    if cfg.family in ("vlm", "audio"):
        return forward_a(params_a, cfg, batch), None
    S = batch["tokens_a"].shape[1]
    pos = jnp.arange(S, dtype=jnp.int32)
    cap = serve_capacity(cfg, max(total_len, S))
    xa = params_a["embed"][batch["tokens_a"]]
    ctx_a = Ctx(cfg, positions=pos, window=cfg.sliding_window)
    z_a, _, cache_a = tower_prefill(params_a["tower"], xa, cfg,
                                    stages_a(cfg), ctx_a, cap)
    return z_a, cache_a


def prefill_b(params_b, cfg: ArchConfig, z_a, batch, total_len: int = 0):
    """Party B's half of prefill: consumes the exchanged z_a, returns
    (last-position logits, {"b","top"} caches).  Party A's params never
    enter this function — the party boundary is the argument list."""
    S = batch["tokens"].shape[1]
    pos = jnp.arange(S, dtype=jnp.int32)
    cap = serve_capacity(cfg, max(total_len, S))
    caches: Dict[str, Any] = {}
    x = params_b["embed"][batch["tokens"]]
    fusion = cfg.vfl_split.fusion
    mem = z_a if fusion == "cross_attn" else None
    ctx = Ctx(cfg, positions=pos, memory=mem, window=cfg.sliding_window)
    x, _, caches["b"] = tower_prefill(params_b["bottom"], x, cfg,
                                      stages_b(cfg), ctx, cap)
    if fusion == "add":
        x = x + jnp.einsum("bsd,de->bse", z_a, params_b["fuse_proj"])
    x, _, caches["top"] = tower_prefill(params_b["top"], x, cfg,
                                        stages_top(cfg), ctx, cap)
    logits = _logits(x[:, -1:], params_b, cfg)
    return logits, caches


def prefill(params, cfg: ArchConfig, batch, total_len: int = 0):
    """Full-context forward producing last-position logits + decode caches.

    ``total_len``: prompt + expected generation length — sizes the KV ring
    buffer so full-attention archs don't silently evict the oldest tokens
    during decode (sliding-window archs cap at the window regardless).
    Composed from the per-party halves (prefill_a / prefill_b)."""
    z_a, cache_a = prefill_a(params["a"], cfg, batch, total_len)
    caches: Dict[str, Any] = {}
    if cache_a is not None:
        caches["a"] = cache_a
    logits, caches_b = prefill_b(params["b"], cfg, z_a, batch, total_len)
    caches.update(caches_b)
    return logits, caches


def decode_step_a(params_a, cfg: ArchConfig, cache_a, token_a, pos):
    """Party A's half of one-token decode -> (z_a_t (B,1,d), new_cache_a).

    z_a_t is the per-step boundary activation: on the serving wire it is
    what the up-codec encodes and the decode activation ring stores."""
    ctx = Ctx(cfg, pos=pos, window=cfg.sliding_window)
    xa = params_a["embed"][token_a]
    z_a_t, _, new_cache_a = tower_decode(params_a["tower"], xa, cfg,
                                         stages_a(cfg), ctx, cache_a)
    return z_a_t, new_cache_a


def decode_step_b(params_b, cfg: ArchConfig, caches, token, z_a_t, pos):
    """Party B's half of one-token decode.  caches: {"b","top"}; z_a_t is
    the (possibly cache-served, possibly dequantized) Party-A activation
    (None for cross-attn families).  -> (logits (B,1,V), new caches)."""
    ctx = Ctx(cfg, pos=pos, window=cfg.sliding_window)
    new_caches = dict(caches)
    x = params_b["embed"][token]
    x, _, new_caches["b"] = tower_decode(params_b["bottom"], x, cfg,
                                         stages_b(cfg), ctx, caches["b"])
    if cfg.vfl_split.fusion == "add":
        x = x + jnp.einsum("bsd,de->bse", z_a_t, params_b["fuse_proj"])
    x, _, new_caches["top"] = tower_decode(params_b["top"], x, cfg,
                                           stages_top(cfg), ctx,
                                           caches["top"])
    logits = _logits(x, params_b, cfg)
    return logits, new_caches


def decode_step(params, cfg: ArchConfig, caches, step_batch, pos):
    """One-token decode.  step_batch: {"token": (B,1)[, "token_a": (B,1)]}.

    pos: scalar int32 absolute position of the new token.  Returns
    (logits (B,1,V), new_caches).  Composed from the per-party halves."""
    new_caches = dict(caches)
    if cfg.family in ("vlm", "audio"):
        z_a_t = None
    else:
        z_a_t, new_caches["a"] = decode_step_a(
            params["a"], cfg, caches["a"], step_batch["token_a"], pos)
    logits, caches_b = decode_step_b(
        params["b"], cfg, {"b": caches["b"], "top": caches["top"]},
        step_batch["token"], z_a_t, pos)
    new_caches["b"] = caches_b["b"]
    new_caches["top"] = caches_b["top"]
    return logits, new_caches
