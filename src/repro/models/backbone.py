"""Family backbones: blocks + scan-stacked towers for all assigned archs.

A *tower* is a list of stages; each stage is ``(pattern, repeat)`` where
``pattern`` is a tuple of block types forming a "super-block" that repeats
``repeat`` times via ``lax.scan`` over stacked params.  This keeps the HLO
O(1) in depth (one lowered super-block per stage) — essential for the
100-layer dry-runs — and lets heterogeneous layouts (xLSTM's sLSTM/mLSTM
alternation, the VLM's every-5th cross-attention) compile as scans too.

Block types:
  dense   : RMSNorm -> GQA attn -> RMSNorm -> gated MLP     (llama family)
  moe     : RMSNorm -> GQA attn -> RMSNorm -> MoE FFN
  sconv   : RMSNorm -> gated short conv -> RMSNorm -> gated MLP   (lfm2)
  sconv_moe : RMSNorm -> gated short conv -> RMSNorm -> MoE FFN   (lfm2)
  hybrid  : RMSNorm -> (attn ∥ mamba)/2 -> RMSNorm -> MLP   (hymba)
  mlstm   : RMSNorm -> mLSTM cell                            (xlstm)
  slstm   : RMSNorm -> sLSTM cell                            (xlstm)
  cross   : RMSNorm -> self attn -> RMSNorm -> cross attn -> RMSNorm -> MLP
  enc     : RMSNorm -> bidirectional attn -> RMSNorm -> MLP  (audio encoder)

Three execution modes share block code: ``train`` (full seq, remat),
``prefill`` (full seq, emits KV/state caches), ``decode`` (1 token + cache).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from .initializers import PARAM_DTYPE, dense_init, stacked_init
from . import layers as L
from . import moe as M
from . import ssm as S
from . import xlstm as X


# --------------------------------------------------------------------------
# Tower stage layouts
# --------------------------------------------------------------------------
def layer_block(cfg: ArchConfig, i: int) -> str:
    """The block type of published layer ``i`` of a per-layer-kind config
    (``cfg.layer_types``): its mixer, and a dense FFN for the first
    ``n_dense_layers`` layers, the MoE after them."""
    moe = cfg.moe is not None and i >= cfg.n_dense_layers
    if cfg.layer_types[i] == "conv":
        return "sconv_moe" if moe else "sconv"
    if cfg.layer_types[i] == "full_attention":
        return "moe" if moe else "dense"
    raise ValueError(f"layer type {cfg.layer_types[i]!r}")


def tower_stages(cfg: ArchConfig, n_layers: int, role: str, first: int = 0
                 ) -> Sequence[Tuple[Tuple[str, ...], int]]:
    """role: text | vlm | enc | audio_dec.  ``first``: the published index
    of the tower's first layer, for configs with per-layer kinds, whose
    towers are slices of the published layer sequence (a stage per run of
    one block type)."""
    if n_layers <= 0:
        return []
    if cfg.layer_types and role == "text":
        stages = []
        for i in range(first, first + n_layers):
            bt = layer_block(cfg, i)
            if stages and stages[-1][0] == (bt,):
                stages[-1] = ((bt,), stages[-1][1] + 1)
            else:
                stages.append(((bt,), 1))
        return stages
    if role == "enc":
        return [(("enc",), n_layers)]
    if role == "audio_dec":
        return [(("cross",), n_layers)]
    if role == "vlm":
        k = cfg.cross_attn_every
        stages = []
        n_super, rem = divmod(n_layers, k)
        if n_super:
            stages.append((("dense",) * (k - 1) + ("cross",), n_super))
        if rem:
            stages.append((("dense",), rem))
        return stages
    # text families
    if cfg.family == "ssm":  # xlstm
        k = cfg.xlstm.slstm_every if cfg.xlstm else 4
        stages = []
        n_super, rem = divmod(n_layers, k)
        if n_super:
            stages.append((("slstm",) + ("mlstm",) * (k - 1), n_super))
        if rem:
            stages.append((("mlstm",), rem))
        return stages
    btype = {"dense": "dense", "moe": "moe", "hybrid": "hybrid"}.get(
        cfg.family, "dense")
    return [((btype,), n_layers)]


# --------------------------------------------------------------------------
# Blocks
# --------------------------------------------------------------------------
def block_init(rng, cfg: ArchConfig, btype: str):
    d, hd = cfg.d_model, cfg.resolved_head_dim
    ks = jax.random.split(rng, 8)
    ln = lambda: L.rmsnorm_init(d)
    if btype in ("dense", "moe", "enc"):
        p = {"ln1": ln(), "ln2": ln(),
             "attn": L.attention_init(ks[0], d, cfg.n_heads, cfg.n_kv_heads,
                                      hd, qkv_bias=cfg.qkv_bias,
                                      qk_norm=cfg.qk_norm)}
        if btype == "moe":
            p["ffn"] = M.moe_init(ks[1], d, cfg.d_ff, cfg.moe)
        else:
            p["ffn"] = L.mlp_init(ks[1], d, cfg.d_ff)
        return p
    if btype in ("sconv", "sconv_moe"):
        return {"ln1": ln(), "ln2": ln(),
                "conv": S.short_conv_init(ks[0], d),
                "ffn": (M.moe_init(ks[1], d, cfg.d_ff, cfg.moe)
                        if btype == "sconv_moe"
                        else L.mlp_init(ks[1], d, cfg.d_ff))}
    if btype == "hybrid":
        return {"ln1": ln(), "lnm": ln(), "ln2": ln(),
                "attn": L.attention_init(ks[0], d, cfg.n_heads,
                                         cfg.n_kv_heads, hd),
                "mamba": S.mamba_init(ks[1], d, cfg.ssm),
                "ffn": L.mlp_init(ks[2], d, cfg.d_ff)}
    if btype == "cross":
        return {"ln1": ln(), "lnx": ln(), "ln2": ln(),
                "attn": L.attention_init(ks[0], d, cfg.n_heads,
                                         cfg.n_kv_heads, hd),
                "xattn": L.attention_init(ks[1], d, cfg.n_heads,
                                          cfg.n_kv_heads, hd),
                "ffn": L.mlp_init(ks[2], d, cfg.d_ff)}
    if btype == "mlstm":
        return {"ln1": ln(), "cell": X.mlstm_init(ks[0], d, cfg.n_heads)}
    if btype == "slstm":
        return {"ln1": ln(), "cell": X.slstm_init(ks[0], d, cfg.n_heads)}
    raise ValueError(btype)


@dataclass
class Ctx:
    cfg: ArchConfig
    positions: Any = None          # (S,) int32 for full/prefill
    memory: Any = None             # (B, S_mem, d) for cross blocks
    memory_positions: Any = None
    window: int = 0                # sliding window (0 = full)
    causal: bool = True
    pos: Any = None                # scalar int32, decode
    train: bool = False
    # Activation checkpointing of the per-stage scan body (train only):
    # recompute block activations in the backward pass instead of storing
    # S*d per layer — the standard trade that makes full LLM geometry fit.
    # False stores everything (faster backward, O(layers) more activation
    # HBM); surfaced as --remat/--no-remat in launch.train.
    remat: bool = True


def _ffn(params, x, cfg, btype):
    """-> (y, aux loss, expert rows computed)."""
    if btype in ("moe", "sconv_moe"):
        return M.moe_apply(params["ffn"], x, cfg.moe)
    return L.mlp_apply(params["ffn"], x), 0.0, 0


def block_apply_full(params, x, btype: str, ctx: Ctx):
    """Full-sequence forward.  Returns (x, aux_loss, expert_rows)."""
    cfg = ctx.cfg
    eps = cfg.norm_eps
    if btype in ("mlstm", "slstm"):
        # mLSTM trains in the chunkwise-PARALLEL form (MXU matmuls; exact —
        # see xlstm.mlstm_apply_chunked); sLSTM is inherently sequential.
        cell = X.mlstm_apply_chunked if btype == "mlstm" else X.slstm_apply
        h, _ = cell(params["cell"], L.rmsnorm(params["ln1"], x, eps))
        return x + h, 0.0, 0
    if btype in ("sconv", "sconv_moe"):
        x = x + S.short_conv_apply(params["conv"],
                                   L.rmsnorm(params["ln1"], x, eps))
        y, aux, rows = _ffn(params, L.rmsnorm(params["ln2"], x, eps), cfg,
                            btype)
        return x + y, aux, rows
    attn_kw = dict(positions=ctx.positions, theta=cfg.rope_theta,
                   causal=(ctx.causal and btype != "enc"),
                   window=ctx.window, eps=eps)
    h = L.rmsnorm(params["ln1"], x, eps)
    a = L.attention_apply(params["attn"], h, **attn_kw)
    if btype == "hybrid":
        m = S.mamba_apply(params["mamba"],
                          L.rmsnorm(params["lnm"], x, eps), cfg.ssm)
        x = x + (a + m) * 0.5
    else:
        x = x + a
    if btype == "cross":
        hx = L.rmsnorm(params["lnx"], x, eps)
        x = x + L.attention_apply(params["xattn"], hx, positions=ctx.positions,
                                  theta=cfg.rope_theta, memory=ctx.memory,
                                  memory_positions=ctx.memory_positions,
                                  use_rope=False)
    h2 = L.rmsnorm(params["ln2"], x, eps)
    y, aux, rows = _ffn(params, h2, cfg, btype)
    return x + y, aux, rows


# ---- caches ---------------------------------------------------------------
def block_make_cache(cfg: ArchConfig, btype: str, batch: int, capacity: int,
                     memory_len: int = 0):
    d, hd, kv = cfg.d_model, cfg.resolved_head_dim, cfg.n_kv_heads
    if btype in ("dense", "moe", "enc"):
        return {"attn": L.make_kv_cache(batch, capacity, kv, hd)}
    if btype == "hybrid":
        return {"attn": L.make_kv_cache(batch, capacity, kv, hd),
                "ssm": S.make_ssm_cache(batch, d, cfg.ssm)}
    if btype == "cross":
        return {"attn": L.make_kv_cache(batch, capacity, kv, hd),
                "xmem": {"k": jnp.zeros((batch, memory_len, kv, hd),
                                        PARAM_DTYPE),
                         "v": jnp.zeros((batch, memory_len, kv, hd),
                                        PARAM_DTYPE)}}
    if btype in ("sconv", "sconv_moe"):
        return {"conv": jnp.zeros((batch, S.SHORT_CONV_KERNEL - 1, d),
                                  PARAM_DTYPE)}
    if btype == "mlstm":
        return {"state": X.make_mlstm_state(batch, cfg.n_heads, d // cfg.n_heads)}
    if btype == "slstm":
        return {"state": X.make_slstm_state(batch, cfg.n_heads, d // cfg.n_heads)}
    raise ValueError(btype)


def block_decode(params, x, btype: str, ctx: Ctx, cache):
    """One-token step.  Returns (x, aux, new_cache)."""
    cfg = ctx.cfg
    eps = cfg.norm_eps
    if btype in ("mlstm", "slstm"):
        cell = X.mlstm_decode if btype == "mlstm" else X.slstm_decode
        h, st = cell(params["cell"], L.rmsnorm(params["ln1"], x, eps),
                     cache["state"])
        return x + h, 0.0, {"state": st}
    if btype in ("sconv", "sconv_moe"):
        h, st = S.short_conv_decode(params["conv"],
                                    L.rmsnorm(params["ln1"], x, eps),
                                    cache["conv"])
        x = x + h
        y, aux, _ = _ffn(params, L.rmsnorm(params["ln2"], x, eps), cfg,
                         btype)
        return x + y, aux, {"conv": st}
    h = L.rmsnorm(params["ln1"], x, eps)
    a, kv = L.attention_decode(params["attn"], h, cache["attn"], ctx.pos,
                               theta=cfg.rope_theta, window=ctx.window,
                               eps=eps)
    new_cache = dict(cache)
    new_cache["attn"] = kv
    if btype == "hybrid":
        m, sc = S.mamba_decode(params["mamba"],
                               L.rmsnorm(params["lnm"], x, eps),
                               cache["ssm"], cfg.ssm)
        new_cache["ssm"] = sc
        x = x + (a + m) * 0.5
    else:
        x = x + a
    if btype == "cross":
        hx = L.rmsnorm(params["lnx"], x, eps)
        x = x + L.cross_attention_decode(params["xattn"], hx, cache["xmem"])
    h2 = L.rmsnorm(params["ln2"], x, eps)
    y, aux, _ = _ffn(params, h2, cfg, btype)
    return x + y, aux, new_cache


def block_prefill(params, x, btype: str, ctx: Ctx, capacity: int):
    """Full-sequence forward that also emits the decode cache."""
    cfg = ctx.cfg
    y, aux, _ = block_apply_full(params, x, btype, ctx)
    B, Sq = x.shape[0], x.shape[1]
    if btype in ("mlstm", "slstm"):
        cell = X.mlstm_apply_chunked if btype == "mlstm" else X.slstm_apply
        _, st = cell(params["cell"],
                     L.rmsnorm(params["ln1"], x, cfg.norm_eps))
        return y, aux, {"state": st}
    if btype in ("sconv", "sconv_moe"):
        return y, aux, {"conv": S.short_conv_state(
            params["conv"], L.rmsnorm(params["ln1"], x, cfg.norm_eps))}
    # KV cache from the (normed) block input — recompute K/V projections
    h = L.rmsnorm(params["ln1"], x, cfg.norm_eps)
    k = jnp.einsum("bsd,dhk->bshk", h, params["attn"]["wk"])
    v = jnp.einsum("bsd,dhk->bshk", h, params["attn"]["wv"])
    if "bk" in params["attn"]:
        k = k + params["attn"]["bk"]
        v = v + params["attn"]["bv"]
    if "k_norm" in params["attn"]:
        k = L.rmsnorm(params["attn"]["k_norm"], k, cfg.norm_eps)
    k = L.rope(k, ctx.positions, cfg.rope_theta)
    cap = capacity
    tail = min(cap, Sq)
    k_t = k[:, Sq - tail:]
    v_t = v[:, Sq - tail:]
    tail_pos = ctx.positions[Sq - tail:]
    slots = jnp.mod(tail_pos, cap)
    kc = jnp.zeros((B, cap) + k.shape[2:], k.dtype).at[:, slots].set(k_t)
    vc = jnp.zeros((B, cap) + v.shape[2:], v.dtype).at[:, slots].set(v_t)
    sp = jnp.full((cap,), -(2 ** 30), jnp.int32).at[slots].set(tail_pos)
    cache = {"attn": {"k": kc, "v": vc, "slot_pos": sp}}
    if btype == "hybrid":
        x_in, _ = S._precompute(params["mamba"],
                                L.rmsnorm(params["lnm"], x, cfg.norm_eps))
        K = cfg.ssm.conv_dim
        xc = jax.nn.silu(S._causal_conv(x_in, params["mamba"]["conv_w"])
                         .astype(jnp.float32)).astype(x.dtype)
        dt, B_t, C_t = S._dtbc(params["mamba"], xc)
        A = -jnp.exp(params["mamba"]["A_log"])
        h0 = jnp.zeros((B, A.shape[0], A.shape[1]), jnp.float32)
        _, h_last = S._selective_ssm(xc.astype(jnp.float32), dt, B_t, C_t,
                                     A, h0)
        cache["ssm"] = {"h": h_last, "conv": x_in[:, Sq - (K - 1):]}
    if btype == "cross":
        cache["xmem"] = L.project_memory_kv(params["xattn"], ctx.memory)
    return y, aux, cache


# --------------------------------------------------------------------------
# Towers
# --------------------------------------------------------------------------
def tower_init(rng, cfg: ArchConfig, stages):
    params = []
    for (pattern, repeat) in stages:
        r = jax.random.fold_in(rng, len(params))
        def one(k, _pattern=pattern):
            sks = jax.random.split(k, len(_pattern))
            return {f"b{i}": block_init(sks[i], cfg, bt)
                    for i, bt in enumerate(_pattern)}
        params.append(stacked_init(one, r, repeat))
    return params


def tower_make_cache(cfg: ArchConfig, stages, batch: int, capacity: int,
                     memory_len: int = 0):
    caches = []
    for (pattern, repeat) in stages:
        one = {f"b{i}": block_make_cache(cfg, bt, batch, capacity, memory_len)
               for i, bt in enumerate(pattern)}
        caches.append(jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a, (repeat,) + a.shape).copy(), one))
    return caches


def tower_apply(params, x, cfg: ArchConfig, stages, ctx: Ctx,
                counts: bool = False):
    """Train/eval full-sequence forward.  Returns (x, aux), and with
    ``counts`` (x, aux, expert rows the tower's MoE layers computed)."""
    carry = (x, jnp.float32(0.0)) + ((jnp.int32(0),) if counts else ())
    for sp, (pattern, repeat) in zip(params, stages):
        def body(carry, p_layer, _pattern=pattern):
            h, a, *r = carry
            h = L.shard_batch_dim(h)   # pin batch sharding in the loop body
            for i, bt in enumerate(_pattern):
                h, ai, ri = block_apply_full(p_layer[f"b{i}"], h, bt, ctx)
                a = a + ai
                r = [r[0] + ri] if r else r
            return (L.shard_batch_dim(h), a, *r), None
        if ctx.train and ctx.remat:
            body = jax.checkpoint(body)
        carry, _ = jax.lax.scan(body, carry, sp)
    return carry


def tower_prefill(params, x, cfg: ArchConfig, stages, ctx: Ctx,
                  capacity: int):
    aux = jnp.float32(0.0)
    caches = []
    for sp, (pattern, repeat) in zip(params, stages):
        def body(carry, p_layer, _pattern=pattern):
            h, a = carry
            cs = {}
            for i, bt in enumerate(_pattern):
                h, ai, c = block_prefill(p_layer[f"b{i}"], h, bt, ctx,
                                         capacity)
                a = a + ai
                cs[f"b{i}"] = c
            return (h, a), cs
        (x, aux), stage_cache = jax.lax.scan(body, (x, aux), sp)
        caches.append(stage_cache)
    return x, aux, caches


def tower_decode(params, x, cfg: ArchConfig, stages, ctx: Ctx, caches):
    aux = jnp.float32(0.0)
    new_caches = []
    for sp, sc, (pattern, repeat) in zip(params, caches, stages):
        def body(carry, xs, _pattern=pattern):
            h, a = carry
            p_layer, c_layer = xs
            ncs = {}
            for i, bt in enumerate(_pattern):
                h, ai, nc = block_decode(p_layer[f"b{i}"], h, bt, ctx,
                                         c_layer[f"b{i}"])
                a = a + ai
                ncs[f"b{i}"] = nc
            return (h, a), ncs
        (x, aux), nc = jax.lax.scan(body, (x, aux), (sp, sc))
        new_caches.append(nc)
    return x, aux, new_caches
