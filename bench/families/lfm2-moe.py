"""LFM2-MoE (LiquidAI's ``lfm2_moe``) split between two parties: the
program under test, its weights, and its plain reference.

The layers, after the published model (HF transformers' ``lfm2_moe``):

* decoder layer: ``h = x + op(RMSNorm_op(x))``, where ``op`` is the
  layer's short convolution or attention (``layer_types``), then
  ``out = h + ffn(RMSNorm_ffn(h))``; RMSNorm is
  ``x * rsqrt(mean(x^2) + eps) * w`` with ``w`` starting at 1;
* gated short convolution: ``B, C, xs = split3(x @ W_in)`` (``W_in``
  d x 3d, no bias), ``y = C * causal_depthwise_conv_3(B * xs)``,
  ``out = y @ W_out``; the conv weight ``w[j]`` multiplies position
  ``t - j`` (the published Conv1d weight reversed in time);
* attention: GQA with a per-head RMSNorm on q and on k before RoPE
  (rotate-half), causal softmax, output projection;
* FFN: a dense SwiGLU for the first ``num_dense_layers`` layers, then
  the MoE: ``s = sigmoid(x @ W_router)`` over all ``router_experts``,
  ``idx = top-k(s + expert_bias)``, ``g = s[idx] / (sum s[idx] + 1e-6) *
  routed_scaling_factor``, ``y = sum_j g_j * SwiGLU_{idx_j}(x)``.

Party A embeds its auxiliary token stream and runs the first
``layers_a`` layers; their output is the cut activation Z_A.  Party B
embeds the tokens, runs the next ``layers_b`` layers, adds Z_A through a
d x d fusion projection, runs the ``layers_top`` layers after them, and
takes next-token cross-entropy through a final RMSNorm (the published
``embedding_norm``) and an output head, averaged over the positions of
each sequence.

Departures from the published model, each as the configuration file
states it: the layers are the first ``num_hidden_layers`` of the
published order; the MoE holds ``num_experts`` of the ``router_experts``
(from ``experts_offset``), and the output is those experts' part alone,
with the gates normalised over the full top-k; the vocabulary is a slice;
the head is untied; the expert bias is fixed as drawn (the published
load-balancing update runs outside the gradient step); there is no
auxiliary loss.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

GENERATOR = "tokens"
Q_BLOCK = 512            # the reference's attention, in query blocks


def data_params(cfg, job):
    return {"vocab": cfg["vocab_size"],
            "aux_vocab": cfg["split"]["aux_vocab_size"],
            "seq_len": job["seq_len"], "batch": job["batch"]}


# ------------------------------------------------------------------ layout
def layer_kind(cfg, i):
    """(mixer, ffn) of layer ``i``: ("conv" | "full_attention",
    "dense" | "moe")."""
    return (cfg["layer_types"][i],
            "dense" if i < cfg["num_dense_layers"] else "moe")


def tower_layers(cfg, tower):
    """The published layer indices of ``tower`` ("a", "bottom", "top")."""
    s = cfg["split"]
    first = {"a": 0, "bottom": s["layers_a"],
             "top": s["layers_a"] + s["layers_b"]}[tower]
    n = {"a": s["layers_a"], "bottom": s["layers_b"],
         "top": s["layers_top"]}[tower]
    return list(range(first, first + n))


def stages(cfg, tower):
    """Runs of one layer kind: [(kind, [layer indices])], the program's
    scan stages."""
    out = []
    for i in tower_layers(cfg, tower):
        k = layer_kind(cfg, i)
        if out and out[-1][0] == k:
            out[-1][1].append(i)
        else:
            out.append((k, [i]))
    return out


# ------------------------------------------------------------------- FLOPs
def layer_weights(cfg, i):
    """Matmul weights a token meets in layer ``i``; an MoE layer counts
    its router and, by expectation, num_experts_per_tok x held /
    router_experts experts (1 here: every chip computes its share)."""
    d, H, Kv = cfg["hidden_size"], cfg["num_attention_heads"], \
        cfg["num_key_value_heads"]
    hd = cfg["head_dim"]
    mixer, ffn = layer_kind(cfg, i)
    w = 4 * d * d if mixer == "conv" else \
        d * H * hd + 2 * d * Kv * hd + H * hd * d
    if ffn == "dense":
        return w + 3 * d * cfg["intermediate_size"]
    share = cfg["num_experts_per_tok"] * cfg["num_experts"] \
        / cfg["router_experts"]
    return w + d * cfg["router_experts"] \
        + share * 3 * d * cfg["moe_intermediate_size"]


def train_flops_per_row(cfg, job):
    """FLOPs of one forward + backward pass per training sequence: 6 per
    matmul weight per token (``layer_weights``, the fusion projection
    and the head), and attention's scores and values over ``seq_len``
    in the attention layers alone (12 * heads * head_dim * seq_len a
    token).  The short convolution's 3 taps, the routing and the
    dispatch are not counted."""
    S, d = job["seq_len"], cfg["hidden_size"]
    n = cfg["num_hidden_layers"]
    weights = sum(layer_weights(cfg, i) for i in range(n)) + d * d \
        + d * cfg["vocab_size"]
    n_attn = sum(t == "full_attention" for t in cfg["layer_types"][:n])
    attn = 12.0 * n_attn * cfg["num_attention_heads"] * cfg["head_dim"] * S
    return S * (6.0 * weights + attn)


# ----------------------------------------------------------------- program
def arch(cfg):
    """The program's ``ArchConfig`` for this configuration."""
    from repro.configs.base import ArchConfig, MoEConfig, VFLConfig
    s = cfg["split"]
    return ArchConfig(
        name=cfg["name"], family="moe",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        head_dim=cfg["head_dim"], rope_theta=float(cfg["rope_theta"]),
        norm_eps=cfg["norm_eps"], aux_vocab_size=s["aux_vocab_size"],
        qk_norm=True, layer_types=tuple(cfg["layer_types"]),
        n_dense_layers=cfg["num_dense_layers"],
        moe=MoEConfig(n_experts=cfg["router_experts"],
                      top_k=cfg["num_experts_per_tok"],
                      d_expert=cfg["moe_intermediate_size"],
                      router="sigmoid", router_aux_coef=0.0,
                      n_held=cfg["num_experts"],
                      held_offset=cfg["experts_offset"]),
        vfl=VFLConfig(layers_a=s["layers_a"], layers_b=s["layers_b"],
                      layers_top=s["layers_top"], fusion="add"),
        source=cfg["source"])


def program(cfg):
    """The program's two-party task and the shapes of its parameters."""
    from repro.launch.train import llm_task
    from repro.models import vfl
    a = arch(cfg)
    return llm_task(a, remat=True), jax.eval_shape(
        lambda k: vfl.init_all(k, a), jax.random.PRNGKey(0))


# ---------------------------------------------------------------- weights
def _uniform(key, shape, fan_in):
    lim = 1.0 / jnp.sqrt(jnp.float32(fan_in))
    return jax.random.uniform(key, shape, jnp.float32, -lim, lim)


def _block(key, cfg, kind, n):
    """``n`` stacked layers of one kind, in the program's layout."""
    d, H, Kv = cfg["hidden_size"], cfg["num_attention_heads"], \
        cfg["num_key_value_heads"]
    hd, K = cfg["head_dim"], cfg["conv_L_cache"]
    mixer, ffn = kind
    k = jax.random.split(key, 12)
    ones = lambda *s: jnp.ones((n,) + s, jnp.float32)  # noqa: E731
    p = {"ln1": {"scale": ones(d)}, "ln2": {"scale": ones(d)}}
    if mixer == "conv":
        p["conv"] = {"in_proj": _uniform(k[0], (n, d, 3 * d), d),
                     "conv_w": _uniform(k[1], (n, K, d), K),
                     "out_proj": _uniform(k[2], (n, d, d), d)}
    else:
        p["attn"] = {"wq": _uniform(k[0], (n, d, H, hd), d),
                     "wk": _uniform(k[1], (n, d, Kv, hd), d),
                     "wv": _uniform(k[2], (n, d, Kv, hd), d),
                     "wo": _uniform(k[3], (n, H, hd, d), H * hd),
                     "q_norm": {"scale": ones(hd)},
                     "k_norm": {"scale": ones(hd)}}
    if ffn == "dense":
        f = cfg["intermediate_size"]
        p["ffn"] = {"wg": _uniform(k[4], (n, d, f), d),
                    "wu": _uniform(k[5], (n, d, f), d),
                    "wd": _uniform(k[6], (n, f, d), f)}
    else:
        f, E, Hx = cfg["moe_intermediate_size"], cfg["router_experts"], \
            cfg["num_experts"]
        p["ffn"] = {"router": _uniform(k[7], (n, d, E), d),
                    "wg": _uniform(k[8], (n, Hx, d, f), d),
                    "wu": _uniform(k[9], (n, Hx, d, f), d),
                    "wd": _uniform(k[10], (n, Hx, f, d), f),
                    "expert_bias": jax.random.normal(k[11], (n, E)) * 0.01}
    return {"b0": p}


def _tower(key, cfg, tower):
    st = stages(cfg, tower)
    keys = jax.random.split(key, max(len(st), 1))
    return [_block(keys[j], cfg, kind, len(ix))
            for j, (kind, ix) in enumerate(st)]


def make_weights(cfg, key):
    """Initial weights in the program's layout (traced inside one jit),
    in bfloat16 as the configuration states: embeddings N(0, 0.02^2),
    matmul weights U(+-1/sqrt(fan_in)) (the conv taps over their 3),
    norm scales 1, the expert bias N(0, 0.01^2)."""
    s, d, V = cfg["split"], cfg["hidden_size"], cfg["vocab_size"]
    va = -(-s["aux_vocab_size"] // 256) * 256
    k = jax.random.split(key, 8)
    p = {"a": {"embed": jax.random.normal(k[0], (va, d)) * 0.02,
               "tower": _tower(k[1], cfg, "a")},
         "b": {"embed": jax.random.normal(k[2], (V, d)) * 0.02,
               "bottom": _tower(k[3], cfg, "bottom"),
               "top": _tower(k[4], cfg, "top"),
               "ln_f": {"scale": jnp.ones((d,), jnp.float32)},
               "head": _uniform(k[5], (d, V), d),
               "fuse_proj": _uniform(k[6], (d, d), d)}}
    return jax.tree_util.tree_map(lambda x: x.astype(jnp.bfloat16), p)


# -------------------------------------------------------------- reference
def _rms(x, scale, eps):
    xf = x.astype(jnp.float32)
    return xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps) \
        * scale


def _rope(x, theta):
    S, half = x.shape[1], x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(
        jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(a, h, cfg, P):
    """GQA with QK-norm and RoPE, causal softmax over all keys, computed
    in blocks of ``Q_BLOCK`` query positions (each recomputed for its
    backward), so that one block's scores are held at a time."""
    eps, theta = cfg["norm_eps"], float(cfg["rope_theta"])
    H, Kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    q = P.dot("bsd,dhk->bshk", h, a["wq"])
    k = P.dot("bsd,dhk->bshk", h, a["wk"])
    v = P.dot("bsd,dhk->bshk", h, a["wv"])
    q = P.cast(_rope(P.cast(_rms(q, a["q_norm"]["scale"], eps)), theta))
    k = P.cast(_rope(P.cast(_rms(k, a["k_norm"]["scale"], eps)), theta))
    k = jnp.repeat(k, H // Kv, axis=2)
    v = jnp.repeat(v, H // Kv, axis=2)
    S = h.shape[1]
    qb = min(Q_BLOCK, S)

    @jax.checkpoint
    def block(q_blk, start):
        s = P.dot("bqhd,bkhd->bhqk", q_blk, k).astype(jnp.float32) \
            / jnp.sqrt(jnp.float32(q_blk.shape[-1]))
        pos = start + jnp.arange(q_blk.shape[1])
        causal = pos[:, None] >= jnp.arange(S)[None, :]
        w = jax.nn.softmax(jnp.where(causal, s, -1e30), axis=-1)
        return P.dot("bhqk,bkhd->bqhd", w, v)

    o = jnp.concatenate([block(q[:, i:i + qb], i)
                         for i in range(0, S, qb)], axis=1)
    return P.dot("bqhd,hdo->bqo", o, a["wo"])


def _short_conv(c, h, cfg, P):
    """y = C * sum_j w[j] * (B * xs)[t - j], then W_out: the taps as a
    plain sum of shifted copies, zeros before the first position."""
    bcx = P.dot("bsd,de->bse", h, c["in_proj"])
    d = h.shape[-1]
    b, cg, xs = bcx[..., :d], bcx[..., d:2 * d], bcx[..., 2 * d:]
    bx = P.cast(b * xs).astype(jnp.float32)
    conv = jnp.zeros_like(bx)
    for j in range(c["conv_w"].shape[0]):
        shifted = jnp.pad(bx, ((0, 0), (j, 0), (0, 0)))[:, :bx.shape[1]]
        conv = conv + shifted * c["conv_w"][j].astype(jnp.float32)
    y = P.cast(cg * P.cast(conv))
    return P.dot("bsd,de->bse", y, c["out_proj"])


def _swiglu(h, wg, wu, wd, P):
    g = jax.nn.silu(P.dot("bsd,df->bsf", h, wg).astype(jnp.float32))
    u = P.dot("bsd,df->bsf", h, wu)
    return P.dot("bsf,fd->bsd", P.cast(g) * u, wd)


def _moe(f, h, cfg, P):
    """Sigmoid routing over all ``router_experts``, top-k by score plus
    bias, gates normalised over the top-k; each held expert computed
    densely over every token, weighted by its gate (zero where it is not
    among the token's top-k)."""
    logits = P.dot("bsd,de->bse", h, f["router"]).astype(jnp.float32)
    s = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(s + f["expert_bias"].astype(jnp.float32),
                           cfg["num_experts_per_tok"])
    g = jnp.take_along_axis(s, idx, axis=-1)
    g = g / (jnp.sum(g, -1, keepdims=True) + 1e-6) \
        * cfg["routed_scaling_factor"]
    y = jnp.zeros(h.shape, jnp.float32)
    for j in range(cfg["num_experts"]):
        e = cfg["experts_offset"] + j
        gate = jnp.sum(jnp.where(idx == e, g, 0.0), axis=-1)
        o = _swiglu(h, f["wg"][j], f["wu"][j], f["wd"][j], P)
        y = y + gate[..., None] * o.astype(jnp.float32)
    return y


def _layer(kind, p, x, cfg, P):
    eps = cfg["norm_eps"]
    mixer, ffn = kind
    h = P.cast(_rms(x, p["ln1"]["scale"], eps))
    op = _short_conv(p["conv"], h, cfg, P) if mixer == "conv" else \
        _attention(p["attn"], h, cfg, P)
    x = P.cast(x + op)
    h2 = P.cast(_rms(x, p["ln2"]["scale"], eps))
    f = p["ffn"]
    y = _swiglu(h2, f["wg"], f["wu"], f["wd"], P) if ffn == "dense" else \
        _moe(f, h2, cfg, P)
    return P.cast(x + y)


def _stack(params, cfg, tower, x, P):
    """Each layer of the tower in turn, each under ``jax.checkpoint``."""
    for stage, (kind, ix) in zip(params, stages(cfg, tower)):
        for j in range(len(ix)):
            p = jax.tree_util.tree_map(lambda a, j=j: a[j], stage["b0"])
            x = jax.checkpoint(functools.partial(_layer, kind, cfg=cfg,
                                                 P=P))(p, x)
    return x


def reference(cfg):
    """-> (forward_a(pa, batch_a, P), loss_b(pb, z, batch_b, P))."""
    def forward_a(pa, batch_a, P):
        x = P.cast(pa["embed"][batch_a["tokens_a"]])
        return _stack(pa["tower"], cfg, "a", x, P)

    def loss_b(pb, z, batch_b, P):
        x = P.cast(pb["embed"][batch_b["tokens"]])
        x = _stack(pb["bottom"], cfg, "bottom", x, P)
        x = P.cast(x + P.dot("bsd,de->bse", z, pb["fuse_proj"]))
        x = _stack(pb["top"], cfg, "top", x, P)
        h = P.cast(_rms(x, pb["ln_f"]["scale"], cfg["norm_eps"]))
        logits = P.dot("bsd,dv->bsv", h, pb["head"]).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        lab = jnp.take_along_axis(logits, batch_b["labels"][..., None],
                                  axis=-1)[..., 0]
        return jnp.mean(lse - lab, axis=-1)

    return forward_a, loss_b
