"""A dense Llama-style decoder split between two parties: the program
under test, its weights, and its plain reference.

Party A embeds its auxiliary token stream and runs ``layers_a`` decoder
layers; their output is the cut activation Z_A.  Party B embeds the
tokens, runs ``layers_b`` layers, adds Z_A through a d x d fusion
projection, runs ``layers_top`` more, and takes next-token cross-entropy
through a final RMSNorm and an untied output head, averaged over the
positions of each sequence.  A decoder layer: RMSNorm, grouped-query
causal attention with rotary embeddings (rotate-half), residual,
RMSNorm, SwiGLU MLP, residual.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

GENERATOR = "tokens"


def data_params(cfg, job):
    return {"vocab": cfg["vocab_size"], "aux_vocab": cfg["split"]["aux_vocab_size"],
            "seq_len": job["seq_len"], "batch": job["batch"]}


def train_flops_per_row(cfg, job):
    """FLOPs of one forward + backward pass per training sequence of
    ``seq_len`` tokens (``flops.llm_flops_per_token``)."""
    import flops
    return job["seq_len"] * flops.llm_flops_per_token(cfg, job["seq_len"])


def arch(cfg):
    """The program's ``ArchConfig`` for this configuration."""
    from repro.configs.base import ArchConfig, VFLConfig
    s = cfg["split"]
    return ArchConfig(
        name=cfg["name"], family="dense",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        head_dim=cfg["head_dim"], rope_theta=cfg["rope_theta"],
        norm_eps=cfg["rms_norm_eps"], aux_vocab_size=s["aux_vocab_size"],
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


def _layers(key, cfg, n):
    d, H, Kv = cfg["hidden_size"], cfg["num_attention_heads"], \
        cfg["num_key_value_heads"]
    hd, f = cfg["head_dim"], cfg["intermediate_size"]
    k = jax.random.split(key, 7)
    return [{"b0": {
        "ln1": {"scale": jnp.ones((n, d), jnp.float32)},
        "ln2": {"scale": jnp.ones((n, d), jnp.float32)},
        "attn": {"wq": _uniform(k[0], (n, d, H, hd), d),
                 "wk": _uniform(k[1], (n, d, Kv, hd), d),
                 "wv": _uniform(k[2], (n, d, Kv, hd), d),
                 "wo": _uniform(k[3], (n, H, hd, d), H * hd)},
        "ffn": {"wg": _uniform(k[4], (n, d, f), d),
                "wu": _uniform(k[5], (n, d, f), d),
                "wd": _uniform(k[6], (n, f, d), f)}}}]


def make_weights(cfg, key):
    """Initial weights in the program's layout (traced inside one jit),
    in bfloat16 as the configuration states: embeddings N(0, 0.02^2),
    dense weights U(+-1/sqrt(fan_in)), norm scales 1."""
    s, d, V = cfg["split"], cfg["hidden_size"], cfg["vocab_size"]
    va = -(-s["aux_vocab_size"] // 256) * 256
    k = jax.random.split(key, 8)
    p = {"a": {"embed": jax.random.normal(k[0], (va, d)) * 0.02,
               "tower": _layers(k[1], cfg, s["layers_a"])},
         "b": {"embed": jax.random.normal(k[2], (V, d)) * 0.02,
               "bottom": _layers(k[3], cfg, s["layers_b"]),
               "top": _layers(k[4], cfg, s["layers_top"]),
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


def _layer(p, x, cfg, P):
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    H, Kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    a = p["attn"]
    h = P.cast(_rms(x, p["ln1"]["scale"], eps))
    q = P.cast(_rope(P.dot("bsd,dhk->bshk", h, a["wq"]), theta))
    k = P.cast(_rope(P.dot("bsd,dhk->bshk", h, a["wk"]), theta))
    v = P.dot("bsd,dhk->bshk", h, a["wv"])
    k = jnp.repeat(k, H // Kv, axis=2)
    v = jnp.repeat(v, H // Kv, axis=2)
    S = x.shape[1]
    s = P.dot("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) \
        / jnp.sqrt(jnp.float32(q.shape[-1]))
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    w = jax.nn.softmax(jnp.where(causal, s, -1e30), axis=-1)
    o = P.dot("bhqk,bkhd->bqhd", w, v)
    x = P.cast(x + P.dot("bqhd,hdo->bqo", o, a["wo"]))
    f = p["ffn"]
    h2 = P.cast(_rms(x, p["ln2"]["scale"], eps))
    g = jax.nn.silu(P.dot("bsd,df->bsf", h2, f["wg"]).astype(jnp.float32))
    u = P.dot("bsd,df->bsf", h2, f["wu"])
    return P.cast(x + P.dot("bsf,fd->bsd", P.cast(g) * u, f["wd"]))


def _stack(stages, x, cfg, P):
    for stage in stages:
        def body(h, p):
            return _layer(p["b0"], h, cfg, P), None
        x, _ = jax.lax.scan(jax.checkpoint(body), x, stage)
    return x


def reference(cfg):
    """-> (forward_a(pa, batch_a, P), loss_b(pb, z, batch_b, P))."""
    def forward_a(pa, batch_a, P):
        x = P.cast(pa["embed"][batch_a["tokens_a"]])
        return _stack(pa["tower"], x, cfg, P)

    def loss_b(pb, z, batch_b, P):
        x = P.cast(pb["embed"][batch_b["tokens"]])
        x = _stack(pb["bottom"], x, cfg, P)
        x = P.cast(x + P.dot("bsd,de->bse", z, pb["fuse_proj"]))
        x = _stack(pb["top"], x, cfg, P)
        h = P.cast(_rms(x, pb["ln_f"]["scale"], cfg["rms_norm_eps"]))
        logits = P.dot("bsd,dv->bsv", h, pb["head"]).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        lab = jnp.take_along_axis(logits, batch_b["labels"][..., None],
                                  axis=-1)[..., 0]
        return jnp.mean(lse - lab, axis=-1)

    return forward_a, loss_b
