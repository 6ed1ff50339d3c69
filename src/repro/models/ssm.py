"""Selective SSM (Mamba-style) block, TPU-adapted.

The CUDA selective-scan kernel is replaced by a *chunked associative scan*:
``lax.scan`` over sequence chunks with ``lax.associative_scan`` inside each
chunk — the memory-optimal TPU formulation (working set O(B * chunk * d * N)
instead of O(B * S * d * N)), mapping the recurrence onto the VPU instead of
porting warp-level primitives (DESIGN §2).

Recurrence (diagonal A):   h_t = exp(dt_t * A) h_{t-1} + dt_t * B_t * x_t
                           y_t = C_t · h_t + D * x_t
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..configs.base import SSMConfig
from .initializers import dense_init, ones_init, zeros_init

SCAN_CHUNK = 256


def mamba_init(rng, d_model: int, cfg: SSMConfig):
    di = cfg.expand * d_model
    N = cfg.state_dim
    r = max(16, d_model // 16)
    ks = jax.random.split(rng, 8)
    a = jnp.tile(jnp.arange(1, N + 1, dtype=jnp.float32)[None, :], (di, 1))
    return {
        "in_proj": dense_init(ks[0], d_model, 2 * di),
        "conv_w": (jax.random.normal(ks[1], (cfg.conv_dim, di), jnp.float32)
                   * 0.1).astype(jnp.bfloat16),
        "w_b": dense_init(ks[2], di, N),
        "w_c": dense_init(ks[3], di, N),
        "w_dt1": dense_init(ks[4], di, r),
        "w_dt2": dense_init(ks[5], r, di),
        "dt_bias": zeros_init((di,), jnp.float32),
        "A_log": jnp.log(a),
        "D": ones_init((di,), jnp.float32),
        "out_proj": dense_init(ks[6], di, d_model),
    }


def _causal_conv(x, w):
    """Depthwise causal conv.  x: (B, S, di); w: (K, di)."""
    K = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    out = jnp.zeros_like(x, dtype=jnp.float32)
    for j in range(K):
        out = out + xp[:, j:j + x.shape[1], :].astype(jnp.float32) * \
            w[K - 1 - j].astype(jnp.float32)
    return out.astype(x.dtype)


def _ssm_scan(decay, inc):
    """Associative scan of h_t = decay_t * h_{t-1} + inc_t over axis 1."""
    def combine(a, b):
        return (a[0] * b[0], b[0] * a[1] + b[1])
    d, h = jax.lax.associative_scan(combine, (decay, inc), axis=1)
    return d, h


def _selective_ssm(xc, dt, B_t, C_t, A, h0):
    """xc/dt: (B,S,di); B_t/C_t: (B,S,N); A: (di,N); h0: (B,di,N)."""
    Bsz, S, di = xc.shape
    N = A.shape[1]
    chunk = min(SCAN_CHUNK, S)
    n_chunks = S // chunk
    assert S % chunk == 0, (S, chunk)

    def step(h, idx):
        from .layers import shard_batch_dim
        h = shard_batch_dim(h)
        sl = lambda a: shard_batch_dim(
            jax.lax.dynamic_slice_in_dim(a, idx * chunk, chunk, 1))
        xcs, dts, Bs, Cs = sl(xc), sl(dt), sl(B_t), sl(C_t)
        decay = jnp.exp(dts[..., None] * A[None, None])        # (B,c,di,N)
        inc = (dts * xcs)[..., None] * Bs[:, :, None, :]       # (B,c,di,N)
        cum_decay, h_local = _ssm_scan(decay, inc)
        h_all = h_local + cum_decay * h[:, None]               # add carry
        y = jnp.einsum("bcdn,bcn->bcd", h_all, Cs)
        return h_all[:, -1], y

    h_last, ys = jax.lax.scan(step, h0, jnp.arange(n_chunks))
    y = ys.transpose(1, 0, 2, 3).reshape(Bsz, S, di)
    return y, h_last


def _precompute(params, x):
    di = params["D"].shape[0]
    xz = jnp.einsum("bsd,de->bse", x, params["in_proj"])
    x_in, z = xz[..., :di], xz[..., di:]
    return x_in, z


def _dtbc(params, xc):
    dt_pre = jnp.einsum("bsd,dr,re->bse", xc.astype(jnp.float32),
                        params["w_dt1"].astype(jnp.float32),
                        params["w_dt2"].astype(jnp.float32))
    dt = jax.nn.softplus(dt_pre + params["dt_bias"])
    B_t = jnp.einsum("bsd,dn->bsn", xc, params["w_b"]).astype(jnp.float32)
    C_t = jnp.einsum("bsd,dn->bsn", xc, params["w_c"]).astype(jnp.float32)
    return dt, B_t, C_t


def mamba_apply(params, x, cfg: SSMConfig):
    """Full-sequence forward.  x: (B, S, d)."""
    x_in, z = _precompute(params, x)
    xc = jax.nn.silu(_causal_conv(x_in, params["conv_w"])
                     .astype(jnp.float32)).astype(x.dtype)
    dt, B_t, C_t = _dtbc(params, xc)
    A = -jnp.exp(params["A_log"])
    h0 = jnp.zeros((x.shape[0], A.shape[0], A.shape[1]), jnp.float32)
    y, _ = _selective_ssm(xc.astype(jnp.float32), dt, B_t, C_t, A, h0)
    y = y + params["D"] * xc.astype(jnp.float32)
    y = y.astype(x.dtype) * jax.nn.sigmoid(
        z.astype(jnp.float32)).astype(x.dtype) * z  # silu(z) gate
    return jnp.einsum("bsd,de->bse", y, params["out_proj"])


def make_ssm_cache(batch: int, d_model: int, cfg: SSMConfig,
                   dtype=jnp.float32):
    di = cfg.expand * d_model
    return {
        "h": jnp.zeros((batch, di, cfg.state_dim), jnp.float32),
        "conv": jnp.zeros((batch, cfg.conv_dim - 1, di), dtype),
    }


def mamba_decode(params, x, cache, cfg: SSMConfig):
    """One-token decode.  x: (B, 1, d) -> (y, cache)."""
    x_in, z = _precompute(params, x)
    window = jnp.concatenate([cache["conv"], x_in.astype(cache["conv"].dtype)],
                             axis=1)                       # (B, K, di)
    w = params["conv_w"].astype(jnp.float32)
    xc = jnp.einsum("bkd,kd->bd", window.astype(jnp.float32), w)[:, None]
    xc = jax.nn.silu(xc).astype(x.dtype)
    dt, B_t, C_t = _dtbc(params, xc)
    A = -jnp.exp(params["A_log"])
    decay = jnp.exp(dt[:, 0, :, None] * A[None])           # (B,di,N)
    inc = (dt[:, 0] * xc[:, 0].astype(jnp.float32))[..., None] * \
        B_t[:, 0, None, :]
    h = decay * cache["h"] + inc
    y = jnp.einsum("bdn,bn->bd", h, C_t[:, 0])[:, None]
    y = y + params["D"] * xc.astype(jnp.float32)
    y = y.astype(x.dtype) * jax.nn.sigmoid(
        z.astype(jnp.float32)).astype(x.dtype) * z
    out = jnp.einsum("bsd,de->bse", y, params["out_proj"])
    return out, {"h": h, "conv": window[:, 1:]}


# --------------------------------------------------------------------------
# Gated short convolution (lfm2's conv mixer)
# --------------------------------------------------------------------------
SHORT_CONV_SCOPE = "short_conv"
SHORT_CONV_KERNEL = 3            # lfm2's taps (``conv_L_cache``)


def short_conv_init(rng, d_model: int, kernel: int = SHORT_CONV_KERNEL):
    """``in_proj`` d x 3d (B, C, x in that order), ``conv_w`` (K, d) with
    ``conv_w[j]`` the weight of position t - j (``_causal_conv``'s layout),
    ``out_proj`` d x d; no biases."""
    ks = jax.random.split(rng, 3)
    lim = 1.0 / jnp.sqrt(jnp.float32(kernel))
    return {"in_proj": dense_init(ks[0], d_model, 3 * d_model),
            "conv_w": jax.random.uniform(ks[1], (kernel, d_model),
                                         jnp.float32, -lim, lim
                                         ).astype(jnp.bfloat16),
            "out_proj": dense_init(ks[2], d_model, d_model)}


def _short_conv_gates(params, x):
    bcx = jnp.einsum("bsd,de->bse", x, params["in_proj"])
    b, c, xs = jnp.split(bcx, 3, axis=-1)
    return b * xs, c


def short_conv_apply(params, x):
    """y = C * causal_depthwise_conv(B * x) @ W_out over a full sequence."""
    with jax.named_scope(SHORT_CONV_SCOPE):
        bx, c = _short_conv_gates(params, x)
        y = c * _causal_conv(bx, params["conv_w"])
        return jnp.einsum("bsd,de->bse", y, params["out_proj"])


def short_conv_state(params, x):
    """The decode state after a full sequence: its last K-1 values of
    B * x, zeros before the first position."""
    bx, _ = _short_conv_gates(params, x)
    K = params["conv_w"].shape[0]
    bx = jnp.pad(bx, ((0, 0), (K - 1, 0), (0, 0)))
    return bx[:, bx.shape[1] - (K - 1):]


def short_conv_decode(params, x, state):
    """One token.  x: (B, 1, d); state: (B, K-1, d) -> (y, state)."""
    with jax.named_scope(SHORT_CONV_SCOPE):
        bx, c = _short_conv_gates(params, x)
        window = jnp.concatenate([state, bx.astype(state.dtype)], axis=1)
        conv = jnp.einsum("bkd,kd->bd", window[:, ::-1].astype(jnp.float32),
                          params["conv_w"].astype(jnp.float32))
        y = c * conv[:, None].astype(x.dtype)
        return jnp.einsum("bsd,de->bse", y, params["out_proj"]), window[:, 1:]
