"""Pluggable wire codecs for the compressed K-party transport
(Compressed-VFL, Castiglia et al. — top-k sparsification and low-bit
quantization of the exchanged cut tensors preserve convergence when
combined with the engine's multiple local steps per round).

A codec maps an arbitrary-shape float array to a *payload* (a pytree of
wire arrays) and back:

    encode(rng, x)        -> payload
    decode(payload, like) -> array with ``like``'s shape/dtype
    wire_bytes(shape, dtype) -> int  — EXACT payload size: equals the sum
        of ``leaf.nbytes`` over the payload for an input of that shape
        (tests pin this), so transport byte accounting is honest.
    lossless              -> bool   — lossless codecs skip error feedback.

Codecs here:

  * :class:`IdentityCodec` — the wire as-is;
  * :class:`StochasticQuantCodec` — int8 / int4 quantization with one fp32
    absmax scale per ``tile`` values and stochastic rounding
    (``floor(x/s + u)``, unbiased); int4 codes are nibble-packed two per
    byte.  The encode hot path is the fused Pallas kernel
    ``kernels.ops.quantize_stochastic`` (absmax + scale + round in one
    VMEM pass); tile counts the kernel can't split fall back to the
    bit-identical jnp reference;
  * :class:`TopKCodec` — keep the k = ratio * n largest-magnitude values
    (indices int16 when they fit, else int32).  ``value_codec`` chains a
    second codec over the kept values (top-k + int8 is Compressed-VFL's
    sketch);
  * :class:`ChainCodec` — residual chaining: stage i encodes what stages
    < i failed to reconstruct, the wire carries every stage's payload, and
    decode sums the stage reconstructions (multi-stage quantization:
    ``int4x2`` ~ int8 quality at int8 cost, but each stage tolerates the
    other's outliers).

Error feedback lives in the transport, not the codec
(:class:`repro.core.engine.CompressedWANTransport`): the per-direction
residual ``r`` is carried in the engine round state, the transport sends
``decode(encode(x + r))`` and keeps ``r' = (x + r) - decoded`` — so
compression error is delayed into the next round's message instead of
lost, and the decoded messages telescope to the uncompressed sum.
"""
from __future__ import annotations

import math
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

TILE = 128          # values per fp32 quantization scale
INT16_MAX = 2 ** 15 - 1


def _nelem(shape) -> int:
    return int(math.prod(int(s) for s in shape))


def payload_nbytes(payload) -> int:
    """Actual wire size of an encoded payload (what wire_bytes must match)."""
    return sum(int(leaf.nbytes) for leaf in jax.tree_util.tree_leaves(payload))


class IdentityCodec:
    """The wire as-is (accounting follows the given dtype — the transport
    passes its wire dtype, so this reproduces the plain SimWAN bytes)."""

    lossless = True
    exact = True      # decode(encode(x)) is x BITWISE -> skippable on send

    def encode(self, rng, x):
        return {"x": x}

    def decode(self, payload, like):
        return payload["x"]

    def wire_bytes(self, shape, dtype) -> int:
        return _nelem(shape) * jnp.dtype(dtype).itemsize


class StochasticQuantCodec:
    """int8 / int4 stochastic-rounding quantization, one fp32 absmax scale
    per ``tile`` consecutive values (the flattened array is zero-padded to
    whole tiles; padding decodes to exact zeros)."""

    lossless = False
    exact = False

    def __init__(self, bits: int = 8, tile: int = TILE):
        assert bits in (4, 8), bits
        assert tile % 2 == 0, tile
        self.bits = bits
        self.tile = tile
        self.levels = (1 << (bits - 1)) - 1      # 127 / 7

    def _tiles(self, n: int) -> int:
        return -(-n // self.tile)

    def _quantize(self, rng, x2d):
        """(T, tile) -> (codes int8, scales f32); fused kernel when the
        Pallas grid can tile T, bit-identical jnp reference otherwise."""
        from ..kernels.quantize import BLOCK_T
        T = x2d.shape[0]
        u = jax.random.uniform(rng, x2d.shape, jnp.float32)
        if T % min(BLOCK_T, T) == 0:
            from ..kernels import ops as kops
            return kops.quantize_stochastic(x2d, u, self.levels)
        from ..kernels.ref import quantize_sr_ref
        return quantize_sr_ref(x2d, u, self.levels)

    def encode(self, rng, x):
        n = _nelem(x.shape)
        T = self._tiles(n)
        flat = jnp.ravel(x).astype(jnp.float32)
        x2d = jnp.pad(flat, (0, T * self.tile - n)).reshape(T, self.tile)
        q, scale = self._quantize(rng, x2d)
        if self.bits == 4:
            b = (q + 8).astype(jnp.uint8)        # [-7, 7] -> [1, 15]
            q = b[:, 0::2] | (b[:, 1::2] << 4)   # two nibbles per byte
        return {"q": q, "scale": scale}

    def decode(self, payload, like):
        q, scale = payload["q"], payload["scale"]
        if self.bits == 4:
            lo = (q & 0xF).astype(jnp.int8) - 8
            hi = (q >> 4).astype(jnp.int8) - 8
            q = jnp.stack([lo, hi], axis=-1).reshape(q.shape[0], -1)
        x2d = q.astype(jnp.float32) * scale      # (T, 1) row scales
        n = _nelem(like.shape)
        return x2d.ravel()[:n].reshape(like.shape).astype(like.dtype)

    def wire_bytes(self, shape, dtype) -> int:
        T = self._tiles(_nelem(shape))
        code_bytes = self.tile if self.bits == 8 else self.tile // 2
        return T * code_bytes + T * 4            # codes + fp32 scales


class PlateauRatioSchedule:
    """Adaptive top-k keep-ratio: loosen sparsity as the loss plateaus.

    Early in training the gradients' energy is concentrated and an
    aggressive sketch is nearly free; near convergence the signal spreads
    out and the sparsification error (even under error feedback, a
    one-round delay) caps the reachable loss.  This host-side control
    plane watches the (smoothed) training loss between jitted rounds:
    when ``patience`` consecutive observations fail to improve the best
    seen loss by ``min_delta``, it steps the keep-ratio up the ``ratios``
    ladder.  Monotone by construction — sparsity only loosens.

    The schedule lives OUTSIDE the jit: a ratio change re-specializes the
    round function (``k`` is a static shape), which is cheap because it
    happens a handful of times per run.  Error-feedback residuals are
    dense fp32 regardless of ratio, so they carry across the change."""

    def __init__(self, ratios: Sequence[float] = (0.0625, 0.125, 0.25, 0.5),
                 patience: int = 3, min_delta: float = 1e-3):
        rs = tuple(float(r) for r in ratios)
        assert rs == tuple(sorted(rs)) and rs, "ratios must ascend"
        self.ratios = rs
        self.patience = patience
        self.min_delta = min_delta
        self.idx = 0
        self.best = float("inf")
        self.stall = 0

    @property
    def ratio(self) -> float:
        return self.ratios[self.idx]

    def update(self, loss) -> Optional[float]:
        """Observe one smoothed loss; return the NEW ratio when the
        plateau rule fires (else None).

        Non-finite observations are IGNORED (no stall tick, no ratio
        step): a depth-D pipeline reports NaN losses for its D-1 warmup
        rounds, and `NaN < best` / `NaN >= patience-threshold` both being
        False used to route NaN into the stall branch — a ratio ladder
        driven entirely by warmup artifacts before the first real loss
        arrived."""
        loss = float(loss)
        if not math.isfinite(loss):
            return None
        if loss < self.best - self.min_delta:
            self.best = loss
            self.stall = 0
            return None
        self.stall += 1
        if self.stall >= self.patience and self.idx + 1 < len(self.ratios):
            self.idx += 1
            self.stall = 0
            self.best = min(self.best, loss)
            return self.ratio
        return None


class TopKCodec:
    """Keep the k = ceil(ratio * n) largest-magnitude values; the rest
    decode to zero.  ``value_codec`` compresses the kept-value vector
    (codec chaining — e.g. top-k indices + int8 values).

    ``ratio_schedule`` (a :class:`PlateauRatioSchedule`-like object) is the
    adaptive-sparsity hook: callers feed it the training loss via
    :meth:`scheduled` between rounds and swap in the returned codec when
    the keep-ratio steps."""

    lossless = False
    exact = False

    def __init__(self, ratio: float = 0.25,
                 value_codec: Optional[object] = None,
                 ratio_schedule: Optional[PlateauRatioSchedule] = None):
        assert 0.0 < ratio <= 1.0, ratio
        self.ratio = ratio
        self.value_codec = value_codec or IdentityCodec()
        self.ratio_schedule = ratio_schedule
        if ratio_schedule is not None and ratio_schedule.ratio != ratio:
            # sync the ladder to the codec's starting ratio, else a fired
            # step could TIGHTEN the wire (monotone-loosening contract)
            if ratio not in ratio_schedule.ratios:
                raise ValueError(
                    f"codec ratio {ratio} not on the schedule ladder "
                    f"{ratio_schedule.ratios}")
            ratio_schedule.idx = ratio_schedule.ratios.index(ratio)

    def with_ratio(self, ratio: float) -> "TopKCodec":
        """Same codec (and schedule hook) at a different keep-ratio."""
        return TopKCodec(ratio, value_codec=self.value_codec,
                         ratio_schedule=self.ratio_schedule)

    def scheduled(self, loss) -> "TopKCodec":
        """Consult the ratio_schedule with one loss observation; returns
        ``self`` unchanged or a re-ratioed clone (caller rebuilds the
        round function around it — error-feedback residuals carry)."""
        if self.ratio_schedule is None:
            return self
        r = self.ratio_schedule.update(loss)
        if r is None or r == self.ratio:
            return self
        return self.with_ratio(r)

    def k_of(self, n: int) -> int:
        return max(1, int(math.ceil(n * self.ratio)))

    @staticmethod
    def _idx_dtype(n: int):
        return jnp.int16 if n - 1 <= INT16_MAX else jnp.int32

    def encode(self, rng, x):
        flat = jnp.ravel(x).astype(jnp.float32)
        n = flat.shape[0]
        k = self.k_of(n)
        _, idx = jax.lax.top_k(jnp.abs(flat), k)
        vals = flat[idx]
        vp = self.value_codec.encode(jax.random.fold_in(rng, 1), vals)
        return {"idx": idx.astype(self._idx_dtype(n)), "val": vp}

    def decode(self, payload, like):
        n = _nelem(like.shape)
        k = self.k_of(n)
        vals = self.value_codec.decode(
            payload["val"], jax.ShapeDtypeStruct((k,), jnp.float32))
        flat = jnp.zeros((n,), jnp.float32)
        flat = flat.at[payload["idx"].astype(jnp.int32)].set(vals)
        return flat.reshape(like.shape).astype(like.dtype)

    def wire_bytes(self, shape, dtype) -> int:
        n = _nelem(shape)
        k = self.k_of(n)
        idx_bytes = jnp.dtype(self._idx_dtype(n)).itemsize
        return k * idx_bytes + self.value_codec.wire_bytes((k,), jnp.float32)


class ChainCodec:
    """Residual chaining: ``encode`` runs the stages left to right, each on
    the running reconstruction error; ``decode`` sums the stages."""

    # lossless chains (one ending in identity) reconstruct only to fp32
    # rounding — the transport must still run encode/decode for them
    exact = False

    def __init__(self, stages: Sequence[object]):
        assert stages, "empty chain"
        self.stages = list(stages)

    @property
    def lossless(self) -> bool:
        # ANY lossless stage makes the chain exact: that stage's payload
        # carries the entire remaining residual.
        return any(s.lossless for s in self.stages)

    def encode(self, rng, x):
        e = x.astype(jnp.float32)
        payloads = []
        for i, c in enumerate(self.stages):
            p = c.encode(jax.random.fold_in(rng, i), e)
            e = e - c.decode(p, e)
            payloads.append(p)
        return {"stages": payloads}

    def decode(self, payload, like):
        f32 = jax.ShapeDtypeStruct(like.shape, jnp.float32)
        out = jnp.zeros(like.shape, jnp.float32)
        for c, p in zip(self.stages, payload["stages"]):
            out = out + c.decode(p, f32)
        return out.astype(like.dtype)

    def wire_bytes(self, shape, dtype) -> int:
        return sum(c.wire_bytes(shape, dtype) for c in self.stages)


# --------------------------------------------------------------------------
# Named specs (the `--compression` axis / CELUConfig.compression values)
# --------------------------------------------------------------------------
def make_codec(name: str):
    """One codec by name: identity | int8 | int4 | int4x2 | topk |
    topk_int8 | topk_int4."""
    if name == "identity":
        return IdentityCodec()
    if name == "int8":
        return StochasticQuantCodec(8)
    if name == "int4":
        return StochasticQuantCodec(4)
    if name == "int4x2":
        return ChainCodec([StochasticQuantCodec(4), StochasticQuantCodec(4)])
    if name == "topk":
        return TopKCodec(0.25)
    if name == "topk_int8":
        return TopKCodec(0.25, value_codec=StochasticQuantCodec(8))
    if name == "topk_int4":
        return TopKCodec(0.25, value_codec=StochasticQuantCodec(4))
    raise ValueError(f"unknown codec {name!r}")


# Asymmetric up/down presets: sparse sketches uplink (Z_i), dense low-bit
# downlink (∇Z_i — top-k on derivatives interacts badly with Algorithm-2's
# cosine staleness measure, so the downlink stays dense).
_PAIRS = {
    "int8_topk": ("topk_int8", "int8"),
    "int4_topk": ("topk_int4", "int4"),
}

CODEC_SPECS = ("identity", "int8", "int4", "int4x2", "topk", "topk_int8",
               "topk_int4") + tuple(_PAIRS)


def make_codec_pair(spec: str) -> Tuple[object, object]:
    """Codec spec -> (uplink codec, downlink codec).

    ``"up/down"`` picks each direction explicitly (e.g. ``"topk/int8"``);
    a name from ``_PAIRS`` is a curated asymmetric preset; any single
    codec name is used for both directions."""
    if "/" in spec:
        up, down = spec.split("/", 1)
        return make_codec(up), make_codec(down)
    if spec in _PAIRS:
        up, down = _PAIRS[spec]
        return make_codec(up), make_codec(down)
    return make_codec(spec), make_codec(spec)
