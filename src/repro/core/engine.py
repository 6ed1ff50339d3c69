"""The K-party CELU-VFL round engine — the ONE implementation of the
paper's round structure (arXiv:2207.14628, Algorithms 1-2).

A *round* is: exchange ⟨Z_i, ∇Z_i⟩ once for every feature party A_i
(i = 1..K), apply the fresh SGD step to all parties, insert the released
statistics into each party's device-resident workset table, then run up to
``R`` staleness-weighted local updates per party from that table.  The
named protocols are presets of this one structure:

  * Vanilla  = ``local_steps=0`` (exchange every model update);
  * FedBCD   = ``W=1`` consecutive sampling, no weighting;
  * CELU-VFL = round-robin sampling over W slots + Algorithm-2 weighting.

Two axes of parameterization:

**K feature parties.**  ``K`` is inferred from ``state["params"]["a"]`` (a
list of per-party pytrees).  ``K=1`` is the paper's two-party setting and
reproduces the historical ``core.protocol`` implementation bit-for-bit
(``tests/test_engine.py`` pins this against golden traces recorded from the
seed implementation).  ``K>=2`` is the multi-party extension the paper
defers to future work (§6): Party B weights each cached instance by the
MINIMUM per-party derivative cosine — an instance is only trusted if it is
fresh w.r.t. EVERY party's cut tensor.

**Transport.**  How the cut tensors move between parties is pluggable:

  * :class:`SimWANTransport` — in-process simulated WAN: wire-dtype
    quantization (bf16 wire halves bytes), optional Gaussian-mechanism DP
    noise, and byte accounting.  Subsumes the old ``protocol`` /
    ``multiparty`` paths.
  * :class:`CompressedWANTransport` — SimWAN plus a pluggable wire codec
    per direction (``core.compression``): top-k sparsification and/or
    int8/int4 stochastic-rounding quantization of every released message,
    with per-direction error-feedback residuals carried in the round
    state.
  * :class:`PodTransport` — ``lax.ppermute`` over the pod mesh axis for
    the SPMD party-to-pod mapping (:func:`make_pod_round`); the slow
    inter-pod DCN link plays the WAN.  Subsumes the old ``pod_protocol``
    exchange.

**Transports & compression.**  A transport exposes
``send(rng, x, res, direction) -> (wire_value, new_res)`` plus byte
accounting split by direction — ``uplink_bytes(shape)`` (Z_i, A_i -> B),
``downlink_bytes(shape)`` (∇Z_i, B -> A_i) and ``round_bytes(z_shapes) =
Σ_i up_i + down_i`` — so asymmetric wires (sparse top-k sketches up, dense
low-bit down) account exactly.  Codec selection: set
``CELUConfig.compression`` (or pass ``compression=`` to
:func:`make_round`) to a spec from ``core.compression.CODEC_SPECS``
("int8", "int4", "topk", "int8_topk" = top-k+int8 up / dense int8 down,
"up/down" picks each direction) and build the transport with
:func:`make_transport`.  Lossy codecs keep one error-feedback residual
per feature party per direction in ``state["transport"]`` (zeros from
``init_state(..., transport=...)``): each round the transport sends
``decode(encode(x + r))`` and carries ``r' = (x + r) - decoded`` forward,
so the decoded messages telescope to the uncompressed sum and compression
error is a one-round delay, not a loss.  The identity codec is
bit-identical to plain :class:`SimWANTransport` (golden-trace pinned).

The Algorithm-2 weighting hot path routes through the fused Pallas kernel
``kernels.ops.weighted_cotangent`` (cosine + threshold + cotangent scale in
one VMEM pass; bit-exact with the reference composition).  Pass
``fused_weighting=False`` to pin the pure-jnp reference path (the parity
oracle).

**Workset cache precision & the fused sample path.**  The ring buffers
behind the R-per-round local updates are built with
``CELUConfig.cache_dtype`` (``core.workset`` storage codec): "float32"
(verbatim, golden-pinned), "bfloat16", or "int8" (SR-quantized codes +
one fp32 scale per instance row — ~4x smaller; the table dominates
training-state memory at realistic W).  With ``CELUConfig.cache_fused``
(default on) each party-A local update consumes the sampled slot through
the gather→dequant→weight megakernel (``kernels/fused_sample.py``,
scalar-prefetched slot index): the stale ⟨Z, ∇Z⟩ rows are read once, in
storage precision, straight into the cosine/threshold/cotangent pass —
no full-precision entry copy is ever materialized in HBM.  The fp32
fused path is bit-identical to materialize-then-weight (the golden traces
run it); ``cache_fused=False`` pins the materializing reference.

The whole round is ONE jitted function (exchange + ``lax.scan`` over local
steps) so XLA's latency-hiding scheduler can overlap the cross-party
transfer with the local-update chain — the SPMD analogue of the paper's
background communication worker.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, NamedTuple, Optional, \
    Sequence, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import CELUConfig, validate_pipeline_depth
from ..optim import Optimizer, apply_updates
from .rows import RowTables, compact
from .weighting import (instance_weights, pipeline_attenuation,
                        static_staleness, xi_to_cos)
from .workset import (CastLeaf, Quant4Leaf, QuantLeaf, decode_entry,
                      workset_draw, workset_entry, workset_init,
                      workset_insert,
                      workset_sample)  # noqa: F401  (workset_sample re-exported: historical import site)

# Stable ``jax.named_scope`` names of the round's stages, and of the parts
# inside them.  Every operation of a round sits under exactly one stage;
# a profile reader (``bench/stages.py``) charges device time to these.
# Scopes only write location metadata: the compiled device code is the
# same with or without them.  (A Pallas kernel's serialized body keeps its
# locations, so a round holding one gets a new persistent-cache key.)
EXCHANGE_COMPUTE = "exchange_compute"
EXCHANGE_APPLY = "exchange_apply"
LOCAL_SCAN = "local_scan"
STAGE_SCOPES = (EXCHANGE_COMPUTE, EXCHANGE_APPLY, LOCAL_SCAN)
OPTIMIZER = "optimizer"             # opt.update + apply_updates
WORKSET_INSERT = "workset_insert"   # the fresh exchange into the ring
WORKSET_DRAW = "workset_draw"       # a local update's slot from the ring
LOCAL_GRAD = "local_grad"           # a local update's weighted gradient
LEAF_SCOPES = (OPTIMIZER, WORKSET_INSERT, WORKSET_DRAW, LOCAL_GRAD)


class KPartyTask(NamedTuple):
    """K-party split-model interface (information-flow discipline at
    function granularity — no function sees two parties' raw features):

        forward_a(params_a_i, batch_a_i) -> Z_i
        loss_b(params_b, [Z_1..Z_K], batch_b) -> (per-instance loss, aux)

    ``row_tables`` (optional) declares each party's field-indexed
    embedding tables (``core.rows``): with an optimizer that has a row
    update, the rounds differentiate and step only the rows each batch
    touches.

    ``counted``: ``forward_a`` returns ``(Z_i, counts)`` and ``loss_b``
    ``(per-instance loss, aux, counts)``, ``counts`` a dict of int32
    scalars (``expert_rows``: the (token, slot) assignments a model's held
    experts computed).  The round's metrics sum each over every forward
    pass the round makes (``COUNTERS``)."""
    forward_a: Callable[[Any, Any], jnp.ndarray]
    loss_b: Callable[[Any, Sequence[jnp.ndarray], Any],
                     Tuple[jnp.ndarray, jnp.ndarray]]
    row_tables: Optional[RowTables] = None
    counted: bool = False


# Metrics that a round sums over its steps and passes, not averages.
COUNTERS = ("rows_updated", "expert_rows")


def lift_two_party(task) -> KPartyTask:
    """Adapt a two-party task (``loss_b`` over one Z_A) to the K-party
    interface (``loss_b`` over ``[Z_1..Z_K]``, K=1)."""
    return KPartyTask(
        task.forward_a,
        lambda pb, z_list, batch_b: task.loss_b(pb, z_list[0], batch_b),
        task.row_tables, task.counted)


def _add_counts(*cs):
    """Sum of counters dicts (``None``: a pass that counts nothing)."""
    cs = [c for c in cs if c]
    return {k: sum(c[k] for c in cs) for k in cs[0]} if cs else {}


def lift_two_party_params(params: Dict[str, Any]) -> Dict[str, Any]:
    """{"a": pa, "b": pb} -> the engine's {"a": [pa], "b": pb}."""
    return {"a": [params["a"]], "b": params["b"]}


def unlift_params(params: Dict[str, Any]) -> Dict[str, Any]:
    """Engine {"a": [pa], "b": pb} -> the two-party {"a": pa, "b": pb}."""
    (pa,) = params["a"]
    return {"a": pa, "b": params["b"]}


# --------------------------------------------------------------------------
# Transports
# --------------------------------------------------------------------------
class SimWANTransport:
    """In-process slow link: each released message is round-tripped through
    the wire dtype (simulating quantized transmission) after optional
    DP noising; byte accounting follows the wire precision.

    The noised + quantized value is what BOTH sides see and what gets
    cached, so local updates reuse already-released messages at no extra
    privacy cost."""

    def __init__(self, celu: CELUConfig):
        self.celu = celu
        self.wire = jnp.dtype(celu.wire_dtype)

    @property
    def stateful_directions(self):
        """Directions ("up"/"down") whose per-round state must exist in
        ``state["transport"]`` (none: this transport is stateless)."""
        return ()

    def init_state(self, z_examples: Sequence) -> Dict[str, Any]:
        """Per-round transport state (empty: this transport is stateless)."""
        return {}

    def _wire_cast(self, x):
        """Round-trip through the wire dtype (the simulated quantized
        transmission).  A separate method so every send path shares one
        wire stage — and so the static auditor
        (:mod:`repro.analysis`) can mark exactly this op as the
        registered wire crossing."""
        if x.dtype != self.wire:
            x = x.astype(self.wire).astype(x.dtype)
        return x

    def send(self, rng, x, res=None, direction: str = "up"):
        """The message actually released across the link.  ``res`` is the
        per-message error-feedback residual (unused here — threaded through
        for stateful transports).  -> (wire value, new residual)."""
        if self.celu.dp_sigma > 0.0:
            from .privacy import DPConfig, privatize
            x = privatize(rng, x, DPConfig(clip=self.celu.dp_clip,
                                           sigma=self.celu.dp_sigma))
        return self._wire_cast(x), res

    def message_bytes(self, z_shape) -> int:
        import numpy as np
        return int(np.prod(z_shape)) * self.wire.itemsize

    def uplink_bytes(self, z_shape) -> int:
        """Bytes of one released Z_i (feature party -> label party)."""
        return self.message_bytes(z_shape)

    def downlink_bytes(self, z_shape) -> int:
        """Bytes of one released ∇Z_i (label party -> feature party)."""
        return self.message_bytes(z_shape)

    def round_bytes(self, z_shapes: Sequence) -> int:
        """Bytes per communication round: the message count is explicit —
        one uplink (Z_i) plus one downlink (∇Z_i) per feature party —
        so transports with asymmetric up/down payloads account correctly."""
        return sum(self.uplink_bytes(s) + self.downlink_bytes(s)
                   for s in z_shapes)

    def recover_dropped(self, fresh: Dict[str, Any]) -> Dict[str, Any]:
        """Transport state to resume from when ``fresh``'s wire transfer
        is LOST (the chaos engine abandons an exchange after its retry
        budget).  A stateless transport has nothing to recover — the
        update the dropped messages carried is simply gone (graceful
        degradation: the local scan keeps running on cached statistics).
        Stateful transports override this to fold the lost messages back
        into their error-feedback residuals."""
        return fresh["tstate"]


class CompressedWANTransport(SimWANTransport):
    """Compressed wire (Compressed-VFL): every released message passes the
    SimWAN pipeline (DP noise + wire dtype) and then a per-direction codec
    from :mod:`repro.core.compression` under error feedback.

    Lossy directions carry one residual per feature party in the engine's
    ``state["transport"]`` (``{"up": [r_1..r_K], "down": [...]}`` — built
    by :meth:`init_state`); each send compresses ``x + r`` and keeps the
    compression error as the next round's residual.  With the identity
    codec the pipeline is bit-identical to plain :class:`SimWANTransport`
    and no residual state is kept."""

    def __init__(self, celu: CELUConfig, up_codec=None, down_codec=None):
        super().__init__(celu)
        from .compression import IdentityCodec
        up = up_codec if up_codec is not None else IdentityCodec()
        self.codecs = {"up": up,
                       "down": down_codec if down_codec is not None else up}

    @property
    def stateful_directions(self):
        return tuple(d for d, c in self.codecs.items() if not c.lossless)

    def init_state(self, z_examples: Sequence) -> Dict[str, Any]:
        """Zero error-feedback residuals, one per party per lossy
        direction; ``z_examples`` are the K cut-tensor avals."""
        return {d: [jnp.zeros(z.shape, jnp.float32) for z in z_examples]
                for d in self.stateful_directions}

    def send(self, rng, x, res=None, direction: str = "up"):
        codec = self.codecs[direction]
        exact = getattr(codec, "exact", False)
        if self.celu.dp_sigma > 0.0 and not exact:
            # DP over a LOSSY codec: the noise must ride the ENCODED
            # value, not the pre-compression one.  Noising before encode
            # would (a) spend wire bits and top-k slots on transmitting
            # noise and (b) leak the noise into the error-feedback
            # residual, whose next-round retransmission CANCELS it —
            # error feedback would silently undo the privacy mechanism.
            # So: clip -> wire cast -> +residual -> encode/decode ->
            # noise-free residual -> Gaussian noise on the decoded wire
            # value.  The residual never sees (and never repays) the
            # noise; sensitivity is still dp_clip because clipping
            # happens before everything the other party observes.
            from .privacy import DPConfig, clip_rows, wire_noise
            cfg = DPConfig(clip=self.celu.dp_clip,
                           sigma=self.celu.dp_sigma)
            xc = self._wire_cast(clip_rows(x, cfg.clip))
            e = xc.astype(jnp.float32)
            if res is not None:
                e = e + res
            payload = codec.encode(jax.random.fold_in(rng, 1), e)
            y = codec.decode(payload, e)
            new_res = None if res is None else e - y
            y = wire_noise(jax.random.fold_in(rng, 2), y, cfg)
            return y.astype(x.dtype), new_res
        x, _ = super().send(rng, x, None, direction)
        if exact:
            # bitwise round-trip (identity): nothing to encode — this is
            # what keeps the identity wire golden-trace-identical to
            # SimWANTransport.  Merely-lossless codecs (fp32-rounding
            # round-trips like a chain ending in identity) still run
            # encode/decode so the wire matches the byte accounting.
            return x, res
        e = x.astype(jnp.float32)
        if res is not None:
            e = e + res
        payload = codec.encode(jax.random.fold_in(rng, 1), e)
        y = codec.decode(payload, e)
        return y.astype(x.dtype), None if res is None else e - y

    def uplink_bytes(self, z_shape) -> int:
        return self.codecs["up"].wire_bytes(z_shape, self.wire)

    def downlink_bytes(self, z_shape) -> int:
        return self.codecs["down"].wire_bytes(z_shape, self.wire)

    def recover_dropped(self, fresh: Dict[str, Any]) -> Dict[str, Any]:
        """Error-feedback recovery of a LOST exchange: fold each dropped
        decoded message back into its direction's residual.

        The send computed ``y = decode(encode(x + r))`` and carried
        ``r' = (x + r) - y`` forward; if ``y`` never arrives, setting
        ``r'' = r' + y = x + r`` makes the NEXT successful send transmit
        the accumulated ``x + r`` in full — the telescoping invariant
        (decoded messages sum to the uncompressed signal) survives the
        drop as a delay instead of a loss.  Under DP the dropped ``y``
        includes its noise draw, so the recovered residual carries that
        noise into the next release — conservative (the eventually
        delivered value is noisier than required), never under-noised,
        and the dropped noise was never observed so no budget is
        double-spent.  Lossless directions keep no residual and degrade
        like the stateless base."""
        ts = dict(fresh["tstate"])
        for d in self.stateful_directions:
            vals = fresh["zs"] if d == "up" else fresh["dzs"]
            ts[d] = [r + v.astype(jnp.float32)
                     for r, v in zip(ts[d], vals)]
        return ts

    def scheduled(self, loss) -> "CompressedWANTransport":
        """Host-side control plane: offer one (smoothed) loss observation
        to each direction codec's adaptive hook (e.g. the top-k
        ``ratio_schedule``).  Returns ``self`` when nothing fired, else a
        new transport around the re-ratioed codecs — rebuild the jitted
        round with it; the error-feedback residuals in the round state are
        dense and carry over unchanged."""
        # consult each DISTINCT codec once: with a symmetric wire both
        # directions alias one codec object, and double-consulting would
        # halve the schedule's patience and let the directions diverge
        seen: Dict[int, Any] = {}
        for c in self.codecs.values():
            if id(c) not in seen:
                seen[id(c)] = c.scheduled(loss) if hasattr(c, "scheduled") \
                    else c
        new = {d: seen[id(c)] for d, c in self.codecs.items()}
        if all(new[d] is self.codecs[d] for d in self.codecs):
            return self
        return CompressedWANTransport(self.celu, new["up"], new["down"])


def make_transport(celu: CELUConfig, compression: Optional[str] = None):
    """Transport factory for the simulated WAN.  ``compression`` (falling
    back to ``celu.compression``) is a codec spec from
    ``core.compression.CODEC_SPECS``; empty -> plain SimWANTransport."""
    name = celu.compression if compression is None else compression
    if not name:
        return SimWANTransport(celu)
    from .compression import make_codec_pair
    up, down = make_codec_pair(name)
    return CompressedWANTransport(celu, up, down)


class PodTransport:
    """Cut-tensor exchange as ``lax.ppermute`` over the pod mesh axis (the
    ONLY collectives crossing the slow inter-pod link).  Party A lives on
    pod 0, Party B on pod 1 by default."""

    def __init__(self, axis: str = "pod",
                 up: Sequence[Tuple[int, int]] = ((0, 1), (1, 0)),
                 down: Sequence[Tuple[int, int]] = ((1, 0), (0, 1))):
        self.axis = axis
        self.up = [tuple(p) for p in up]
        self.down = [tuple(p) for p in down]

    def send_up(self, z):
        """Z_A: feature pod -> label pod."""
        return jax.lax.ppermute(z, self.axis, self.up)

    def send_down(self, dz):
        """∇Z_A: label pod -> feature pod."""
        return jax.lax.ppermute(dz, self.axis, self.down)


# --------------------------------------------------------------------------
# Algorithm-2 weighting (the shared hot path)
# --------------------------------------------------------------------------
def _bcast(w, like):
    """(B,) weights -> broadcastable to ``like``'s shape."""
    return w.reshape(w.shape + (1,) * (like.ndim - 1)).astype(jnp.float32)


def _fusable(x) -> bool:
    """The Pallas kernel tiles the batch dim at BLOCK_B; odd batch sizes
    fall back to the reference composition."""
    from ..kernels.cosine_weight import BLOCK_B
    B = x.shape[0]
    return B % min(BLOCK_B, B) == 0


def staleness_weights(ad_hoc, stale, cos_xi: float, *,
                      fused: bool = False) -> jnp.ndarray:
    """Algorithm-2 ``InsWeight``: per-instance cosine floored at cos ξ.

    NOTE: the pipeline-staleness discount is NOT applied here — callers
    that need it (``local_grad_b`` after its K-party minimum,
    ``weighted_cotangent`` for the feature-party path) apply
    :func:`repro.core.weighting.pipeline_attenuation` exactly once."""
    if fused and _fusable(ad_hoc):
        from ..kernels import ops as kops
        return kops.cosine_weight(ad_hoc, stale, cos_xi)
    return instance_weights(ad_hoc, stale, cos_xi)


def _attenuate_post_scale(w, cot, staleness):
    """Compose the depth-s pipeline discount onto a fused kernel's
    (w, w ⊙ ∇Z): -> (w^(1+s), w^s ⊙ (w ⊙ ∇Z)) — the same law as
    :func:`repro.core.weighting.pipeline_attenuation`, applied so the
    discounted weight still multiplies the cotangent exactly once.

    ``staleness`` may be a static Python int (depths 0/1 — 0 skips the
    post-scale entirely, preserving the golden-pinned bitstream) or a jnp
    int scalar: the depth-D queue's PER-SLOT offset, traced through the
    jitted scan.  The dynamic path always applies the scale — ``w ** 0``
    is exactly 1 (also at w = 0), so runtime s = 0 is still the
    identity."""
    if static_staleness(staleness) and not staleness:
        return w, cot
    extra = w ** staleness
    w = w * extra
    cot = cot * _bcast(extra, cot)
    return w, cot


def weighted_cotangent(ad_hoc, stale, dz, cos_xi: float, *,
                       fused: bool = True, pipeline_staleness=0
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """InsWeight + weights ⊙ ∇Z -> (weights (B,), fp32 weighted cotangent).

    ``fused=True`` runs the single-VMEM-pass Pallas kernel; the reference
    composition is its bit-exact oracle.  ``pipeline_staleness`` (static
    int or a traced per-slot jnp scalar) composes with the fused kernel as
    a cheap post-scale (see :func:`_attenuate_post_scale`)."""
    if fused and _fusable(ad_hoc):
        from ..kernels import ops as kops
        w, cot = kops.weighted_cotangent(ad_hoc, stale,
                                         dz.astype(jnp.float32), cos_xi)
        return _attenuate_post_scale(w, cot, pipeline_staleness)
    w = instance_weights(ad_hoc, stale, cos_xi)
    w = pipeline_attenuation(w, pipeline_staleness)
    return w, _bcast(w, dz) * dz.astype(jnp.float32)


# --------------------------------------------------------------------------
# Local-update gradients (Algorithm 2) — shared by every protocol shape
# --------------------------------------------------------------------------
def _grad_a_tail(z_new, vjp, stale_z, stale_dz, cos_xi: float, *,
                 weighting: bool, fused: bool, mask,
                 pipeline_staleness):
    """Shared tail of the feature-party local update once the stale
    statistics are materialized: InsWeight + cotangent scale + backward."""
    if weighting:
        w, cot = weighted_cotangent(z_new, stale_z, stale_dz, cos_xi,
                                    fused=fused,
                                    pipeline_staleness=pipeline_staleness)
    else:
        w = jnp.ones((z_new.shape[0],), jnp.float32)
        cot = _bcast(w, z_new) * stale_dz.astype(jnp.float32)
    if mask is not None:
        w = w * mask
        cot = cot * mask
    (g,) = vjp(cot.astype(z_new.dtype))
    return g, w


def local_grad_a(forward_a, params_a, entry, cos_xi: float, *,
                 weighting: bool = True, fused: bool = True, mask=None,
                 pipeline_staleness=0):
    """Feature-party local update: ad-hoc forward on the cached batch,
    stale cotangent ∇Z^(i) weighted by cos(Z^(i,j), Z^(i)).

    ``entry`` is a workset row {"z": stale Z, "dz": stale ∇Z, "batch": own
    features}.  ``mask`` (scalar 0/1, optional) zeroes the whole draw (a
    round-robin bubble).  Returns (grads, weights)."""
    z_new, vjp = jax.vjp(lambda p: forward_a(p, entry["batch"]), params_a)
    return _grad_a_tail(z_new, vjp, entry["z"], entry["dz"], cos_xi,
                        weighting=weighting, fused=fused, mask=mask,
                        pipeline_staleness=pipeline_staleness)


def _ring_view(store):
    """Storage leaf -> the raw full-precision-or-bf16 ring array (QuantLeaf
    handled separately by the q8 kernel)."""
    return store.v if isinstance(store, CastLeaf) else store


def _fused_ring_sample(slot, z_new, z_store, dz_store, cos_xi: float):
    """One-VMEM-pass sample: gather slot from the (possibly quantized)
    ring, dequantize, row-cosine vs the ad-hoc z, threshold, scale the
    stale cotangent.  -> (weights (B,), fp32 weighted cotangent)."""
    from ..kernels import ops as kops
    if isinstance(z_store, Quant4Leaf):
        return kops.fused_gather_weight_q4(
            slot, z_new.astype(jnp.float32), z_store.q, z_store.scale,
            dz_store.q, dz_store.scale, cos_xi)
    if isinstance(z_store, QuantLeaf):
        return kops.fused_gather_weight_q8(
            slot, z_new.astype(jnp.float32), z_store.q, z_store.scale,
            dz_store.q, dz_store.scale, cos_xi)
    return kops.fused_gather_weight(slot, z_new, _ring_view(z_store),
                                    _ring_view(dz_store), cos_xi)


def local_grad_a_cached(forward_a, params_a, ws, slot, cos_xi: float, *,
                        weighting: bool = True, fused: bool = True,
                        cache_fused: bool = True, mask=None,
                        pipeline_staleness=0, tables=None, counted=False):
    """Feature-party local update straight off the workset ring — the
    single-pass hot path.  Only the party's OWN cached features are
    gathered (the forward needs them); the cut statistics ⟨Z, ∇Z⟩ are
    consumed by the fused gather→dequant→weight megakernel
    (``kernels/fused_sample.py``) without ever materializing a
    full-precision entry copy in HBM.  ``cache_fused=False`` (or an
    unfusable batch tiling, or ``weighting``/``fused`` off) falls back to
    materialize-then-weight — the bit-exact reference composition.
    Returns (grads, weights); with ``tables`` (the party's declared
    ``core.rows.Tables``) the gradient is taken on the compact tables of
    the slot's ids, and (grads, weights, (rows, n)) carries
    ``core.rows.compact``'s rows tree and count.  ``counted``: the
    forward returns (Z, counts), and the counts end the result."""
    buf = ws["buf"]
    batch = jax.tree_util.tree_map(lambda b: b[slot], buf["batch"])
    params_a, batch, rows, n = compact(params_a, batch, tables)
    fwd = forward_a if counted else (lambda p, b: (forward_a(p, b), None))
    z_new, vjp, counts = jax.vjp(lambda p: fwd(p, batch), params_a,
                                 has_aux=True)
    if weighting and fused and cache_fused and _fusable(z_new):
        w, cot = _fused_ring_sample(slot, z_new, buf["z"], buf["dz"],
                                    cos_xi)
        w, cot = _attenuate_post_scale(w, cot, pipeline_staleness)
        if mask is not None:
            w = w * mask
            cot = cot * mask
        (g,) = vjp(cot.astype(z_new.dtype))
    else:
        entry = workset_entry(ws, slot)
        g, w = _grad_a_tail(z_new, vjp, entry["z"], entry["dz"], cos_xi,
                            weighting=weighting, fused=fused, mask=mask,
                            pipeline_staleness=pipeline_staleness)
    out = (g, w) if tables is None else (g, w, (rows, n))
    return out + (counts,) if counted else out


def local_grad_b(loss_b, params_b, entry, cos_xi: float, *,
                 weighting: bool = True, fused: bool = True, mask=None,
                 pipeline_staleness=0):
    """Label-party local update: stale Z_i's + ad-hoc own features; the
    ad-hoc ∇Z_i^(i,j) is computed only to measure staleness (paper
    footnote 2), then the weighted per-instance losses drive the backward
    pass.  K>1 composes conservatively: the instance weight is the MINIMUM
    cosine over parties (the pipeline discount is applied once, after the
    minimum).  Returns (grads, weights)."""
    zs, dzs, batch_b = entry["z"], entry["dz"], entry["batch"]
    if weighting:
        dz_new = jax.grad(
            lambda zl: jnp.mean(loss_b(params_b, zl, batch_b)[0]))(
            [z.astype(jnp.float32) for z in zs])
        w = staleness_weights(dz_new[0], dzs[0], cos_xi, fused=fused)
        for i in range(1, len(zs)):
            w = jnp.minimum(
                w, staleness_weights(dz_new[i], dzs[i], cos_xi, fused=fused))
        w = pipeline_attenuation(w, pipeline_staleness)
    else:
        w = jnp.ones((zs[0].shape[0],), jnp.float32)
    if mask is not None:
        w = w * mask

    def weighted(p):
        li, aux = loss_b(p, zs, batch_b)
        return jnp.mean(w * li) + aux

    g = jax.grad(weighted)(params_b)
    return g, w


def _fused_ring_weights(slot, dz_new, dz_store, cos_xi: float):
    """Weights-only fused sample for Party B: gather the slot's stale
    ∇Z_i straight from the (possibly quantized) ring and row-cosine it
    against the ad-hoc derivative in one VMEM pass.  Reuses the sample
    megakernel with the ∇Z ring in both operand positions — the weight
    output is bit-identical to ``cosine_weight`` over the materialized
    row (same reduction order, same blocks); the cotangent output rides
    along unused."""
    from ..kernels import ops as kops
    if isinstance(dz_store, Quant4Leaf):
        w, _ = kops.fused_gather_weight_q4(
            slot, dz_new.astype(jnp.float32), dz_store.q, dz_store.scale,
            dz_store.q, dz_store.scale, cos_xi)
        return w
    if isinstance(dz_store, QuantLeaf):
        w, _ = kops.fused_gather_weight_q8(
            slot, dz_new.astype(jnp.float32), dz_store.q, dz_store.scale,
            dz_store.q, dz_store.scale, cos_xi)
        return w
    ring = _ring_view(dz_store)
    w, _ = kops.fused_gather_weight(slot, dz_new, ring, ring, cos_xi)
    return w


def local_grad_b_cached(loss_b, params_b, ws, slot, cos_xi: float, *,
                        weighting: bool = True, fused: bool = True,
                        cache_fused: bool = True, mask=None,
                        pipeline_staleness=0, tables=None, counted=False):
    """Label-party local update straight off the workset ring.  The loss
    CONSUMES the decoded Z list, so the K ``z`` entries must still be
    materialized — but the K ``dz`` entries' only consumer is the
    Algorithm-2 cosine, so the fused path reads them in storage precision
    through the gather→dequant→weight megakernel and never materializes
    the decoded ∇Z list in HBM.  ``cache_fused=False`` (or an unfusable
    batch tiling, or ``weighting``/``fused`` off) falls back to
    materialize-then-weight — the bit-exact reference composition.
    Returns (grads, weights), or with ``tables`` (grads on the compact
    tables, weights, (rows, n)) as :func:`local_grad_a_cached` does;
    ``counted``: the loss returns counts as its third value, and the
    counts of its passes end the result."""
    buf = ws["buf"]
    batch_b = jax.tree_util.tree_map(lambda b: b[slot], buf["batch"])
    params_b, batch_b, rows, n = compact(params_b, batch_b, tables)
    zs = decode_entry(jax.tree_util.tree_map(lambda b: b[slot], buf["z"]))
    K = len(zs)

    def parts(p, zl):
        out = loss_b(p, zl, batch_b)
        return out if counted else out + (None,)

    c_dz = None
    if weighting:
        def adhoc(zl):
            li, _, c = parts(params_b, zl)
            return jnp.mean(li), c
        dz_new, c_dz = jax.grad(adhoc, has_aux=True)(
            [z.astype(jnp.float32) for z in zs])
        if fused and cache_fused and _fusable(dz_new[0]):
            w = _fused_ring_weights(slot, dz_new[0], buf["dz"][0], cos_xi)
            for i in range(1, K):
                w = jnp.minimum(w, _fused_ring_weights(
                    slot, dz_new[i], buf["dz"][i], cos_xi))
        else:
            dzs = decode_entry(jax.tree_util.tree_map(
                lambda b: b[slot], buf["dz"]))
            w = staleness_weights(dz_new[0], dzs[0], cos_xi, fused=fused)
            for i in range(1, K):
                w = jnp.minimum(w, staleness_weights(
                    dz_new[i], dzs[i], cos_xi, fused=fused))
        w = pipeline_attenuation(w, pipeline_staleness)
    else:
        w = jnp.ones((zs[0].shape[0],), jnp.float32)
    if mask is not None:
        w = w * mask

    def weighted(p):
        li, aux, c = parts(p, zs)
        return jnp.mean(w * li) + aux, c

    g, c = jax.grad(weighted, has_aux=True)(params_b)
    out = (g, w) if tables is None else (g, w, (rows, n))
    return out + (_add_counts(c_dz, c),) if counted else out


# --------------------------------------------------------------------------
# State
# --------------------------------------------------------------------------
def init_state(task: KPartyTask, params: Dict[str, Any], opt: Optimizer,
               celu: CELUConfig, batches_a: Sequence[Any], batch_b,
               transport=None, compression: Optional[str] = None):
    """Build the K-party training state.

    ``params = {"a": [pa_1..pa_K], "b": pb}``; ``batches_a`` are K example
    batches (abstract ok) used to size the workset ring buffers.
    ``transport``/``compression`` must mirror what :func:`make_round` gets
    (both default to :func:`make_transport` over ``celu``): the transport
    sizes the per-direction error-feedback residuals carried in
    ``state["transport"]`` (empty for stateless transports)."""
    K = len(params["a"])
    zs = [jax.eval_shape(task.forward_a, params["a"][i], batches_a[i])
          for i in range(K)]
    if task.counted:
        zs = [z for z, _ in zs]
    z_like = [jnp.zeros(z.shape, z.dtype) for z in zs]
    ws_a = [workset_init(celu.W, {"z": z_like[i], "dz": z_like[i],
                                  "batch": batches_a[i]},
                         cache_dtype=celu.cache_dtype)
            for i in range(K)]
    ws_b = workset_init(celu.W, {"z": list(z_like), "dz": list(z_like),
                                 "batch": batch_b},
                        cache_dtype=celu.cache_dtype)
    return {
        "params": {"a": list(params["a"]), "b": params["b"]},
        "opt": {"a": [opt.init(p) for p in params["a"]],
                "b": opt.init(params["b"])},
        "ws": {"a": ws_a, "b": ws_b},
        "steps": {"a": [jnp.int32(0) for _ in range(K)], "b": jnp.int32(0)},
        "comm_rounds": jnp.int32(0),
        "transport": (transport if transport is not None
                      else make_transport(celu, compression)
                      ).init_state(z_like),
    }


# --------------------------------------------------------------------------
# The two round stages (exchange / local updates) — shared by the
# sequential round and the pipelined scheduler
# --------------------------------------------------------------------------
def _make_stages(task: KPartyTask, opt: Optimizer, celu: CELUConfig, *,
                 n_local: int, tp, fused: bool, pipeline_staleness=0,
                 lr_damping: float = 0.0, cos_xi=None, rng_keys=None):
    """Build the round's two first-class stages over the shared state
    layout:

      * ``exchange_compute(params, tstate, batches_a, batch_b,
        comm_rounds)`` — everything the paper's background communication
        worker does WITHOUT mutating training state: party forward passes,
        transport send up (Z_i) and down (∇Z_i), Party B's loss, and all
        fresh gradients.  Returns the in-flight exchange payload (wire
        values + gradients + updated transport residuals) — the
        double-buffered workset slot the pipeline carries while round t's
        local updates run.
      * ``exchange_apply(state, fresh, batches_a, batch_b, batch_idx)`` —
        merge an in-flight exchange into the round state: optimizer steps
        from the fresh gradients, workset inserts, counters, transport
        residual adoption.
      * ``local_scan(state)`` — the R staleness-weighted local updates per
        party sampled from the workset (Algorithm 2).

    :func:`make_round` composes compute -> apply -> scan inside ONE jit
    (today's sequential semantics, golden-trace pinned);
    :class:`PipelinedEngine` jits each stage separately so round t+1's
    exchange can be dispatched while round t's local scan runs.

    ``pipeline_staleness`` (the scheduler's depth) tightens the workset
    validity window and attenuates Algorithm-2 instance weights: under a
    depth-D pipeline every cached entry is D exchanges older (relative to
    the params it is used against) than the sequential schedule would make
    it.  Both ``local_scan`` and ``exchange_apply`` additionally accept an
    optional traced ``staleness`` scalar — the depth-D queue's PER-SLOT
    offset (in-flight count at scan time / merged exchange's age), which
    overrides the static depth so warmup and drain phases are charged
    their actual staleness, not the steady-state bound.  When a dynamic
    staleness is supplied and ``lr_damping`` (the ``c`` of the
    ``eta / (1 + c*s)`` schedule) is positive, the optimizer updates that
    stage produces are damped accordingly — the FedBCD-style guard that
    keeps the sub-linear rate as queued staleness grows.  Depths 0/1 never
    pass a dynamic staleness, so their golden-pinned numerics are
    untouched.

    ``cos_xi`` and ``rng_keys`` widen the stages to per-job TRACED
    hyper-parameters for the vmapped fleet runner (``repro.fleet``):
    ``cos_xi`` overrides the Algorithm-2 threshold (default: the static
    ``xi_to_cos(celu.xi_degrees)``, bit-for-bit the historical constant)
    and ``rng_keys`` is a ``{"exchange", "insert", "draw"}`` dict of PRNG
    keys replacing the engine's fixed bases — a job with the default keys
    reproduces the scalar engine's rng chain exactly, a job with
    seed-folded keys draws an independent stream.  Both may be tracers
    (closed over during a jit/vmap trace of the caller).

    Row path: where the task declares its embedding tables
    (``task.row_tables``) and ``opt`` has a row update
    (``opt.update_rows``), each party's gradient is taken on compact
    tables of its batch's distinct ids (``core.rows.compact``) and its
    optimizer steps write those rows only; the exchange payload carries
    the ids (``fresh["rows"]``), and the metrics count the (field, id)
    entries the steps wrote (``rows_updated``).  Otherwise every leaf
    takes the dense step."""
    if cos_xi is None:
        cos_xi = xi_to_cos(celu.xi_degrees)
    if rng_keys is None:
        rng_keys = {"exchange": jax.random.PRNGKey(17),
                    "insert": jax.random.PRNGKey(0xCE1),
                    "draw": jax.random.PRNGKey(29)}
    s_pipe = int(pipeline_staleness)
    uniform = celu.sampling == "uniform"
    rt = task.row_tables
    if rt is None or opt.update_rows is None:
        rt = RowTables()
    tab_a, tab_b = rt
    row_path = tab_a is not None or tab_b is not None
    counted = task.counted
    # forward_a -> (Z, counts); loss_b -> (loss, aux, counts); counts None
    # for a task that counts nothing
    fwd_a = task.forward_a if counted else \
        (lambda p, b: (task.forward_a(p, b), None))
    loss_b = task.loss_b if counted else \
        (lambda p, zl, b: task.loss_b(p, zl, b) + (None,))

    def _step(g, ostate, p, rows, scale):
        """One party's optimizer step -> (params, opt state): the row
        update where ``rows`` names tables, else the dense one (its
        update times ``scale`` when given)."""
        if rows is not None:
            return opt.update_rows(g, ostate, p, rows, scale)
        upd, ostate = opt.update(g, ostate, p)
        if scale is not None:
            upd = jax.tree_util.tree_map(lambda u: u * scale, upd)
        return apply_updates(p, upd), ostate

    def _damp(staleness):
        """1 / (1 + c*s) update scale; None when the static path (or a
        zero coefficient) should leave the updates untouched."""
        if staleness is None or lr_damping <= 0.0:
            return None
        return jnp.float32(1.0) / (
            1.0 + jnp.float32(lr_damping)
            * jnp.asarray(staleness).astype(jnp.float32))

    @jax.named_scope(EXCHANGE_COMPUTE)
    def exchange_compute(params, tstate, batches_a, batch_b, comm_rounds):
        pas, pb = params["a"], params["b"]
        K = len(pas)
        rng = jax.random.fold_in(rng_keys["exchange"], comm_rounds)
        keys = jax.random.split(rng, 2 * K)
        missing = [d for d in getattr(tp, "stateful_directions", ())
                   if d not in tstate]
        if missing:
            raise ValueError(
                f"transport keeps error-feedback residuals for "
                f"{missing} but the round state has none — pass the same "
                f"transport (or compression spec) to init_state")
        up_res = list(tstate["up"]) if "up" in tstate else [None] * K
        down_res = list(tstate["down"]) if "down" in tstate else [None] * K

        # uplinks: every A_i's forward -> Z_i, released in wire precision
        zs, vjps, rows_a, counts = [], [], [], []
        for i in range(K):
            pa, ba, rows, n = compact(pas[i], batches_a[i], tab_a)
            z, vjp, c = jax.vjp(lambda p, ba=ba: fwd_a(p, ba), pa,
                                has_aux=True)
            z, up_res[i] = tp.send(keys[2 * i], z, up_res[i], "up")
            zs.append(z)
            vjps.append(vjp)
            rows_a.append((rows, n))
            counts.append(c)

        # Party B: loss + grads wrt (params_b, all Z_i); ∇Z_i are downlinks
        pb, bb, rows_b, n_b = compact(pb, batch_b, tab_b)

        def mean_loss(p, z_list):
            li, aux, c = loss_b(p, z_list, bb)
            return jnp.mean(li) + aux, c
        (loss, c), (g_b, dzs) = jax.value_and_grad(
            mean_loss, argnums=(0, 1), has_aux=True)(pb, zs)
        counts.append(c)
        dzs = list(dzs)
        for i in range(K):
            dzs[i], down_res[i] = tp.send(keys[2 * i + 1], dzs[i],
                                          down_res[i], "down")
        new_tstate = dict(tstate)
        if "up" in tstate:
            new_tstate["up"] = up_res
        if "down" in tstate:
            new_tstate["down"] = down_res

        # every A_i's backward with its (wire-precision) cotangent
        g_as = [vjps[i](dzs[i].astype(zs[i].dtype))[0] for i in range(K)]
        fresh = {"zs": zs, "dzs": dzs, "g_as": g_as, "g_b": g_b,
                 "loss": loss, "tstate": new_tstate}
        if row_path:
            fresh["rows"] = {"a": [r for r, _ in rows_a], "b": rows_b,
                             "n": _count([n for _, n in rows_a] + [n_b])}
        if counted:
            fresh["counts"] = _add_counts(*counts)
        return fresh

    @jax.named_scope(EXCHANGE_APPLY)
    def exchange_apply(state, fresh, batches_a, batch_b, batch_idx,
                       staleness=None):
        pas, pb = state["params"]["a"], state["params"]["b"]
        K = len(pas)
        zs, dzs = fresh["zs"], fresh["dzs"]
        rows = fresh.get("rows", {"a": [None] * K, "b": None})
        damp = _damp(staleness)
        new_pas, new_oas = [], []
        with jax.named_scope(OPTIMIZER):
            for i in range(K):
                pa, oa = _step(fresh["g_as"][i], state["opt"]["a"][i],
                               pas[i], rows["a"][i], damp)
                new_pas.append(pa)
                new_oas.append(oa)
            if rows["b"] is not None:
                new_pb, ob = _step(fresh["g_b"], state["opt"]["b"], pb,
                                   rows["b"], damp)
            else:
                upd_b, ob = opt.update(fresh["g_b"], state["opt"]["b"], pb)
                if damp is not None:
                    upd_b = jax.tree_util.tree_map(lambda u: u * damp,
                                                   upd_b)

        with jax.named_scope(WORKSET_INSERT):
            # rounding noise for quantized-at-rest caches (unused — and
            # DCE'd — by the fp32 table); per-party keys keep the SR noise
            # independent
            ins_rng = jax.random.fold_in(rng_keys["insert"],
                                         state["comm_rounds"])
            ws_a = [workset_insert(state["ws"]["a"][i],
                                   {"z": zs[i], "dz": dzs[i],
                                    "batch": batches_a[i]}, batch_idx,
                                   rng=jax.random.fold_in(ins_rng, i))
                    for i in range(K)]
            ws_b = workset_insert(state["ws"]["b"],
                                  {"z": zs, "dz": dzs, "batch": batch_b},
                                  batch_idx,
                                  rng=jax.random.fold_in(ins_rng, K))
        if rows["b"] is None:
            # traced after the inserts, as it always was: the program's
            # operation order stays as it is
            with jax.named_scope(OPTIMIZER):
                new_pb = apply_updates(pb, upd_b)
        new_state = {
            "params": {"a": new_pas, "b": new_pb},
            "opt": {"a": new_oas, "b": ob},
            "ws": {"a": ws_a, "b": ws_b},
            "steps": {"a": [s + 1 for s in state["steps"]["a"]],
                      "b": state["steps"]["b"] + 1},
            "comm_rounds": state["comm_rounds"] + 1,
            "transport": fresh["tstate"],
        }
        m = {"loss": fresh["loss"], **fresh.get("counts", {})}
        if "rows" in fresh:
            m["rows_updated"] = fresh["rows"]["n"]
        return new_state, m

    @jax.named_scope(LOCAL_SCAN)
    def local_scan(state, staleness=None, party_mask=None):
        # ``party_mask`` ((K+1,) float32 — a_0..a_{K-1}, b; None = all
        # live) freezes a dropped-out party's local updates: its draw's
        # valid factor is multiplied by the mask, zeroing the weights,
        # the cotangent, and the optimizer update while the surviving
        # parties keep local-updating off their cached statistics.  The
        # masked party's ring clocks still tick (use_count, cursor) — a
        # conservative choice that drains its cache at the same rate as
        # everyone else's, so rejoin never resurrects over-aged entries.
        K = len(state["params"]["a"])
        if n_local == 0:
            zero = jnp.float32(0.0)
            return state, {"local_steps": jnp.int32(0), "w_mean": zero,
                           "w_zero_frac": zero}

        s_loc = s_pipe if staleness is None else staleness
        damp = _damp(staleness)
        scale = jnp.float32(1.0 / (K + 1))
        comm_rounds = state["comm_rounds"]
        draw_base = rng_keys["draw"]
        if staleness is not None:
            # the depth-D queue can run several scans at the SAME
            # comm_rounds (warmup: no merges yet; manual local() calls
            # between merges) — fold the per-slot staleness in so their
            # uniform draws stay independent.  (comm_rounds, s) is unique
            # per scan under every supported schedule; the static path
            # keeps the historical key chain bit-for-bit.
            draw_base = jax.random.fold_in(draw_base, s_loc)

        def body(carry, _):
            if uniform:
                pas, oas, wsas, nas, pb, ob, wsb, nb, j = carry
                draw_key = jax.random.fold_in(
                    jax.random.fold_in(draw_base, comm_rounds), j)
            else:
                pas, oas, wsas, nas, pb, ob, wsb, nb = carry
                draw_key = None
            pas, oas, wsas, nas = list(pas), list(oas), list(wsas), list(nas)
            w_means, w_zeros, counts, passes = [], [], [], []
            for i in range(K):
                with jax.named_scope(WORKSET_DRAW):
                    ki = None if draw_key is None \
                        else jax.random.fold_in(draw_key, i)
                    wsas[i], slot, _, valid = workset_draw(
                        wsas[i], celu.R, celu.sampling, rng=ki,
                        pipeline_staleness=s_loc)
                    vf = valid.astype(jnp.float32)
                    if party_mask is not None:
                        vf = vf * party_mask[i]
                with jax.named_scope(LOCAL_GRAD):
                    g, w, *rows = local_grad_a_cached(
                        task.forward_a, pas[i], wsas[i], slot, cos_xi,
                        weighting=celu.weighting, fused=fused,
                        cache_fused=celu.cache_fused, mask=vf,
                        pipeline_staleness=s_loc, tables=tab_a,
                        counted=counted)
                    passes.append(rows.pop() if counted else None)
                    rows, n = rows[0] if rows else (None, None)
                with jax.named_scope(OPTIMIZER):
                    uf = vf if damp is None else vf * damp
                    pas[i], oas[i] = _step(g, oas[i], pas[i], rows, uf)
                counts.append(n)
                nas[i] = nas[i] + (valid.astype(jnp.int32)
                                   if party_mask is None
                                   else (vf > 0).astype(jnp.int32))
                w_means.append(jnp.mean(w))
                w_zeros.append(jnp.mean(w == 0.0))

            with jax.named_scope(WORKSET_DRAW):
                kb = None if draw_key is None \
                    else jax.random.fold_in(draw_key, K)
                wsb, slot_b, _, valid = workset_draw(
                    wsb, celu.R, celu.sampling, rng=kb,
                    pipeline_staleness=s_loc)
                vf = valid.astype(jnp.float32)
                if party_mask is not None:
                    vf = vf * party_mask[K]
            with jax.named_scope(LOCAL_GRAD):
                g, w, *rows = local_grad_b_cached(
                    task.loss_b, pb, wsb, slot_b, cos_xi,
                    weighting=celu.weighting, fused=fused,
                    cache_fused=celu.cache_fused, mask=vf,
                    pipeline_staleness=s_loc, tables=tab_b,
                    counted=counted)
                passes.append(rows.pop() if counted else None)
                rows, n = rows[0] if rows else (None, None)
            with jax.named_scope(OPTIMIZER):
                uf = vf if damp is None else vf * damp
                pb, ob = _step(g, ob, pb, rows, uf)
            counts.append(n)
            nb = nb + (valid.astype(jnp.int32) if party_mask is None
                       else (vf > 0).astype(jnp.int32))
            w_means.append(jnp.mean(w))
            w_zeros.append(jnp.mean(w == 0.0))

            lm = {"w_mean": sum(w_means) * scale,
                  "w_zero_frac": sum(w_zeros) * scale}
            if row_path:
                lm["rows_updated"] = _count(counts)
            lm.update(_add_counts(*passes))
            carry = (pas, oas, wsas, nas, pb, ob, wsb, nb)
            if uniform:
                carry = carry + (j + 1,)
            return carry, lm

        init = (state["params"]["a"], state["opt"]["a"], state["ws"]["a"],
                [jnp.int32(0) for _ in range(K)],
                state["params"]["b"], state["opt"]["b"], state["ws"]["b"],
                jnp.int32(0))
        if uniform:
            init = init + (jnp.int32(0),)
        out, lm = jax.lax.scan(body, init, None, length=n_local)
        pas, oas, wsas, nas, pb, ob, wsb, nb = out[:8]
        state = {
            "params": {"a": pas, "b": pb},
            "opt": {"a": oas, "b": ob},
            "ws": {"a": wsas, "b": wsb},
            "steps": {"a": [s + n for s, n in zip(state["steps"]["a"], nas)],
                      "b": state["steps"]["b"] + nb},
            "comm_rounds": state["comm_rounds"],
            "transport": state["transport"],
        }
        out = {"local_steps": sum(nas) + nb,
               "w_mean": jnp.mean(lm["w_mean"]),
               "w_zero_frac": jnp.mean(lm["w_zero_frac"])}
        out.update({k: jnp.sum(lm[k]) for k in COUNTERS if k in lm})
        return state, out

    return exchange_compute, exchange_apply, local_scan


def _count(ns):
    """Sum of the parties' row counts (None: a party without tables)."""
    return sum(n for n in ns if n is not None)


def merge_metrics(m, lm):
    """A round's metrics from its exchange's (``m``) and its local
    scan's (``lm``); their ``COUNTERS`` add up."""
    out = {**m, **lm}
    for k in COUNTERS:
        if k in m and k in lm:
            with jax.named_scope(LOCAL_SCAN), jax.named_scope(OPTIMIZER):
                out[k] = m[k] + lm[k]
    return out


# --------------------------------------------------------------------------
# One full communication round (exchange + R local updates per party)
# --------------------------------------------------------------------------
def make_round(task: KPartyTask, opt: Optimizer, celu: CELUConfig, *,
               local_steps: int = -1, transport=None,
               compression: Optional[str] = None,
               fused_weighting: bool = True, jit: bool = True,
               donate: bool = False):
    """fn(state, batches_a: list, batch_b, batch_idx) -> (state, metrics).

    ``local_steps`` defaults to R (steady state: one fresh insert funds R
    uses); Vanilla training = ``local_steps=0``.  ``transport`` defaults to
    :func:`make_transport` over ``celu`` — i.e. :class:`SimWANTransport`
    unless ``compression`` (or ``celu.compression``) names a wire codec.

    This is the SEQUENTIAL schedule: the exchange stage and the local-update
    scan run back-to-back inside one jit (XLA may still hide some latency,
    but the simulated WAN stall serializes with compute).  For the paper's
    two-worker overlap, build the same stages through
    :func:`make_pipeline` / :class:`PipelinedEngine` instead."""
    n_local = celu.R if local_steps < 0 else local_steps
    tp = transport if transport is not None \
        else make_transport(celu, compression)
    exchange_compute, exchange_apply, local_scan = _make_stages(
        task, opt, celu, n_local=n_local, tp=tp, fused=fused_weighting)

    def round_fn(state, batches_a, batch_b, batch_idx):
        fresh = exchange_compute(state["params"], state.get("transport", {}),
                                 batches_a, batch_b, state["comm_rounds"])
        state, m = exchange_apply(state, fresh, batches_a, batch_b,
                                  batch_idx)
        state, lm = local_scan(state)
        return state, merge_metrics(m, lm)

    if jit:
        return jax.jit(round_fn, donate_argnums=(0,) if donate else ())
    return round_fn


# --------------------------------------------------------------------------
# The pipelined scheduler (paper §4.1 Fig. 4, generalized to a D-deep
# exchange queue)
# --------------------------------------------------------------------------
class PendingExchange(NamedTuple):
    """An in-flight exchange: one slot of the scheduler's exchange queue.

    ``fresh`` is ``exchange_compute``'s payload — wire-precision ⟨Z_i, ∇Z_i⟩
    (the statistics that will be inserted), the fresh gradients, Party B's
    loss, and the updated transport error-feedback residuals (in flight
    with the exchange: they are not adopted into the round state until the
    merge).  The batches ride along because the deferred workset insert
    needs each party's own features.  ``dispatched_at`` records
    ``comm_rounds`` (merges completed) at dispatch time — the merge uses
    it to charge the fresh gradients their actual per-slot staleness
    (``comm_rounds_at_merge - dispatched_at``, = D-1 at steady state)."""
    fresh: Dict[str, Any]
    batches_a: Sequence[Any]
    batch_b: Any
    batch_idx: Any
    dispatched_at: Any = None


class RoundState(NamedTuple):
    """Typed round state shared by the pipeline stages.

    The first six fields mirror the engine's state dict (the canonical
    wire format of :func:`init_state` — convert with :meth:`from_state` /
    :meth:`as_state`); ``pending`` is the scheduler's exchange queue: the
    in-flight :class:`PendingExchange` slots, oldest first (at most
    ``max(depth, 1)`` deep; a 1-tuple is the paper's double buffer,
    ``()`` means no exchange is in flight)."""
    params: Dict[str, Any]
    opt: Dict[str, Any]
    ws: Dict[str, Any]
    steps: Dict[str, Any]
    comm_rounds: Any
    transport: Dict[str, Any]
    pending: Tuple[PendingExchange, ...] = ()

    @classmethod
    def from_state(cls, state: Dict[str, Any],
                   pending: Tuple[PendingExchange, ...] = ()
                   ) -> "RoundState":
        return cls(params=state["params"], opt=state["opt"],
                   ws=state["ws"], steps=state["steps"],
                   comm_rounds=state["comm_rounds"],
                   transport=state.get("transport", {}), pending=pending)

    def as_state(self) -> Dict[str, Any]:
        return {"params": self.params, "opt": self.opt, "ws": self.ws,
                "steps": self.steps, "comm_rounds": self.comm_rounds,
                "transport": self.transport}


def fresh_counters(fresh) -> Tuple[str, ...]:
    """The ``COUNTERS`` an exchange payload's merge reports."""
    return (("rows_updated",) if "rows" in fresh else ()) \
        + tuple(fresh.get("counts", {}))


def _zero_local_metrics(counters: Sequence[str] = ()):
    zero = jnp.float32(0.0)
    out = {"local_steps": jnp.int32(0), "w_mean": zero, "w_zero_frac": zero}
    out.update({k: jnp.int32(0) for k in counters})
    return out


def _flush_metrics(scans, merged=()):
    """A drain's metrics: its local scans' steps summed and weight
    statistics averaged; its ``COUNTERS`` sum the scans' and the
    ``merged`` exchanges' counts."""
    n = len(scans)
    out = {"local_steps": sum(s["local_steps"] for s in scans),
           "w_mean": sum(s["w_mean"] for s in scans) / n,
           "w_zero_frac": sum(s["w_zero_frac"] for s in scans) / n}
    for k in COUNTERS:
        if k in scans[0]:
            out[k] = sum(s[k] for s in list(scans) + list(merged))
    return out


class PipelinedEngine:
    """Explicitly staged round scheduler: the paper's two-worker pipeline,
    generalized to a depth-D exchange queue.

    Depth 0 runs the stages sequentially — dispatch, merge, local scan —
    and is bit-identical to :func:`make_round`'s fused round on the golden
    traces.  Depth 1 dispatches round t+1's exchange and runs round t's
    local scan while it is in flight:

        dispatch(batch t+1)   # exchange_compute — async, never blocked on
        local()               # round t's R local updates (the overlap)
        merge()               # adopt the arrived exchange: opt step + insert

    Depth D >= 2 keeps a ring of up to D in-flight exchanges
    (``rs.pending``, oldest first) for the high-RTT regime where one
    exchange cannot hide behind one local scan: each step dispatches a new
    exchange, runs the local scan with the whole queue in flight, and
    merges the OLDEST exchange once the queue is full — so an exchange
    rides the wire for D local scans before its statistics land.  The
    first D-1 steps only fill the queue (no merge: their metrics carry a
    NaN ``loss``), and :meth:`flush` drains the remaining in-flight
    exchanges, alternating scan/merge so every inserted batch still gets
    its local scan.

    On the host-sim path the overlap is real at the dispatch level — the
    three stages are separate jits and nothing calls
    ``jax.block_until_ready`` between them, so XLA's async dispatch queues
    the exchange behind no host barrier while the local scan is enqueued;
    the simulated WAN clock (``repro.launch.wan.WANClock``) charges the
    D-deep ``max`` schedule per round instead of the sum.  The pipeline's
    cost is staleness, and it is accounted PER SLOT at depth >= 2: the
    local scan is passed the live in-flight count (= D at steady state,
    smaller during warmup/drain) as a traced staleness scalar — it
    tightens the workset validity window (``workset_draw``), attenuates
    the Algorithm-2 weights ``w -> w^(1+s)``
    (:func:`repro.core.weighting.pipeline_attenuation`, fused-kernel
    post-scale included), and damps the local optimizer steps by
    ``1 / (1 + c*s)`` (``CELUConfig.pipeline_lr_damping``); the merge
    charges the fresh gradients their own slot age
    (``comm_rounds - dispatched_at``).  Depths 0/1 keep the historical
    static plumbing, bit-for-bit.

    Drive it as::

        pe = make_pipeline(task, opt, celu, depth=2)
        rs = pe.init(engine.init_state(...))
        for t, (bi, ba, bb) in enumerate(batches):
            rs, m = pe.step(rs, ba, bb, bi)
        rs, m = pe.flush(rs)          # drain the in-flight queue
        state = pe.finalize(rs)
    """

    def __init__(self, task: KPartyTask, opt: Optimizer, celu: CELUConfig,
                 *, depth: Optional[int] = None, local_steps: int = -1,
                 transport=None, compression: Optional[str] = None,
                 fused_weighting: bool = True, jit: bool = True,
                 dynamic_staleness: Optional[bool] = None):
        if depth is None:
            depth = celu.pipeline_depth
        # same rule, same message as CELUConfig.__post_init__ — an
        # explicit depth= override must not bypass the capacity check
        validate_pipeline_depth(depth, celu.W)
        self.depth = depth
        self.celu = celu
        # depth >= 2 threads the PER-SLOT staleness dynamically (warmup
        # and drain see their true, smaller offsets); depths 0/1 keep the
        # static golden-pinned plumbing.  ``dynamic_staleness=True``
        # forces the dynamic path at ANY depth — the chaos engine needs
        # it to charge fault-induced extra age even at depths 0/1
        # (core/faults.py; a ``FaultPlan=None`` chaos engine keeps the
        # default so the no-fault schedule stays golden-identical).
        self.dynamic = (depth >= 2) if dynamic_staleness is None \
            else bool(dynamic_staleness)
        n_local = celu.R if local_steps < 0 else local_steps
        self.n_local = n_local
        tp = transport if transport is not None \
            else make_transport(celu, compression)
        self.transport = tp
        compute, apply_, scan = _make_stages(
            task, opt, celu, n_local=n_local, tp=tp, fused=fused_weighting,
            pipeline_staleness=depth,
            lr_damping=celu.pipeline_lr_damping if self.dynamic else 0.0)
        wrap = jax.jit if jit else (lambda f: f)
        self._compute = wrap(compute)
        self._apply = wrap(apply_)
        self._scan = wrap(scan)

    @property
    def queue_capacity(self) -> int:
        """Max in-flight exchanges (depth 0 still buffers the one exchange
        between its dispatch and its immediate merge)."""
        return max(self.depth, 1)

    # ---- stages ----------------------------------------------------------
    def init(self, state: Dict[str, Any]) -> RoundState:
        """Adopt an :func:`init_state` dict into the scheduler's state."""
        return RoundState.from_state(state)

    def dispatch(self, rs: RoundState, batches_a, batch_b,
                 batch_idx) -> RoundState:
        """Start a new exchange (the background worker): compute the wire
        statistics and fresh gradients from the CURRENT params.  Does not
        block — the result is appended to the ``rs.pending`` queue until
        its :meth:`merge`."""
        if len(rs.pending) >= self.queue_capacity:
            raise RuntimeError(
                f"{len(rs.pending)} exchange(s) already in flight — the "
                f"depth-{self.depth} queue holds at most "
                f"{self.queue_capacity}; merge() the oldest before "
                f"dispatching another")
        # The error-feedback residual chain follows DISPATCH order (the
        # encoder runs at dispatch), so a new exchange must start from the
        # newest in-flight exchange's transport state, not the
        # merged-prefix state in rs.transport — otherwise the D-1
        # intervening residual updates would be silently dropped and the
        # telescoping invariant broken.  Empty queue (depths 0/1) reduces
        # to rs.transport — golden-pinned.
        tstate = rs.pending[-1].fresh["tstate"] if rs.pending \
            else rs.transport
        # rng folds over the DISPATCH sequence number (merges completed +
        # in-flight count), not comm_rounds alone: during warmup several
        # exchanges are dispatched before the first merge advances the
        # round counter, and they must not share wire noise.
        fresh = self._compute(rs.params, tstate, batches_a, batch_b,
                              rs.comm_rounds + len(rs.pending))
        pe = PendingExchange(fresh, batches_a, batch_b, batch_idx,
                             dispatched_at=rs.comm_rounds)
        return rs._replace(pending=rs.pending + (pe,))

    def local(self, rs: RoundState, *, staleness=None, party_mask=None
              ) -> Tuple[RoundState, Dict[str, Any]]:
        """Run the R staleness-weighted local updates (the foreground
        worker) against the workset as of the last merged exchange.  At
        depth >= 2 the scan is charged the CURRENT in-flight count as its
        per-slot staleness.  ``staleness`` overrides that charge and
        ``party_mask`` ((K+1,) floats) freezes dropped-out parties — both
        are the chaos scheduler's hooks and need the dynamic stage
        plumbing."""
        if staleness is not None or party_mask is not None:
            if not self.dynamic:
                raise RuntimeError(
                    "staleness/party_mask overrides need the dynamic "
                    "stage plumbing — build the engine with "
                    "dynamic_staleness=True")
            s = jnp.int32(len(rs.pending)) if staleness is None \
                else jnp.int32(staleness)
            state, lm = self._scan(rs.as_state(), s, party_mask)
        elif self.dynamic:
            state, lm = self._scan(rs.as_state(),
                                   jnp.int32(len(rs.pending)))
        else:
            state, lm = self._scan(rs.as_state())
        return RoundState.from_state(state, rs.pending), lm

    def merge(self, rs: RoundState, *, staleness=None
              ) -> Tuple[RoundState, Dict[str, Any]]:
        """Adopt the OLDEST in-flight exchange: fresh optimizer steps
        (applied to the params as they are NOW — after any overlapped
        local updates, lr-damped by the slot's age at depth >= 2), workset
        inserts, transport residuals, counters.  ``staleness`` overrides
        the slot-age charge (the chaos scheduler passes the true
        scheduler-round age, which exceeds ``comm_rounds - dispatched_at``
        when merges were missed to faults)."""
        if not rs.pending:
            raise RuntimeError("no exchange in flight — dispatch() first")
        p, rest = rs.pending[0], rs.pending[1:]
        if staleness is not None and not self.dynamic:
            raise RuntimeError(
                "staleness override needs the dynamic stage plumbing — "
                "build the engine with dynamic_staleness=True")
        if self.dynamic:
            s = (rs.comm_rounds - p.dispatched_at) if staleness is None \
                else jnp.int32(staleness)
            state, m = self._apply(rs.as_state(), p.fresh, p.batches_a,
                                   p.batch_b, p.batch_idx, s)
        else:
            state, m = self._apply(rs.as_state(), p.fresh, p.batches_a,
                                   p.batch_b, p.batch_idx)
        return RoundState.from_state(state, rest), m

    # ---- schedules -------------------------------------------------------
    def step(self, rs: RoundState, batches_a, batch_b, batch_idx
             ) -> Tuple[RoundState, Dict[str, Any]]:
        """One communication round.  Depth 0: exchange then local scan
        (sequential).  Depth 1: the local scan of the PREVIOUS round runs
        between this round's dispatch and merge — its WAN exchange is in
        flight the whole time.  Depth D >= 2: dispatch, scan with the full
        queue in flight, then merge the oldest exchange once the queue
        holds D (the first D-1 steps only fill the queue and report a NaN
        ``loss``)."""
        rs = self.dispatch(rs, batches_a, batch_b, batch_idx)
        if self.depth == 0:
            rs, m = self.merge(rs)
            rs, lm = self.local(rs)
        elif self.depth == 1:
            rs, lm = self.local(rs)
            rs, m = self.merge(rs)
        else:
            rs, lm = self.local(rs)
            if len(rs.pending) == self.depth:
                rs, m = self.merge(rs)
            else:
                m = {"loss": jnp.float32(jnp.nan)}   # warmup: queue filling
        return rs, merge_metrics(m, lm)

    def flush(self, rs: RoundState) -> Tuple[RoundState, Dict[str, Any]]:
        """Drain the pipeline.  Depth 0 is a no-op; depth 1 runs the one
        local scan the last merge still owes.  Depth >= 2 alternates
        scan/merge until the queue is empty (per-slot staleness decaying
        as it drains), then scans once more over the final inserts."""
        if self.depth == 0:
            return rs, _zero_local_metrics()
        if self.depth == 1:
            return self.local(rs)
        scans, merged = [], []
        while rs.pending:
            rs, lm = self.local(rs)
            scans.append(lm)
            rs, m = self.merge(rs)
            merged.append(m)
        rs, lm = self.local(rs)
        scans.append(lm)
        return rs, _flush_metrics(scans, merged)

    def finalize(self, rs: RoundState) -> Dict[str, Any]:
        """Back to the engine's canonical state dict."""
        if rs.pending:
            raise RuntimeError(
                f"{len(rs.pending)} exchange(s) still in flight — merge() "
                f"(or flush()) or drop them before finalizing")
        return rs.as_state()


def make_pipeline(task: KPartyTask, opt: Optimizer, celu: CELUConfig, *,
                  depth: Optional[int] = None, local_steps: int = -1,
                  transport=None, compression: Optional[str] = None,
                  fused_weighting: bool = True,
                  jit: bool = True) -> PipelinedEngine:
    """Build the staged round scheduler.  ``depth`` defaults to
    ``celu.pipeline_depth``; depth 0 reproduces :func:`make_round`'s
    sequential semantics bit-for-bit, depth 1 overlaps round t+1's WAN
    exchange with round t's local updates (paper §4.1), and depth D >= 2
    keeps a D-deep queue of in-flight exchanges with per-slot
    staleness-aware damping (see :class:`PipelinedEngine`).  ``depth``
    must stay < ``celu.W`` — the ring cannot serve a deeper queue."""
    return PipelinedEngine(task, opt, celu, depth=depth,
                           local_steps=local_steps, transport=transport,
                           compression=compression,
                           fused_weighting=fused_weighting, jit=jit)


# --------------------------------------------------------------------------
# Named protocol presets (the paper's three competitors)
# --------------------------------------------------------------------------
def preset_config(name: str, base: CELUConfig) -> Tuple[CELUConfig, int]:
    """-> (celu_cfg, local_steps) for name in {vanilla, fedbcd, celu}."""
    if name == "vanilla":
        return dataclasses.replace(base, weighting=False), 0
    if name == "fedbcd":
        return dataclasses.replace(base, W=1, weighting=False,
                                   sampling="consecutive"), base.R
    if name == "celu":
        return base, base.R
    raise ValueError(name)


# --------------------------------------------------------------------------
# SPMD party-to-pod round (PodTransport over the pod mesh axis)
# --------------------------------------------------------------------------
def make_pod_round(mesh, opt: Optimizer, *, R: int, cos_xi: float,
                   weighting: bool = True, tower_fwd=None, top_loss=None,
                   transport: Optional[PodTransport] = None,
                   fused_weighting: bool = False,
                   pipeline_depth: int = 0):
    """Build the jitted multi-pod CELU round (party p's weights live on
    pod p; the exchange is the transport's ppermute pair).

    ``tower_fwd(tower_params, x) -> Z`` and
    ``top_loss(top_params, z_a, z_b, y) -> per-instance loss`` define the
    party-stacked model (see ``core.pod_protocol`` for the WDL demo).

    ``pipeline_depth=1`` is the ppermute-overlapped schedule (paper §4.1's
    two-worker pipeline on the pod path): the round issues the up-permute,
    then runs the R local updates against the PREVIOUS rounds' workset and
    the dispatch-time params — the scan has no data dependency on the
    in-flight collective, so the XLA/Mosaic scheduler overlaps the slow
    inter-pod DCN transfer with the local compute — and only then consumes
    the permuted cut tensors (fresh update + insert, applied to the
    post-scan params).  Depth 0 is the sequential schedule (exchange,
    insert, then the scan over the just-updated workset) — bit-identical
    to the historical pod round.

    State pytree (all party-stacked, party axis over ``pod``):
      params:   {"tower": (2,...), "top": (2,...)}
      opt:      accumulators, same structure
      ws:       workset ring buffers (2, W, B_local, ...) — per-party caches
    Batch: x (2, B, F) int32 — party p's features on pod p;
           y (2, B) — labels valid on party 1's slot only.
    """
    from jax.sharding import PartitionSpec as P

    assert tower_fwd is not None and top_loss is not None
    if pipeline_depth not in (0, 1):
        raise ValueError(
            f"make_pod_round supports pipeline_depth 0 or 1 (got "
            f"{pipeline_depth}): the D-deep exchange queue is scheduled "
            f"on the HOST — PipelinedEngine keeps the in-flight "
            f"PendingExchange slots in ``rs.pending`` between three "
            f"separately jitted stage calls, and the pod round is ONE "
            f"jitted SPMD program with no host in the loop to carry that "
            f"queue.  A depth-D pod schedule needs the device-side "
            f"ppermute-chained queue tracked in ROADMAP.md "
            f"('Mosaic/pod — the real-TPU milestone').  Use "
            f"make_pipeline/PipelinedEngine for D >= 2, or depth 1 here "
            f"(the compiler-overlapped two-worker schedule).")
    tp = transport if transport is not None else PodTransport()
    fused = fused_weighting

    def b_loss(pb, z_list, batch):
        """Party B's towers as a K-party loss_b over pb={"top","tower"}."""
        z_b = tower_fwd(pb["tower"], batch["x"])
        return top_loss(pb["top"], z_list[0], z_b, batch["y"]), \
            jnp.float32(0.0)

    def exchange_and_local(params, opt_state, ws, x, y):
        """Runs per-pod (inside shard_map, pod axis size 2).

        Shapes here are the PER-POD view: params leaves (1, ...), x (1,B,F).
        The stages carry the engine's scope names, so a pod trace reads
        like a one-chip one.
        """
        with jax.named_scope(EXCHANGE_COMPUTE):
            pod = jax.lax.axis_index(tp.axis)
            tower = jax.tree_util.tree_map(lambda a: a[0], params["tower"])
            top = jax.tree_util.tree_map(lambda a: a[0], params["top"])
            xb = x[0]                                   # (B, F)
            yb = y[0]                                   # (B,)

        # ---- R local updates, round-robin over the given workset ---------
        @jax.named_scope(LOCAL_SCAN)
        def local_scan(params, opt_state, ws):
            W = ws["z"].shape[1]

            def local_step(carry, j):
                params, opt_state, cursor = carry
                with jax.named_scope(WORKSET_DRAW):
                    t = ws["time"][0]
                    n_alive = jnp.minimum(t, W)
                    slot_j = jnp.mod(cursor, jnp.maximum(n_alive, 1))
                    # decode the at-rest ring precision (bf16 cache
                    # upcasts; the fp32 ring is untouched — bit-identical)
                    zs = ws["z"][0, slot_j].astype(jnp.float32)
                    dzs = ws["dz"][0, slot_j].astype(jnp.float32)
                    xs = ws["x"][0, slot_j]
                    ys_ = ws["y"][0, slot_j]
                with jax.named_scope(LOCAL_GRAD):
                    tower_j = jax.tree_util.tree_map(lambda a: a[0],
                                                     params["tower"])
                    top_j = jax.tree_util.tree_map(lambda a: a[0],
                                                   params["top"])

                    # Party A: ad-hoc forward, cosine vs stale Z, weighted
                    # stale ∇Z
                    g_tower_a, _ = local_grad_a(
                        tower_fwd, tower_j,
                        {"z": zs, "dz": dzs, "batch": xs},
                        cos_xi, weighting=weighting, fused=fused,
                        pipeline_staleness=pipeline_depth)

                    # Party B: stale Z_A + ad-hoc own tower; weight by ∇Z_A
                    # cosine
                    g_b, _ = local_grad_b(
                        b_loss, {"top": top_j, "tower": tower_j},
                        {"z": [zs], "dz": [dzs],
                         "batch": {"x": xs, "y": ys_}},
                        cos_xi, weighting=weighting, fused=fused,
                        pipeline_staleness=pipeline_depth)
                    g_top_b, g_tower_b = g_b["top"], g_b["tower"]

                    is_a_ = (pod == 0)
                    g_tower_sel = jax.tree_util.tree_map(
                        lambda ga, gb: jnp.where(is_a_, ga, gb)[None],
                        g_tower_a, g_tower_b)
                    g_top_sel = jax.tree_util.tree_map(
                        lambda g: jnp.where(is_a_, 0.0, g)[None], g_top_b)
                    grads_j = {"tower": g_tower_sel, "top": g_top_sel}
                with jax.named_scope(OPTIMIZER):
                    upd_j, opt_state = opt.update(grads_j, opt_state,
                                                  params)
                    params = apply_updates(params, upd_j)
                return (params, opt_state, cursor + 1), None

            (params, opt_state, _), _ = jax.lax.scan(
                local_step, (params, opt_state, jnp.int32(0)), None,
                length=R)
            return params, opt_state

        # ---- fresh exchange (the paper's communication worker) ----------
        with jax.named_scope(EXCHANGE_COMPUTE):
            z_mine, tower_vjp = jax.vjp(lambda tpm: tower_fwd(tpm, xb),
                                        tower)
            # Z_A: pod0 -> pod1 (pod0 receives pod1's Z_B slot, unused)
            z_a_at_b = tp.send_up(z_mine)                # on pod 1: Z_A

        if pipeline_depth:
            # Overlap window: the scan reads only the dispatch-time params
            # and the PREVIOUS rounds' workset, so it has no dependency on
            # the in-flight ppermute — the compiler is free to run the DCN
            # transfer and the R local updates concurrently.  The fresh
            # gradients below are still taken at the dispatch-time params
            # (that is the pipeline's gradient staleness) and applied to
            # the post-scan params when the stats "arrive".
            params, opt_state = local_scan(params, opt_state, ws)

        with jax.named_scope(EXCHANGE_COMPUTE):
            def loss_fn(top_p, z_a):
                return jnp.mean(top_loss(top_p, z_a, z_mine, yb))
            (loss, (g_top, dz_a)) = (loss_fn(top, z_a_at_b),
                                     jax.grad(loss_fn, argnums=(0, 1))(
                                         top, z_a_at_b))
            # ∇Z_A: pod1 -> pod0 (the symmetric permute)
            dz_back = tp.send_down(dz_a)

            is_a = (pod == 0)
            # Party A's tower cotangent is the received ∇Z_A; Party B's is
            # its local ∂loss/∂Z_B.  Both computed, selected by pod id.
            dz_b_local = jax.grad(
                lambda z_b: jnp.mean(top_loss(top, z_a_at_b, z_b, yb)))(
                    z_mine)
            cot = jnp.where(is_a, dz_back, dz_b_local)
            (g_tower,) = tower_vjp(cot)
            g_top = jax.tree_util.tree_map(
                lambda g: jnp.where(is_a, 0.0, g), g_top)

        # ---- update + insert into the device-resident workset -----------
        with jax.named_scope(EXCHANGE_APPLY), jax.named_scope(OPTIMIZER):
            grads = {
                "tower": jax.tree_util.tree_map(lambda g: g[None], g_tower),
                "top": jax.tree_util.tree_map(lambda g: g[None], g_top)}
            upd, opt_state = opt.update(grads, opt_state, params)
            params = apply_updates(params, upd)

        with jax.named_scope(EXCHANGE_APPLY), \
                jax.named_scope(WORKSET_INSERT):
            W = ws["z"].shape[1]
            slot = jnp.mod(ws["time"][0], W)
            ws = dict(ws)
            # cache: stale z (own Z for A's weighting / Z_A for B), stale
            # dz, own features (+ labels at B)
            z_cache = jnp.where(is_a, z_mine, z_a_at_b)
            dz_cache = jnp.where(is_a, dz_back, dz_a)
            ws["z"] = jax.lax.dynamic_update_index_in_dim(
                ws["z"], z_cache[None].astype(ws["z"].dtype), slot, 1)
            ws["dz"] = jax.lax.dynamic_update_index_in_dim(
                ws["dz"], dz_cache[None].astype(ws["dz"].dtype), slot, 1)
            ws["x"] = jax.lax.dynamic_update_index_in_dim(
                ws["x"], xb[None], slot, 1)
            ws["y"] = jax.lax.dynamic_update_index_in_dim(
                ws["y"], yb[None], slot, 1)
            ws["time"] = ws["time"] + 1

        if not pipeline_depth:
            # sequential schedule: the scan runs after the insert, over the
            # just-refreshed workset and post-exchange params
            params, opt_state = local_scan(params, opt_state, ws)
        with jax.named_scope(EXCHANGE_COMPUTE):
            loss = loss[None]
        return params, opt_state, ws, loss

    pp = P(tp.axis)  # every party-stacked leaf shards dim0 over pod
    fn = jax.shard_map(
        exchange_and_local, mesh=mesh,
        in_specs=(pp, pp, pp, pp, pp),
        out_specs=(pp, pp, pp, pp),
        check_vma=False)
    return jax.jit(fn)
