"""Architecture / shape / run configuration dataclasses.

Every assigned architecture gets one module in this package exporting
``CONFIG: ArchConfig`` with the exact assigned hyper-parameters (citation in
``source``).  ``ArchConfig.reduced()`` produces the CPU-smoke variant
(<=2 layers, d_model<=512, <=4 experts) of the same family.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Tuple


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


@dataclass(frozen=True)
class MoEConfig:
    n_experts: int           # the router's outputs: every expert routed over
    top_k: int
    n_shared: int = 0        # shared (always-on) experts, llama4-style
    router_aux_coef: float = 0.01   # Switch-style load-balance loss; 0: none
    # "ep" shards the expert dim over the model axis (all-to-all dispatch),
    # "tp" shards each expert's FFN over the model axis (no all-to-all).
    sharding: str = "tp"
    d_expert: int = 0        # an expert's FFN width; 0 -> ArchConfig.d_ff
    # "softmax": gates are the softmax of the top-k logits; "sigmoid":
    # s = sigmoid(logits), top-k of s + expert bias (selection only),
    # gates s[top-k] normalised over the top-k
    router: str = "softmax"
    # The experts this chip holds: ``n_held`` of them (0 -> all) from
    # ``held_offset`` on.  Every token is routed over all ``n_experts``;
    # the layer computes the held experts' part of the output.
    n_held: int = 0
    held_offset: int = 0

    @property
    def held(self) -> int:
        return self.n_held or self.n_experts


@dataclass(frozen=True)
class SSMConfig:
    """Mamba-style selective SSM (used by hybrid archs)."""
    state_dim: int = 16
    conv_dim: int = 4
    expand: int = 2


@dataclass(frozen=True)
class XLSTMConfig:
    """xLSTM block layout: sLSTM at layer indices i % slstm_every == 0."""
    slstm_every: int = 4
    conv_dim: int = 4


@dataclass(frozen=True)
class VFLConfig:
    """How the backbone is split across the two parties (see DESIGN §3)."""
    layers_a: int            # Party A bottom tower depth
    layers_b: int            # Party B bottom tower depth
    layers_top: int          # Party B top tower depth (+ head)
    fusion: str = "add"      # add | cross_attn
    z_dim: int = 0           # dim of the exchanged Z_A; 0 -> d_model


@dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str              # dense | moe | hybrid | ssm | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0        # 0 -> d_model // n_heads
    source: str = ""

    # family extras
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    xlstm: Optional[XLSTMConfig] = None
    cross_attn_every: int = 0      # vlm: every k-th layer cross-attends
    enc_layers: int = 0            # audio: encoder depth (Party A tower)
    qkv_bias: bool = False         # qwen-style attention bias
    qk_norm: bool = False          # per-head RMSNorm on q and k before RoPE

    # Per-layer kinds (lfm2-style hybrids), in published order: each layer's
    # mixer ("conv" = gated short convolution, "full_attention") from
    # ``layer_types``, and its FFN: dense for the first ``n_dense_layers``,
    # the MoE after them.  Empty: every layer is the family's block.
    layer_types: Tuple[str, ...] = ()
    n_dense_layers: int = 0

    # attention window; 0 = full causal.  long_500k configs override this.
    sliding_window: int = 0

    # modality frontends (stubs; see DESIGN §5)
    n_patches: int = 0             # vlm: patch tokens from the vision stub
    d_frontend: int = 0            # vlm/audio: stub embedding dim
    audio_downsample: int = 4      # audio: frames = seq_len // downsample

    aux_vocab_size: int = 65536    # Party A token stream vocab (text archs)
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    vfl: Optional[VFLConfig] = None

    # ---- derived ----
    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def padded_vocab(self) -> int:
        return _round_up(self.vocab_size, 256)

    @property
    def vfl_split(self) -> VFLConfig:
        if self.vfl is not None:
            return self.vfl
        if self.family == "vlm":
            # Party A = vision owner; bottom_A is the projector, all decoder
            # layers belong to Party B; top = last quarter.
            lt = max(1, self.n_layers // 4)
            return VFLConfig(layers_a=0, layers_b=self.n_layers - lt,
                             layers_top=lt, fusion="cross_attn")
        if self.family == "audio":
            lt = max(1, self.n_layers // 4)
            return VFLConfig(layers_a=self.enc_layers,
                             layers_b=self.n_layers - lt, layers_top=lt,
                             fusion="cross_attn")
        la = max(1, self.n_layers // 4)
        lt = max(1, self.n_layers // 4)
        return VFLConfig(layers_a=la, layers_b=self.n_layers - la - lt,
                         layers_top=lt, fusion="add")

    def with_sliding_window(self, window: int) -> "ArchConfig":
        return dataclasses.replace(self, sliding_window=window)

    def reduced(self) -> "ArchConfig":
        """CPU smoke variant: same family, tiny dims."""
        d = 128
        heads, kv = 4, 2
        moe = None
        if self.moe is not None:
            moe = dataclasses.replace(
                self.moe, n_experts=4, top_k=min(self.moe.top_k, 2),
                d_expert=64 if self.moe.d_expert else 0, n_held=0,
                held_offset=0)
        return dataclasses.replace(
            self,
            name=self.name + "-smoke",
            n_layers=2,
            d_model=d,
            n_heads=heads,
            n_kv_heads=kv,
            head_dim=d // heads,
            d_ff=256 if self.d_ff else 0,
            vocab_size=512,
            aux_vocab_size=512,
            moe=moe,
            cross_attn_every=2 if self.cross_attn_every else 0,
            enc_layers=2 if self.enc_layers else 0,
            n_patches=16 if self.n_patches else 0,
            d_frontend=32 if self.d_frontend else 0,
            vfl=VFLConfig(
                layers_a=0 if self.family == "vlm" else 1,
                layers_b=1, layers_top=1,
                fusion=self.vfl_split.fusion),
        )


@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str                # train | prefill | decode


TRAIN_4K = ShapeConfig("train_4k", 4096, 256, "train")
PREFILL_32K = ShapeConfig("prefill_32k", 32768, 32, "prefill")
DECODE_32K = ShapeConfig("decode_32k", 32768, 128, "decode")
LONG_500K = ShapeConfig("long_500k", 524288, 1, "decode")

SHAPES = {s.name: s for s in (TRAIN_4K, PREFILL_32K, DECODE_32K, LONG_500K)}

# Window applied to attention archs for the long_500k decode config
# (DESIGN §3 long_500k policy).
LONG_CONTEXT_WINDOW = 8192


def validate_pipeline_depth(depth: int, W: int) -> None:
    """THE pipeline-depth capacity rule, stated once.

    A depth-D exchange queue retires the oldest D workset ring slots early
    (every in-flight exchange owns the slot its merge will overwrite), so
    D must stay < W or every draw is a bubble.  ``CELUConfig.__post_init__``
    and the ``PipelinedEngine`` scheduler both call this — the queue-overflow
    RuntimeErrors at dispatch time derive their capacity from the same
    ``depth`` and need no second copy of the rule."""
    if depth < 0:
        raise ValueError(f"pipeline_depth must be >= 0, got {depth}")
    if depth and depth >= max(W, 1):
        raise ValueError(
            f"pipeline_depth ({depth}) must be < W "
            f"({W}): a depth-D queue retires the oldest D ring "
            f"slots early, so D >= W leaves no valid workset draws")


@dataclass(frozen=True)
class CELUConfig:
    """Hyper-parameters of the paper's technique (Section 3 notation)."""
    R: int = 5               # max local updates per cached batch
    W: int = 5               # workset table capacity (mini-batches)
    xi_degrees: float = 60.0 # weighting threshold ξ (cos ξ floor)
    weighting: bool = True
    # round_robin | consecutive (FedBCD) | uniform (random over alive slots)
    sampling: str = "round_robin"
    # BEYOND-PAPER: wire precision of the exchanged ⟨Z_A, ∇Z_A⟩.  The paper
    # sends fp32; "bfloat16" halves WAN bytes per round (EXPERIMENTS §Perf
    # pair 3 validates convergence parity).
    wire_dtype: str = "float32"
    # BEYOND-PAPER: Gaussian-mechanism DP on the wire (core/privacy.py);
    # sigma = 0 disables.  Noised statistics are what gets cached, so local
    # updates reuse already-released messages at no extra privacy cost.
    dp_sigma: float = 0.0
    dp_clip: float = 1.0
    # BEYOND-PAPER: wire codec spec for the compressed transport
    # (Compressed-VFL-style top-k / low-bit sketches with error feedback).
    # "" = plain SimWANTransport; see core/compression.py CODEC_SPECS for
    # names ("int8", "int4", "topk", "int8_topk", "up/down" pairs, ...).
    compression: str = ""
    # BEYOND-PAPER: at-rest precision of the workset cache (the z/dz
    # subtrees of every ring buffer; core/workset.py storage codec).
    # "float32" stores the statistics verbatim (bit-identical to the
    # historical table — golden-pinned); "bfloat16" halves the footprint;
    # "int8" stores SR-quantized codes + one fp32 scale per instance row
    # (~4x smaller; unbiased through Algorithm-2's cosine — see
    # tests/test_workset_cache.py tolerance sweeps); "int4" nibble-packs
    # two SR codes per byte (levels=7, same per-row scale — ~8x smaller,
    # the at-rest floor that makes full LLM geometry fit; see
    # docs/llm_memory.md).
    cache_dtype: str = "float32"
    # Route party-A local updates through the fused gather→dequant→weight
    # megakernel (kernels/fused_sample.py): the sampled ring rows are read
    # once, in storage precision, straight into the weighting pass — no
    # HBM-side entry copy.  False pins the materializing reference path.
    cache_fused: bool = True
    # Paper §4.1 (Fig. 4), generalized: the exchange-queue depth.  0 =
    # sequential rounds (exchange then local updates, the WAN stall
    # serialized with compute); 1 = the paper's two-worker overlap (round
    # t+1's exchange in flight during round t's local updates); D >= 2 = a
    # D-deep queue of in-flight exchanges (engine.PipelinedEngine) for the
    # high-RTT regime where one exchange cannot hide behind one local
    # scan.  The depth is also the extra staleness every cached entry
    # accrues — it tightens workset validity and attenuates the
    # Algorithm-2 weights (weighting.pipeline_attenuation; per-slot
    # dynamic offsets at D >= 2), so it must stay < W or every draw
    # becomes a bubble (validated below).
    pipeline_depth: int = 0
    # Staleness-aware lr damping for the depth-D queue: local and fresh
    # updates under the pipelined schedule are scaled by 1 / (1 + c * s)
    # where s is the update's pipeline staleness in exchanges and c this
    # coefficient.  Applied only on the dynamic (depth >= 2) schedule —
    # depths 0 and 1 keep the historical golden-pinned numerics (s = 0 at
    # depth 1's merge, so damping would be a no-op there anyway).
    pipeline_lr_damping: float = 0.25

    def __post_init__(self):
        validate_pipeline_depth(self.pipeline_depth, self.W)
        if self.pipeline_lr_damping < 0.0:
            raise ValueError(
                f"pipeline_lr_damping must be >= 0, got "
                f"{self.pipeline_lr_damping}")


@dataclass(frozen=True)
class DropoutSpan:
    """One party's outage: ``party`` ("a0".."a{K-1}" or "b") is down for
    ``rounds`` consecutive scheduler rounds starting at ``start`` (and
    rejoins elastically at ``start + rounds``)."""
    party: str
    start: int
    rounds: int

    def __post_init__(self):
        if not (self.party == "b" or (self.party.startswith("a")
                                      and self.party[1:].isdigit())):
            raise ValueError(
                f"DropoutSpan.party must be 'a<i>' or 'b', got "
                f"{self.party!r}")
        if self.start < 0 or self.rounds <= 0:
            raise ValueError(
                f"DropoutSpan needs start >= 0 and rounds >= 1, got "
                f"start={self.start} rounds={self.rounds}")

    def covers(self, round_idx: int) -> bool:
        return self.start <= round_idx < self.start + self.rounds


@dataclass(frozen=True)
class FaultPlan:
    """Deterministic, seeded fault schedule for the chaos engine
    (``core.faults.ChaosEngine``).  Pure configuration — every fate is a
    function of ``(seed, round_idx)`` alone, so two runs (or a run and
    its checkpoint-restored resumption) see identical faults.

    ``party_clocks`` are per-FEATURE-party heterogeneous WAN links as
    plain ``(up_bandwidth_Bps, down_bandwidth_Bps, latency_s)`` tuples
    (converted lazily to ``launch.wan.WANClock`` — this module stays a
    leaf dependency); the slowest party paces each exchange.
    """
    seed: int = 0
    # per-attempt exchange loss probability; a dropped attempt is retried
    # up to max_retries times with exponential backoff before the round's
    # exchange is abandoned (the error-feedback residual then absorbs the
    # lost update — see docs/FAULTS.md)
    drop_prob: float = 0.0
    max_retries: int = 2
    retry_backoff_s: float = 0.5
    # straggler injection: a delivered exchange arrives this many rounds
    # late with probability straggler_prob (delay uniform on
    # [1, straggler_rounds])
    straggler_prob: float = 0.0
    straggler_rounds: int = 2
    dropouts: Tuple[DropoutSpan, ...] = ()
    party_clocks: Optional[Tuple[Tuple[float, float, float], ...]] = None

    def __post_init__(self):
        if not (0.0 <= self.drop_prob < 1.0):
            raise ValueError(f"drop_prob must be in [0, 1), got "
                             f"{self.drop_prob}")
        if not (0.0 <= self.straggler_prob <= 1.0):
            raise ValueError(f"straggler_prob must be in [0, 1], got "
                             f"{self.straggler_prob}")
        if self.max_retries < 0 or self.straggler_rounds < 1:
            raise ValueError(
                f"need max_retries >= 0 and straggler_rounds >= 1, got "
                f"{self.max_retries} / {self.straggler_rounds}")
        if self.retry_backoff_s < 0.0:
            raise ValueError(f"retry_backoff_s must be >= 0, got "
                             f"{self.retry_backoff_s}")
        object.__setattr__(self, "dropouts", tuple(self.dropouts))
        if self.party_clocks is not None:
            object.__setattr__(
                self, "party_clocks",
                tuple(tuple(float(v) for v in c)
                      for c in self.party_clocks))
            for c in self.party_clocks:
                if len(c) != 3 or c[0] <= 0 or c[1] <= 0 or c[2] < 0:
                    raise ValueError(
                        f"party_clocks entries are (up_Bps, down_Bps, "
                        f"latency_s) with positive bandwidths, got {c}")

    def down_parties(self, round_idx: int) -> Tuple[str, ...]:
        """Parties down at ``round_idx`` (sorted, deduplicated)."""
        return tuple(sorted({d.party for d in self.dropouts
                             if d.covers(round_idx)}))


@dataclass(frozen=True)
class TrainConfig:
    batch_size: int = 256
    lr: float = 0.01
    optimizer: str = "adagrad"      # paper uses AdaGrad
    steps: int = 200
    seed: int = 0
    celu: CELUConfig = field(default_factory=CELUConfig)
