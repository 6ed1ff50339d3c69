"""Pallas kernel-contract lint.

Each fused kernel in :mod:`repro.kernels` carries an implicit contract
the engine relies on but nothing enforced statically until now:

  * a **jnp oracle** must exist in ``kernels/ref.py`` (the golden tests
    and the un-fusable fallback paths both depend on it);
  * the **grid/BlockSpec divisibility** rule must hold at the call-site
    geometries the engine actually audits (otherwise the engine silently
    falls back to the reference path — correct but not the perf the
    results tables assume);
  * the kernel's **VMEM residency** (block operands x2 for
    double-buffering) must fit the per-core budget from the Pallas TPU
    guide;
  * the wrapper must **trace** at the audited geometry (``eval_shape``
    probe: shape-rule asserts inside the wrapper surface as findings
    instead of engine-time crashes).

The companion check — no narrowing precision cast outside a declared
wire/encode/cache stage — runs in the taint walk (``taint.py``), where
dataflow context exists; its findings share the ``kernel.`` family.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Sequence, Tuple

from .report import Finding

# Pallas TPU guide: ~16 MiB VMEM per core; keep headroom for the
# compiler's own scratch.
VMEM_BUDGET = 16 * 2 ** 20
VMEM_HEADROOM = 0.75


@dataclass
class Geometry:
    """One audited call-site shape set (engine defaults + stress point)."""
    name: str
    B: int = 64          # batch rows per workset draw
    F: int = 8           # cut-layer width (z_dim)
    W: int = 5           # workset ring depth
    P: int = 4096        # largest flat param block fed to fused_adagrad
    S: int = 2048        # flash-attention sequence length
    H: int = 4           # flash heads
    hd: int = 128        # flash head dim
    T: int = 0           # quantizer tiles; derived from B*F when 0

    def tiles(self, tile: int = 128) -> int:
        n = self.B * self.F
        return self.T or -(-n // tile)


DEFAULT_GEOMETRIES = (
    Geometry("round-default", B=64, F=8),
    Geometry("round-wide", B=4096, F=128),
    Geometry("flash-long", B=2, F=8, S=2048, hd=128),
)


def _f32(*shape):
    import jax
    import jax.numpy as jnp
    return jax.ShapeDtypeStruct(tuple(shape), jnp.float32)


def _i8(*shape):
    import jax
    import jax.numpy as jnp
    return jax.ShapeDtypeStruct(tuple(shape), jnp.int8)


def _i32(*shape):
    import jax
    import jax.numpy as jnp
    return jax.ShapeDtypeStruct(tuple(shape), jnp.int32)


def _u8(*shape):
    import jax
    import jax.numpy as jnp
    return jax.ShapeDtypeStruct(tuple(shape), jnp.uint8)


@dataclass
class KernelContract:
    name: str                       # kernels/<name>.py
    oracle: str                     # required symbol in kernels/ref.py
    # (geometry) -> (block div ok?, human rule text); None = self-padding
    divisibility: Any
    # (geometry) -> resident VMEM bytes for one grid step's blocks
    vmem: Callable[[Geometry], int]
    # (geometry) -> (callable, args) eval_shape probe; None to skip
    probe: Any


def _cw_div(g: Geometry):
    from ..kernels.cosine_weight import BLOCK_B
    bb = min(BLOCK_B, g.B)
    return (g.B % bb == 0,
            f"B={g.B} % min(BLOCK_B={BLOCK_B}, B)={bb}")


def _cw_vmem(g: Geometry) -> int:
    from ..kernels.cosine_weight import BLOCK_B
    bb = min(BLOCK_B, g.B)
    # a, s, dz blocks in; w + out blocks out (f32)
    return (3 * bb * g.F + bb * g.F + bb) * 4


def _fs_vmem(g: Geometry) -> int:
    from ..kernels.fused_sample import BLOCK_B
    bb = min(BLOCK_B, g.B)
    # slot + ad_hoc block + one ring slot's z/dz blocks + outputs
    return (bb * g.F * 4 + 2 * bb * g.F * 4 + bb * g.F * 4 + bb * 4 + 4)


def _fs_q8_vmem(g: Geometry) -> int:
    from ..kernels.fused_sample import BLOCK_B
    bb = min(BLOCK_B, g.B)
    # int8 rings + f32 row scales + f32 ad_hoc/out blocks
    return (2 * bb * g.F + 2 * bb * 4 + 2 * bb * g.F * 4 + bb * 4 + 4)


def _fs_q4_vmem(g: Geometry) -> int:
    from ..kernels.fused_sample import BLOCK_B
    bb = min(BLOCK_B, g.B)
    P = -(-g.F // 2)
    # packed uint8 rings + f32 row scales + f32 ad_hoc/out blocks; the
    # unpacked fp32 rows live only in registers/VPU, never as an operand
    return (2 * bb * P + 2 * bb * 4 + 2 * bb * 2 * P * 4 + bb * 4 + 4)


def _ag_q8_vmem(g: Geometry) -> int:
    from ..kernels.fused_adagrad import BLOCK, ROWS
    # grad f32 + codes int8 + scale f32 + uniforms f32 in;
    # update f32 + codes int8 + scale f32 out
    return ROWS * BLOCK * (4 + 1 + 4 + 4 + 1) + 2 * ROWS * 4


def _q_div(g: Geometry):
    from ..kernels.quantize import BLOCK_T
    T = g.tiles()
    bt = min(BLOCK_T, T)
    return (T % bt == 0, f"T={T} % min(BLOCK_T={BLOCK_T}, T)={bt}")


def _q_vmem(g: Geometry) -> int:
    from ..kernels.quantize import BLOCK_T
    T = g.tiles()
    bt = min(BLOCK_T, T)
    # x + u blocks f32 in, q int8 + scale f32 out; tile=128 values
    return bt * 128 * (4 + 4 + 1) + bt * 4


def _fa_div(g: Geometry):
    from ..kernels.flash_attention import BLOCK_Q
    bq = min(BLOCK_Q, g.S)
    return (g.S % bq == 0, f"S={g.S} % min(BLOCK_Q={BLOCK_Q}, S)={bq}")


def _fa_vmem(g: Geometry) -> int:
    from ..kernels.flash_attention import BLOCK_Q
    bq = min(BLOCK_Q, g.S)
    # q block + FULL-length k/v blocks (they ride as (S, hd)) + o block
    # + m/l accumulators
    return (bq * g.hd + 2 * g.S * g.hd + bq * g.hd + 2 * bq) * 4


def _ag_vmem(g: Geometry) -> int:
    from ..kernels.fused_adagrad import BLOCK, ROWS
    # grad + accum in, update + accum out, all f32, self-padded tiles
    return ROWS * BLOCK * 4 * 4


def _probe_cw(g: Geometry):
    from ..kernels import ops
    return ops.cosine_weight, (_f32(g.B, g.F), _f32(g.B, g.F), 0.5)


def _probe_wc(g: Geometry):
    from ..kernels import ops
    return ops.weighted_cotangent, (_f32(g.B, g.F), _f32(g.B, g.F),
                                    _f32(g.B, g.F), 0.5)


def _probe_fs(g: Geometry):
    from ..kernels import ops
    return ops.fused_gather_weight, (_i32(), _f32(g.B, g.F),
                                     _f32(g.W, g.B, g.F),
                                     _f32(g.W, g.B, g.F), 0.5)


def _probe_fs_q8(g: Geometry):
    from ..kernels import ops
    return ops.fused_gather_weight_q8, (_i32(), _f32(g.B, g.F),
                                        _i8(g.W, g.B, g.F),
                                        _f32(g.W, g.B, 1),
                                        _i8(g.W, g.B, g.F),
                                        _f32(g.W, g.B, 1), 0.5)


def _probe_fs_q4(g: Geometry):
    from ..kernels import ops
    P = -(-g.F // 2)
    return ops.fused_gather_weight_q4, (_i32(), _f32(g.B, g.F),
                                        _u8(g.W, g.B, P),
                                        _f32(g.W, g.B, 1),
                                        _u8(g.W, g.B, P),
                                        _f32(g.W, g.B, 1), 0.5)


def _probe_ag_q8(g: Geometry):
    from ..kernels import ops
    from ..kernels.fused_adagrad import BLOCK, ROWS
    return ops.fused_adagrad_q8, (_f32(ROWS, BLOCK), _i8(ROWS, BLOCK),
                                  _f32(ROWS, 1), _f32(ROWS, BLOCK),
                                  0.1, 1e-10)


def _probe_q(g: Geometry):
    from ..kernels import ops
    T = g.tiles()
    return ops.quantize_stochastic, (_f32(T, 128), _f32(T, 128), 127)


def _probe_flash(g: Geometry):
    from ..kernels import ops
    return (lambda q, k, v: ops.flash_attention(q, k, v, causal=True),
            (_f32(2, g.H, g.S, g.hd),) * 3)


def _probe_ag(g: Geometry):
    from ..kernels import ops
    return ops.fused_adagrad, (_f32(g.P), _f32(g.P), 0.1, 1e-10)


CONTRACTS: Tuple[KernelContract, ...] = (
    KernelContract("cosine_weight", "cosine_weight_ref",
                   _cw_div, _cw_vmem, _probe_cw),
    KernelContract("cosine_weight", "weighted_cotangent_ref",
                   _cw_div, _cw_vmem, _probe_wc),
    KernelContract("fused_sample", "fused_sample_ref",
                   _cw_div, _fs_vmem, _probe_fs),
    KernelContract("fused_sample", "fused_sample_q8_ref",
                   _cw_div, _fs_q8_vmem, _probe_fs_q8),
    KernelContract("fused_sample", "fused_sample_q4_ref",
                   _cw_div, _fs_q4_vmem, _probe_fs_q4),
    KernelContract("quantize", "quantize_sr_ref",
                   _q_div, _q_vmem, _probe_q),
    KernelContract("flash_attention", "flash_attention_ref",
                   _fa_div, _fa_vmem, _probe_flash),
    KernelContract("fused_adagrad", "fused_adagrad_ref",
                   None, _ag_vmem, _probe_ag),
    KernelContract("fused_adagrad", "fused_adagrad_q8_ref",
                   None, _ag_q8_vmem, _probe_ag_q8),
)


def lint_kernels(geometries: Sequence[Geometry] = DEFAULT_GEOMETRIES
                 ) -> List[Finding]:
    import jax

    from ..kernels import ref as kref

    findings: List[Finding] = []
    seen_oracles = set()

    for c in CONTRACTS:
        # 1. registered jnp oracle
        if c.oracle not in seen_oracles:
            seen_oracles.add(c.oracle)
            if not callable(getattr(kref, c.oracle, None)):
                findings.append(Finding(
                    code="kernel.missing-oracle", severity="error",
                    where=f"kernels/ref.py::{c.oracle}",
                    detail=f"kernel '{c.name}' has no registered jnp "
                           f"oracle — golden tests and the un-fusable "
                           f"fallback both require it"))
                continue

        for g in geometries:
            # flash has its own geometry axis; round kernels skip it
            if (c.name == "flash_attention") != g.name.startswith("flash"):
                continue

            # 2. grid divisibility at the audited geometry
            if c.divisibility is not None:
                ok, rule = c.divisibility(g)
                if not ok:
                    findings.append(Finding(
                        code="kernel.grid-divisibility", severity="error",
                        where=f"kernels/{c.name} @ {g.name}",
                        detail=f"BlockSpec rule {rule} != 0: the fused "
                               f"Pallas path is DISABLED at this "
                               f"geometry and the engine silently takes "
                               f"the jnp reference fallback — resize the "
                               f"block or the call-site shape"))

            # 3. VMEM residency (x2 for double buffering)
            resident = 2 * c.vmem(g)
            budget = int(VMEM_BUDGET * VMEM_HEADROOM)
            if resident > budget:
                findings.append(Finding(
                    code="kernel.vmem-budget", severity="error",
                    where=f"kernels/{c.name} @ {g.name}",
                    detail=f"double-buffered block residency "
                           f"{resident} B exceeds the {budget} B VMEM "
                           f"budget (16 MiB/core x {VMEM_HEADROOM} "
                           f"headroom) — shrink the block shape"))

            # 4. wrapper traces at the audited geometry
            if c.probe is not None:
                fn, args = c.probe(g)
                try:
                    jax.eval_shape(fn, *args)
                except Exception as e:  # noqa: BLE001 - report, not crash
                    findings.append(Finding(
                        code="kernel.probe-failed", severity="error",
                        where=f"kernels/{c.name} @ {g.name}",
                        detail=f"eval_shape probe raised "
                               f"{type(e).__name__}: {e}"))
    return findings


def lint_engine_fusability(celu, B: int, case: str) -> List[Finding]:
    """The engine promises the fused cache path at the audited batch
    geometry; verify the promise is actually live (mirrors
    ``engine._fusable``)."""
    from ..kernels.cosine_weight import BLOCK_B as CW_B
    from ..kernels.fused_sample import BLOCK_B as FS_B

    findings: List[Finding] = []
    for name, blk in (("cosine_weight", CW_B), ("fused_sample", FS_B)):
        bb = min(blk, B)
        if B % bb != 0:
            findings.append(Finding(
                code="kernel.fused-path-disabled", severity="error",
                where=f"kernels/{name} @ B={B}",
                detail=f"audited round geometry B={B} is not divisible "
                       f"by min(BLOCK_B={blk}, B)={bb}: the fused "
                       f"{name} path the config promises silently "
                       f"degrades to the reference fallback",
                case=case))
    return findings
