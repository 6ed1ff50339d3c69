import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) against
abstract inputs on the production mesh, and extract the roofline terms.

The two lines above MUST run before any jax import (device count locks on
first init) — which is why this module is the only entry point that sees
512 placeholder devices; smoke tests and benches see the host's real 1.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch deepseek-7b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --out results/dryrun.jsonl
  ... add --multi-pod for the 2x16x16 512-chip mesh.

Per run it records: lowering/compile success, per-device memory analysis,
HLO FLOPs/bytes from cost_analysis, collective bytes parsed from the
partitioned HLO, and the three roofline terms (§Roofline in EXPERIMENTS.md).
"""

import argparse
import json
import re
import sys
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import SHAPES, arch_for_shape
from ..configs.base import ArchConfig, ShapeConfig
from ..optim import adagrad
from ..sharding.rules import (batch_pspec, cache_pspecs, params_pspecs)
from .mesh import (HBM_BW, ICI_BW, PEAK_FLOPS_BF16, data_axes,
                   make_production_mesh)
from .steps import abstract_params, input_specs, make_step

P = jax.sharding.PartitionSpec

COLLECTIVE_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                  "collective-permute")

_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "c64": 8, "c128": 16}

_SHAPE_RE = re.compile(r"\b(f64|f32|f16|bf16|s64|u64|s32|u32|s16|u16|s8|u8|"
                       r"pred|c64|c128)\[([0-9,]*)\]")


def _cost_dict(cost) -> Dict[str, float]:
    """Normalize ``Compiled.cost_analysis()`` across JAX versions: older
    releases return one dict, JAX 0.4.3x returns a LIST of per-program
    dicts, and some backends return None.  Sum numeric fields across
    programs into a single flat dict."""
    if cost is None:
        return {}
    if isinstance(cost, dict):
        return cost
    merged: Dict[str, float] = {}
    for prog in cost:
        for k, v in (prog or {}).items():
            if isinstance(v, (int, float)):
                merged[k] = merged.get(k, 0.0) + float(v)
    return merged


def _shape_bytes(m) -> int:
    dt, dims = m.group(1), m.group(2)
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dt]


_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_COLL_LINE_RE = re.compile(
    r"^%?[\w.\-]+\s*=\s*(\(?[\w\[\],{}\s]*?\)?)\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\(")
# TPU layouts ({1,0:T(8,128)S(1)}) trail every shape in compiled TPU HLO
_LAYOUT_RE = re.compile(r"\{[^{}]*\}")


def _line_collective(s: str):
    """(op, bytes) for a collective instruction line, else None."""
    m = _COLL_LINE_RE.match(_LAYOUT_RE.sub("", s))
    if not m:
        return None
    result_types, op, started = m.group(1), m.group(2), m.group(3)
    shapes = list(_SHAPE_RE.finditer(result_types))
    if started and result_types.startswith("(") and shapes:
        # async start (TPU): a (operand, result, context...) tuple — the
        # first element IS the operand
        return op, _shape_bytes(shapes[0])
    nbytes = sum(_shape_bytes(sm) for sm in shapes)
    gm = _GROUPS_RE.search(s)
    g = int(gm.group(2)) if gm else 1
    if op == "all-gather" and g:
        nbytes //= g
    elif op == "reduce-scatter":
        nbytes *= g
    return op, nbytes


_COMP_HDR_RE = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s*\(")
_WHILE_RE = re.compile(r"\bwhile\(.*?\)\s*,\s*condition=%?([\w.\-]+)\s*,\s*"
                       r"body=%?([\w.\-]+)")
_CONST_RE = re.compile(r"\bconstant\((\d+)\)")


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum operand bytes of every collective, EXECUTION-weighted.

    Post-SPMD HLO prints only the RESULT type inline, so operand bytes come
    from the result shape and replica-group size g
    (``replica_groups=[n,g]<=...``):

      all-reduce / all-to-all / collective-permute : operand = result
      all-gather : result/g        reduce-scatter : result*g

    Collectives inside ``while`` bodies (layer scans, flash-attention
    q-block scans, microbatch accumulation) execute TRIP-COUNT times but
    appear once in the text — this parser walks the computation graph and
    multiplies nested-loop bodies by their trip counts (read as the max
    integer literal in the loop condition, which is the scan bound for all
    jax-emitted loops).
    """
    # 1. split into computations
    comps: Dict[str, list] = {}
    entry = None
    cur = None
    for line in hlo_text.splitlines():
        if not line.startswith(" ") and "{" in line:
            m = _COMP_HDR_RE.match(line.strip())
            if m:
                cur = m.group(2)
                comps[cur] = []
                if m.group(1):
                    entry = cur
                continue
        if line.startswith("}"):
            cur = None
            continue
        if cur is not None:
            comps[cur].append(line.strip())

    # 2. trip count of a loop-condition computation
    def trip_count(cond_name: str) -> int:
        best = 1
        for s in comps.get(cond_name, ()):
            for cm in _CONST_RE.finditer(s):
                best = max(best, int(cm.group(1)))
        return best

    # 3. execution-weighted bytes per computation (memoized DFS)
    memo: Dict[str, Dict[str, int]] = {}

    def walk(name: str) -> Dict[str, int]:
        if name in memo:
            return memo[name]
        out = {k: 0 for k in COLLECTIVE_OPS}
        memo[name] = out          # break cycles defensively
        for s in comps.get(name, ()):
            lc = _line_collective(s)
            if lc:
                out[lc[0]] += lc[1]
            wm = _WHILE_RE.search(s)
            if wm:
                cond, body = wm.group(1), wm.group(2)
                n = trip_count(cond)
                sub = walk(body)
                for k, v in sub.items():
                    out[k] += n * v
        return out

    if entry is None:             # fall back to flat counting
        out = {k: 0 for k in COLLECTIVE_OPS}
        for line in hlo_text.splitlines():
            lc = _line_collective(line.strip())
            if lc:
                out[lc[0]] += lc[1]
        return out
    return dict(walk(entry))


# --------------------------------------------------------------------------
def _flops_dense(cfg: ArchConfig) -> int:
    """Total (and MoE-active) param counts from abstract shapes."""
    params = abstract_params(cfg)
    total = sum(int(np.prod(x.shape)) for x in
                jax.tree_util.tree_leaves(params))
    active = total
    if cfg.moe is not None:
        expert = 0
        for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
            keys = [getattr(p, "key", None) for p in path]
            if any(k in ("wg", "wu", "wd") for k in keys) and leaf.ndim >= 3:
                expert += int(np.prod(leaf.shape))
        frac = (cfg.moe.top_k + cfg.moe.n_shared) / cfg.moe.n_experts
        active = total - expert + int(expert * frac)
    return total, active


def model_flops(cfg: ArchConfig, shape: ShapeConfig) -> float:
    """6·N·D for training, 2·N_active per generated token for decode."""
    total, active = _flops_dense(cfg)
    if shape.kind == "train":
        return 6.0 * active * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * active * shape.global_batch * shape.seq_len
    return 2.0 * active * shape.global_batch  # one token


# --------------------------------------------------------------------------
def dryrun(arch_id: str, shape_name: str, *, multi_pod: bool = False,
           fsdp: bool = True, moe_sharding: str = "",
           donate: bool = True, extra_tag: str = "",
           microbatches: int = 1, unroll_microbatches: bool = False,
           pure_dp: bool = False, zero1: bool = False) -> Dict[str, Any]:
    """``pure_dp``: batch over (pod, data, model) — all 256/512 chips data-
    parallel, tower weights replicated (embeddings/head still model-sharded
    via the name rules' divisibility checks being moot doesn't apply — in
    pure-DP we replicate everything but shard the batch).  The right profile
    for archs whose head/expert counts defeat 16-way TP (§Perf pair 2)."""
    cfg = arch_for_shape(arch_id, shape_name)
    if not moe_sharding:   # default: the arch config's choice (§Perf 2.4)
        moe_sharding = cfg.moe.sharding if cfg.moe is not None else "tp"
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = int(np.prod(list(mesh.shape.values())))
    daxes = data_axes(mesh) + (("model",) if pure_dp else ())
    dsize = int(np.prod([mesh.shape[a] for a in daxes]))
    from ..models.layers import set_batch_axes
    set_batch_axes(daxes, dsize,
                   vocab_axis=None if pure_dp else "model",
                   vocab_size=int(mesh.shape["model"]))
    t0 = time.time()

    params = abstract_params(cfg)
    if pure_dp:
        pspecs = params_pspecs(params, mesh, model_axis="__none__",
                               fsdp_axis="data" if fsdp else None)
    else:
        pspecs = params_pspecs(params, mesh, moe_sharding=moe_sharding,
                               fsdp_axis="data" if fsdp else None)
    shard = lambda t, s: jax.tree_util.tree_map(
        lambda leaf, sp: jax.ShapeDtypeStruct(
            leaf.shape, leaf.dtype,
            sharding=jax.sharding.NamedSharding(mesh, sp)),
        t, s, is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))

    specs = input_specs(cfg, shape)
    opt = adagrad(0.01)

    if shape.kind == "train":
        opt_state = jax.eval_shape(opt.init, params)
        if zero1:
            # ZeRO-1: shard ONLY the fp32 accumulators over `data`, keeping
            # params replicated (pairs with pure_dp for awkward-dim archs)
            opt_specs = {"accum": params_pspecs(
                params, mesh,
                model_axis="__none__" if pure_dp else "model",
                moe_sharding=moe_sharding, fsdp_axis="data")}
        else:
            opt_specs = {"accum": pspecs}
        from .steps import make_train_step
        step = make_train_step(cfg, opt, microbatches=microbatches,
                               unroll_microbatches=unroll_microbatches)
        in_shardings = (
            jax.tree_util.tree_map(
                lambda s: jax.sharding.NamedSharding(mesh, s), pspecs,
                is_leaf=lambda x: isinstance(x, P)),
            jax.tree_util.tree_map(
                lambda s: jax.sharding.NamedSharding(mesh, s),
                opt_specs, is_leaf=lambda x: isinstance(x, P)),
            jax.tree_util.tree_map(
                lambda l: jax.sharding.NamedSharding(
                    mesh, batch_pspec(l.shape, mesh, data_axes=daxes)),
                specs["batch"],
                is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct)),
        )
        out_shardings = (in_shardings[0], in_shardings[1],
                         jax.sharding.NamedSharding(mesh, P()))
        args = (shard(params, pspecs),
                {"accum": shard(opt_state["accum"], opt_specs["accum"])},
                specs["batch"])
        fn = jax.jit(step, in_shardings=in_shardings,
                     out_shardings=out_shardings,
                     donate_argnums=(0, 1) if donate else ())
    elif shape.kind == "prefill":
        step = make_step(cfg, shape)
        bspecs = jax.tree_util.tree_map(
            lambda l: jax.sharding.NamedSharding(
                mesh, batch_pspec(l.shape, mesh, data_axes=daxes)),
            specs["batch"],
            is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
        in_shardings = (
            jax.tree_util.tree_map(
                lambda s: jax.sharding.NamedSharding(mesh, s), pspecs,
                is_leaf=lambda x: isinstance(x, P)),
            bspecs)
        fn = jax.jit(step, in_shardings=in_shardings)
        args = (shard(params, pspecs), specs["batch"])
    else:  # decode
        step = make_step(cfg, shape)
        caches = specs["caches"]
        cspecs = cache_pspecs(caches, mesh, data_axes=daxes)
        in_shardings = (
            jax.tree_util.tree_map(
                lambda s: jax.sharding.NamedSharding(mesh, s), pspecs,
                is_leaf=lambda x: isinstance(x, P)),
            jax.tree_util.tree_map(
                lambda s: jax.sharding.NamedSharding(mesh, s), cspecs,
                is_leaf=lambda x: isinstance(x, P)),
            jax.tree_util.tree_map(
                lambda l: jax.sharding.NamedSharding(
                    mesh, batch_pspec(l.shape, mesh, data_axes=daxes)),
                specs["step_batch"],
                is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct)),
            jax.sharding.NamedSharding(mesh, P()),
        )
        fn = jax.jit(step, in_shardings=in_shardings,
                     donate_argnums=(1,) if donate else ())
        args = (shard(params, pspecs),
                jax.tree_util.tree_map(
                    lambda l, sp: jax.ShapeDtypeStruct(
                        l.shape, l.dtype,
                        sharding=jax.sharding.NamedSharding(mesh, sp)),
                    caches, cspecs,
                    is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct)),
                specs["step_batch"], specs["pos"])

    with mesh:
        lowered = fn.lower(*args)
        compiled = lowered.compile()

    mem = compiled.memory_analysis()
    cost = _cost_dict(compiled.cost_analysis())
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)
    coll_total = sum(coll.values())

    # XLA's flop count uses the M*N*K convention (one per MAC); double it to
    # compare against the 2*M*N*K convention of MODEL_FLOPS = 6*N*D.
    flops = 2.0 * float(cost.get("flops", 0.0))
    # "bytes accessed" sums operand+result bytes over all HLO ops — an
    # un-fused upper bound on HBM traffic (fusion collapses most of it);
    # relative comparisons under the same convention remain meaningful.
    bytes_acc = float(cost.get("bytes accessed", 0.0))
    mf = model_flops(cfg, shape)

    terms = {
        # cost_analysis reports the per-device (partitioned) program
        "compute_s": flops / PEAK_FLOPS_BF16,
        "memory_s": bytes_acc / HBM_BW,
        "collective_s": coll_total / ICI_BW,
    }
    dominant = max(terms, key=terms.get)
    result = {
        "arch": arch_id, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": chips, "fsdp": fsdp, "moe_sharding": moe_sharding,
        "tag": extra_tag,
        "pure_dp": pure_dp, "microbatches": microbatches,
        "ok": True,
        "compile_s": round(time.time() - t0, 1),
        "hlo_flops_per_dev": flops,
        "hlo_bytes_per_dev": bytes_acc,
        "collective_bytes_per_dev": coll_total,
        "collectives": coll,
        "model_flops_global": mf,
        "model_flops_per_dev": mf / chips,
        "useful_flops_frac": (mf / chips) / flops if flops else 0.0,
        "roofline": terms,
        "dominant": dominant,
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
            "output_bytes": getattr(mem, "output_size_in_bytes", 0),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
            "peak_bytes": getattr(mem, "temp_size_in_bytes", 0)
            + getattr(mem, "argument_size_in_bytes", 0),
        },
    }
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="every assigned arch x shape")
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--moe-sharding", default="",
                    choices=("", "tp", "ep"))
    ap.add_argument("--microbatch", type=int, default=1)
    ap.add_argument("--unroll-microbatch", action="store_true")
    ap.add_argument("--pure-dp", action="store_true")
    ap.add_argument("--zero1", action="store_true")
    ap.add_argument("--tag", default="")
    ap.add_argument("--out", default=None, help="append JSONL here")
    args = ap.parse_args(argv)

    from ..configs import ARCH_IDS
    pairs = []
    archs = ARCH_IDS if args.all else [args.arch]
    shapes = list(SHAPES) if args.all or args.shape is None else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    for a in archs:
        for s in shapes:
            for mp in meshes:
                pairs.append((a, s, mp))

    rc = 0
    for a, s, mp in pairs:
        try:
            res = dryrun(a, s, multi_pod=mp, fsdp=not args.no_fsdp,
                         moe_sharding=args.moe_sharding, extra_tag=args.tag,
                         microbatches=args.microbatch,
                         unroll_microbatches=args.unroll_microbatch,
                         pure_dp=args.pure_dp, zero1=args.zero1)
        except Exception as e:  # noqa: BLE001 — record failures, keep going
            res = {"arch": a, "shape": s,
                   "mesh": "2x16x16" if mp else "16x16", "ok": False,
                   "tag": args.tag, "error": f"{type(e).__name__}: {e}"}
            rc = 1
        line = json.dumps(res)
        print(line, flush=True)
        if args.out:
            import pathlib
            pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
            with open(args.out, "a") as f:
                f.write(line + "\n")
    return rc


if __name__ == "__main__":
    sys.exit(main())
