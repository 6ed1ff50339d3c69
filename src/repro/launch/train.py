"""End-to-end VFL training driver.

Two modes:
  * DLRM (the paper's workloads): --arch wdl-criteo | dssm-avazu, trains on
    the synthetic vertically-partitioned stream with the selected protocol
    (vanilla | fedbcd | celu) and reports AUC + communication accounting
    (rounds, bytes, simulated-WAN seconds).
  * LLM backbones: --arch <assigned-id> trains the split LLM for --rounds
    rounds (--reduced shrinks the widths for a quick CPU run).

At pipeline depth 0 the round program is compiled before the first round
and its compile time printed as set-up.

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch wdl-criteo \
      --protocol celu --rounds 300 --R 5 --W 5 --xi 60
  PYTHONPATH=src python -m repro.launch.train --arch smollm-360m \
      --protocol celu --rounds 20 --reduced
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import SHAPES, get_config
from ..configs.base import ArchConfig, CELUConfig
from ..core import engine
from ..core import protocol as proto
from ..data import synthetic as synth
from ..models import vfl
from ..models.tabular import DLRMConfig, auc, make_dlrm
from ..optim import make_optimizer
from .wan import WANClock, transport_round_updown, wan_seconds  # noqa: F401

# Simulated-WAN wall-clock model (paper §2.1: 300 Mbps, gateway latency)
# lives in launch.wan — per-direction bandwidth + RTT, overlap-aware round
# latency.  ``wan_seconds(up_bytes, down_bytes)`` is re-exported above.
DEFAULT_WAN = WANClock()


def _as_jax(d: Dict[str, np.ndarray]):
    return {k: jnp.asarray(v) for k, v in d.items()}


def _fault_plan_from_args(args):
    """Build a ``FaultPlan`` from the --fault-* flags (None when no fault
    axis is set — the scheduler stays golden-identical)."""
    from ..configs.base import DropoutSpan, FaultPlan
    spans = []
    for s in args.fault_dropout or ():
        try:
            party, start, rounds = s.split(":")
            spans.append(DropoutSpan(party=party, start=int(start),
                                     rounds=int(rounds)))
        except ValueError:
            raise SystemExit(
                f"--fault-dropout wants PARTY:START:ROUNDS (e.g. "
                f"a0:40:5), got {s!r}")
    if not (args.fault_drop_prob or args.fault_straggler_prob or spans):
        return None
    return FaultPlan(seed=args.fault_seed,
                     drop_prob=args.fault_drop_prob,
                     max_retries=args.fault_max_retries,
                     straggler_prob=args.fault_straggler_prob,
                     straggler_rounds=args.fault_straggler_rounds,
                     dropouts=tuple(spans))


def _ckpt_extra_ref(n_pending: int, chaos: bool):
    """Structural reference for the checkpoint's extra pytree: the resume
    round, plus (chaos runs only) the scheduler's host bookkeeping with
    one arrival/dispatch entry per in-flight exchange."""
    extra = {"round": 0}
    if chaos:
        extra["host"] = {"now": 0, "dispatch_seq": 0,
                         "arrival": [0] * n_pending,
                         "dispatch_round": [0] * n_pending,
                         "last_merged_dispatch": 0}
    return extra


# --------------------------------------------------------------------------
def llm_task(cfg: ArchConfig, remat: bool = True) -> proto.VFLTask:
    """VFLTask over the LLM backbone split (text archs).  ``remat``
    toggles activation checkpointing of the tower scans (models.backbone
    Ctx.remat).  A model with experts counts the (token, slot) rows its
    held experts compute (``expert_rows`` in the round's metrics)."""
    counted = cfg.moe is not None

    def forward_a(pa, batch_a):
        out = vfl.forward_a(pa, cfg, batch_a, train=True, remat=remat,
                            counts=counted)
        return (out[0], {"expert_rows": out[1]}) if counted else out

    def loss_b(pb, z_a, batch_b):
        out = vfl.per_instance_loss(pb, cfg, z_a, batch_b, train=True,
                                    remat=remat, counts=counted)
        return out[:2] + ({"expert_rows": out[2]},) if counted else out

    return proto.VFLTask(forward_a, loss_b, counted=counted)


def make_opt(args):
    """Optimizer from --optimizer/--lr/--opt-state-dtype; the state dtype
    only routes for adagrad (the paper's optimizer — sgd/adam/sm3 keep
    their native state)."""
    kw = {}
    if args.opt_state_dtype != "float32":
        if args.optimizer != "adagrad":
            raise SystemExit("--opt-state-dtype requires --optimizer "
                             "adagrad (sm3 is already factored; sgd/adam "
                             "keep fp32 state)")
        kw["state_dtype"] = args.opt_state_dtype
    return make_optimizer(args.optimizer, args.lr, **kw)


def _compile_round(rnd, state, batches_a, batch_b):
    """Compile the jitted round for the first batch's shapes before the
    loop, so compilation is set-up time, not part of round 1.  -> (the
    compiled round, compile seconds); the caller may inspect the
    compiled program (``.as_text()``)."""
    t0 = time.perf_counter()
    compiled = rnd.lower(state, batches_a, batch_b, 0).compile()
    compile_s = time.perf_counter() - t0
    print(f"[compile] round program in {compile_s:.1f}s", flush=True)
    return compiled, compile_s


def train_dlrm(args) -> Dict[str, Any]:
    cfg: DLRMConfig = get_config(args.arch)
    if args.small:
        cfg = dataclasses.replace(cfg, vocab=128, embed_dim=8, z_dim=32,
                                  hidden=(64, 32))
    spec_name = {"wdl-criteo": "criteo", "dssm-avazu": "avazu"}[args.arch]
    spec = dataclasses.replace(synth.TABULAR_SPECS[spec_name],
                               vocab=cfg.vocab, n_train=args.n_train,
                               n_test=args.n_test)
    data = synth.make_tabular(spec, seed=args.seed)
    init_fn, task, predict = make_dlrm(cfg)

    base = CELUConfig(R=args.R, W=args.W, xi_degrees=args.xi,
                      weighting=not args.no_weighting,
                      compression=args.compression,
                      pipeline_depth=args.pipeline_depth,
                      pipeline_lr_damping=args.pipeline_lr_damping,
                      cache_dtype=args.cache_dtype,
                      cache_fused=not args.no_cache_fusion)
    celu_cfg, n_local = engine.preset_config(args.protocol, base)
    params = init_fn(jax.random.PRNGKey(args.seed), cfg)
    opt = make_opt(args)

    it = synth.aligned_batches(data["train"], args.batch_size,
                               seed=args.seed)
    _, ba0, bb0 = next(it)
    etask = engine.lift_two_party(task)
    transport = engine.make_transport(celu_cfg)
    state = engine.init_state(etask, engine.lift_two_party_params(params),
                              opt, celu_cfg, [_as_jax(ba0)], _as_jax(bb0),
                              transport=transport)
    from ..core.workset import QUANT_KEYS, workset_nbytes
    cache_stat_b = sum(workset_nbytes(w, QUANT_KEYS)
                       for w in state["ws"]["a"] + [state["ws"]["b"]])
    cache_total_b = sum(workset_nbytes(w)
                        for w in state["ws"]["a"] + [state["ws"]["b"]])
    print(f"[cache] workset tables: {cache_total_b / 1e6:.2f} MB "
          f"({cache_stat_b / 1e6:.2f} MB cut statistics at "
          f"{celu_cfg.cache_dtype}; fused sample "
          f"{'on' if celu_cfg.cache_fused else 'off'})", flush=True)
    depth = celu_cfg.pipeline_depth
    plan = _fault_plan_from_args(args)
    # chaos, checkpointing, and resume all need the explicit scheduler
    # object (ChaosEngine with plan=None is bit-identical to the base
    # pipeline, so the checkpoint paths reuse it at every depth)
    engineful = bool(depth) or plan is not None or args.checkpoint \
        or args.resume
    if engineful:
        from .. import checkpoint as ckpt
        from ..core.faults import ChaosEngine
        pe = ChaosEngine(etask, opt, celu_cfg, plan=plan, depth=depth,
                         local_steps=n_local, transport=transport)
        rs = pe.init(state)
    else:
        rnd, compile_s = _compile_round(
            engine.make_round(etask, opt, celu_cfg, local_steps=n_local,
                              transport=transport, donate=True),
            state, [_as_jax(ba0)], _as_jax(bb0))
    start_round = 0
    if args.resume:
        n_pend = ckpt.peek_pending_len(args.resume)
        # fabricate a structural reference: same engine, n dispatches
        # (values are irrelevant — every leaf is overwritten)
        it_ref = synth.aligned_batches(data["train"], args.batch_size,
                                       seed=args.seed)
        rs_ref = rs
        for _ in range(n_pend):
            bi_r, ba_r, bb_r = next(it_ref)
            rs_ref = pe.dispatch(rs_ref, [_as_jax(ba_r)], _as_jax(bb_r),
                                 bi_r)
        rs, extra = ckpt.restore_round_state(
            args.resume, rs_ref,
            extra_reference=_ckpt_extra_ref(n_pend, plan is not None))
        start_round = int(extra["round"])
        if plan is not None:
            pe.load_host_state(extra["host"])
        else:   # fabrication advanced the (unused) chaos counters
            pe.load_host_state(_ckpt_extra_ref(0, True)["host"])
        print(f"[resume] {args.resume}: round {start_round}, "
              f"{n_pend} in-flight exchange(s)", flush=True)
    # per-direction wire accounting from the transport's explicit split
    # (asymmetric codecs: sparse sketches up, dense low-bit down)
    z_shapes = [(args.batch_size, cfg.z_dim)]
    up_bytes, down_bytes = transport_round_updown(transport, z_shapes)
    z_bytes = up_bytes + down_bytes

    te = data["test"]
    tea, teb = ({"x_a": jnp.asarray(te["x_a"])},
                {"x_b": jnp.asarray(te["x_b"]), "y": jnp.asarray(te["y"])})
    it = synth.aligned_batches(data["train"], args.batch_size,
                               seed=args.seed)
    for _ in range(start_round):    # deterministic stream: replay the
        next(it)                    # consumed prefix, bit-consistent
    t0 = time.time()
    history = []
    for i in range(start_round, args.rounds):
        bi, ba, bb = next(it)
        if engineful:
            rs, m = pe.step(rs, [_as_jax(ba)], _as_jax(bb), bi)
        else:
            state, m = rnd(state, [_as_jax(ba)], _as_jax(bb), bi)
        if args.checkpoint and (i + 1) % args.checkpoint_every == 0:
            extra = {"round": i + 1}
            if plan is not None:
                extra["host"] = pe.host_state()
            ckpt.save_round_state(args.checkpoint, rs, extra=extra)
        if (i + 1) % max(1, args.rounds // 10) == 0:
            cur = rs.params if engineful else state["params"]
            logits = predict(engine.unlift_params(cur), cfg, tea, teb)
            a = auc(np.asarray(logits), te["y"])
            history.append((i + 1, float(m["loss"]), a))
            print(f"round {i+1:6d} loss {float(m['loss']):.4f} "
                  f"AUC {a:.4f} local_steps {int(m.get('local_steps', 0))} "
                  f"w_mean {float(m.get('w_mean', 0)):.3f}", flush=True)
    if engineful:
        rs, _ = pe.flush(rs)
        state = pe.finalize(rs)
    if plan is not None:
        tel = pe.telemetry()
        print(f"[chaos] {tel['merges']} merges / {tel['dispatches']} "
              f"dispatches over {tel['rounds']} rounds: "
              f"{tel['drops']} drops, {tel['stalls']} stalls, "
              f"{tel['dropout_rounds']} dropout rounds, "
              f"{tel['wire_attempts']} wire attempts", flush=True)
    wall = time.time() - t0
    # overlap-aware simulated wall-clock: split the measured compute into
    # the exchange share (1 fresh update) and the local share (n_local
    # updates); the clock serializes them with the wire at depth 0 and
    # charges max(exchange, local) at depth >= 1
    compute_per_round = wall / max(args.rounds, 1)
    ex_c = compute_per_round / (1 + n_local)
    loc_c = compute_per_round - ex_c
    comm_s = DEFAULT_WAN.time_to_target(
        args.rounds, up_bytes, down_bytes, exchange_compute_s=ex_c,
        local_compute_s=loc_c, pipeline_depth=depth)
    seq_s = DEFAULT_WAN.time_to_target(
        args.rounds, up_bytes, down_bytes, exchange_compute_s=ex_c,
        local_compute_s=loc_c, pipeline_depth=0)
    # chaos runs charge the wire per ATTEMPT (retries re-send; dropout/
    # stall rounds send nothing)
    wire_rounds = pe.counters["wire_attempts"] if plan is not None \
        else args.rounds
    out = {
        "arch": args.arch, "protocol": args.protocol,
        "rounds": args.rounds, "final_auc": history[-1][2] if history else None,
        "comm_bytes": wire_rounds * z_bytes,
        "uplink_bytes": wire_rounds * up_bytes,
        "downlink_bytes": wire_rounds * down_bytes,
        "fault_telemetry": pe.telemetry() if plan is not None else None,
        "sim_wan_s": comm_s, "sim_wan_sequential_s": seq_s,
        "pipeline_depth": depth, "compute_wall_s": wall,
        "history": history,
    }
    if not engineful:
        out.update(compile_s=compile_s, round=rnd)
    pipe_note = (f" (sequential would be {seq_s:.1f}s -> "
                 f"{seq_s / comm_s:.2f}x overlap win)") if depth else ""
    auc_note = "n/a" if out["final_auc"] is None \
        else f"{out['final_auc']:.4f}"
    print(f"[done] {args.protocol}: AUC={auc_note} "
          f"comm={out['comm_bytes']/1e6:.1f}MB "
          f"(up {up_bytes/1e3:.0f}KB/dn {down_bytes/1e3:.0f}KB per round) "
          f"simWAN={comm_s:.1f}s wall={wall:.1f}s{pipe_note}")
    return out


def train_llm(args) -> Dict[str, Any]:
    cfg: ArchConfig = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if cfg.family in ("vlm", "audio"):
        raise SystemExit("protocol training demo uses text-family archs; "
                         "vlm/audio exercise the serving path "
                         "(launch.serve) and the dry-run")
    B, S = args.batch_size, args.seq_len
    data = synth.make_token_stream(max(B * 8, 64), S, cfg.vocab_size,
                                   cfg.aux_vocab_size, seed=args.seed)
    task = llm_task(cfg, remat=args.remat)
    base = CELUConfig(R=args.R, W=args.W, xi_degrees=args.xi,
                      weighting=not args.no_weighting,
                      compression=args.compression,
                      pipeline_depth=args.pipeline_depth,
                      pipeline_lr_damping=args.pipeline_lr_damping,
                      cache_dtype=args.cache_dtype,
                      cache_fused=not args.no_cache_fusion)
    celu_cfg, n_local = engine.preset_config(args.protocol, base)
    params = vfl.init_all(jax.random.PRNGKey(args.seed), cfg)
    opt = make_opt(args)

    it = synth.token_batches(data, B, seed=args.seed)
    _, ba0, bb0 = next(it)
    etask = engine.lift_two_party(task)
    state = engine.init_state(etask, engine.lift_two_party_params(params),
                              opt, celu_cfg, [_as_jax(ba0)], _as_jax(bb0))
    depth = celu_cfg.pipeline_depth
    if depth:
        pe = engine.make_pipeline(etask, opt, celu_cfg, depth=depth,
                                  local_steps=n_local)
        rs = pe.init(state)
    else:
        rnd, compile_s = _compile_round(
            engine.make_round(etask, opt, celu_cfg, local_steps=n_local,
                              donate=True),
            state, [_as_jax(ba0)], _as_jax(bb0))
    it = synth.token_batches(data, B, seed=args.seed)
    losses = []
    for i in range(args.rounds):
        bi, ba, bb = next(it)
        if depth:
            rs, m = pe.step(rs, [_as_jax(ba)], _as_jax(bb), bi)
        else:
            state, m = rnd(state, [_as_jax(ba)], _as_jax(bb), bi)
        losses.append(float(m["loss"]))
        if (i + 1) % max(1, args.rounds // 10) == 0:
            print(f"round {i+1:4d} loss {losses[-1]:.4f}", flush=True)
    if depth:
        rs, _ = pe.flush(rs)       # drain the last in-flight local scan
        state = pe.finalize(rs)    # train_dlrm pattern: state holds the
                                   # drained model for future extension
    print(f"[done] {args.arch} {args.protocol}: "
          f"loss {losses[0]:.4f} -> {losses[-1]:.4f}")
    out = {"arch": args.arch, "losses": losses}
    if not depth:
        out.update(compile_s=compile_s, round=rnd)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--protocol", default="celu",
                    choices=("vanilla", "fedbcd", "celu"))
    ap.add_argument("--rounds", type=int, default=100)
    ap.add_argument("--batch-size", type=int, default=256)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--R", type=int, default=5)
    ap.add_argument("--W", type=int, default=5)
    ap.add_argument("--xi", type=float, default=60.0)
    ap.add_argument("--no-weighting", action="store_true")
    ap.add_argument("--compression", default="", metavar="CODEC",
                    help="wire codec for the simulated WAN (e.g. int8_topk;"
                         " see repro.core.compression.CODEC_SPECS)")
    ap.add_argument("--pipeline-depth", type=int, default=0, metavar="D",
                    help="0 = sequential rounds; 1 = overlap round t+1's "
                         "WAN exchange with round t's local updates "
                         "(paper §4.1 two-worker pipeline); D >= 2 = a "
                         "D-deep queue of in-flight exchanges for "
                         "high-RTT links where one exchange cannot hide "
                         "behind one local scan.  Every cached entry gets "
                         "D exchanges staler, so D >= 2 trades rounds for "
                         "wall-clock: weights are attenuated w -> w^(1+s) "
                         "per slot and updates lr-damped by "
                         "1/(1 + c*s) (see --pipeline-lr-damping); D must "
                         "stay < W")
    ap.add_argument("--pipeline-lr-damping", type=float, default=0.25,
                    metavar="C",
                    help="staleness-aware lr damping coefficient c of the "
                         "eta/(1 + c*s) schedule applied to local and "
                         "fresh updates on the depth-D (D >= 2) pipeline; "
                         "0 disables (depths 0/1 never damp)")
    ap.add_argument("--cache-dtype", default="float32",
                    choices=("float32", "bfloat16", "int8", "int4"),
                    help="at-rest precision of the workset cache (int8 = "
                         "SR-quantized codes + fp32 per-row scales, ~4x "
                         "smaller; int4 nibble-packs two codes per byte, "
                         "~8x smaller; core/workset.py storage codec)")
    ap.add_argument("--no-cache-fusion", action="store_true",
                    help="disable the fused gather→dequant→weight sample "
                         "megakernel (pin the materializing reference "
                         "path)")
    ap.add_argument("--fault-drop-prob", type=float, default=0.0,
                    metavar="P",
                    help="per-attempt exchange drop probability of the "
                         "chaos layer (core/faults.py); any --fault-* "
                         "axis switches the scheduler to the seeded "
                         "ChaosEngine")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="seed of the deterministic fault schedule")
    ap.add_argument("--fault-max-retries", type=int, default=2,
                    help="wire retries per exchange before the round's "
                         "update is abandoned (residuals absorb it)")
    ap.add_argument("--fault-straggler-prob", type=float, default=0.0,
                    metavar="P",
                    help="probability a delivered exchange arrives late")
    ap.add_argument("--fault-straggler-rounds", type=int, default=2,
                    help="max rounds of straggler delay")
    ap.add_argument("--fault-dropout", action="append", default=[],
                    metavar="PARTY:START:ROUNDS",
                    help="drop a party for a span of rounds (repeatable), "
                         "e.g. a0:40:5 or b:100:10; the survivors keep "
                         "local-updating on cached statistics")
    ap.add_argument("--checkpoint", default="", metavar="PATH",
                    help="save the FULL round state (params, optimizer, "
                         "worksets, transport residuals, in-flight "
                         "exchange queue) to PATH every "
                         "--checkpoint-every rounds; restored runs are "
                         "bit-consistent")
    ap.add_argument("--checkpoint-every", type=int, default=50,
                    metavar="N")
    ap.add_argument("--resume", default="", metavar="PATH",
                    help="resume from a --checkpoint file (bit-exact: "
                         "same flags, same seed)")
    ap.add_argument("--optimizer", default="adagrad",
                    choices=("adagrad", "sgd", "adam", "sm3"))
    ap.add_argument("--opt-state-dtype", default="float32",
                    choices=("float32", "bfloat16", "int8"),
                    help="at-rest precision of the AdaGrad accumulator "
                         "(int8 = sqrt-space codes + fp32 per-row master "
                         "scales, ~4x smaller; optim/quantized.py)")
    ap.add_argument("--remat", default=True,
                    action=argparse.BooleanOptionalAction,
                    help="activation-checkpoint the LLM tower scans "
                         "(recompute in backward; --no-remat stores all "
                         "activations)")
    ap.add_argument("--lr", type=float, default=0.01)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--small", action="store_true",
                    help="smaller DLRM dims for quick CPU runs")
    ap.add_argument("--n-train", type=int, default=32768)
    ap.add_argument("--n-test", type=int, default=8192)
    args = ap.parse_args(argv)

    if args.arch in ("wdl-criteo", "dssm-avazu"):
        return train_dlrm(args)
    return train_llm(args)


if __name__ == "__main__":
    from .compile_cache import enable
    enable()
    main()
