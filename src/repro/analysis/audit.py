"""The audit orchestrator: build round traces for every supported config
and run the three invariant families over each.

Per audited case this module:

  1. builds a small but structurally faithful K-party task (real
     ``KPartyTask``, real ``init_state``, real ``_make_stages`` with the
     production transport/codec/cache path — only the model is tiny);
  2. composes the stages in the ORDER the schedule under audit executes
     them — depth 0 sequential, depth 1 static-staleness overlap, depth
     D >= 2 as two CHAINED exchange dispatches (the ``PendingExchange``
     queue's residual chain) plus dynamic-staleness scan/merge — and
     traces the composition to one jaxpr under
     :func:`markers.instrumented`;
  3. walks the jaxpr with the taint engine (``taint.py``), reconciles
     the byte ledger (``wire_audit.py``), and lints the engine's fused
     kernel promises at the audited geometry (``kernel_lint.py``).

Input taints: each party's params / optimizer state / raw batch / cached
features are that party's raw sources; workset ``z``/``dz`` rings hold
already-released messages (untainted); error-feedback residuals are raw
to their owner and enter pre-seeded with the ``wire`` stage — they are
differences of wire-cast values by construction (every registered send
path maintains that invariant), and without the seed every stateful
codec path would false-positive on its first re-encode.
"""
from __future__ import annotations

from dataclasses import asdict, dataclass
from typing import Any, Dict, List, Optional, Sequence

from .report import AuditReport, CaseResult, Finding
from .taint import EMPTY, OutTag, Taint, TraceAudit, audit_trace, raw_of

AUDIT_B = 64        # audited batch geometry (fusable: 64 | BLOCK_B)
AUDIT_Z = 8         # cut-layer width
AUDIT_FA = 6        # feature-party input width
AUDIT_FB = 5        # label-party own-feature width
AUDIT_T = 3         # fields of each party's embedding table (tables=True)
AUDIT_V = 11        # rows of each field's table


@dataclass(frozen=True)
class AuditCase:
    name: str
    K: int = 1
    depth: int = 0
    compression: str = ""
    cache_dtype: str = "float32"
    dp_sigma: float = 0.0
    wire_dtype: str = "float32"
    # chaos-layer schedule: the first dispatch's wire transfer is LOST
    # and the transport's recover_dropped folds its decoded messages back
    # into the error-feedback residuals before the next dispatch
    # (core/faults.py) — the audit proves the absorbed residuals still
    # clear the boundary theorem on the retransmission
    dropped: bool = False
    # each party also embeds field ids through a declared table, so the
    # rounds take the row path (core/rows.py): compact tables, id remap,
    # row-sparse optimizer steps
    tables: bool = False


def default_cases(quick: bool = False) -> List[AuditCase]:
    """The supported-config matrix, factorized so every axis value is
    covered without the full cross product: codec x DP at depth 0, depth
    x K at the heaviest codec, cache dtypes at depth 2, wire dtype."""
    from ..core.compression import CODEC_SPECS

    def mk(**kw):
        kw.setdefault("name", "-".join(
            [f"K{kw.get('K', 1)}", f"d{kw.get('depth', 0)}",
             kw.get("compression") or "wire",
             kw.get("cache_dtype", "float32"),
             f"dp{kw.get('dp_sigma', 0.0):g}",
             ] + ([kw["wire_dtype"]] if kw.get("wire_dtype",
                                               "float32") != "float32"
                  else [])
               + (["drop"] if kw.get("dropped") else [])
               + (["tables"] if kw.get("tables") else [])))
        return AuditCase(**kw)

    if quick:
        return [mk(), mk(tables=True, depth=1),
                mk(compression="topk_int8", dp_sigma=0.3, depth=2,
                   cache_dtype="int8"),
                mk(compression="topk_int8", dp_sigma=0.3, depth=2,
                   cache_dtype="int8", dropped=True),
                mk(depth=2, compression="int8", cache_dtype="int4"),
                mk(compression="int8", wire_dtype="bfloat16")]

    cases = []
    for spec in ("",) + tuple(CODEC_SPECS):
        for dp in (0.0, 0.3):
            cases.append(mk(compression=spec, dp_sigma=dp))
    for K in (1, 3):
        for depth in (0, 1, 2, 4):
            cases.append(mk(K=K, depth=depth, compression="topk_int8",
                            cache_dtype="int8", dp_sigma=0.3))
    for cd in ("float32", "bfloat16", "int8", "int4"):
        cases.append(mk(depth=2, compression="int8", cache_dtype=cd))
    # int4 at-rest rides the packed-nibble fused sample path; cover it at
    # K > 1 and under the chaos drop-absorb schedule too
    cases.append(mk(K=3, depth=2, compression="topk_int8",
                    cache_dtype="int4", dp_sigma=0.3))
    cases.append(mk(depth=2, compression="topk_int8", cache_dtype="int4",
                    dropped=True))
    for spec in ("", "int8"):
        cases.append(mk(compression=spec, wire_dtype="bfloat16"))
    # chaos layer: lost exchange absorbed into the residuals, with and
    # without DP noise riding the dropped messages, at both K widths
    for K in (1, 3):
        cases.append(mk(K=K, depth=2, compression="topk_int8",
                        cache_dtype="int8", dp_sigma=0.3, dropped=True))
    cases.append(mk(depth=2, compression="topk_int8", dropped=True))
    # the row path: embedding tables at every depth shape, K > 1
    for depth in (0, 1, 2):
        cases.append(mk(depth=depth, tables=True))
    cases.append(mk(K=3, depth=2, compression="topk_int8",
                    cache_dtype="int8", dp_sigma=0.3, tables=True))
    # dedupe (the sweeps overlap at the origin), keep first occurrence
    seen, out = set(), []
    for c in cases:
        if c.name not in seen:
            seen.add(c.name)
            out.append(c)
    return out


# --------------------------------------------------------------------------
# Toy-but-faithful K-party task
# --------------------------------------------------------------------------
def _toy_task(K: int, tables: bool = False):
    import jax.numpy as jnp

    from ..core import engine as E
    from ..core.rows import RowTables, Tables

    def embed(p, batch):
        if not tables:
            return 0.0
        f = jnp.arange(AUDIT_T)[None, :]
        return p["embed"][f, batch["ids"]].sum(axis=1)

    def forward_a(p, batch):
        return jnp.tanh(batch["x"] @ p["w"] + p["b"] + embed(p, batch))

    def loss_b(p, z_list, batch):
        own = jnp.tanh(batch["x"] @ p["w_own"] + embed(p, batch))
        h = jnp.concatenate(list(z_list) + [own], axis=1)
        logits = (h @ p["w_top"])[:, 0]
        y = batch["y"]
        li = jnp.maximum(logits, 0.0) - logits * y + \
            jnp.log1p(jnp.exp(-jnp.abs(logits)))
        return li, jnp.float32(0.0)

    params = {
        "a": [{"w": jnp.zeros((AUDIT_FA, AUDIT_Z)),
               "b": jnp.zeros((AUDIT_Z,))} for _ in range(K)],
        "b": {"w_own": jnp.zeros((AUDIT_FB, AUDIT_Z)),
              "w_top": jnp.zeros(((K + 1) * AUDIT_Z, 1))},
    }
    batches_a = [{"x": jnp.zeros((AUDIT_B, AUDIT_FA))} for _ in range(K)]
    batch_b = {"x": jnp.zeros((AUDIT_B, AUDIT_FB)),
               "y": jnp.zeros((AUDIT_B,))}
    if not tables:
        return E.KPartyTask(forward_a, loss_b), params, batches_a, batch_b
    table = jnp.zeros((AUDIT_T, AUDIT_V, AUDIT_Z))
    ids = jnp.zeros((AUDIT_B, AUDIT_T), jnp.int32)
    params = {"a": [{**p, "embed": table} for p in params["a"]],
              "b": {**params["b"], "embed": table}}
    batches_a = [{**b, "ids": ids} for b in batches_a]
    batch_b = {**batch_b, "ids": ids}
    decl = Tables("ids", ("embed",))
    task = E.KPartyTask(forward_a, loss_b, RowTables(a=decl, b=decl))
    return task, params, batches_a, batch_b


# --------------------------------------------------------------------------
# Input / output tag trees
# --------------------------------------------------------------------------
def _const(tree, taint):
    import jax
    return jax.tree_util.tree_map(lambda _: taint, tree)


def _ws_tags(ws, batch_taint: Taint):
    """Workset rings hold RELEASED z/dz messages (untainted) plus the
    owner's raw batch; the clocks are public."""
    tags = {k: _const(v, EMPTY) for k, v in ws.items() if k != "buf"}
    tags["buf"] = {k: _const(sub, batch_taint if k == "batch" else EMPTY)
                   for k, sub in ws["buf"].items()}
    return tags


def _residual_seed(party: str) -> Taint:
    # raw to the owner, pre-seeded with the wire stage (see module doc)
    return Taint(raw=frozenset({party}), san=(("wire", 0),))


def _transport_tags(tstate, K: int):
    tags: Dict[str, Any] = {}
    for d, lst in tstate.items():
        owners = [f"a{i}" for i in range(K)] if d == "up" else ["b"] * K
        tags[d] = [_const(lst[i], _residual_seed(owners[i]))
                   for i in range(len(lst))]
    return tags


def _state_tags(state, K: int):
    A = [raw_of(f"a{i}") for i in range(K)]
    b = raw_of("b")
    return {
        "params": {"a": [_const(state["params"]["a"][i], A[i])
                         for i in range(K)],
                   "b": _const(state["params"]["b"], b)},
        "opt": {"a": [_const(state["opt"]["a"][i], A[i])
                      for i in range(K)],
                "b": _const(state["opt"]["b"], b)},
        "ws": {"a": [_ws_tags(state["ws"]["a"][i], A[i])
                     for i in range(K)],
               "b": _ws_tags(state["ws"]["b"], b)},
        "steps": _const(state["steps"], EMPTY),
        "comm_rounds": EMPTY,
        "transport": _transport_tags(state["transport"], K),
    }


_PUBLIC = frozenset()


def _out_state_tags(st_sds, K: int):
    A = [frozenset({f"a{i}"}) for i in range(K)]
    b = frozenset({"b"})

    def reg(tree, allowed, label):
        import jax
        return jax.tree_util.tree_map(lambda _: OutTag(allowed, label),
                                      tree)

    tp_tags: Dict[str, Any] = {}
    for d, lst in st_sds["transport"].items():
        owners = A if d == "up" else [b] * K
        tp_tags[d] = [reg(lst[i], owners[i], f"state.transport.{d}[{i}]")
                      for i in range(len(lst))]
    return {
        "params": {"a": [reg(st_sds["params"]["a"][i], A[i],
                             f"state.params.a[{i}]") for i in range(K)],
                   "b": reg(st_sds["params"]["b"], b, "state.params.b")},
        "opt": {"a": [reg(st_sds["opt"]["a"][i], A[i],
                          f"state.opt.a[{i}]") for i in range(K)],
                "b": reg(st_sds["opt"]["b"], b, "state.opt.b")},
        "ws": {"a": [reg(st_sds["ws"]["a"][i], A[i], f"state.ws.a[{i}]")
                     for i in range(K)],
               "b": reg(st_sds["ws"]["b"], b, "state.ws.b")},
        "steps": reg(st_sds["steps"], _PUBLIC, "state.steps"),
        "comm_rounds": reg(st_sds["comm_rounds"], _PUBLIC,
                           "state.comm_rounds"),
        "transport": tp_tags,
    }


# w_mean / w_zero_frac / rows_updated aggregate per-party statistics
# across ALL parties by design (sim-level diagnostics) — host rule skipped
# (None).
_METRIC_ALLOWED = {"loss": frozenset({"b"}), "local_steps": _PUBLIC,
                   "w_mean": None, "w_zero_frac": None, "rows_updated": None}


def _out_metric_tags(m_sds):
    import jax
    return {k: jax.tree_util.tree_map(
        lambda _: OutTag(_METRIC_ALLOWED.get(k, None), f"metrics.{k}"),
        v) for k, v in m_sds.items()}


# --------------------------------------------------------------------------
# One case
# --------------------------------------------------------------------------
def _make_celu(case: AuditCase):
    from ..configs.base import CELUConfig
    return CELUConfig(R=2, W=5, compression=case.compression,
                      cache_dtype=case.cache_dtype,
                      dp_sigma=case.dp_sigma,
                      wire_dtype=case.wire_dtype,
                      pipeline_depth=case.depth)


def _compose(case: AuditCase, stages, tp=None):
    """Wire the three stages in the order the schedule under audit runs
    them.  Depth >= 2 chains TWO exchange dispatches through the
    transport-residual state — the PendingExchange queue slots — and
    drives scan/apply with dynamic staleness scalars, exactly like
    ``PipelinedEngine`` does.  ``case.dropped`` (needs ``tp`` and depth
    >= 2) audits the chaos layer's drop-absorb path instead: the first
    dispatch's wire transfer is lost, ``tp.recover_dropped`` folds its
    decoded messages back into the residuals, and only the SECOND
    dispatch is merged — the scan rides stale cached statistics the
    whole time.  Both dispatches still count as wire sends (the bytes
    left the box before the loss)."""
    import jax.numpy as jnp
    compute, apply_, scan = stages
    depth = case.depth

    if case.dropped:
        if depth < 2 or tp is None:
            raise ValueError("dropped cases need depth >= 2 and the "
                             "audited transport")

        def fn(state, batches_a, batch_b, batch_idx):
            f1 = compute(state["params"], state["transport"], batches_a,
                         batch_b, state["comm_rounds"])
            ts = tp.recover_dropped(f1)          # f1's wire is LOST
            f2 = compute(state["params"], ts, batches_a, batch_b,
                         state["comm_rounds"] + 1)
            state, lm = scan(state, jnp.int32(depth))
            state, m = apply_(state, f2, batches_a, batch_b,
                              batch_idx + 1, jnp.int32(depth - 1))
            return state, {**m, **lm}
        return fn, 2

    if depth == 0:
        def fn(state, batches_a, batch_b, batch_idx):
            fresh = compute(state["params"], state["transport"],
                            batches_a, batch_b, state["comm_rounds"])
            state, m = apply_(state, fresh, batches_a, batch_b, batch_idx)
            state, lm = scan(state)
            return state, {**m, **lm}
        return fn, 1

    if depth == 1:
        def fn(state, batches_a, batch_b, batch_idx):
            fresh = compute(state["params"], state["transport"],
                            batches_a, batch_b, state["comm_rounds"])
            state, lm = scan(state)
            state, m = apply_(state, fresh, batches_a, batch_b, batch_idx)
            return state, {**m, **lm}
        return fn, 1

    def fn(state, batches_a, batch_b, batch_idx):
        f1 = compute(state["params"], state["transport"], batches_a,
                     batch_b, state["comm_rounds"])
        f2 = compute(state["params"], f1["tstate"], batches_a, batch_b,
                     state["comm_rounds"] + 1)
        state, lm = scan(state, jnp.int32(depth))
        state, _ = apply_(state, f1, batches_a, batch_b, batch_idx,
                          jnp.int32(depth - 1))
        state, m = apply_(state, f2, batches_a, batch_b, batch_idx + 1,
                          jnp.int32(depth - 1))
        return state, {**m, **lm}
    return fn, 2


def _check_collectives(trace: TraceAudit, case: str,
                       pod_axis: Optional[str] = None) -> List[Finding]:
    """Simulated-WAN traces must contain NO mesh collectives; pod traces
    may only cross the pod axis through marked ppermutes."""
    findings = []
    colls = list(trace.collectives.values())
    if pod_axis is None:
        if colls:
            findings.append(Finding(
                code="taint.unmarked-collective", severity="error",
                where=f"{colls[0][0]}",
                detail=f"simulated-WAN trace contains mesh collective(s) "
                       f"{sorted({c[0] for c in colls})} — cross-device "
                       f"data movement outside the audited transport",
                case=case))
        return findings
    n_pp = 0
    for prim, axes in colls:
        if pod_axis in axes and prim != "ppermute":
            findings.append(Finding(
                code="taint.unmarked-collective", severity="error",
                where=prim,
                detail=f"collective '{prim}' crosses the '{pod_axis}' "
                       f"axis; only the transport's marked ppermute pair "
                       f"may move data over the inter-pod link",
                case=case))
        elif prim == "ppermute" and pod_axis in axes:
            n_pp += 1
    if n_pp != len(trace.boundaries):
        findings.append(Finding(
            code="taint.unmarked-collective", severity="error",
            where="ppermute",
            detail=f"trace contains {n_pp} ppermute(s) over "
                   f"'{pod_axis}' but only {len(trace.boundaries)} "
                   f"transport boundary mark(s) — a raw ppermute "
                   f"bypasses the transport",
            case=case))
    return findings


def trace_case(case: AuditCase, transport=None) -> CaseResult:
    """Trace + audit one configuration.  ``transport`` overrides the
    config-derived inner transport (used by the mutation self-tests)."""
    import jax
    import jax.numpy as jnp

    from ..core import engine as E
    from ..optim import make_optimizer
    from .kernel_lint import lint_engine_fusability
    from .markers import AuditedTransport, instrumented
    from .wire_audit import audit_wire

    celu = _make_celu(case)
    task, params, batches_a, batch_b = _toy_task(case.K, case.tables)
    opt = make_optimizer("adagrad", 0.1)
    tp_inner = transport if transport is not None \
        else E.make_transport(celu)
    tp = AuditedTransport(tp_inner, celu)

    state = E.init_state(task, params, opt, celu, batches_a, batch_b,
                         transport=tp_inner)
    stages = E._make_stages(
        task, opt, celu, n_local=celu.R, tp=tp, fused=True,
        pipeline_staleness=case.depth,
        lr_damping=celu.pipeline_lr_damping if case.depth >= 2 else 0.0)
    fn, n_computes = _compose(case, stages, tp)
    args = (state, batches_a, batch_b, jnp.int32(3))

    # ONE trace, instrumented, returning the output structure too.  (An
    # uninstrumented jax.eval_shape first would poison the jit trace
    # cache: make_jaxpr on the same fn + avals reuses the cached,
    # mark-free jaxpr and the audit would silently check nothing.)
    tp._counts.clear()                  # fresh party indices per trace
    with instrumented():
        closed, out_sds = jax.make_jaxpr(fn, return_shape=True)(*args)

    in_tags = (_state_tags(state, case.K),
               [_const(batches_a[i], raw_of(f"a{i}"))
                for i in range(case.K)],
               _const(batch_b, raw_of("b")), EMPTY)
    in_leaves = jax.tree_util.tree_leaves(
        in_tags, is_leaf=lambda x: isinstance(x, Taint))
    assert len(in_leaves) == len(closed.jaxpr.invars), \
        (case.name, len(in_leaves), len(closed.jaxpr.invars))

    st_sds, m_sds = out_sds
    out_tags = (_out_state_tags(st_sds, case.K), _out_metric_tags(m_sds))
    out_leaves = jax.tree_util.tree_leaves(
        out_tags, is_leaf=lambda x: isinstance(x, OutTag))

    trace = audit_trace(closed, in_leaves, out_leaves, case=case.name)
    findings = list(trace.findings)
    findings += _check_collectives(trace, case.name)

    z_shapes = [(AUDIT_B, AUDIT_Z)] * case.K
    wire_findings, stats = audit_wire(tp_inner, celu, z_shapes, trace,
                                      n_computes, case.name)
    findings += wire_findings
    findings += lint_engine_fusability(celu, AUDIT_B, case.name)

    if not trace.boundaries:
        findings.append(Finding(
            code="audit.no-boundaries", severity="error",
            where="instrumented trace",
            detail="the trace contains no boundary marks at all — the "
                   "analyzer instrumentation is broken, the audit "
                   "proves nothing", case=case.name))
    if celu.cache_fused and not trace.pallas_calls:
        findings.append(Finding(
            code="audit.no-pallas", severity="warning",
            where="instrumented trace",
            detail="no pallas_call in a cache_fused trace at a fusable "
                   "geometry — the fused path the config promises did "
                   "not trace", case=case.name))

    stats["eqns"] = len(closed.jaxpr.eqns)
    stats["pallas_calls"] = len(trace.pallas_calls)
    return CaseResult(name=case.name, config=asdict(case),
                      findings=findings, stats=stats)


# --------------------------------------------------------------------------
# Fleet (vmapped batched-state) case
# --------------------------------------------------------------------------
AUDIT_JOBS = 2      # fleet width of the batched-state audit trace


def _pending_tags(pending, K: int):
    """Input taints for an adopted steady-state exchange queue.  Queue
    slots hold what a prior compute dispatch produced: RELEASED z/dz
    messages (the boundary mark cleared their raw taint when they crossed
    the wire), each party's own gradient and cached batch (raw to the
    owner — the host rule's PendingExchange theorem), B's loss, and the
    wire-seeded error-feedback residual snapshot."""
    fresh = pending.fresh
    ftags = dict(
        zs=[_const(z, EMPTY) for z in fresh["zs"]],
        dzs=[_const(z, EMPTY) for z in fresh["dzs"]],
        g_as=[_const(fresh["g_as"][i], raw_of(f"a{i}")) for i in range(K)],
        g_b=_const(fresh["g_b"], raw_of("b")),
        loss=raw_of("b"),
        tstate=_transport_tags(fresh["tstate"], K),
    )
    return pending._replace(
        fresh=ftags,
        batches_a=[_const(pending.batches_a[i], raw_of(f"a{i}"))
                   for i in range(K)],
        batch_b=_const(pending.batch_b, raw_of("b")),
        batch_idx=EMPTY, dispatched_at=EMPTY)


def _out_pending_tags(p_sds, K: int):
    """Host rule for the OUTPUT queue: released messages must stay
    public, every private leaf must stay with its owner — a refactor
    that parks a pre-release cut tensor in a queue slot another party
    reads is exactly what this region catches (taint.py module doc)."""
    import jax

    def reg(tree, allowed, label):
        return jax.tree_util.tree_map(lambda _: OutTag(allowed, label),
                                      tree)

    A = [frozenset({f"a{i}"}) for i in range(K)]
    b = frozenset({"b"})
    fresh = p_sds.fresh
    tp_tags = {}
    for d, lst in fresh["tstate"].items():
        owners = A if d == "up" else [b] * K
        tp_tags[d] = [reg(lst[i], owners[i],
                          f"fleet.pending.tstate.{d}[{i}]")
                      for i in range(len(lst))]
    ftags = dict(
        zs=[reg(fresh["zs"][i], _PUBLIC, f"fleet.pending.zs[{i}]")
            for i in range(K)],
        dzs=[reg(fresh["dzs"][i], _PUBLIC, f"fleet.pending.dzs[{i}]")
             for i in range(K)],
        g_as=[reg(fresh["g_as"][i], A[i], f"fleet.pending.g_as[{i}]")
              for i in range(K)],
        g_b=reg(fresh["g_b"], b, "fleet.pending.g_b"),
        loss=OutTag(b, "fleet.pending.loss"),
        tstate=tp_tags,
    )
    return p_sds._replace(
        fresh=ftags,
        batches_a=[reg(p_sds.batches_a[i], A[i],
                       f"fleet.pending.batches_a[{i}]") for i in range(K)],
        batch_b=reg(p_sds.batch_b, b, "fleet.pending.batch_b"),
        batch_idx=OutTag(_PUBLIC, "fleet.pending.batch_idx"),
        dispatched_at=OutTag(_PUBLIC, "fleet.pending.dispatched_at"))


def trace_fleet_case(case: Optional[AuditCase] = None,
                     jobs: int = AUDIT_JOBS, transport=None) -> CaseResult:
    """Audit the vmapped fleet step: ``jobs`` stacked scheduler states
    (engine state + PendingExchange queue + traced phase) driven through
    ONE batched jaxpr, at the heaviest supported config by default
    (depth 2, top-k + int8 codec, DP noise, int8 cache).

    The batched-state theorem this proves: the taint, sanitizer-ordering
    and byte-ledger analyses are invariant under the leading job axis —
    every boundary crossing carries ``(jobs,) + z_shape`` (ONE mark moves
    the fleet's messages), the queue's host rule still separates parties
    per slot, and the per-job wire ledger reconciles unchanged."""
    import jax
    import jax.numpy as jnp

    from ..core import engine as E
    from ..fleet.scheduler import JobHyper, make_fleet_step
    from ..optim import make_optimizer
    from .kernel_lint import lint_engine_fusability
    from .markers import AuditedTransport, instrumented
    from .wire_audit import audit_wire

    if case is None:
        case = AuditCase(name=f"fleet-N{jobs}-K1-d2-topk_int8-int8-dp0.3",
                         K=1, depth=2, compression="topk_int8",
                         cache_dtype="int8", dp_sigma=0.3)
    celu = _make_celu(case)
    task, params, batches_a, batch_b = _toy_task(case.K)
    opt = make_optimizer("adagrad", 0.1)
    tp_inner = transport if transport is not None \
        else E.make_transport(celu)
    tp = AuditedTransport(tp_inner, celu)

    state = E.init_state(task, params, opt, celu, batches_a, batch_b,
                         transport=tp_inner)
    init, step, _ = make_fleet_step(task, celu, depth=case.depth,
                                    transport=tp)
    fs = init(state, batches_a, batch_b)
    # steady-state queue phase: slots adopted as if a prior dispatch
    # filled them, so the traced merge cond sees a live queue
    fs = fs._replace(n_pending=jnp.int32(case.depth))
    stack = lambda t: jax.tree_util.tree_map(
        lambda x: jnp.stack([jnp.asarray(x)] * jobs), t)
    fs_j, hyper_j = stack(fs), stack(JobHyper.for_spec(0.1, 60.0))
    vstep = jax.vmap(step, in_axes=(0, 0, None, None, None))
    args = (fs_j, hyper_j, batches_a, batch_b, jnp.int32(3))

    tp._counts.clear()
    with instrumented():
        closed, out_sds = jax.make_jaxpr(vstep, return_shape=True)(*args)

    K = case.K
    fs_tags = fs._replace(state=_state_tags(state, K),
                          pending=_pending_tags(fs.pending, K),
                          n_pending=EMPTY)
    hyper_tags = JobHyper(lr=EMPTY, cos_xi=EMPTY,
                          keys={k: EMPTY for k in hyper_j.keys})
    in_tags = (fs_tags, hyper_tags,
               [_const(batches_a[i], raw_of(f"a{i}")) for i in range(K)],
               _const(batch_b, raw_of("b")), EMPTY)
    in_leaves = jax.tree_util.tree_leaves(
        in_tags, is_leaf=lambda x: isinstance(x, Taint))
    assert len(in_leaves) == len(closed.jaxpr.invars), \
        (case.name, len(in_leaves), len(closed.jaxpr.invars))

    fs_sds, m_sds = out_sds
    out_tags = (fs_sds._replace(
        state=_out_state_tags(fs_sds.state, K),
        pending=_out_pending_tags(fs_sds.pending, K),
        n_pending=OutTag(_PUBLIC, "fleet.n_pending")),
        _out_metric_tags(m_sds))
    out_leaves = jax.tree_util.tree_leaves(
        out_tags, is_leaf=lambda x: isinstance(x, OutTag))

    trace = audit_trace(closed, in_leaves, out_leaves, case=case.name)
    findings = list(trace.findings)
    findings += _check_collectives(trace, case.name)

    z_shapes = [(AUDIT_B, AUDIT_Z)] * K
    wire_findings, stats = audit_wire(tp_inner, celu, z_shapes, trace,
                                      n_computes=1, case=case.name,
                                      jobs=jobs)
    findings += wire_findings
    findings += lint_engine_fusability(celu, AUDIT_B, case.name)

    if not trace.boundaries:
        findings.append(Finding(
            code="audit.no-boundaries", severity="error",
            where="instrumented fleet trace",
            detail="the vmapped trace contains no boundary marks — the "
                   "mark primitive's batching rule is broken and the "
                   "fleet audit proves nothing", case=case.name))

    stats["eqns"] = len(closed.jaxpr.eqns)
    stats["pallas_calls"] = len(trace.pallas_calls)
    cfg = asdict(case)
    cfg["jobs"] = jobs
    return CaseResult(name=case.name, config=cfg, findings=findings,
                      stats=stats)


# --------------------------------------------------------------------------
# Pod (SPMD) case
# --------------------------------------------------------------------------
def trace_pod_case() -> CaseResult:
    """Audit the shard_map pod round: both ppermute crossings must be the
    transport's marked pair and nothing else may cross the pod axis.
    Party-stacked arrays hold both parties in one leaf, so the per-party
    host rule does not apply here — the collective whitelist is the
    boundary theorem on this path."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    name = "pod-shardmap-d1"
    if len(jax.devices()) < 2:
        return CaseResult(
            name=name, config={"skipped": True},
            findings=[Finding(
                code="audit.pod-skipped", severity="info",
                where="jax.devices()",
                detail="pod audit needs >= 2 devices; run the CLI (it "
                       "forces a 2-device CPU mesh) or set XLA_FLAGS="
                       "--xla_force_host_platform_device_count=2",
                case=name)],
            stats={"skipped": True})

    from jax.sharding import Mesh

    from ..core import engine as E
    from ..optim import make_optimizer
    from .markers import AuditedPodTransport, instrumented

    B, F, Z, W = 16, 6, 8, 4
    mesh = Mesh(np.array(jax.devices()[:2]), ("pod",))
    tp = AuditedPodTransport(E.PodTransport())
    opt = make_optimizer("adagrad", 0.1)

    def tower_fwd(p, x):
        return jnp.tanh(x @ p["w"])

    def top_loss(p, za, zb, y):
        logits = ((za + zb) @ p["w"])[:, 0]
        return jnp.maximum(logits, 0.0) - logits * y + \
            jnp.log1p(jnp.exp(-jnp.abs(logits)))

    params = {"tower": {"w": jnp.zeros((2, F, Z))},
              "top": {"w": jnp.zeros((2, Z, 1))}}
    opt_state = opt.init(params)
    ws = {"z": jnp.zeros((2, W, B, Z)), "dz": jnp.zeros((2, W, B, Z)),
          "x": jnp.zeros((2, W, B, F)), "y": jnp.zeros((2, W, B)),
          "time": jnp.zeros((2,), jnp.int32)}
    x = jnp.zeros((2, B, F))
    y = jnp.zeros((2, B))

    fn = E.make_pod_round(mesh, opt, R=2, cos_xi=0.5,
                          tower_fwd=tower_fwd, top_loss=top_loss,
                          transport=tp, pipeline_depth=1)
    tp._n = 0
    with instrumented():
        closed = jax.make_jaxpr(fn)(params, opt_state, ws, x, y)

    in_leaves = [EMPTY] * len(closed.jaxpr.invars)
    out_leaves = [OutTag(None, "pod")] * len(closed.jaxpr.outvars)
    trace = audit_trace(closed, in_leaves, out_leaves, case=name)
    findings = list(trace.findings)
    findings += _check_collectives(trace, name, pod_axis="pod")
    if len(trace.boundaries) != 2:
        findings.append(Finding(
            code="audit.no-boundaries", severity="error",
            where="pod trace",
            detail=f"expected the up/down ppermute boundary pair, found "
                   f"{len(trace.boundaries)} boundary mark(s)",
            case=name))
    return CaseResult(name=name, config={"K": 1, "depth": 1,
                                         "transport": "PodTransport"},
                      findings=findings,
                      stats={"boundaries": len(trace.boundaries),
                             "eqns": len(closed.jaxpr.eqns)})


# --------------------------------------------------------------------------
# Serving (continuous-batching decode) case
# --------------------------------------------------------------------------
def trace_serve_case(transport=None) -> CaseResult:
    """Audit ONE exchange decode step of the serving engine
    (``repro.serve.engine.make_step_fn`` with ``exchange=True``) at
    reduced smollm-360m geometry.

    The serving boundary theorem: Party A's raw material (embedding
    params, tower KV cache, aux token) may reach Party B's logits — and
    hence the emitted token — ONLY through the uplink boundary (wire +
    codec encode under int8 compression), and the activation ring Party B
    fuses against may hold ONLY released (post-wire) rows.  Concretely
    the output tags require: new ``cache_a`` stays with A, new
    ``cache_b`` / the token stay with B, the ring contents and A's next
    aux token (downlink product) are fully released.  A refactor that
    inserts the pre-wire ``z`` into the ring, or derives ``token_a``
    from the logits without the downlink crossing, fails this case."""
    import jax
    import jax.numpy as jnp

    from ..configs import get_config
    from ..configs.base import CELUConfig
    from ..core import engine as E
    from ..models import vfl
    from ..serve.engine import ServeConfig, ServeEngine, make_step_fn
    from .markers import AuditedTransport, instrumented

    name = "serve-cb2-int8-int8"
    cfg = get_config("smollm-360m").reduced()
    scfg = ServeConfig(capacity=2, prompt_len=4, max_new_tokens=2,
                       compression="int8", cache_dtype="int8",
                       ring_slots=2)
    celu = CELUConfig(compression="int8/identity")
    tp_inner = transport if transport is not None \
        else E.make_transport(celu)
    tp = AuditedTransport(tp_inner, celu)
    params = vfl.init_all(jax.random.PRNGKey(0), cfg)
    # the engine only supplies the stacked state template; the traced fn
    # is the raw (unjitted) exchange step wired to the audited transport
    state = ServeEngine(params, cfg, scfg).state
    step = make_step_fn(cfg, scfg, tp, exchange=True)
    args = (params, state, jax.random.PRNGKey(0))

    tp._counts.clear()
    with instrumented():
        closed, out_sds = jax.make_jaxpr(step, return_shape=True)(*args)

    a, b = raw_of("a0"), raw_of("b")
    in_tags = (
        {"a": _const(params["a"], a), "b": _const(params["b"], b)},
        {"cache_a": _const(state["cache_a"], a),
         "cache_b": _const(state["cache_b"], b),
         # ring rows are RELEASED messages; tokens already crossed the
         # downlink; the schedule vectors are public
         "ws": _const(state["ws"], EMPTY),
         "active": EMPTY, "pos": EMPTY,
         "token": b,            # B's own last emission feeds only B
         "token_a": EMPTY,      # A's aux token is a downlink product
         "remaining": EMPTY},
        EMPTY)                  # rng
    in_leaves = jax.tree_util.tree_leaves(
        in_tags, is_leaf=lambda x: isinstance(x, Taint))
    assert len(in_leaves) == len(closed.jaxpr.invars), \
        (name, len(in_leaves), len(closed.jaxpr.invars))

    A0, B = frozenset({"a0"}), frozenset({"b"})

    def reg(tree, allowed, label):
        return jax.tree_util.tree_map(lambda _: OutTag(allowed, label),
                                      tree)

    st_sds, tok_sds, prod_sds = out_sds
    out_tags = (
        {"cache_a": reg(st_sds["cache_a"], A0, "serve.cache_a"),
         "cache_b": reg(st_sds["cache_b"], B, "serve.cache_b"),
         "ws": reg(st_sds["ws"], _PUBLIC, "serve.ws"),
         "active": OutTag(_PUBLIC, "serve.active"),
         "pos": OutTag(_PUBLIC, "serve.pos"),
         "token": OutTag(B, "serve.token"),
         "token_a": OutTag(_PUBLIC, "serve.token_a"),
         "remaining": OutTag(_PUBLIC, "serve.remaining")},
        reg(tok_sds, B, "serve.tokens"),
        OutTag(_PUBLIC, "serve.produced"))
    out_leaves = jax.tree_util.tree_leaves(
        out_tags, is_leaf=lambda x: isinstance(x, OutTag))

    trace = audit_trace(closed, in_leaves, out_leaves, case=name)
    # Declared exception: the downlink carries a token ID as float32 (the
    # wire dtype) and Party A converts it back with float32->int32.  The
    # cast lint counts every f->i conversion as narrowing, but this one
    # is exact by construction — token ids < 2^24 are exactly
    # representable in float32 — and it sits AFTER the wire mark, so no
    # declared stage can clear it.  Any OTHER cast on any other output
    # still fails the case.
    def _declared_token_cast(f):
        return (f.code == "kernel.unmediated-cast"
                and f.where == "serve.token_a"
                and "float32->int32" in f.detail
                and "bf16" not in f.detail and "int8" not in f.detail)
    declared = [f for f in trace.findings if _declared_token_cast(f)]
    findings = [f for f in trace.findings if not _declared_token_cast(f)]
    findings += _check_collectives(trace, name)

    # one vmapped uplink mark (the C stacked z rows) + one vmapped
    # downlink mark (the C token ids) per exchange step
    ups = [r for r in trace.boundaries.values() if r.direction == "up"]
    downs = [r for r in trace.boundaries.values() if r.direction == "down"]
    if len(ups) != 1 or len(downs) != 1:
        findings.append(Finding(
            code="audit.no-boundaries", severity="error",
            where="serve exchange step",
            detail=f"expected exactly 1 uplink + 1 downlink boundary "
                   f"mark (the vmapped per-lane sends), found "
                   f"{len(ups)} up / {len(downs)} down — a decode "
                   f"release is bypassing the serving wire",
            case=name))
    if not trace.pallas_calls:
        findings.append(Finding(
            code="audit.no-pallas", severity="warning",
            where="serve exchange step",
            detail="int8 ring read did not trace through a fused "
                   "gather→dequant pallas_call", case=name))

    stats = {"eqns": len(closed.jaxpr.eqns),
             "boundaries": len(trace.boundaries),
             "uplink_marks": len(ups), "downlink_marks": len(downs),
             "pallas_calls": len(trace.pallas_calls),
             "declared_token_id_casts": len(declared)}
    return CaseResult(
        name=name,
        config={"capacity": scfg.capacity, "compression": "int8/identity",
                "cache_dtype": scfg.cache_dtype, "arch": "smollm-360m",
                "reduced": True},
        findings=findings, stats=stats)


# --------------------------------------------------------------------------
# Entry point
# --------------------------------------------------------------------------
def run_audit(cases: Optional[Sequence[AuditCase]] = None, *,
              include_pod: bool = True,
              include_fleet: bool = True,
              include_serve: bool = True,
              include_kernel_lint: bool = True) -> AuditReport:
    import jax

    from .kernel_lint import CONTRACTS, DEFAULT_GEOMETRIES, lint_kernels

    if cases is None:
        cases = default_cases()
    results: List[CaseResult] = []
    if include_kernel_lint:
        kf = lint_kernels(DEFAULT_GEOMETRIES)
        results.append(CaseResult(
            name="kernel-contracts",
            config={"geometries": [g.name for g in DEFAULT_GEOMETRIES]},
            findings=kf,
            stats={"contracts": len(CONTRACTS),
                   "geometries": len(DEFAULT_GEOMETRIES)}))
    for case in cases:
        results.append(trace_case(case))
    if include_fleet:
        results.append(trace_fleet_case())
    if include_serve:
        results.append(trace_serve_case())
    if include_pod:
        results.append(trace_pod_case())
    return AuditReport(
        cases=results,
        meta={"jax": jax.__version__, "devices": len(jax.devices()),
              "audited_cases": len(results)})
