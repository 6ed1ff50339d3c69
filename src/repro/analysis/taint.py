"""Cross-party information-flow analysis over traced round jaxprs.

The lattice: every traced value carries

  * ``raw``  — the set of parties whose UNRELEASED private data (features,
    labels, pre-release cut tensors, optimizer state, error-feedback
    residuals) flowed into it;
  * ``san``  — the sanitizer stages the value passed while tainted
    (``wire`` / ``encode`` / ``dp`` / ``cache``, as marked by
    :mod:`repro.analysis.markers`), with the eqn index of the latest
    application (for ordering checks);
  * ``casts`` — narrowing precision-cast sites (fp32 -> bf16/int8/int4 or
    float -> int) the value passed that no declared wire/encode/cache
    stage has vouched for yet.

Propagation is a forward walk of the jaxpr: outputs union the ``raw`` and
``casts`` of their inputs and intersect the ``san`` of their *tainted*
inputs (a value mixed from a sanitized and an unsanitized raw source is
not sanitized).  ``audit_mark`` eqns apply the semantics:

  * sanitizer marks add their stage (and clear pending casts for the
    declared stages);
  * boundary marks CHECK — raw taint present means the required stages
    must all be in ``san`` and the ordering constraints must hold — then
    release: raw taint converts to nothing (the value is now a released
    message both parties may hold).

Subjaxprs (pjit, scan, cond, custom_jvp/vjp, shard_map) are walked
recursively with 1:1 var mapping; scan runs its body to a fixed point so
carry-loop flows converge.  ``pallas_call`` is treated as an opaque
(conservative) op and recorded for the kernel-usage stats.  Collectives
are recorded with their axis names for the pod-boundary whitelist.

The host rule closes the theorem: every stage OUTPUT is declared hosted
at a party, and must carry no OTHER party's raw taint.  This is what
catches a refactor that routes a pre-release cut tensor into Party B's
loss, caches it in B's workset, or parks it in a ``PendingExchange``
queue slot — the value never reaches a transport send, so only the
output rule can see it.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, FrozenSet, List, Optional, Sequence, Tuple

from jax.extend.core import Literal

from .report import Finding

# Collectives that move DATA across a mesh axis (the pod boundary);
# axis_index only reads coordinates and is always allowed.
DATA_COLLECTIVES = ("ppermute", "psum", "pmax", "pmin", "pmean",
                    "all_gather", "all_to_all", "reduce_scatter",
                    "pbroadcast", "pgather")

_NARROW_FLOATS = ("bfloat16", "float16", "float8_e4m3fn", "float8_e5m2")


# --------------------------------------------------------------------------
# The taint lattice
# --------------------------------------------------------------------------
@dataclass(frozen=True)
class Taint:
    raw: FrozenSet[str] = frozenset()
    san: Tuple[Tuple[str, int], ...] = ()       # (stage, latest eqn idx)
    casts: FrozenSet[str] = frozenset()         # unmediated narrowing casts

    @property
    def san_names(self) -> FrozenSet[str]:
        return frozenset(n for n, _ in self.san)

    def san_idx(self, name: str) -> Optional[int]:
        for n, i in self.san:
            if n == name:
                return i
        return None

    def key(self):
        """Convergence key for scan fixed points: eqn indices shift
        between body re-walks, taint CONTENT must not."""
        return (self.raw, self.san_names, self.casts)


EMPTY = Taint()


def raw_of(party: str) -> Taint:
    return Taint(raw=frozenset({party}))


def _san_dict(t: Taint) -> Dict[str, int]:
    return dict(t.san)


def join(taints: Sequence[Taint]) -> Taint:
    """Output taint of a generic eqn over these input taints."""
    raw: FrozenSet[str] = frozenset()
    casts: FrozenSet[str] = frozenset()
    for t in taints:
        raw = raw | t.raw
        casts = casts | t.casts
    tainted = [t for t in taints if t.raw]
    if not tainted:
        return Taint(raw=raw, casts=casts)
    names = frozenset.intersection(*[t.san_names for t in tainted])
    san = tuple(sorted(
        (n, min(_san_dict(t)[n] for t in tainted)) for n in names))
    return Taint(raw=raw, san=san, casts=casts)


def sanitize(t: Taint, name: str, idx: int) -> Taint:
    san = dict(t.san)
    san[name] = idx
    casts = t.casts
    from .markers import DECLARED_CAST_STAGES
    if name in DECLARED_CAST_STAGES:
        casts = frozenset()
    return Taint(raw=t.raw, san=tuple(sorted(san.items())), casts=casts)


# --------------------------------------------------------------------------
# Trace-level evidence collected during the walk
# --------------------------------------------------------------------------
@dataclass
class BoundaryRecord:
    direction: str
    party: int
    transport: str
    shape: Tuple[int, ...]
    dtype: str
    satisfied: bool


@dataclass
class TraceAudit:
    """Everything one walk learns about one traced function."""
    case: str = ""
    findings: List[Finding] = field(default_factory=list)
    boundaries: Dict[int, BoundaryRecord] = field(default_factory=dict)
    pallas_calls: Dict[int, str] = field(default_factory=dict)
    collectives: Dict[int, Tuple[str, Tuple[str, ...]]] = \
        field(default_factory=dict)
    _seen: set = field(default_factory=set)

    def add_finding(self, code: str, severity: str, where: str,
                    detail: str) -> None:
        key = (code, where, detail)
        if key in self._seen:
            return
        self._seen.add(key)
        self.findings.append(Finding(code=code, severity=severity,
                                     where=where, detail=detail,
                                     case=self.case))


# --------------------------------------------------------------------------
# The walker
# --------------------------------------------------------------------------
def _axis_names(params: Dict[str, Any]) -> Tuple[str, ...]:
    names = []
    for k in ("axis_name", "axes", "axis"):
        v = params.get(k)
        if v is None:
            continue
        if isinstance(v, (tuple, list)):
            names.extend(str(a) for a in v)
        else:
            names.append(str(v))
    return tuple(names)


def _is_narrowing(src, dst) -> bool:
    import numpy as np
    src, dst = np.dtype(src), np.dtype(dst)
    if src.kind != "f":
        return False
    if dst.kind == "f":
        return dst.itemsize < src.itemsize or dst.name in _NARROW_FLOATS \
            and src.name == "float32" and dst.itemsize < src.itemsize
    return dst.kind in ("i", "u")


class TaintWalker:
    SUBJAXPR_KEYS = ("jaxpr", "call_jaxpr", "fun_jaxpr")

    def __init__(self, audit: TraceAudit):
        self.audit = audit
        self.idx = 0

    # -- env helpers -------------------------------------------------------
    @staticmethod
    def _read(env: Dict[Any, Taint], v) -> Taint:
        if isinstance(v, Literal):
            return EMPTY
        return env.get(v, EMPTY)

    # -- entry points ------------------------------------------------------
    def walk_closed(self, closed, in_taints: Sequence[Taint]
                    ) -> List[Taint]:
        jaxpr = closed.jaxpr
        consts = [EMPTY] * len(jaxpr.constvars)
        return self.walk(jaxpr, list(in_taints), consts)

    def walk(self, jaxpr, in_taints: Sequence[Taint],
             const_taints: Sequence[Taint]) -> List[Taint]:
        assert len(in_taints) == len(jaxpr.invars), \
            (len(in_taints), len(jaxpr.invars))
        env: Dict[Any, Taint] = {}
        for v, t in zip(jaxpr.constvars, const_taints):
            env[v] = t
        for v, t in zip(jaxpr.invars, in_taints):
            env[v] = t
        for eqn in jaxpr.eqns:
            self._eqn(env, eqn)
        return [self._read(env, v) for v in jaxpr.outvars]

    # -- per-eqn semantics -------------------------------------------------
    def _eqn(self, env: Dict[Any, Taint], eqn) -> None:
        self.idx += 1
        idx = self.idx
        prim = eqn.primitive.name
        ts = [self._read(env, v) for v in eqn.invars]

        if prim == "audit_mark":
            out = self._mark(eqn, ts[0], idx)
            env[eqn.outvars[0]] = out
            return

        if prim == "convert_element_type":
            src = eqn.invars[0].aval.dtype
            dst = eqn.params.get("new_dtype", src)
            out = join(ts)
            if _is_narrowing(src, dst):
                site = f"convert {src}->{dst} (eqn #{idx})"
                out = Taint(raw=out.raw, san=out.san,
                            casts=out.casts | {site})
            env[eqn.outvars[0]] = out
            return

        if prim == "pallas_call":
            if id(eqn) not in self.audit.pallas_calls:
                name = str(eqn.params.get("name",
                                          eqn.params.get("name_and_src",
                                                         "pallas")))
                self.audit.pallas_calls[id(eqn)] = name
            self._smear(env, eqn, ts)
            return

        if prim in DATA_COLLECTIVES:
            if id(eqn) not in self.audit.collectives:
                self.audit.collectives[id(eqn)] = \
                    (prim, _axis_names(eqn.params))
            self._smear(env, eqn, ts)
            return

        if prim == "scan":
            self._scan(env, eqn, ts)
            return

        if prim == "cond":
            self._cond(env, eqn, ts)
            return

        if prim == "while":
            # no while in the audited engine; conservative smear
            self._smear(env, eqn, ts)
            return

        sub = self._subjaxpr(eqn)
        if sub is not None:
            closed, open_jaxpr = sub
            n_in = len(closed.jaxpr.invars) if closed is not None \
                else len(open_jaxpr.invars)
            if n_in == len(ts):
                if closed is not None:
                    outs = self.walk_closed(closed, ts)
                else:
                    outs = self.walk(open_jaxpr, ts,
                                     [EMPTY] * len(open_jaxpr.constvars))
                n_out = len(eqn.outvars)
                if len(outs) == n_out:
                    for v, t in zip(eqn.outvars, outs):
                        env[v] = t
                    return
            # arity mismatch: fall through to the conservative smear
        self._smear(env, eqn, ts)

    def _smear(self, env, eqn, ts) -> None:
        out = join(ts)
        for v in eqn.outvars:
            env[v] = out

    def _subjaxpr(self, eqn):
        for k in self.SUBJAXPR_KEYS:
            v = eqn.params.get(k)
            if v is None:
                continue
            if hasattr(v, "jaxpr"):          # ClosedJaxpr
                return v, None
            if hasattr(v, "eqns"):           # open Jaxpr
                return None, v
        return None

    # -- structured primitives --------------------------------------------
    def _scan(self, env, eqn, ts) -> None:
        p = eqn.params
        nc, ncar = p["num_consts"], p["num_carry"]
        body = p["jaxpr"]
        const_t = ts[:nc]
        carry_t = list(ts[nc:nc + ncar])
        xs_t = ts[nc + ncar:]
        outs: List[Taint] = []
        for _ in range(32):
            outs = self.walk_closed(body, const_t + carry_t + xs_t)
            new_carry = [join([c, o])
                         for c, o in zip(carry_t, outs[:ncar])]
            if [t.key() for t in new_carry] == \
                    [t.key() for t in carry_t]:
                carry_t = new_carry
                break
            carry_t = new_carry
        final = carry_t + outs[ncar:]
        for v, t in zip(eqn.outvars, final):
            env[v] = t

    def _cond(self, env, eqn, ts) -> None:
        branches = eqn.params["branches"]
        opts = [self.walk_closed(b, ts[1:]) for b in branches]
        for j, v in enumerate(eqn.outvars):
            env[v] = join([o[j] for o in opts])

    # -- marks -------------------------------------------------------------
    def _mark(self, eqn, t: Taint, idx: int) -> Taint:
        role = eqn.params["role"]
        name = eqn.params["name"]
        if role == "sanitizer":
            return sanitize(t, name, idx)
        assert role == "boundary", role
        meta = dict(eqn.params.get("meta", ()))
        require = tuple(meta.get("require", ()))
        order = tuple(meta.get("order", ()))
        aval = eqn.outvars[0].aval
        satisfied = True
        if t.raw:
            missing = [r for r in require if r not in t.san_names]
            if missing:
                satisfied = False
                self.audit.add_finding(
                    "taint.raw-boundary", "error",
                    f"{meta.get('transport', '?')}.send "
                    f"{name} {tuple(aval.shape)}:{aval.dtype}",
                    f"raw value tainted by part{'ies' if len(t.raw) > 1 else 'y'} "
                    f"{sorted(t.raw)} reaches the {meta.get('direction')} "
                    f"boundary without the registered "
                    f"{'/'.join(missing)} stage(s) "
                    f"(required: {list(require)}, seen: "
                    f"{sorted(t.san_names)})")
            for before, after in order:
                bi, ai = t.san_idx(before), t.san_idx(after)
                if bi is not None and ai is not None and ai <= bi:
                    satisfied = False
                    self.audit.add_finding(
                        "taint.sanitizer-order", "error",
                        f"{meta.get('transport', '?')}.send {name}",
                        f"'{after}' stage applied BEFORE '{before}' on the "
                        f"{meta.get('direction')} boundary value — with a "
                        f"lossy codec the DP noise must ride the decoded "
                        f"wire value (after encode), or error feedback "
                        f"re-transmits and cancels it")
        if id(eqn) not in self.audit.boundaries:
            self.audit.boundaries[id(eqn)] = BoundaryRecord(
                direction=str(meta.get("direction", "?")),
                party=int(meta.get("party", -1)),
                transport=str(meta.get("transport", "?")),
                shape=tuple(aval.shape), dtype=str(aval.dtype),
                satisfied=satisfied)
        # release: the value is now a sanitized public message
        return Taint(casts=t.casts)


# --------------------------------------------------------------------------
# Output host rule
# --------------------------------------------------------------------------
@dataclass(frozen=True)
class OutTag:
    """Host declaration for one stage-output region.  ``allowed`` is the
    set of parties whose RAW taint the output may carry (None = skip the
    check: sim-level metrics that legitimately mix parties)."""
    allowed: Optional[FrozenSet[str]]
    label: str


def check_outputs(out_taints: Sequence[Taint], out_tags: Sequence[OutTag],
                  audit: TraceAudit) -> None:
    assert len(out_taints) == len(out_tags), \
        (len(out_taints), len(out_tags))
    for t, tag in zip(out_taints, out_tags):
        if t.casts:
            audit.add_finding(
                "kernel.unmediated-cast", "error", tag.label,
                f"narrowing precision cast(s) {sorted(t.casts)} reach this "
                f"output without passing a declared wire/encode/cache "
                f"stage — precision loss outside the registered codecs")
        if tag.allowed is None:
            continue
        extra = t.raw - tag.allowed
        if extra:
            audit.add_finding(
                "taint.foreign-raw-output", "error", tag.label,
                f"output hosted at {sorted(tag.allowed) or ['<public>']} "
                f"carries raw taint of part"
                f"{'ies' if len(extra) > 1 else 'y'} {sorted(extra)} — a "
                f"pre-release private value escaped into another party's "
                f"state")


def audit_trace(closed_jaxpr, in_taints: Sequence[Taint],
                out_tags: Sequence[OutTag], case: str = "") -> TraceAudit:
    """Walk one traced round function end to end: propagate taint, check
    every boundary mark, then apply the host rule to the outputs."""
    audit = TraceAudit(case=case)
    walker = TaintWalker(audit)
    outs = walker.walk_closed(closed_jaxpr, in_taints)
    check_outputs(outs, out_tags, audit)
    return audit
