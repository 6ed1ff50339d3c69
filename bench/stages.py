"""Charge a traced round's device time to the stages of the CELU round.

The program names its stages with ``jax.named_scope``: the names are
``repro.core.engine.STAGE_SCOPES`` (``exchange_compute``,
``exchange_apply``, ``local_scan``), and the parts inside them
``LEAF_SCOPES`` (``optimizer``, ``workset_insert``, ``workset_draw``,
``local_grad``).  The compiled text keeps a scope in an instruction's
``metadata={op_name=...}``, but XLA leaves many instructions without one:
its own loops (``wide.body.N.sunk``) and the ``while``s that run them,
copies, bitcasts and many fusions.  Each HLO instruction is given a stage
(and a leaf: the first leaf scope under the stage, else the stage itself)
by the first of these rules that finds one:

1. its own ``op_name``;
2. else the scoped instructions inside the computations it calls, however
   deep (a fusion's body, an XLA-made loop), taking the majority;
3. else its nearest scoped operand in its own computation (by rules 1-2,
   fewest hops first, the majority among equals);
4. else its nearest scoped user there, by the same measure (a buffer
   that XLA fills with a literal for the loop that consumes it);
5. else the instruction that calls its computation, by rules 1-5, and so
   up to ``ENTRY``.

What is still left has no stage.  Device seconds are summed over the
operation events of the traced window (``Trace.devices``, inside
``Trace.window()``), leaving out the enclosing ``while`` / ``conditional``
/ ``call`` events as ``Trace.op_seconds`` does, and averaged over the
devices that ran any.  *Unattributed* is the time of events with no
stage plus the busy time in which only an enclosing event ran (loop
control), so that the stages and the unattributed time make up the busy
time when no two operations overlap.
"""
from __future__ import annotations

import collections
import os
import re
import sys
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import harness

try:
    from repro.core.engine import (EXCHANGE_APPLY, EXCHANGE_COMPUTE,
                                   LEAF_SCOPES, LOCAL_SCAN, OPTIMIZER,
                                   STAGE_SCOPES)
except ImportError:          # a program that names no stage
    EXCHANGE_APPLY = EXCHANGE_COMPUTE = LOCAL_SCAN = OPTIMIZER = None
    STAGE_SCOPES = LEAF_SCOPES = ()

T = sys.modules.get("bench_trace") or harness.load_module(
    os.path.join(harness.BENCH, "trace.py"), "bench_trace")

Scope = Tuple[str, str]             # (stage, leaf)
NAME = re.compile(r"%([\w.\-]+)")


def scope_of(op_name: str) -> Optional[Scope]:
    """``jit(f)/local_scan/while/body/optimizer/mul`` ->
    ``("local_scan", "optimizer")``; None where no stage is named."""
    parts = op_name.split("/")
    for i, p in enumerate(parts):
        if p in STAGE_SCOPES:
            return p, next((q for q in parts[i + 1:] if q in LEAF_SCOPES),
                           p)
    return None


def majority(votes: List[Scope]) -> Optional[Scope]:
    """The stage most votes name, then its most named leaf; ties go to
    the earlier name in the program's own order."""
    if not votes:
        return None
    order = {n: i for i, n in enumerate(STAGE_SCOPES + LEAF_SCOPES)}
    key = lambda kv: (-kv[1], order.get(kv[0], len(order)))  # noqa: E731
    stage = min(collections.Counter(s for s, _ in votes).items(),
                key=key)[0]
    leaf = min(collections.Counter(l for s, l in votes if s == stage)
               .items(), key=key)[0]
    return stage, leaf


@dataclass
class Instr:
    comp: str
    own: Optional[Scope]
    operands: List[str]
    callees: List[str]
    users: List[str] = field(default_factory=list)


class Attribution:
    """Compiled HLO text -> the stage of each instruction, by the rules
    of the module docstring, computed on demand."""

    def __init__(self, hlo_text: str):
        comps: Dict[str, List[Tuple[str, str]]] = {}
        comp = None
        for line in hlo_text.splitlines():
            m = T.COMPUTATION.match(line)
            if m:
                comp = m.group(1)
                comps[comp] = []
            elif line.strip() == "}":
                comp = None
            elif comp is not None and T.INSTR.match(line):
                comps[comp].append((T.instruction(line), line))
        self.instrs: Dict[str, Instr] = {}
        self.members: Dict[str, List[str]] = {}
        self.callers: Dict[str, List[str]] = collections.defaultdict(list)
        for comp, lines in comps.items():
            mine = {n for n, _ in lines}
            self.members[comp] = [n for n, _ in lines]
            for name, line in lines:
                m = T.OP_NAME.search(line)
                tokens = NAME.findall(line.split("=", 1)[1])
                callees = [t for t in tokens if t in comps]
                ins = Instr(comp, scope_of(m.group(1)) if m else None,
                            [t for t in tokens if t in mine and t != name],
                            callees)
                self.instrs[name] = ins
                for c in callees:
                    self.callers[c].append(name)
        for name, ins in self.instrs.items():
            for o in ins.operands:
                self.instrs[o].users.append(name)
        self._inner: Dict[str, List[Scope]] = {}
        self._memo: Dict[str, Optional[Scope]] = {}

    def names_a_stage(self) -> bool:
        return any(i.own for i in self.instrs.values())

    def _votes(self, comp: str) -> List[Scope]:
        """Rule 1's scopes of every instruction in ``comp`` and in the
        computations it calls (HLO calls form no cycle)."""
        if comp not in self._inner:
            out = []
            for n in self.members[comp]:
                ins = self.instrs[n]
                if ins.own:
                    out.append(ins.own)
                for c in ins.callees:
                    out.extend(self._votes(c))
            self._inner[comp] = out
        return self._inner[comp]

    def _direct(self, name: str) -> Optional[Scope]:
        ins = self.instrs[name]
        if ins.own:                                     # rule 1
            return ins.own
        return majority([v for c in ins.callees         # rule 2
                         for v in self._votes(c)])

    def _nearest(self, name: str, edge: str) -> Optional[Scope]:
        """Rules 3 and 4: the nearest instructions along ``edge``
        (``operands`` or ``users``) that rules 1-2 place."""
        level, seen = getattr(self.instrs[name], edge), {name}
        while level:
            seen.update(level)
            found = [s for s in map(self._direct, level) if s]
            if found:
                return majority(found)
            level = list(dict.fromkeys(
                o for n in level for o in getattr(self.instrs[n], edge)
                if o not in seen))
        return None

    def scope(self, name: str) -> Optional[Scope]:
        """(stage, leaf) of the instruction ``name``; None if no rule
        finds one (or the program has no such instruction)."""
        if name not in self.instrs:
            return None
        if name not in self._memo:
            s = self._direct(name) or self._nearest(name, "operands") \
                or self._nearest(name, "users")
            if s is None:                                # rule 5
                s = majority([c for c in map(
                    self.scope, self.callers[self.instrs[name].comp]) if c])
            self._memo[name] = s
        return self._memo[name]


@dataclass
class Split:
    """Device seconds of the traced window, averaged over the devices
    that ran any operation."""
    stages: Dict[str, float]
    leaves: Dict[str, float]
    unattributed: float
    busy: float


def split(trace, hlo_text: Optional[str]) -> Optional[Split]:
    """The traced window's device seconds by stage; None where the
    compiled round names no stage (a program without the scopes, or a
    compile-cache entry made before them: the cache key ignores
    metadata)."""
    attr = Attribution(hlo_text) if hlo_text and STAGE_SCOPES else None
    if attr is None or not attr.names_a_stage():
        harness.say("[bench] stages: the compiled round names no stage")
        return None
    stages: Dict[str, float] = collections.Counter()
    leaves: Dict[str, float] = collections.Counter()
    unattributed = 0.0
    devs = [d for d in trace.devices if d.events]
    w0, w1 = trace.window()
    for d in devs:
        ops = []
        for s, e, instr in d.events:
            if e <= w0 or s >= w1 or T.opcode(instr) in T.ENCLOSING:
                continue
            s, e = max(s, w0), min(e, w1)
            ops.append((s, e))
            sc = attr.scope(instr)
            if sc is None:
                unattributed += (e - s) / 1e9
            else:
                stages[sc[0]] += (e - s) / 1e9
                leaves[sc[1]] += (e - s) / 1e9
        loop_only = T.total(trace.busy_intervals(d)) - T.total(T.union(ops))
        unattributed += max(loop_only, 0) / 1e9
    n = len(devs)
    return Split({k: v / n for k, v in stages.items()},
                 {k: v / n for k, v in leaves.items()},
                 unattributed / n, trace.busy_s())


def compiled_text(rec) -> Optional[str]:
    """The traced round's compiled text: ``rec["trace"].hlo`` where the
    trace carries it, else the ``hlo`` that the harness's ``main``
    fetched for the breakdown and holds beside this ``rec`` (a reader is
    handed ``rec`` alone, and the run's program is released by then)."""
    text = getattr(rec["trace"], "hlo", None)
    frame = sys._getframe(1)
    while text is None and frame is not None:
        local = frame.f_locals
        if local.get("rec") is rec and isinstance(local.get("hlo"), str):
            text = local["hlo"]
        frame = frame.f_back
    return text


def of(rec) -> Optional[Split]:
    """The run's split, computed once per trace."""
    t = rec["trace"]
    if t is None:
        return None
    if not hasattr(t, "stage_split"):
        t.stage_split = split(t, compiled_text(rec))
    return t.stage_split


def ms_per_round(rec, name: str) -> Optional[float]:
    """Device milliseconds per traced round in the stage or leaf scope
    ``name``."""
    s = of(rec)
    if s is None:
        return None
    secs = (s.stages if name in STAGE_SCOPES else s.leaves).get(name, 0.0)
    return 1e3 * secs / rec["traced_steps"]


def unattributed_share(rec) -> Optional[float]:
    """Share of the traced busy time that no stage was given, in %."""
    s = of(rec)
    return None if s is None else 100.0 * s.unattributed / s.busy
