"""Charge a traced round's device time to the MoE layers' spans.

The program names each MoE layer ``jax.named_scope("moe")`` and, inside
it, the grouped expert matmuls ``"moe_experts"`` (``repro.models.moe``).
Each HLO instruction is placed by ``bench/stages.py``'s rules, voting by
these names in place of the stages': ``(moe, moe_experts)`` where its
``op_name`` path holds both, ``(moe, moe)`` where it holds ``moe``
alone, ``(other, other)`` where it holds neither.  A path part counts
with the wrappers JAX puts round a scope under differentiation
(``transpose(jvp(moe))``).  The TPU compiler lowers ``ragged_dot`` to a
grouped-matmul kernel and a kernel for its group metadata, and gives
them its own ``op_name`` (``ragged-dot-none``, ``ragged-dot-metadata``)
in place of the scopes: they are placed in ``moe_experts``, since the
program's only ``ragged_dot`` is there.

Device seconds are summed over the traced window's operation events as
``stages.split`` sums them, and averaged over the devices that ran any.
"""
from __future__ import annotations

import collections
import re
from typing import Optional, Tuple

import stages

T = stages.T
MOE, EXPERTS, OTHER = "moe", "moe_experts", "other"
RAGGED_DOT = "ragged-dot"


def _part(name: str):
    return re.compile(r"^(?:[\w.]+\()*" + re.escape(name) + r"\)*$")


_MOE, _EXPERTS = _part(MOE), _part(EXPERTS)


def scope_of(op_name: str) -> stages.Scope:
    if op_name.startswith(RAGGED_DOT):
        return MOE, EXPERTS
    parts = op_name.split("/")
    for i, p in enumerate(parts):
        if _MOE.match(p):
            return MOE, EXPERTS if any(_EXPERTS.match(q)
                                       for q in parts[i + 1:]) else MOE
    return OTHER, OTHER


class Attribution(stages.Attribution):
    """``stages.Attribution`` with rule 1 voting by the MoE scopes."""

    def __init__(self, hlo_text: str):
        super().__init__(hlo_text)
        for line in hlo_text.splitlines():
            if not T.INSTR.match(line):
                continue
            ins = self.instrs.get(T.instruction(line))
            if ins is not None:
                m = T.OP_NAME.search(line)
                ins.own = scope_of(m.group(1)) if m else None

    def names_moe(self) -> bool:
        return any(i.own and i.own[0] == MOE for i in self.instrs.values())


def seconds(rec) -> Optional[Tuple[float, float]]:
    """(device seconds under ``moe``, of which under ``moe_experts``) per
    traced round; None where the run has no trace or its compiled round
    names no MoE layer."""
    trace = rec["trace"]
    if trace is None:
        return None
    if not hasattr(trace, "moe_seconds"):
        text = stages.compiled_text(rec)
        attr = Attribution(text) if text else None
        trace.moe_seconds = _sum(trace, attr) \
            if attr is not None and attr.names_moe() else None
    if trace.moe_seconds is None:
        return None
    moe, experts = trace.moe_seconds
    n = rec["traced_steps"]
    return moe / n, experts / n


def _sum(trace, attr: Attribution) -> Tuple[float, float]:
    leaves = collections.Counter()
    devs = [d for d in trace.devices if d.events]
    w0, w1 = trace.window()
    for d in devs:
        for s, e, instr in d.events:
            if e <= w0 or s >= w1 or T.opcode(instr) in T.ENCLOSING:
                continue
            sc = attr.scope(instr)
            if sc is not None and sc[0] == MOE:
                leaves[sc[1]] += (min(e, w1) - max(s, w0)) / 1e9
    n = len(devs)
    return (leaves[MOE] + leaves[EXPERTS]) / n, leaves[EXPERTS] / n
