"""Fault injection over the pipelined round engine (the chaos layer).

CELU-VFL's premise is hiding a slow, unreliable WAN behind cached local
updates — this module makes the "unreliable" part real.  A seeded
:class:`repro.configs.base.FaultPlan` drives a deterministic
:class:`FaultSchedule` (every fate is a pure function of
``(seed, round_idx)``), and :class:`ChaosEngine` — a
:class:`repro.core.engine.PipelinedEngine` subclass — replays it over the
exchange queue:

  * **Exchange drop w/ bounded retry.**  Each round's exchange is
    attempted up to ``max_retries + 1`` times (exponential backoff priced
    by ``launch.wan.retry_exchange_seconds``); if every attempt drops,
    the exchange is abandoned for the round.  The transport's
    ``recover_dropped`` hook folds the lost decoded messages back into
    the error-feedback residuals (``CompressedWANTransport``: the
    telescoping invariant survives the drop as a delay, not a loss;
    stateless transports degrade gracefully — the update is gone but the
    schedule continues on cached statistics).
  * **Straggler delay.**  A delivered exchange may arrive ``d`` rounds
    late; its merge is deferred until arrival, and while the queue is
    full with an unarrived head, dispatches stall (a lost round, charged
    as staleness).
  * **Party dropout spans + elastic rejoin.**  While any party is down,
    no exchange is dispatched or merged and the down party's local
    updates are frozen via the scan's ``party_mask``; the surviving
    parties keep local-updating off their cached stale statistics.  At
    the span's end the party rejoins with no special ceremony — its
    params/opt state were frozen, its ring kept ticking conservatively.
  * **Staleness accounting.**  The scan is charged
    ``t - dispatch_round(last merged exchange)`` — identical to the
    in-flight count on the fault-free schedule, and growing by one per
    round while faults starve the merge path — so the PR-5 machinery
    (validity-window tightening, ``w^(1+s)`` attenuation,
    ``eta / (1 + c*s)`` lr damping) charges fault-induced extra age with
    no new mechanism.  Merges are charged their true scheduler-round
    age.

``FaultPlan=None`` defers every decision to the base scheduler —
bit-identical to :class:`PipelinedEngine` (the golden traces pin this).

Recovery rides the checkpoint module: ``checkpoint.save_round_state``
persists the FULL :class:`RoundState` (params, opt, rings, transport
residuals, the in-flight queue) plus :meth:`ChaosEngine.host_state`, and
a restored run resumes bit-consistently (``tests/test_faults.py``).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from ..configs.base import CELUConfig, FaultPlan
from ..optim import Optimizer
from .engine import KPartyTask, PendingExchange, PipelinedEngine, \
    RoundState, _flush_metrics, _zero_local_metrics, merge_metrics


@dataclasses.dataclass(frozen=True)
class ExchangeFate:
    """The deterministic fate of one round's exchange attempt(s)."""
    delivered: bool
    attempts: int       # wire attempts actually made (1..max_retries+1)
    delay_rounds: int   # straggler delay in rounds (0 = on time)


class FaultSchedule:
    """Deterministic fate oracle over a :class:`FaultPlan`.

    Every decision derives from a fresh ``np.random.default_rng((seed,
    round_idx))`` stream — independent of call history, so a
    checkpoint-restored run (or a re-run) sees the identical fault
    sequence without replaying the earlier rounds."""

    def __init__(self, plan: FaultPlan):
        self.plan = plan

    def down(self, round_idx: int) -> Tuple[str, ...]:
        return self.plan.down_parties(round_idx)

    def party_mask(self, round_idx: int, K: int):
        """(K+1,) float32 mask (a_0..a_{K-1}, b) or None when everyone is
        up.  Validates the plan's party names against the actual K."""
        down = self.down(round_idx)
        if not down:
            return None
        mask = np.ones(K + 1, np.float32)
        for p in down:
            idx = K if p == "b" else int(p[1:])
            # feature parties occupy slots 0..K-1; slot K is party b's —
            # an out-of-range "a{K}" must error, not silently mask b
            if p != "b" and idx >= K:
                raise ValueError(
                    f"FaultPlan drops party {p!r} but the engine has "
                    f"only K={K} feature parties (a0..a{K - 1}) plus b")
            mask[idx] = 0.0
        return jnp.asarray(mask)

    def exchange_fate(self, round_idx: int) -> ExchangeFate:
        plan = self.plan
        if plan.drop_prob <= 0.0 and plan.straggler_prob <= 0.0:
            return ExchangeFate(True, 1, 0)
        rng = np.random.default_rng((plan.seed, round_idx))
        attempts, delivered = 0, False
        for _ in range(plan.max_retries + 1):
            attempts += 1
            if rng.random() >= plan.drop_prob:
                delivered = True
                break
        delay = 0
        if delivered and plan.straggler_prob > 0.0 \
                and rng.random() < plan.straggler_prob:
            delay = int(rng.integers(1, plan.straggler_rounds + 1))
        return ExchangeFate(delivered, attempts, delay)


class ChaosEngine(PipelinedEngine):
    """The pipelined scheduler under a seeded fault plan.

    Same ``step``/``flush``/``finalize`` driving contract as
    :class:`PipelinedEngine`; per-round metrics additionally report a NaN
    ``loss`` on rounds whose merge was starved by a fault.  Host-side
    fault bookkeeping (the scheduler clock, per-slot arrival rounds, the
    event log) lives on the engine — persist it with :meth:`host_state`
    next to the ``RoundState`` checkpoint for bit-consistent resume."""

    def __init__(self, task: KPartyTask, opt: Optimizer, celu: CELUConfig,
                 *, plan: Optional[FaultPlan] = None,
                 depth: Optional[int] = None, local_steps: int = -1,
                 transport=None, compression: Optional[str] = None,
                 fused_weighting: bool = True, jit: bool = True):
        super().__init__(
            task, opt, celu, depth=depth, local_steps=local_steps,
            transport=transport, compression=compression,
            fused_weighting=fused_weighting, jit=jit,
            # None plan -> base scheduler, bit-for-bit (golden-pinned)
            dynamic_staleness=True if plan is not None else None)
        self.plan = plan
        self.schedule = None if plan is None else FaultSchedule(plan)
        self.now = 0                    # scheduler rounds elapsed
        self.events: List[Dict[str, Any]] = []
        self._dispatch_seq = 0          # rng stream position (see dispatch)
        self._arrival: List[int] = []   # per pending slot, oldest first
        self._dispatch_round: List[int] = []
        self._last_merged_dispatch = -1
        self.counters = {"dispatches": 0, "drops": 0, "stalls": 0,
                         "stalled_dispatches": 0, "dropout_rounds": 0,
                         "merges": 0, "wire_attempts": 0,
                         "straggler_delay_rounds": 0}

    # ---- host bookkeeping ------------------------------------------------
    def _event(self, t: int, kind: str, **detail):
        self.events.append({"round": t, "kind": kind, **detail})

    def host_state(self) -> Dict[str, Any]:
        """The scheduler's host-side fault bookkeeping as a plain pytree —
        checkpoint it next to the ``RoundState`` for bit-consistent
        resume (``checkpoint.save`` handles the int leaves)."""
        return {"now": self.now, "dispatch_seq": self._dispatch_seq,
                "arrival": list(self._arrival),
                "dispatch_round": list(self._dispatch_round),
                "last_merged_dispatch": self._last_merged_dispatch}

    def load_host_state(self, hs: Dict[str, Any]) -> None:
        self.now = int(hs["now"])
        self._dispatch_seq = int(hs["dispatch_seq"])
        self._arrival = [int(x) for x in hs["arrival"]]
        self._dispatch_round = [int(x) for x in hs["dispatch_round"]]
        self._last_merged_dispatch = int(hs["last_merged_dispatch"])

    def telemetry(self) -> Dict[str, Any]:
        return {"rounds": self.now, **self.counters,
                "events": list(self.events)}

    # ---- faulty stages ---------------------------------------------------
    def dispatch(self, rs: RoundState, batches_a, batch_b,
                 batch_idx) -> RoundState:
        """Under a plan the exchange rng folds over the host DISPATCH
        sequence number instead of ``comm_rounds + len(pending)``: the
        two agree on the fault-free schedule, but after a dropped
        exchange the base expression would repeat — and a retransmission
        must not reuse the dropped release's DP noise draw."""
        if self.plan is None:
            return super().dispatch(rs, batches_a, batch_b, batch_idx)
        if len(rs.pending) >= self.queue_capacity:
            raise RuntimeError(
                f"{len(rs.pending)} exchange(s) already in flight — the "
                f"depth-{self.depth} queue holds at most "
                f"{self.queue_capacity}; merge() the oldest before "
                f"dispatching another")
        tstate = rs.pending[-1].fresh["tstate"] if rs.pending \
            else rs.transport
        fresh = self._compute(rs.params, tstate, batches_a, batch_b,
                              jnp.int32(self._dispatch_seq))
        self._dispatch_seq += 1
        pe = PendingExchange(fresh, batches_a, batch_b, batch_idx,
                             dispatched_at=rs.comm_rounds)
        return rs._replace(pending=rs.pending + (pe,))

    def _absorb_drop(self, rs: RoundState) -> RoundState:
        """Pop the just-dispatched (newest) exchange whose wire transfer
        was lost and park the transport's recovered residual state where
        the NEXT dispatch (and the next merge's residual adoption) will
        read it: the newest surviving pending slot, or ``rs.transport``
        when the queue is empty — both keep the dispatch-ordered residual
        chain unbroken."""
        pe = rs.pending[-1]
        recovered = self.transport.recover_dropped(pe.fresh)
        pending = rs.pending[:-1]
        if pending:
            prev = pending[-1]
            fresh = dict(prev.fresh)
            fresh["tstate"] = recovered
            return rs._replace(
                pending=pending[:-1] + (prev._replace(fresh=fresh),))
        return rs._replace(pending=(), transport=recovered)

    def _scan_staleness(self, t: int) -> int:
        """Rounds since the newest MERGED exchange was dispatched — equal
        to the in-flight count on the fault-free schedule, and growing by
        one per round while faults starve the merge path."""
        return t - self._last_merged_dispatch

    def _chaos_local(self, rs: RoundState, t: int, mask):
        return self.local(rs, staleness=self._scan_staleness(t),
                          party_mask=mask)

    def _try_merge(self, rs: RoundState, t: int, down: Tuple[str, ...]):
        """Merge the oldest exchange if the schedule allows: queue at
        capacity (the base depth-D rule), head arrived, nobody down."""
        if down or len(rs.pending) < self.queue_capacity:
            return rs, None
        if self._arrival and self._arrival[0] > t:
            self.counters["stalls"] += 1
            self._event(t, "stall", arrives=self._arrival[0])
            return rs, None
        dr = self._dispatch_round.pop(0)
        self._arrival.pop(0)
        rs, m = self.merge(rs, staleness=t - dr)
        self._last_merged_dispatch = max(self._last_merged_dispatch, dr)
        self.counters["merges"] += 1
        return rs, m

    # ---- schedules -------------------------------------------------------
    def step(self, rs: RoundState, batches_a, batch_b, batch_idx
             ) -> Tuple[RoundState, Dict[str, Any]]:
        if self.plan is None:
            return super().step(rs, batches_a, batch_b, batch_idx)
        t = self.now
        K = len(rs.params["a"])
        down = self.schedule.down(t)
        mask = self.schedule.party_mask(t, K)
        if down:
            self.counters["dropout_rounds"] += 1
            if any(d.start == t for d in self.plan.dropouts
                   if d.covers(t)):
                self._event(t, "dropout", parties=list(down))
        elif len(rs.pending) < self.queue_capacity:
            fate = self.schedule.exchange_fate(t)
            self.counters["wire_attempts"] += fate.attempts
            rs = self.dispatch(rs, batches_a, batch_b, batch_idx)
            self.counters["dispatches"] += 1
            if fate.delivered:
                self._arrival.append(t + fate.delay_rounds)
                self._dispatch_round.append(t)
                if fate.delay_rounds:
                    self.counters["straggler_delay_rounds"] += \
                        fate.delay_rounds
                    self._event(t, "straggler", delay=fate.delay_rounds,
                                attempts=fate.attempts)
            else:
                rs = self._absorb_drop(rs)
                self.counters["drops"] += 1
                self._event(t, "drop", attempts=fate.attempts)
        else:
            # queue full with an unarrived head blocked the dispatch —
            # the round's batch is skipped (a straggler's real cost)
            self.counters["stalled_dispatches"] += 1
            self._event(t, "stall-dispatch")
        if self.depth == 0:
            rs, m = self._try_merge(rs, t, down)
            rs, lm = self._chaos_local(rs, t, mask)
        else:
            rs, lm = self._chaos_local(rs, t, mask)
            rs, m = self._try_merge(rs, t, down)
        self.now = t + 1
        if m is None:
            m = {"loss": jnp.float32(jnp.nan)}
        return rs, merge_metrics(m, lm)

    def flush(self, rs: RoundState) -> Tuple[RoundState, Dict[str, Any]]:
        """Drain the queue.  Outstanding merges complete regardless of
        the remaining fault schedule — their transfers already succeeded
        (drops were absorbed at dispatch time); only arrival timing was
        simulated, and shutdown waits it out.  Down parties stay masked
        out of the drain scans."""
        if self.plan is None:
            return super().flush(rs)
        if self.depth == 0 and not rs.pending:
            # sequential schedule, nothing in flight: every merge already
            # got its in-step scan (depth-0 order is merge THEN scan)
            return rs, _zero_local_metrics()
        K = len(rs.params["a"])
        scans, merged = [], []
        while rs.pending:
            t = self.now
            rs, lm = self._chaos_local(
                rs, t, self.schedule.party_mask(t, K))
            scans.append(lm)
            dr = self._dispatch_round.pop(0) if self._dispatch_round \
                else t
            if self._arrival:
                self._arrival.pop(0)
            rs, m = self.merge(rs, staleness=t - dr)
            merged.append(m)
            self._last_merged_dispatch = max(
                self._last_merged_dispatch, dr)
            self.counters["merges"] += 1
            self.now = t + 1
        t = self.now
        rs, lm = self._chaos_local(rs, t, self.schedule.party_mask(t, K))
        scans.append(lm)
        return rs, _flush_metrics(scans, merged)


def make_chaos_engine(task: KPartyTask, opt: Optimizer, celu: CELUConfig,
                      *, plan: Optional[FaultPlan] = None,
                      **kw) -> ChaosEngine:
    """Factory mirroring :func:`repro.core.engine.make_pipeline`;
    ``plan=None`` builds a scheduler bit-identical to the fault-free
    pipeline."""
    return ChaosEngine(task, opt, celu, plan=plan, **kw)
