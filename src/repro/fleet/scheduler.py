"""Device-side round scheduler: the PipelinedEngine schedule as ONE traced
step, batchable over a leading job axis.

``PipelinedEngine`` (core/engine.py) drives its depth-D exchange queue from
the HOST: ``rs.pending`` is a Python tuple, queue fill/merge decisions and
the flush alternation are Python branches, and every round costs several
separately-dispatched jits.  None of that vmaps.  This module re-expresses
the exact same schedule as pure device code:

  * the exchange queue is a FIXED-CAPACITY stacked :class:`PendingExchange`
    (every leaf grows a leading ``depth`` axis) carried in
    :class:`FleetRoundState`;
  * the scheduler phase — the live in-flight count ``n_pending`` — is a
    TRACED ``int32`` carried in the state, and the queue-full merge
    decision (and the flush drain) ride ``lax.cond`` over it instead of
    host branching;
  * per-job hyper-parameters that the scalar engine bakes into closures
    (optimizer lr, the Algorithm-2 ``cos ξ`` threshold, the three PRNG
    base keys) arrive as the traced :class:`JobHyper` argument, so a vmap
    over jobs batches them freely.

One compiled step therefore serves warmup, steady state, and (via
:func:`make_fleet_step`'s flush) the drain — and the whole thing vmaps
over a leading job axis (``repro.fleet.runner``) or lowers per-lane
bit-identically under ``lax.map``.

Bit-exactness contract (the golden gate in tests/test_fleet.py): driven
with the default hyper (``JobHyper.for_spec`` at seed 0), the step at
depth 0/1/2 reproduces ``PipelinedEngine.step``/``flush`` bit-for-bit —
same stage composition, same rng folds, same per-slot staleness charges,
same NaN-loss warmup rows.
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import CELUConfig, validate_pipeline_depth
from ..core.engine import (COUNTERS, KPartyTask, PendingExchange,
                           _make_stages, _zero_local_metrics,
                           fresh_counters, make_transport, merge_metrics)
from ..core.weighting import xi_to_cos
from ..optim import make_optimizer

# the scalar engine's fixed PRNG bases (engine._make_stages defaults) —
# a job carrying exactly these keys replays the historical rng chain
ENGINE_RNG_BASES = {"exchange": 17, "insert": 0xCE1, "draw": 29}


class JobHyper(NamedTuple):
    """Per-job TRACED hyper-parameters — everything a fleet batches over
    without recompiling.  Static knobs (depth, codec, cache dtype, W, R,
    sampling...) stay in :class:`~repro.configs.base.CELUConfig` and
    partition the fleet into cohorts instead (see runner.cohort_key)."""
    lr: Any                     # optimizer step size, f32 scalar
    cos_xi: Any                 # Algorithm-2 threshold cos(xi), f32 scalar
    keys: Dict[str, Any]        # {"exchange","insert","draw"} PRNG keys

    @classmethod
    def for_spec(cls, lr: float, xi_degrees: float, seed: int = 0
                 ) -> "JobHyper":
        """Concrete hyper for one job.  ``seed == 0`` keeps the engine's
        fixed PRNG bases (the golden-pinned chain); any other seed folds
        it in for an independent stream per job."""
        keys = {}
        for name, base in ENGINE_RNG_BASES.items():
            k = jax.random.PRNGKey(base)
            keys[name] = k if seed == 0 else jax.random.fold_in(k, seed)
        return cls(lr=jnp.float32(lr),
                   cos_xi=jnp.float32(xi_to_cos(xi_degrees)), keys=keys)


class FleetRoundState(NamedTuple):
    """Batchable scheduler state: the engine's canonical state dict plus
    the device-side exchange queue.

    ``pending`` is a stacked :class:`PendingExchange` — each leaf carries
    a leading ``depth`` axis (slot 0 oldest) — or ``None`` at depths 0/1,
    whose queue never survives a step.  ``n_pending`` is the traced
    scheduler phase: the live in-flight count that drives dispatch
    chaining, per-slot staleness charges, and the ``lax.cond`` merge."""
    state: Dict[str, Any]
    pending: Optional[PendingExchange]
    n_pending: Any


def _at(tree, i):
    """Slice index ``i`` (traced ok) off every leaf's leading axis."""
    return jax.tree_util.tree_map(
        lambda x: jax.lax.dynamic_index_in_dim(x, i, 0, keepdims=False),
        tree)


def _put(tree, value, i):
    """Write ``value`` into slot ``i`` (traced ok) of every leaf."""
    return jax.tree_util.tree_map(
        lambda buf, v: jax.lax.dynamic_update_index_in_dim(buf, v, i, 0),
        tree, value)


def _pop(tree):
    """Shift the queue left: slot 1 -> 0, ...; the vacated tail slot
    holds a stale copy that the occupancy counter guards from reads."""
    return jax.tree_util.tree_map(
        lambda x: jnp.roll(x, -1, axis=0), tree)


def _select(pred, a, b):
    return jax.tree_util.tree_map(
        lambda x, y: jnp.where(pred, x, y), a, b)


def average_flush_metrics(m: Dict[str, Any]) -> Dict[str, Any]:
    """Finish ONE job's flush metrics on the host: sum the per-scan float
    rows eagerly (one IEEE round-to-nearest per add, exactly
    ``PipelinedEngine.flush``'s ``sum(...) / n`` — an in-program XLA
    accumulate fuses the chain and rounds differently) and divide by the
    number of scans that actually ran.  Idle rows are zeros, so including
    them in the sum is exact.  Depth 0/1 metrics pass through unchanged."""
    if "w_mean_scans" not in m:
        return dict(m)
    n = np.float32(np.asarray(m["n_scans"]))
    out = {k: np.asarray(m[k]) for k in ("local_steps",) + COUNTERS
           if k in m}
    for key in ("w_mean", "w_zero_frac"):
        acc = np.float32(0.0)
        for v in np.asarray(m[key + "_scans"], np.float32):
            acc = np.float32(acc + v)
        out[key] = np.float32(acc / n)
    return out


def make_fleet_step(task: KPartyTask, celu: CELUConfig, *,
                    depth: Optional[int] = None,
                    optimizer: str = "adagrad",
                    opt_kwargs: Optional[Dict[str, Any]] = None,
                    local_steps: int = -1, transport=None,
                    compression: Optional[str] = None,
                    fused_weighting: bool = True):
    """-> ``(init, step, flush)`` — the device-side schedule for ONE job
    (vmap/lax.map over a leading job axis is the caller's move).

      * ``init(state, batches_a, batch_b) -> FleetRoundState`` adopts an
        :func:`~repro.core.engine.init_state` dict and (at depth >= 2)
        allocates the zeroed exchange-queue slots from the payload shapes.
      * ``step(fs, hyper, batches_a, batch_b, batch_idx) -> (fs, metrics)``
        is one communication round — exactly
        :meth:`PipelinedEngine.step`'s composition at this depth, with the
        queue decisions traced (``lax.cond`` over ``fs.n_pending``).
      * ``flush(fs, hyper) -> (fs, metrics)`` drains the queue:
        a static ``depth``-iteration loop of conditional scan+merge pairs
        (no-ops once the queue is empty) plus the final local scan,
        mirroring :meth:`PipelinedEngine.flush`'s alternation.  At
        depth >= 2 the float metrics come back as per-scan rows —
        finish them with :func:`average_flush_metrics`.

    The stages are (re)built inside each trace so ``hyper``'s traced
    lr/cos_xi/rng-keys flow into the optimizer and stage closures."""
    if depth is None:
        depth = celu.pipeline_depth
    validate_pipeline_depth(depth, celu.W)
    dynamic = depth >= 2
    n_local = celu.R if local_steps < 0 else local_steps
    tp = transport if transport is not None \
        else make_transport(celu, compression)

    def _stages(hyper: JobHyper):
        opt = make_optimizer(optimizer, hyper.lr, **(opt_kwargs or {}))
        return _make_stages(
            task, opt, celu, n_local=n_local, tp=tp, fused=fused_weighting,
            pipeline_staleness=depth,
            lr_damping=celu.pipeline_lr_damping if dynamic else 0.0,
            cos_xi=hyper.cos_xi, rng_keys=hyper.keys)

    def init(state: Dict[str, Any], batches_a, batch_b) -> FleetRoundState:
        if not dynamic:
            return FleetRoundState(state, None, jnp.int32(0))
        # size the queue slots from abstract payload shapes — zeros, never
        # read before a dispatch writes them (n_pending guards every read)
        compute, _, _ = _stages(JobHyper.for_spec(1.0, celu.xi_degrees))
        fresh_sd = jax.eval_shape(
            lambda s, ba, bb: compute(s["params"], s["transport"], ba, bb,
                                      s["comm_rounds"]),
            state, batches_a, batch_b)
        slot = PendingExchange(
            fresh=fresh_sd, batches_a=batches_a, batch_b=batch_b,
            batch_idx=jnp.int32(0), dispatched_at=jnp.int32(0))
        pending = jax.tree_util.tree_map(
            lambda x: jnp.zeros((depth,) + jnp.shape(x),
                                jnp.asarray(x).dtype
                                if not hasattr(x, "dtype") else x.dtype),
            slot)
        return FleetRoundState(state, pending, jnp.int32(0))

    def step(fs: FleetRoundState, hyper: JobHyper, batches_a, batch_b,
             batch_idx):
        compute, apply_, scan = _stages(hyper)
        state = fs.state
        if depth == 0:
            # dispatch -> merge -> local: the sequential schedule
            fresh = compute(state["params"], state["transport"],
                            batches_a, batch_b, state["comm_rounds"])
            state, m = apply_(state, fresh, batches_a, batch_b, batch_idx)
            state, lm = scan(state)
            return fs._replace(state=state), merge_metrics(m, lm)
        if depth == 1:
            # dispatch -> local (overlapped) -> merge; the queue fills and
            # drains within the step, so no cross-step slots are carried
            fresh = compute(state["params"], state["transport"],
                            batches_a, batch_b, state["comm_rounds"])
            state, lm = scan(state)
            state, m = apply_(state, fresh, batches_a, batch_b, batch_idx)
            return fs._replace(state=state), merge_metrics(m, lm)

        # depth >= 2: device-side queue.  Dispatch chains the transport
        # residuals off the NEWEST in-flight exchange (dispatch-order
        # telescoping — see PipelinedEngine.dispatch) and folds the rng
        # over the dispatch sequence number comm_rounds + n_pending.
        pending, n = fs.pending, fs.n_pending
        newest = _at(pending, n - 1)            # clamped at n=0; masked below
        tstate = _select(n > 0, newest.fresh["tstate"], state["transport"])
        fresh = compute(state["params"], tstate, batches_a, batch_b,
                        state["comm_rounds"] + n)
        slot = PendingExchange(
            fresh=fresh, batches_a=batches_a, batch_b=batch_b,
            batch_idx=jnp.asarray(batch_idx, jnp.int32),
            dispatched_at=jnp.asarray(state["comm_rounds"], jnp.int32))
        pending = _put(pending, slot, n)
        n = n + 1

        # the local scan is charged the live in-flight count
        state, lm = scan(state, n)

        # merge the oldest exchange once the queue holds `depth`; the
        # first depth-1 steps only fill the queue and report a NaN loss
        def _merge(args):
            state, pending, n = args
            oldest = _at(pending, jnp.int32(0))
            s = state["comm_rounds"] - oldest.dispatched_at
            state, m = apply_(state, oldest.fresh, oldest.batches_a,
                              oldest.batch_b, oldest.batch_idx, s)
            return state, _pop(pending), n - 1, m

        def _warmup(args):
            state, pending, n = args
            m = {"loss": jnp.float32(jnp.nan)}
            # no step ran: every counter reads 0
            m.update({k: jnp.int32(0) for k in fresh_counters(fresh)})
            return state, pending, n, m

        state, pending, n, m = jax.lax.cond(
            n == depth, _merge, _warmup, (state, pending, n))
        return FleetRoundState(state, pending, n), merge_metrics(m, lm)

    def flush(fs: FleetRoundState, hyper: JobHyper):
        _, apply_, scan = _stages(hyper)
        if depth == 0:
            return fs, _zero_local_metrics()
        if depth == 1:
            state, lm = scan(fs.state)
            return fs._replace(state=state), lm

        # depth >= 2: alternate scan/merge while the queue drains (the
        # occupancy is traced, so the loop is a static `depth` iterations
        # of conditional pairs), then scan once more over the final
        # inserts.  The float metrics come back as RAW per-scan rows
        # (idle iterations report zeros) for the HOST to average via
        # :func:`average_flush_metrics` — XLA fuses an in-program
        # accumulate-and-divide into a single differently-rounded chain,
        # which breaks bit-parity with PipelinedEngine.flush's eager
        # per-op adds.
        n0 = fs.n_pending
        zeros = _zero_local_metrics(fresh_counters(fs.pending.fresh))

        def _drain(args):
            state, pending, n = args
            state, lm = scan(state, n)
            oldest = _at(pending, jnp.int32(0))
            s = state["comm_rounds"] - oldest.dispatched_at
            state, m = apply_(state, oldest.fresh, oldest.batches_a,
                              oldest.batch_b, oldest.batch_idx, s)
            m.pop("loss")
            return state, _pop(pending), n - 1, merge_metrics(m, lm)

        def _idle(args):
            state, pending, n = args
            return state, pending, n, zeros

        state, pending, n = fs.state, fs.pending, fs.n_pending
        rows = []
        for _ in range(depth):
            state, pending, n, lm = jax.lax.cond(
                n > 0, _drain, _idle, (state, pending, n))
            rows.append(lm)
        state, lm = scan(state, n)              # n == 0: the final scan
        rows.append(lm)
        metrics = {
            "local_steps": sum(r["local_steps"] for r in rows),
            "w_mean_scans": jnp.stack([r["w_mean"] for r in rows]),
            "w_zero_frac_scans": jnp.stack([r["w_zero_frac"]
                                            for r in rows]),
            "n_scans": n0 + 1,
        }
        for k in COUNTERS:
            if k in lm:
                metrics[k] = sum(r[k] for r in rows)
        return FleetRoundState(state, pending, n), metrics

    return init, step, flush
