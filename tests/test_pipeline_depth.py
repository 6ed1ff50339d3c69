"""The depth-D exchange queue (PR 5 tentpole): scheduling, per-slot
staleness plumbing, and staleness-aware damping.

Depths 0 and 1 stay on the static golden-pinned path (covered by
``test_pipeline.py``); everything here exercises the D >= 2 surface —
queue order and merge determinism, the traced per-slot staleness offsets
reaching ``workset_draw``/``workset_sample`` and the fused kernels'
post-scale, the lr-damping schedule ``eta / (1 + c*s)``, and the
capacity/validation guards.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import CELUConfig
from repro.core import engine
from repro.core.workset import (workset_draw, workset_init, workset_insert,
                                workset_sample)
from repro.data.synthetic import aligned_batches
from repro.models.tabular import make_dlrm
from repro.optim import make_optimizer

from test_pipeline import _run_pipelined, _workload


def _drive(depth, rounds=20, *, W=5, R=3, damping=0.25, lr=0.05,
           sampling="round_robin", compression=None):
    """Like test_pipeline._run_pipelined but with a W wide enough for deep
    queues and exposed damping/sampling/compression knobs.  Returns
    (metric rows, final engine state)."""
    data, cfg = _workload()
    init_fn, task, _ = make_dlrm(cfg)
    ccfg = CELUConfig(R=R, W=W, xi_degrees=60.0, sampling=sampling,
                      pipeline_lr_damping=damping)
    params = init_fn(jax.random.PRNGKey(0), cfg)
    opt = make_optimizer("adagrad", lr)
    it = aligned_batches(data["train"], 64, seed=0)
    _, ba, bb = next(it)
    asj = lambda d: {k: jnp.asarray(v) for k, v in d.items()}
    kw = {} if compression is None else \
        {"transport": engine.make_transport(ccfg, compression)}
    etask = engine.lift_two_party(task)
    state = engine.init_state(etask, engine.lift_two_party_params(params),
                              opt, ccfg, [asj(ba)], asj(bb), **kw)
    pe = engine.make_pipeline(etask, opt, ccfg, depth=depth, **kw)
    rs = pe.init(state)
    it = aligned_batches(data["train"], 64, seed=0)
    rows = []
    for i in range(rounds):
        bi, ba, bb = next(it)
        rs, m = pe.step(rs, [asj(ba)], asj(bb), bi)
        rows.append({"loss": float(np.float32(m["loss"])),
                     "w_mean": float(np.float32(m["w_mean"])),
                     "local_steps": int(m["local_steps"])})
    rs, _ = pe.flush(rs)
    st = pe.finalize(rs)
    rows.append({"steps_a": int(st["steps"]["a"][0]),
                 "steps_b": int(st["steps"]["b"]),
                 "comm_rounds": int(st["comm_rounds"])})
    return rows, st


def _rows_equal(a, b):
    """Row-list equality where NaN == NaN (the warmup losses)."""
    if len(a) != len(b):
        return False
    for ra, rb in zip(a, b):
        if ra.keys() != rb.keys():
            return False
        for k in ra:
            x, y = ra[k], rb[k]
            if isinstance(x, float) and math.isnan(x):
                if not (isinstance(y, float) and math.isnan(y)):
                    return False
            elif x != y:
                return False
    return True


# --------------------------------------------------------------------------
# Scheduling: queue fill, merge order, determinism, accounting
# --------------------------------------------------------------------------
@pytest.mark.parametrize("depth", [2, 4])
def test_depthD_queue_fill_and_step_accounting(depth):
    """The first D-1 steps only fill the queue (NaN loss, no merge); after
    the flush every dispatched exchange has been merged and every funded
    local scan has run."""
    rounds, R = 24, 3
    rows, _ = _drive(depth, rounds=rounds, R=R)
    # warmup: no merge -> NaN loss for exactly the first D-1 rounds
    for i in range(depth - 1):
        assert math.isnan(rows[i]["loss"]), (depth, i)
    assert not math.isnan(rows[depth - 1]["loss"])
    tail = rows[-1]
    assert tail["comm_rounds"] == rounds
    assert rounds < tail["steps_a"] <= rounds * (1 + R)
    assert rounds < tail["steps_b"] <= rounds * (1 + R)
    # the queue starts empty: round 0's scan is a full bubble
    assert rows[0]["local_steps"] == 0


@pytest.mark.parametrize("depth", [2, 4])
def test_depthD_deterministic(depth):
    """Two identical drives produce identical traces — the queue schedule
    (dispatch seq numbers, merge order, per-slot staleness) is pure."""
    a, _ = _drive(depth, rounds=16)
    b, _ = _drive(depth, rounds=16)
    assert _rows_equal(a, b)


def test_merge_consumes_oldest_exchange_first():
    """The queue is FIFO: with two exchanges in flight, merge() adopts the
    first-dispatched one (its batch_idx lands in the workset)."""
    data, cfg = _workload()
    init_fn, task, _ = make_dlrm(cfg)
    ccfg = CELUConfig(R=3, W=5)
    params = init_fn(jax.random.PRNGKey(0), cfg)
    opt = make_optimizer("adagrad", 0.05)
    it = aligned_batches(data["train"], 64, seed=0)
    _, ba, bb = next(it)
    asj = lambda d: {k: jnp.asarray(v) for k, v in d.items()}
    etask = engine.lift_two_party(task)
    state = engine.init_state(etask, engine.lift_two_party_params(params),
                              opt, ccfg, [asj(ba)], asj(bb))
    pe = engine.make_pipeline(etask, opt, ccfg, depth=2)
    rs = pe.init(state)
    rs = pe.dispatch(rs, [asj(ba)], asj(bb), 100)
    rs = pe.dispatch(rs, [asj(ba)], asj(bb), 101)
    assert [int(p.batch_idx) for p in rs.pending] == [100, 101]
    rs, _ = pe.merge(rs)
    inserted = np.asarray(rs.ws["a"][0]["batch_idx"])
    assert 100 in inserted and 101 not in inserted
    rs, _ = pe.merge(rs)
    inserted = np.asarray(rs.ws["a"][0]["batch_idx"])
    assert 101 in inserted
    assert pe.finalize(rs)["comm_rounds"] == 2


def test_dispatch_beyond_queue_capacity_rejected():
    """A depth-D queue holds at most D in-flight exchanges; one more
    dispatch is a scheduler bug."""
    data, cfg = _workload()
    init_fn, task, _ = make_dlrm(cfg)
    ccfg = CELUConfig(R=3, W=5)
    params = init_fn(jax.random.PRNGKey(0), cfg)
    opt = make_optimizer("adagrad", 0.05)
    it = aligned_batches(data["train"], 64, seed=0)
    bi, ba, bb = next(it)
    asj = lambda d: {k: jnp.asarray(v) for k, v in d.items()}
    etask = engine.lift_two_party(task)
    state = engine.init_state(etask, engine.lift_two_party_params(params),
                              opt, ccfg, [asj(ba)], asj(bb))
    pe = engine.make_pipeline(etask, opt, ccfg, depth=2)
    rs = pe.init(state)
    rs = pe.dispatch(rs, [asj(ba)], asj(bb), bi)
    rs = pe.dispatch(rs, [asj(ba)], asj(bb), bi)
    with pytest.raises(RuntimeError, match="already in flight"):
        pe.dispatch(rs, [asj(ba)], asj(bb), bi)
    with pytest.raises(RuntimeError, match="still in flight"):
        pe.finalize(rs)


def test_depth_exceeding_ring_capacity_rejected():
    """D >= W leaves no valid workset draws — rejected at config AND
    scheduler level."""
    with pytest.raises(ValueError, match="pipeline_depth"):
        CELUConfig(W=5, pipeline_depth=5)
    with pytest.raises(ValueError, match="pipeline_depth"):
        CELUConfig(pipeline_depth=-1)
    with pytest.raises(ValueError, match="pipeline_lr_damping"):
        CELUConfig(pipeline_lr_damping=-0.5)
    # the scheduler revalidates an explicit depth= override
    data, cfg = _workload()
    init_fn, task, _ = make_dlrm(cfg)
    opt = make_optimizer("adagrad", 0.05)
    with pytest.raises(ValueError, match="depth"):
        engine.make_pipeline(engine.lift_two_party(task), opt,
                             CELUConfig(W=3), depth=3)


# --------------------------------------------------------------------------
# Convergence: the damped depth-D schedule still trains
# --------------------------------------------------------------------------
def test_depth2_converges_to_depth0_quality():
    """Two exchanges of queued staleness, damped, must still land in the
    sequential schedule's loss region."""
    seq, _ = _drive(0, rounds=40)
    deep, _ = _drive(2, rounds=40)
    l_seq = [r["loss"] for r in seq[:-1]]
    l_deep = [r["loss"] for r in deep[:-1] if not math.isnan(r["loss"])]
    assert np.isfinite(l_deep).all()
    assert np.mean(l_deep[-10:]) < np.mean(l_deep[:5])
    assert np.mean(l_deep[-10:]) <= 1.15 * np.mean(l_seq[-10:])


def test_lr_damping_shrinks_parameter_drift():
    """eta / (1 + c*s): a larger damping coefficient moves the params less
    over the same depth-2 schedule (the staleness guard is live)."""
    data, cfg = _workload()
    init_fn, _, _ = make_dlrm(cfg)
    p0 = init_fn(jax.random.PRNGKey(0), cfg)

    def drift(damping):
        _, st = _drive(2, rounds=12, damping=damping)
        pa = engine.unlift_params(st["params"])
        return float(sum(
            jnp.sum((a - b.astype(jnp.float32)) ** 2)
            for a, b in zip(jax.tree_util.tree_leaves(pa),
                            jax.tree_util.tree_leaves(p0))) ** 0.5)

    d_undamped = drift(0.0)
    d_damped = drift(5.0)
    assert 0 < d_damped < d_undamped


def test_inflight_residual_chain_follows_dispatch_order():
    """Lossy wire + two exchanges in flight: the second dispatch must
    encode against the FIRST in-flight exchange's error-feedback
    residuals (the chain follows dispatch order and rides the queue),
    not the stale merged-prefix residuals in the round state."""
    data, cfg = _workload()
    init_fn, task, _ = make_dlrm(cfg)
    ccfg = CELUConfig(R=3, W=5)
    tp = engine.make_transport(ccfg, "int8_topk")
    params = init_fn(jax.random.PRNGKey(0), cfg)
    opt = make_optimizer("adagrad", 0.05)
    it = aligned_batches(data["train"], 64, seed=0)
    bi, ba, bb = next(it)
    asj = lambda d: {k: jnp.asarray(v) for k, v in d.items()}
    etask = engine.lift_two_party(task)
    state = engine.init_state(etask, engine.lift_two_party_params(params),
                              opt, ccfg, [asj(ba)], asj(bb), transport=tp)
    pe = engine.make_pipeline(etask, opt, ccfg, depth=2, transport=tp)
    rs = pe.init(state)
    rs = pe.dispatch(rs, [asj(ba)], asj(bb), bi)
    bi2, ba2, bb2 = next(it)
    rs = pe.dispatch(rs, [asj(ba2)], asj(bb2), bi2)
    # exchange 1's residuals are live (lossy codec) and distinct from the
    # zero residuals still in the round state
    r1 = np.asarray(rs.pending[0].fresh["tstate"]["up"][0])
    assert np.abs(r1).sum() > 0.0
    # recomputing exchange 2 from exchange 1's transport state (same
    # dispatch seq number) reproduces the dispatched payload exactly...
    expect = pe._compute(rs.params, rs.pending[0].fresh["tstate"],
                         [asj(ba2)], asj(bb2), rs.comm_rounds + 1)
    np.testing.assert_array_equal(
        np.asarray(rs.pending[1].fresh["zs"][0]),
        np.asarray(expect["zs"][0]))
    # ...while the un-chained computation (merged-prefix zero residuals)
    # yields a different wire payload: the chain genuinely engaged
    stale = pe._compute(rs.params, rs.transport, [asj(ba2)], asj(bb2),
                        rs.comm_rounds + 1)
    assert not np.array_equal(np.asarray(rs.pending[1].fresh["zs"][0]),
                              np.asarray(stale["zs"][0]))


def test_depth2_compressed_transport_trains():
    """Error feedback composes with the deep queue: a lossy int8_topk
    wire still converges at depth 2 (residuals telescope through the
    in-flight chain)."""
    rows, st = _drive(2, rounds=14, compression="int8_topk")
    losses = [r["loss"] for r in rows[:-1] if not math.isnan(r["loss"])]
    assert np.isfinite(losses).all()
    assert np.mean(losses[-4:]) < np.mean(losses[:4])
    # the drained state carries live residuals
    assert float(jnp.abs(st["transport"]["up"][0]).sum()) > 0.0


def test_uniform_sampling_depth2_trains():
    """The uniform-draw key chain stays well-defined (and independent
    across same-comm_rounds scans) on the dynamic depth-D path."""
    rows, _ = _drive(2, rounds=16, sampling="uniform")
    losses = [r["loss"] for r in rows[:-1] if not math.isnan(r["loss"])]
    assert np.isfinite(losses).all()
    assert rows[-1]["comm_rounds"] == 16


# --------------------------------------------------------------------------
# Per-slot staleness plumbing: traced offsets through draw + kernels
# --------------------------------------------------------------------------
def _entry(v):
    return {"z": jnp.full((4, 2), float(v)), "dz": jnp.full((4, 2), 1.0)}


def test_traced_staleness_reaches_workset_draw():
    """A traced per-slot offset tightens the validity window exactly like
    the static int: at runtime s the oldest s ring slots are retired."""
    W, R = 4, 8
    ws = workset_init(W, _entry(0))
    for t in range(W):
        ws = workset_insert(ws, _entry(t), t)
    draw = jax.jit(lambda w, s: workset_draw(w, R, "round_robin",
                                             pipeline_staleness=s))
    for s, expected in ((0, W), (1, W - 1), (2, W - 2), (3, W - 3)):
        valid = 0
        w2 = dict(ws)
        for _ in range(W):
            w2, slot, _, v = draw(w2, jnp.int32(s))
            valid += int(v)
        assert valid == expected, (s, valid)


def test_traced_staleness_reaches_workset_sample():
    """workset_sample (the materializing form) accepts the traced offset
    too — one jitted sampler serves every queue occupancy."""
    W, R = 4, 8
    ws = workset_init(W, _entry(0))
    for t in range(W):
        ws = workset_insert(ws, _entry(t), t)
    sample = jax.jit(lambda w, s: workset_sample(w, R, "consecutive",
                                                 pipeline_staleness=s))
    _, e, _, v0 = sample(ws, jnp.int32(0))
    assert bool(v0)
    np.testing.assert_array_equal(np.asarray(e["z"]),
                                  np.asarray(_entry(W - 1)["z"]))
    # the freshest slot dies once the offset eats the whole window
    _, _, _, v_dead = sample(ws, jnp.int32(W))
    assert not bool(v_dead)


@pytest.mark.parametrize("s", [0, 1, 3])
def test_fused_post_scale_traced_staleness_parity(s):
    """The fused kernel's post-scale composition of a TRACED per-slot
    discount equals both the unfused reference and the static-int path."""
    rng = np.random.default_rng(11)
    a = jnp.asarray(rng.normal(size=(64, 8)), jnp.float32)
    st = jnp.asarray(rng.normal(size=(64, 8)), jnp.float32)
    dz = jnp.asarray(rng.normal(size=(64, 8)), jnp.float32)
    fused = jax.jit(lambda s_: engine.weighted_cotangent(
        a, st, dz, 0.5, fused=True, pipeline_staleness=s_))
    ref = jax.jit(lambda s_: engine.weighted_cotangent(
        a, st, dz, 0.5, fused=False, pipeline_staleness=s_))
    w_f, cot_f = fused(jnp.int32(s))
    w_r, cot_r = ref(jnp.int32(s))
    np.testing.assert_allclose(np.asarray(w_f), np.asarray(w_r),
                               rtol=3e-6, atol=3e-7)
    np.testing.assert_allclose(np.asarray(cot_f), np.asarray(cot_r),
                               rtol=3e-6, atol=3e-6)
    # traced == static composition
    w_s, cot_s = engine.weighted_cotangent(a, st, dz, 0.5, fused=True,
                                           pipeline_staleness=s)
    np.testing.assert_allclose(np.asarray(w_f), np.asarray(w_s),
                               rtol=3e-6, atol=3e-7)
    np.testing.assert_allclose(np.asarray(cot_f), np.asarray(cot_s),
                               rtol=3e-6, atol=3e-6)
    # rejected instances stay rejected through the dynamic discount
    assert np.all(np.asarray(w_f)[np.asarray(w_r) == 0.0] == 0.0)


def test_traced_staleness_zero_is_identity():
    """Runtime s = 0 through the dynamic path is the no-discount result —
    the drain scan's final pass loses nothing.  The jitted dynamic path
    and the eager static path are separate XLA:CPU programs whose fused
    row reductions may round differently, so values agree to 2 float32
    ulps (1 ulp apart on JAX 0.9.0), not bitwise."""
    rng = np.random.default_rng(12)
    a = jnp.asarray(rng.normal(size=(64, 8)), jnp.float32)
    st = jnp.asarray(rng.normal(size=(64, 8)), jnp.float32)
    dz = jnp.asarray(rng.normal(size=(64, 8)), jnp.float32)
    dyn = jax.jit(lambda s_: engine.weighted_cotangent(
        a, st, dz, 0.5, fused=True, pipeline_staleness=s_))
    w_d, cot_d = dyn(jnp.int32(0))
    w_0, cot_0 = engine.weighted_cotangent(a, st, dz, 0.5, fused=True,
                                           pipeline_staleness=0)
    ulp2 = 2 * float(np.finfo(np.float32).eps)
    np.testing.assert_allclose(np.asarray(w_d), np.asarray(w_0),
                               rtol=ulp2, atol=0)
    np.testing.assert_allclose(np.asarray(cot_d), np.asarray(cot_0),
                               rtol=ulp2, atol=0)


# --------------------------------------------------------------------------
# Guard rails retained from the static schedules
# --------------------------------------------------------------------------
def test_pod_round_rejects_deep_queue():
    """The single-jit pod round cannot host a D-deep host-side queue."""
    with pytest.raises(ValueError, match="pipeline_depth"):
        engine.make_pod_round(None, make_optimizer("adagrad", 0.01),
                              R=2, cos_xi=0.5, tower_fwd=lambda p, x: x,
                              top_loss=lambda p, a, b, y: y,
                              pipeline_depth=2)
