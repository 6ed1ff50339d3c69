"""Row-sparse tables (``core/rows.py``): a task that declares its
embedding tables trains bit-for-bit as the dense step does, while its
gradients and AdaGrad steps touch only the rows each batch indexes.

The dense reference is the same task with its declaration removed
(``row_tables=None``): every leaf then takes the dense step.  Batches
repeat ids heavily (a vocabulary of 16 under 64 rows) and hold one id in
every row of a field; the first rounds' round-robin draws hit empty ring
slots, which are masked (invalid) local updates over all-zero ids.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import CELUConfig
from repro.core import engine
from repro.core.rows import compact, unique_ids
from repro.models.tabular import DLRMConfig, make_dlrm
from repro.optim import make_optimizer

VOCAB, B, FA, FB = 16, 64, 4, 3
BASE = CELUConfig(R=3, W=3, xi_degrees=60.0)


def _batches(n, seed=0, fixed=False):
    """``n`` rounds of (batch_idx, [batch_a], batch_b) with repeated ids;
    field 0 of each party holds one id in every row.  ``fixed``: the
    same batch every round."""
    rng = np.random.default_rng(seed)
    out = []
    for t in range(n):
        if fixed and out:
            out.append((t, *out[0][1:]))
            continue
        xa = rng.integers(0, VOCAB, (B, FA)).astype(np.int32)
        xb = rng.integers(0, VOCAB, (B, FB)).astype(np.int32)
        xa[:, 0], xb[:, 0] = 5, VOCAB - 1
        y = rng.integers(0, 2, (B,)).astype(np.float32)
        out.append((t, [{"x_a": jnp.asarray(xa)}],
                    {"x_b": jnp.asarray(xb), "y": jnp.asarray(y)}))
    return out


def _setup(model, opt=None):
    cfg = DLRMConfig(model, FA, FB, vocab=VOCAB, embed_dim=4, z_dim=8,
                     hidden=(16, 8))
    init_fn, task, _ = make_dlrm(cfg)
    params = engine.lift_two_party_params(
        init_fn(jax.random.PRNGKey(1), cfg))
    opt = opt if opt is not None else make_optimizer("adagrad", 0.05)
    return engine.lift_two_party(task), params, opt


def _dense(etask):
    return etask._replace(row_tables=None)


def _assert_trees_equal(got, want):
    gl, gd = jax.tree_util.tree_flatten(got)
    wl, wd = jax.tree_util.tree_flatten(want)
    assert gd == wd
    for g, w in zip(gl, wl):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def _run_round(etask, params, opt, protocol, batches):
    celu, nloc = engine.preset_config(protocol, BASE)
    _, a0, b0 = batches[0]
    state = engine.init_state(etask, params, opt, celu, a0, b0)
    rnd = engine.make_round(etask, opt, celu, local_steps=nloc)
    ms = []
    for bi, ba, bb in batches:
        state, m = rnd(state, ba, bb, bi)
        ms.append(m)
    return state, ms


def _same_metrics(ms_rows, ms_dense):
    keys = ("loss", "w_mean", "w_zero_frac", "local_steps")
    for r, d in zip(ms_rows, ms_dense):
        for k in keys:
            np.testing.assert_array_equal(np.asarray(r[k]),
                                          np.asarray(d[k]))


# --------------------------------------------------------------------------
# the compact tables
# --------------------------------------------------------------------------
def test_unique_ids_rows_positions_and_count():
    ids = jnp.asarray(np.random.default_rng(3).integers(
        0, VOCAB, (B, FA)).astype(np.int32)).at[:, 0].set(7)
    rows, pos, n = unique_ids(ids, VOCAB)
    rows, pos, ids = np.asarray(rows), np.asarray(pos), np.asarray(ids)
    assert rows.shape == (FA, B) and pos.shape == (B, FA)
    for f in range(FA):
        want = np.unique(ids[:, f])
        np.testing.assert_array_equal(rows[f, :want.size], want)
        # the pads are out of range, distinct and ascending
        np.testing.assert_array_less(VOCAB - 1, rows[f, want.size:])
        assert np.all(np.diff(rows[f]) > 0)
        np.testing.assert_array_equal(rows[f, pos[:, f]], ids[:, f])
    assert int(n) == sum(np.unique(ids[:, f]).size for f in range(FA))


def test_compact_forward_matches_full_table():
    etask, params, _ = _setup("wdl")
    _, [ba], bb = _batches(1)[0]
    pa, cba, rows, _ = compact(params["a"][0], ba, etask.row_tables.a)
    assert pa["tower"]["embed"].shape == (FA, B, 4)
    assert rows["tower"]["mlp"][0]["w"] is None
    np.testing.assert_array_equal(
        np.asarray(etask.forward_a(pa, cba)),
        np.asarray(etask.forward_a(params["a"][0], ba)))


# --------------------------------------------------------------------------
# bit-for-bit equality with the dense step
# --------------------------------------------------------------------------
@pytest.mark.parametrize("protocol", ["vanilla", "celu"])
@pytest.mark.parametrize("model", ["wdl", "dssm"])
def test_row_path_matches_dense_step(model, protocol):
    etask, params, opt = _setup(model)
    batches = _batches(6)
    st_r, ms_r = _run_round(etask, params, opt, protocol, batches)
    st_d, ms_d = _run_round(_dense(etask), params, opt, protocol, batches)
    assert all("rows_updated" in m for m in ms_r)
    assert not any("rows_updated" in m for m in ms_d)
    _same_metrics(ms_r, ms_d)
    _assert_trees_equal(st_r["params"], st_d["params"])
    _assert_trees_equal(st_r["opt"], st_d["opt"])
    if protocol == "celu":
        # round 1 drew empty ring slots: masked local updates ran
        assert int(ms_r[0]["local_steps"]) < 2 * BASE.R


def test_damped_depth1_pipeline_matches_dense_step():
    """The depth-1 scheduler carries the compact gradients and their ids
    from dispatch to merge; with dynamic staleness its local steps are
    damped by 1 / (1 + c*s)."""
    etask, params, opt = _setup("wdl")
    batches = _batches(6)
    celu, nloc = engine.preset_config("celu", BASE)

    def run(task):
        pe = engine.PipelinedEngine(task, opt, celu, depth=1,
                                    local_steps=nloc,
                                    dynamic_staleness=True)
        _, a0, b0 = batches[0]
        rs = pe.init(engine.init_state(task, params, opt, celu, a0, b0))
        ms = []
        for bi, ba, bb in batches:
            rs, m = pe.step(rs, ba, bb, bi)
            ms.append(m)
        rs, fm = pe.flush(rs)
        return pe.finalize(rs), ms, fm, rs

    st_r, ms_r, fm_r, _ = run(etask)
    st_d, ms_d, fm_d, _ = run(_dense(etask))
    assert celu.pipeline_lr_damping > 0
    _same_metrics(ms_r, ms_d)
    assert "rows_updated" in fm_r and "rows_updated" not in fm_d
    _assert_trees_equal(st_r["params"], st_d["params"])
    _assert_trees_equal(st_r["opt"], st_d["opt"])


@pytest.mark.parametrize("depth,jobs,mode", [
    (0, 1, "vmap"), (2, 1, "vmap"), (2, 2, "map")])
def test_fleet_matches_dense_step(depth, jobs, mode):
    """The device-side scheduler, vmapped over its job axis (or mapped
    lane by lane), and its depth-2 queue of compact payloads.  Bit for
    bit is the fleet's contract against the scalar engine for one vmap
    lane or any number of mapped lanes: XLA:CPU may order a batched
    product's sums differently when the programs around it differ."""
    from repro.fleet.runner import FleetWorkload, JobSpec, run_fleet
    etask, params, _ = _setup("wdl")
    batches = _batches(6)
    celu, nloc = engine.preset_config("celu", BASE)
    specs = [JobSpec(celu=celu, local_steps=nloc, depth=depth)] * jobs

    def fleet(task):
        wl = FleetWorkload(task, lambda seed: params, lambda: iter(batches))
        return run_fleet(specs, len(batches), workload=wl, mode=mode)

    r, d = fleet(etask), fleet(_dense(etask))
    np.testing.assert_array_equal(r.losses, d.losses)
    np.testing.assert_array_equal(r.w_mean, d.w_mean)
    for j in range(len(specs)):
        _assert_trees_equal(r.final_state(j)["params"],
                            d.final_state(j)["params"])
        _assert_trees_equal(r.final_state(j)["opt"],
                            d.final_state(j)["opt"])


# --------------------------------------------------------------------------
# which step runs, and the counter
# --------------------------------------------------------------------------
@pytest.mark.parametrize("name,kw", [
    ("adam", {}),
    ("sgd", {"momentum": 0.9}),
    ("adagrad", {"state_dtype": "int8"}),
    ("adagrad", {"state_dtype": "bfloat16"}),
    ("adagrad", {"use_pallas": True}),
])
def test_optimizers_without_row_update_keep_dense_step(name, kw):
    opt = make_optimizer(name, 0.05, **kw)
    assert opt.update_rows is None
    etask, params, _ = _setup("wdl", opt)
    batches = _batches(1)
    _, ms = _run_round(etask, params, opt, "celu", batches)
    assert "rows_updated" not in ms[0]
    celu, nloc = engine.preset_config("celu", BASE)
    compute, _, _ = engine._make_stages(
        etask, opt, celu, n_local=nloc, tp=engine.make_transport(celu),
        fused=True)
    _, a0, b0 = batches[0]
    fresh = jax.eval_shape(compute, params, {}, a0, b0, jnp.int32(0))
    assert "rows" not in fresh
    # the table gradient is dense: the whole table's shape
    assert fresh["g_as"][0]["tower"]["embed"].shape == (FA, VOCAB, 4)


def test_task_without_tables_keeps_dense_step():
    """An LLM-family task declares no tables: its round has no row path."""
    from repro.configs import get_config
    from repro.data import synthetic as synth
    from repro.launch.train import llm_task
    from repro.models import vfl

    cfg = get_config("smollm-360m").reduced()
    etask = engine.lift_two_party(llm_task(cfg))
    assert etask.row_tables is None
    params = engine.lift_two_party_params(
        vfl.init_all(jax.random.PRNGKey(0), cfg))
    data = synth.make_token_stream(16, 8, cfg.vocab_size,
                                   cfg.aux_vocab_size, seed=0)
    bi, ba, bb = next(synth.token_batches(data, 2, seed=0))
    ba = [{k: jnp.asarray(v) for k, v in ba.items()}]
    bb = {k: jnp.asarray(v) for k, v in bb.items()}
    opt = make_optimizer("adagrad", 0.01)
    celu, nloc = engine.preset_config("celu", BASE)
    state = jax.eval_shape(
        lambda p: engine.init_state(etask, p, opt, celu, ba, bb), params)
    rnd = engine.make_round(etask, opt, celu, local_steps=nloc, jit=False)
    _, m = jax.eval_shape(rnd, state, ba, bb, bi)
    assert "rows_updated" not in m and "loss" in m


@pytest.mark.parametrize("protocol", ["vanilla", "celu"])
def test_rows_updated_counts_distinct_field_ids_per_step(protocol):
    """On one batch fed every round, each optimizer step writes the
    batch's distinct (field, id) pairs of A's ids and of B's (B's embed
    and wide rows share B's ids and count once); after W rounds every
    ring slot holds that batch."""
    etask, params, opt = _setup("wdl")
    batches = _batches(BASE.W + 1, seed=4, fixed=True)
    _, ms = _run_round(etask, params, opt, protocol, batches)
    _, [ba], bb = batches[0]
    distinct = sum(np.unique(np.asarray(x)[:, f]).size
                   for x in (ba["x_a"], bb["x_b"])
                   for f in range(x.shape[1]))
    steps = 1 + (BASE.R if protocol == "celu" else 0)
    assert int(ms[-1]["rows_updated"]) == steps * distinct
