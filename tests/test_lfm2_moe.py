"""LFM2-MoE (``lfm2-8b-a1b``) against the benchmark's plain reference
(``bench/families/lfm2-moe.py``), at the configuration's rehearsal size
on the CPU, with seeded random weights; and the dropless MoE layer of
every MoE config.

The program runs here on float32 weights, so that it and the float32
reference compute the same sums in another order: each tolerance below
is float32 round-off over a few thousand terms, far under what
bfloat16 storage or a dropped (token, slot) would move.
"""
import importlib.util
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import CELUConfig, MoEConfig
from repro.core import engine
from repro.models import moe as M
from repro.models import ssm, vfl
from repro.optim import make_optimizer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import celu_ref  # noqa: E402
import precision  # noqa: E402


def _family():
    spec = importlib.util.spec_from_file_location(
        "family_lfm2_moe", os.path.join(BENCH, "families", "lfm2-moe.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


FAM = _family()
F32 = precision.Policy("float32")


def _cfg():
    """The benchmark configuration at its rehearsal size."""
    with open(os.path.join(BENCH, "configs", "lfm2-8b-a1b.json")) as f:
        cfg = json.load(f)
    over = cfg.pop("rehearse")
    split = dict(cfg["split"], **over.pop("split"))
    cfg.update(over, split=split)
    return cfg


CFG = _cfg()
B, S = 2, 32


def _f32(tree):
    return jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), tree)


def _weights(seed=0, cfg=CFG):
    return _f32(FAM.make_weights(cfg, jax.random.PRNGKey(seed)))


def _batch(seed=1, cfg=CFG):
    rng = np.random.default_rng(seed)
    tok = rng.integers(0, cfg["vocab_size"], (B, S + 1)).astype(np.int32)
    tok_a = rng.integers(0, cfg["split"]["aux_vocab_size"], (B, S)
                         ).astype(np.int32)
    return ({"tokens_a": jnp.asarray(tok_a)},
            {"tokens": jnp.asarray(tok[:, :-1]),
             "labels": jnp.asarray(tok[:, 1:])})


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def _program_task(cfg=CFG):
    task, shapes = FAM.program(cfg)
    return task


# ------------------------------------------------------- program vs reference
def test_exchange_matches_reference():
    """Loss and every gradient leaf of one exchange: float32 program
    against the float32 reference, to 1e-4 of each leaf's norm (round-
    off of float32 sums in another order; routing ties are not near at
    these seeds, and a tie broken the other way would move an expert
    leaf by its whole share)."""
    w = _weights()
    ba, bb = _batch()
    task = _program_task()
    fa, lb = FAM.reference(CFG)
    with jax.default_matmul_precision("highest"):
        def prog(p):
            z, ca = task.forward_a(p["a"], ba)
            li, aux, cb = task.loss_b(p["b"], z, bb)
            return jnp.mean(li) + aux

        def ref(p):
            z = fa(p["a"], ba, F32)
            return jnp.mean(lb(p["b"], z, bb, F32))

        lp, gp = jax.value_and_grad(prog)(w)
        lr, gr = jax.value_and_grad(ref)(w)
    assert abs(float(lp) - float(lr)) <= 1e-5 * abs(float(lr))
    flat_p = jax.tree_util.tree_flatten_with_path(gp)[0]
    flat_r = jax.tree_util.tree_leaves(gr)
    for (path, a), b in zip(flat_p, flat_r):
        name = jax.tree_util.keystr(path)
        if "expert_bias" in name:      # selects only: no gradient
            assert float(jnp.max(jnp.abs(a))) == 0.0, name
            assert float(jnp.max(jnp.abs(b))) == 0.0, name
            continue
        assert _rel(a, b) <= 1e-4, (name, _rel(a, b))


def test_celu_round_matches_reference():
    """Parameters after one CELU round (exchange + R local updates from
    a float32 ring, float32 AdaGrad state): program against
    ``celu_ref.run``, each leaf's change to 3% of its norm.  AdaGrad's
    first step moves every element by about lr whatever the size of its
    gradient (g / sqrt(g^2)), so an element whose gradient is round-off
    sized may go either way: a few such elements move an expert leaf's
    change by up to ~1% here.  A wrong update (a row dropped, a gate or
    a local step missed) moves a change by its whole size."""
    w = _weights()
    ba, bb = _batch()
    job = {"protocol": "celu", "R": 2, "W": 2, "xi": 60.0, "lr": 1e-3}
    fa, lb = FAM.reference(CFG)
    with jax.default_matmul_precision("highest"):
        out = celu_ref.run(fa, lb, w, [(0, ba, bb)], job, F32)
        task = engine.lift_two_party(_program_task())
        celu = CELUConfig(R=2, W=2, xi_degrees=60.0)
        opt = make_optimizer("adagrad", 1e-3)
        st = engine.init_state(task, engine.lift_two_party_params(w), opt,
                               celu, [ba], bb)
        fn = engine.make_round(task, opt, celu, local_steps=2)
        st, m = fn(st, [ba], bb, 0)
    assert abs(float(m["loss"]) - out["losses"][0]) <= \
        1e-5 * abs(out["losses"][0])
    prog = engine.unlift_params(st["params"])
    for (path, p), r, p0 in zip(
            jax.tree_util.tree_flatten_with_path(prog)[0],
            jax.tree_util.tree_leaves(out["params"]),
            jax.tree_util.tree_leaves(w)):
        dp, dr = np.asarray(p) - np.asarray(p0), np.asarray(r) - np.asarray(p0)
        if not np.any(dr):
            assert not np.any(dp), jax.tree_util.keystr(path)
            continue
        assert _rel(dp, dr) <= 0.03, (jax.tree_util.keystr(path),
                                      _rel(dp, dr))


# ------------------------------------------------------------- the MoE layer
def _moe_cfg(cfg=CFG, **kw):
    return MoEConfig(n_experts=cfg["router_experts"],
                     top_k=cfg["num_experts_per_tok"],
                     d_expert=cfg["moe_intermediate_size"], router="sigmoid",
                     router_aux_coef=0.0, **kw)


def _moe_params(key, cfg, n_experts):
    """One MoE layer's float32 weights in the program's layout with
    ``n_experts`` experts, drawn as the benchmark draws them."""
    c = dict(cfg, num_experts=n_experts, num_dense_layers=0)
    p = FAM._block(key, c, ("conv", "moe"), 1)["b0"]["ffn"]
    return _f32(jax.tree_util.tree_map(lambda a: a[0], p))


def _x(seed=3, d=None):
    d = d or CFG["hidden_size"]
    return jax.random.normal(jax.random.PRNGKey(seed), (B, S, d))


def _held(p, off, n):
    return dict(p, **{k: p[k][off:off + n] for k in ("wg", "wu", "wd")})


def test_expert_shares_add_up():
    """Four chips of an expert-parallel group, each holding a quarter of
    the experts: their layer outputs add up to the uncut layer's, and to
    the reference's with every expert held."""
    E = CFG["router_experts"]
    p = _moe_params(jax.random.PRNGKey(5), CFG, E)
    x = _x()
    with jax.default_matmul_precision("highest"):
        whole, _, rows = M.moe_apply(p, x, _moe_cfg())
        parts = [M.moe_apply(_held(p, o, E // 4), x,
                             _moe_cfg(n_held=E // 4, held_offset=o))
                 for o in range(0, E, E // 4)]
        ref = FAM._moe(p, x, dict(CFG, num_experts=E, experts_offset=0), F32)
    assert _rel(sum(y for y, _, _ in parts), whole) <= 1e-5
    assert _rel(whole, ref) <= 1e-5
    assert int(rows) == B * S * CFG["num_experts_per_tok"]
    assert sum(int(r) for _, _, r in parts) == int(rows)


def test_dropless_when_every_token_picks_one_held_expert():
    """A router bias that sends every token to held expert 1: a layer
    with a capacity would drop most of them; this one matches the
    reference, and counts each held (token, slot) assignment once."""
    H = CFG["num_experts"]
    p = _moe_params(jax.random.PRNGKey(6), CFG, H)
    p["expert_bias"] = p["expert_bias"].at[1].set(100.0)
    x = _x(seed=4)
    cfg = dict(CFG, experts_offset=0)
    with jax.default_matmul_precision("highest"):
        y, _, rows = M.moe_apply(p, x, _moe_cfg(n_held=H))
        ref = FAM._moe(p, x, cfg, F32)
        logits = x.reshape(-1, x.shape[-1]) @ p["router"]
    _, idx = jax.lax.top_k(jax.nn.sigmoid(logits) + p["expert_bias"],
                           CFG["num_experts_per_tok"])
    idx = np.asarray(idx)
    assert np.all(np.any(idx == 1, axis=1))
    assert int(rows) == int(np.sum(idx < H))
    assert _rel(y, ref) <= 1e-5


@pytest.mark.parametrize("held,align", [(2, 1), (2, 8), (8, 1), (8, 8)])
def test_layout_places_held_rows_in_padded_blocks(held, align):
    """Each held row goes to its expert's block in its order, blocks are
    multiples of ``align`` long and start where the last ended, rows not
    held go nowhere, and the groups cover every slot."""
    rng = np.random.default_rng(held + align)
    for hi in (held, held + 1):          # every row held, and some not
        key = rng.integers(0, hi, 64).astype(np.int32)
        n_slots = M.slot_count(key.size, held, align)
        dest, src, sizes = (np.asarray(a) for a in M.layout(
            jnp.asarray(key), held, align, n_slots))
        counts = np.bincount(key, minlength=held + 1)[:held]
        block = -(-counts // align) * align
        start = np.concatenate([[0], np.cumsum(block)])
        assert sizes.sum() == n_slots
        assert np.array_equal(sizes[:-1], block[:-1])
        for e in range(held):
            rows = np.flatnonzero(key == e)
            assert np.array_equal(dest[rows], start[e] + np.arange(rows.size))
        assert np.all(dest[key == held] == n_slots)
        placed = dest < n_slots
        assert np.array_equal(src[dest[placed]], np.flatnonzero(placed))
        assert np.sum(src < key.size) == placed.sum()
        assert np.all(src[src >= key.size] == key.size)


def test_move_gradient_is_the_way_back():
    """``_move``'s gradient (a gather by ``dest``) is the transpose of its
    gather by ``src``: the same as differentiating the gather itself."""
    key = np.random.default_rng(0).integers(0, 4, 24).astype(np.int32)
    dest, src, _ = M.layout(jnp.asarray(key), 3, 4,
                            M.slot_count(24, 3, 4))
    x = jax.random.normal(jax.random.PRNGKey(0), (24, 5))
    w = jax.random.normal(jax.random.PRNGKey(1), (src.shape[0], 5))
    plain = jax.grad(lambda x: jnp.sum(w * jnp.take(
        x, src, axis=0, mode="fill", fill_value=0)))(x)
    moved = jax.grad(lambda x: jnp.sum(w * M._move(x, src, dest)))(x)
    np.testing.assert_array_equal(np.asarray(moved), np.asarray(plain))


@pytest.mark.parametrize("align", [1, 8])
def test_rows_not_held_change_no_number(monkeypatch, align):
    """Padding the held blocks, and the rows not held, change no number:
    the layer's output, its row count and every gradient read the same
    as the plain dense-per-expert layer, with most picks to absent
    experts so that the padding and the last group take many rows."""
    H = CFG["num_experts"]
    p = _moe_params(jax.random.PRNGKey(9), CFG, H)
    p["expert_bias"] = p["expert_bias"].at[H:].add(0.3)
    x = _x(seed=10)
    cfg = _moe_cfg(n_held=H)
    monkeypatch.setattr(M, "group_align", lambda rows, H: align)

    def loss(f, p, x):
        y, rows = f(p, x)
        return jnp.sum(y * jnp.cos(y)), (y, rows)

    def layer(p, x):
        y, _, rows = M.moe_apply(p, x, cfg)
        return y, rows

    def dense(p, x):
        return FAM._moe(p, x, dict(CFG, experts_offset=0), F32), 0

    with jax.default_matmul_precision("highest"):
        (_, (y, rows)), g = jax.value_and_grad(
            lambda p, x: loss(layer, p, x), argnums=(0, 1), has_aux=True)(p, x)
        (_, (y0, _)), g0 = jax.value_and_grad(
            lambda p, x: loss(dense, p, x), argnums=(0, 1), has_aux=True)(p, x)
    assert int(rows) < B * S * cfg.top_k // 2
    assert _rel(y, y0) <= 1e-5
    for a, b in zip(jax.tree_util.tree_leaves(g), jax.tree_util.tree_leaves(g0)):
        assert _rel(a, b) <= 1e-5


def _dense_softmax_moe(p, x, cfg: MoEConfig):
    """Every expert over every token, weighted by its softmax gate over
    the top-k logits (zero off the top-k): what a dropless layer gives."""
    x2 = x.reshape(-1, x.shape[-1])
    top, idx = jax.lax.top_k(x2 @ p["router"], cfg.top_k)
    g = jax.nn.softmax(top, -1)
    y = jnp.zeros_like(x2)
    for e in range(cfg.n_experts):
        gate = jnp.sum(jnp.where(idx == e, g, 0.0), -1)
        h = jax.nn.silu(x2 @ p["wg"][e]) * (x2 @ p["wu"][e])
        y = y + gate[:, None] * (h @ p["wd"][e])
    return y.reshape(x.shape)


@pytest.mark.parametrize("arch", ["granite-moe-3b-a800m",
                                  "llama4-scout-17b-a16e", "lfm2-8b-a1b"])
def test_moe_configs_run_dropless(arch):
    """Each MoE config's smoke variant, with a router that sends every
    token's first slot to expert 0: no (token, slot) is dropped (the
    count is B * S * k) and the output is the dense per-expert sum."""
    a = get_config(arch).reduced()
    cfg = a.moe
    p = _f32(M.moe_init(jax.random.PRNGKey(7), a.d_model, a.d_ff, cfg))
    p["router"] = p["router"].at[:, 0].add(50.0)
    x = jnp.abs(_x(seed=8, d=a.d_model))
    with jax.default_matmul_precision("highest"):
        y, aux, rows = M.moe_apply(p, x, cfg)
        if cfg.router == "softmax":
            ref = _dense_softmax_moe(p, x, cfg)
            if "shared" in p:
                from repro.models.layers import mlp_apply
                ref = ref + mlp_apply(p["shared"], x)
        else:
            fam_cfg = {"num_experts": cfg.n_experts, "experts_offset": 0,
                       "num_experts_per_tok": cfg.top_k,
                       "routed_scaling_factor": 1}
            ref = FAM._moe(p, x, fam_cfg, F32)
    assert int(rows) == B * S * cfg.top_k
    assert _rel(y, ref) <= 1e-5
    assert np.isfinite(float(aux))


def test_expert_rows_in_round_metrics():
    """With every token's top-k among the held experts, a round's
    ``expert_rows`` is B * S * k for each MoE layer of each forward
    pass: the exchange's two, and per local update A's forward and B's
    two (the ad-hoc derivative and the weighted loss)."""
    w = _weights(seed=2)
    H, E = CFG["num_experts"], CFG["router_experts"]
    assert H == CFG["num_experts_per_tok"]

    def force(path, x):
        if "expert_bias" in jax.tree_util.keystr(path):
            return x.at[..., :H].add(100.0)
        return x
    w = jax.tree_util.tree_map_with_path(force, w)
    ba, bb = _batch()
    task = engine.lift_two_party(_program_task())
    R = 2
    celu = CELUConfig(R=R, W=2)
    opt = make_optimizer("adagrad", 1e-3)
    st = engine.init_state(task, engine.lift_two_party_params(w), opt, celu,
                           [ba], bb)
    _, m = engine.make_round(task, opt, celu, local_steps=R)(st, [ba], bb, 0)
    moe = {t: sum(FAM.layer_kind(CFG, i)[1] == "moe"
                  for i in FAM.tower_layers(CFG, t))
           for t in ("a", "bottom", "top")}
    per = B * S * CFG["num_experts_per_tok"]
    a, b = moe["a"] * per, (moe["bottom"] + moe["top"]) * per
    assert E > H and a and b
    assert int(m["expert_rows"]) == (a + b) + R * (a + 2 * b)


# ---------------------------------------------------------- short convolution
def test_short_conv_matches_per_position_loop():
    """The gated short convolution against a plain loop over positions
    of the published formula (Conv1d weight (d, 1, K), padding K-1,
    output cut to the sequence), and causal: changing position t+1
    leaves positions up to t as they were."""
    d, K = 16, 3
    p = _f32(ssm.short_conv_init(jax.random.PRNGKey(9), d, K))
    x = jax.random.normal(jax.random.PRNGKey(10), (B, 8, d))
    y = np.asarray(ssm.short_conv_apply(p, x))
    w_hf = np.asarray(p["conv_w"])[::-1].T          # (d, K): tap k at t-K+1+k
    bcx = np.asarray(x) @ np.asarray(p["in_proj"])
    bg, cg, xs = bcx[..., :d], bcx[..., d:2 * d], bcx[..., 2 * d:]
    bx = bg * xs
    ref = np.zeros_like(bx)
    for t in range(x.shape[1]):
        for k in range(K):
            s = t - (K - 1) + k
            if s >= 0:
                ref[:, t] += w_hf[:, k] * bx[:, s]
    ref = (cg * ref) @ np.asarray(p["out_proj"])
    np.testing.assert_allclose(y, ref, rtol=1e-4, atol=1e-5)
    t = 4
    x2 = x.at[:, t + 1].add(1.0)
    y2 = np.asarray(ssm.short_conv_apply(p, x2))
    np.testing.assert_array_equal(y2[:, :t + 1], y[:, :t + 1])
    assert not np.allclose(y2[:, t + 1], y[:, t + 1])


def test_lfm2_decode_matches_prefill_continuation():
    """Decode through the conv states and the KV cache equals a prefill
    one token longer (the smoke variant: conv, dense and MoE layers)."""
    cfg = get_config("lfm2-8b-a1b").reduced()
    params = _f32(vfl.init_all(jax.random.PRNGKey(0), cfg))
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, 9), np.int32))
    toks_a = jnp.asarray(rng.integers(0, 512, (B, 9), np.int32))
    with jax.default_matmul_precision("highest"):
        _, caches = vfl.prefill(params, cfg, {"tokens": toks[:, :8],
                                              "tokens_a": toks_a[:, :8]},
                                total_len=9)
        logits_d, _ = vfl.decode_step(
            params, cfg, caches, {"token": toks[:, 8:], "token_a":
                                  toks_a[:, 8:]}, jnp.int32(8))
        logits_f, _ = vfl.prefill(params, cfg, {"tokens": toks,
                                                "tokens_a": toks_a})
    np.testing.assert_allclose(np.asarray(logits_d[:, 0]),
                               np.asarray(logits_f[:, -1]),
                               rtol=1e-4, atol=1e-4)


# -------------------------------------------------------------- the benchmark
def test_rehearsal_runs():
    """``bench/run.py --rehearse`` drives the cell end to end on the
    CPU: set-up, window, the reference check."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "lfm2-8b-a1b.celu-int8", "--seed", "2147600123", "--seconds", "1",
         "--rehearse"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True
    with open(os.path.join(BENCH, "workloads",
                           "lfm2-8b-a1b.celu-int8.json")) as f:
        limits = json.load(f)
    assert set(line["checks"]) == set(limits["limits"])
