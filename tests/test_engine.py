"""K-party round engine: golden-trace parity with the pre-engine seed
implementation, fused-vs-reference weighting equivalence, and transport
byte accounting.

``golden/two_party_trace.json`` was recorded from the ORIGINAL (pre-engine)
``core.protocol`` implementation at the seed commit — the engine's K=1 path
must reproduce those metrics bit-for-bit for all three protocol presets,
whether constructed through the ``core.protocol`` shim or directly on the
engine.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import CELUConfig
from repro.core import engine
from repro.core import protocol as P
from repro.core.weighting import instance_weights
from repro.data.synthetic import TabularSpec, aligned_batches, make_tabular
from repro.models.tabular import DLRMConfig, make_dlrm
from repro.optim import make_optimizer

GOLDEN = os.path.join(os.path.dirname(__file__), "golden",
                      "two_party_trace.json")
GOLDEN3 = os.path.join(os.path.dirname(__file__), "golden",
                       "three_party_trace.json")


def _workload():
    """The exact tiny workload the golden traces were recorded on."""
    spec = TabularSpec("criteo", fields_a=4, fields_b=3, vocab=32,
                       n_train=2048, n_test=512)
    data = make_tabular(spec, seed=0)
    cfg = DLRMConfig("wdl", 4, 3, vocab=32, embed_dim=4, z_dim=8,
                     hidden=(16, 8))
    return data, cfg


def _run_trace(protocol, *, via_shim, fused=True, rounds=20,
               compression=None):
    data, cfg = _workload()
    init_fn, task, predict = make_dlrm(cfg)
    base = CELUConfig(R=3, W=3, xi_degrees=60.0)
    ccfg, nloc = engine.preset_config(protocol, base)
    params = init_fn(jax.random.PRNGKey(0), cfg)
    opt = make_optimizer("adagrad", 0.05)
    it = aligned_batches(data["train"], 64, seed=0)
    _, ba, bb = next(it)
    asj = lambda d: {k: jnp.asarray(v) for k, v in d.items()}
    kw = {} if compression is None else \
        {"transport": engine.make_transport(ccfg, compression)}

    if via_shim:
        state = P.init_state(task, params, opt, ccfg, asj(ba), asj(bb),
                             **kw)
        rnd = P.make_round(task, opt, ccfg, local_steps=nloc,
                           fused_weighting=fused, **kw)
        step = lambda st, ba, bb, bi: rnd(st, asj(ba), asj(bb), bi)
        steps_of = lambda st: (int(st["steps"]["a"]),
                               int(st["steps"]["b"]))
    else:
        etask = engine.lift_two_party(task)
        state = engine.init_state(etask,
                                  engine.lift_two_party_params(params),
                                  opt, ccfg, [asj(ba)], asj(bb), **kw)
        rnd = engine.make_round(etask, opt, ccfg, local_steps=nloc,
                                fused_weighting=fused, **kw)
        step = lambda st, ba, bb, bi: rnd(st, [asj(ba)], asj(bb), bi)
        steps_of = lambda st: (int(st["steps"]["a"][0]),
                               int(st["steps"]["b"]))

    it = aligned_batches(data["train"], 64, seed=0)
    rows = []
    for i in range(rounds):
        bi, ba, bb = next(it)
        state, m = step(state, ba, bb, bi)
        rows.append({"loss": float(np.float32(m["loss"])),
                     "w_mean": float(np.float32(m["w_mean"])),
                     "w_zero_frac": float(np.float32(m["w_zero_frac"])),
                     "local_steps": int(m["local_steps"])})
    sa, sb = steps_of(state)
    rows.append({"steps_a": sa, "steps_b": sb,
                 "comm_rounds": int(state["comm_rounds"])})
    return rows


@pytest.fixture(scope="module")
def golden():
    with open(GOLDEN) as f:
        return json.load(f)


@pytest.mark.parametrize("protocol", ["vanilla", "fedbcd", "celu"])
def test_golden_trace_parity_via_protocol_shim(protocol, golden):
    """core.protocol (now a preset shim) reproduces the seed implementation
    bit-for-bit: identical loss/weight metrics over 20 rounds."""
    got = _run_trace(protocol, via_shim=True)
    assert got == golden[protocol]


@pytest.mark.parametrize("protocol", ["vanilla", "fedbcd", "celu"])
def test_golden_trace_parity_direct_engine(protocol, golden):
    """Constructing K=1 rounds directly on the engine gives the same
    trace as the shim (and hence the seed)."""
    got = _run_trace(protocol, via_shim=False)
    assert got == golden[protocol]


@pytest.mark.parametrize("via_shim", [True, False])
@pytest.mark.parametrize("protocol", ["vanilla", "celu"])
def test_identity_codec_transport_matches_golden(protocol, via_shim,
                                                 golden):
    """CompressedWANTransport with the identity codec is the SAME wire as
    plain SimWANTransport: bit-for-bit on the seed golden traces."""
    got = _run_trace(protocol, via_shim=via_shim, compression="identity")
    assert got == golden[protocol]


def test_fused_weighting_matches_reference_trace(golden):
    """The fused Pallas weighted-cotangent hot path and the pure-jnp
    reference composition produce identical training traces."""
    ref = _run_trace("celu", via_shim=False, fused=False, rounds=10)
    fused = _run_trace("celu", via_shim=False, fused=True, rounds=10)
    assert ref == fused
    # and both match the golden prefix
    assert ref[:10] == golden["celu"][:10]


def test_fused_weighting_kernel_equivalence():
    """Direct kernel-level check: engine.weighted_cotangent fused path ==
    reference composition (weights AND cotangent).  Single-tile shapes
    (B <= BLOCK_B) compute the same float32 operations, but the kernel
    and the reference are separate XLA:CPU programs whose fused row
    reductions may round differently: they agree to 2 ulps (1 ulp apart
    on JAX 0.9.0).  Tiled grids may also reassociate the row reduction,
    so they get a wider float32-ulp tolerance."""
    from repro.kernels.cosine_weight import BLOCK_B
    ulp2 = 2 * float(np.finfo(np.float32).eps)
    rng = np.random.default_rng(3)
    for B, F in ((64, 8), (128, 32), (256, 16)):
        a = jnp.asarray(rng.normal(size=(B, F)), jnp.float32)
        s = jnp.asarray(rng.normal(size=(B, F)), jnp.float32)
        dz = jnp.asarray(rng.normal(size=(B, F)), jnp.float32)
        w_f, cot_f = engine.weighted_cotangent(a, s, dz, 0.5, fused=True)
        w_r, cot_r = engine.weighted_cotangent(a, s, dz, 0.5, fused=False)
        tol = dict(rtol=ulp2, atol=0) if B <= BLOCK_B else \
            dict(rtol=3e-7, atol=3e-7)
        np.testing.assert_allclose(np.asarray(w_f), np.asarray(w_r), **tol)
        np.testing.assert_allclose(np.asarray(cot_f), np.asarray(cot_r),
                                   **tol)
        np.testing.assert_allclose(
            np.asarray(engine.staleness_weights(a, s, 0.5, fused=True)),
            np.asarray(instance_weights(a, s, 0.5)), rtol=3e-7, atol=3e-7)


def test_fused_weighting_odd_batch_falls_back():
    """Batch sizes the Pallas tiling can't split fall back to the
    reference path instead of failing."""
    rng = np.random.default_rng(4)
    a = jnp.asarray(rng.normal(size=(37, 8)), jnp.float32)
    s = jnp.asarray(rng.normal(size=(37, 8)), jnp.float32)
    dz = jnp.asarray(rng.normal(size=(37, 8)), jnp.float32)
    w, cot = engine.weighted_cotangent(a, s, dz, 0.5, fused=True)
    assert w.shape == (37,) and cot.shape == (37, 8)


def test_sim_wan_transport_byte_accounting():
    t32 = engine.SimWANTransport(CELUConfig(wire_dtype="float32"))
    t16 = engine.SimWANTransport(CELUConfig(wire_dtype="bfloat16"))
    # paper §2.1 geometry: Z_A (4096 x 256 fp32) -> 8 MB both ways
    assert t32.round_bytes([(4096, 256)]) == 2 * 4096 * 256 * 4
    assert t16.round_bytes([(4096, 256)]) == t32.round_bytes([(4096, 256)]) // 2
    # K feature parties: K uplink+downlink pairs
    assert t32.round_bytes([(64, 8)] * 3) == 3 * 2 * 64 * 8 * 4


def test_round_bytes_counts_asymmetric_messages():
    """Regression for the old ``2 * message_bytes`` shortcut: a transport
    with a sparse uplink (top-k indices+values) and a dense downlink must
    sum the two directions, not double one of them."""
    celu = CELUConfig()
    tp = engine.make_transport(celu, "int8_topk")
    shape = (256, 32)
    up, down = tp.uplink_bytes(shape), tp.downlink_bytes(shape)
    assert up != down                       # genuinely asymmetric
    assert tp.round_bytes([shape]) == up + down
    assert tp.round_bytes([shape] * 3) == 3 * (up + down)
    assert tp.round_bytes([shape]) != 2 * tp.message_bytes(shape)
    # symmetric transports still see one up + one down per party
    t32 = engine.SimWANTransport(celu)
    assert t32.round_bytes([shape]) == \
        t32.uplink_bytes(shape) + t32.downlink_bytes(shape) == \
        2 * t32.message_bytes(shape)


def _three_party_workload():
    """The exact K=2-feature-party workload (three parties total:
    A_1, A_2, B) the K=3 golden trace was recorded on."""
    spec = TabularSpec("t", fields_a=8, fields_b=4, vocab=64,
                       n_train=4096, n_test=512)
    data = make_tabular(spec, seed=0)
    cfg = DLRMConfig("wdl", 4, 4, vocab=64, embed_dim=4, z_dim=8,
                     hidden=(16, 8))
    init_fn, _, _ = make_dlrm(cfg)
    from repro.models.tabular import _mlp, _mlp_init, _tower
    pa1 = init_fn(jax.random.PRNGKey(0), cfg)["a"]
    pa2 = init_fn(jax.random.PRNGKey(1), cfg)["a"]
    pb = dict(init_fn(jax.random.PRNGKey(2), cfg)["b"])
    pb["top"] = _mlp_init(jax.random.PRNGKey(3), [3 * cfg.z_dim, 16, 1])

    def forward_a(pa, batch_a):
        return _tower(pa["tower"], batch_a["x_a"])

    def loss_b(pb_, z_list, batch_b):
        z_b = _tower(pb_["tower"], batch_b["x_b"])
        h = jnp.concatenate([z.astype(jnp.float32) for z in z_list] + [z_b],
                            axis=-1)
        logit = _mlp(pb_["top"], h)[:, 0]
        F = batch_b["x_b"].shape[1]
        wide = pb_["wide"][jnp.arange(F)[None, :], batch_b["x_b"]].sum(1)
        logit = logit + wide + pb_["bias"]
        y = batch_b["y"]
        li = jnp.maximum(logit, 0) - logit * y + jnp.log1p(
            jnp.exp(-jnp.abs(logit)))
        return li, jnp.float32(0.0)

    task = engine.KPartyTask(forward_a, loss_b)
    celu = CELUConfig(R=2, W=2, xi_degrees=60.0)
    opt = make_optimizer("adagrad", 0.02)
    split = lambda ba, bb: (
        [{"x_a": jnp.asarray(ba["x_a"][:, :4])},
         {"x_a": jnp.asarray(ba["x_a"][:, 4:])}],
        {"x_b": jnp.asarray(bb["x_b"]), "y": jnp.asarray(bb["y"])})
    params = {"a": [pa1, pa2], "b": pb}
    return task, celu, opt, data, split, params


def _run_three_party_trace(rounds=20, transport=None):
    """Run the K=3 workload and return golden-comparable metric rows
    (same schema as ``_run_trace``, ``steps_a`` is a per-party list)."""
    task, celu, opt, data, split, params = _three_party_workload()
    it = aligned_batches(data["train"], 64, seed=0)
    _, ba, bb = next(it)
    bas, b = split(ba, bb)
    kw = {} if transport is None else {"transport": transport}
    state = engine.init_state(task, params, opt, celu, bas, b, **kw)
    rnd = engine.make_round(task, opt, celu, **kw)
    it = aligned_batches(data["train"], 64, seed=0)
    rows = []
    for i in range(rounds):
        bi, ba, bb = next(it)
        bas, b = split(ba, bb)
        state, m = rnd(state, bas, b, bi)
        rows.append({"loss": float(np.float32(m["loss"])),
                     "w_mean": float(np.float32(m["w_mean"])),
                     "w_zero_frac": float(np.float32(m["w_zero_frac"])),
                     "local_steps": int(m["local_steps"])})
    rows.append({"steps_a": [int(s) for s in state["steps"]["a"]],
                 "steps_b": int(state["steps"]["b"]),
                 "comm_rounds": int(state["comm_rounds"])})
    return rows


def test_engine_three_party_trains_and_counts_steps():
    """K=2 feature parties on the engine: loss falls, per-party step
    counters track 1 fresh + R local updates per round."""
    n_rounds, R = 20, 2
    rows = _run_three_party_trace(rounds=n_rounds)
    losses = [r["loss"] for r in rows[:-1]]
    assert np.isfinite(losses).all()
    assert np.mean(losses[-5:]) < np.mean(losses[:5]), losses
    tail = rows[-1]
    assert tail["comm_rounds"] == n_rounds
    for s in tail["steps_a"]:
        assert n_rounds < s <= n_rounds * (1 + R)
    assert n_rounds < tail["steps_b"] <= n_rounds * (1 + R)


@pytest.fixture(scope="module")
def golden3():
    with open(GOLDEN3) as f:
        return json.load(f)


def test_three_party_golden_trace(golden3):
    """The K=3 multiparty path is pinned bit-for-bit, like K=1
    (``golden/three_party_trace.json``; regenerate with
    ``tests/golden/record_three_party.py`` ONLY on intentional numeric
    changes)."""
    got = _run_three_party_trace(rounds=20)
    assert got == golden3["celu"]


def test_three_party_golden_identity_codec_transport(golden3):
    """The identity-codec compressed transport reproduces the K=3 golden
    trace bit-for-bit too (K residuals per direction collapse to none)."""
    celu = CELUConfig(R=2, W=2, xi_degrees=60.0)
    tp = engine.make_transport(celu, "identity")
    got = _run_three_party_trace(rounds=20, transport=tp)
    assert got == golden3["celu"]


def test_config_driven_compression_keeps_error_feedback():
    """``celu.compression`` alone (no explicit transport threading) must
    give init_state and make_round the SAME lossy transport: the round
    state carries live residuals, not the silent empty-dict fallback."""
    import dataclasses
    task, celu, opt, data, split, params = _three_party_workload()
    celu = dataclasses.replace(celu, compression="int8_topk")
    it = aligned_batches(data["train"], 64, seed=0)
    _, ba, bb = next(it)
    bas, b = split(ba, bb)
    state = engine.init_state(task, params, opt, celu, bas, b)
    assert sorted(state["transport"]) == ["down", "up"]
    rnd = engine.make_round(task, opt, celu)
    bi, ba, bb = next(it)
    bas, b = split(ba, bb)
    state, m = rnd(state, bas, b, bi)
    assert float(jnp.abs(state["transport"]["up"][0]).sum()) > 0.0


def test_half_threaded_lossy_transport_raises():
    """Passing a lossy transport to make_round but not init_state would
    silently drop error feedback — the round must refuse instead."""
    task, celu, opt, data, split, params = _three_party_workload()
    it = aligned_batches(data["train"], 64, seed=0)
    bi, ba, bb = next(it)
    bas, b = split(ba, bb)
    state = engine.init_state(task, params, opt, celu, bas, b)  # stateless
    tp = engine.make_transport(celu, "int8_topk")               # lossy
    rnd = engine.make_round(task, opt, celu, transport=tp)
    with pytest.raises(ValueError, match="error-feedback"):
        rnd(state, bas, b, bi)


def test_three_party_compressed_transport_trains():
    """A genuinely lossy wire (top-k+int8 up, int8 down, error feedback)
    still trains the K=3 workload: finite losses, downward trend, and one
    fp32 residual per feature party per direction in the round state."""
    celu = CELUConfig(R=2, W=2, xi_degrees=60.0)
    tp = engine.make_transport(celu, "int8_topk")
    task, _, opt, data, split, params = _three_party_workload()
    it = aligned_batches(data["train"], 64, seed=0)
    _, ba, bb = next(it)
    bas, b = split(ba, bb)
    state = engine.init_state(task, params, opt, celu, bas, b, transport=tp)
    assert sorted(state["transport"]) == ["down", "up"]
    assert len(state["transport"]["up"]) == 2
    rnd = engine.make_round(task, opt, celu, transport=tp)
    it = aligned_batches(data["train"], 64, seed=0)
    losses = []
    for i in range(20):
        bi, ba, bb = next(it)
        bas, b = split(ba, bb)
        state, m = rnd(state, bas, b, bi)
        losses.append(float(m["loss"]))
    assert np.isfinite(losses).all()
    assert np.mean(losses[-5:]) < np.mean(losses[:5]), losses
    # error feedback engaged: residuals are live, non-zero state
    res = state["transport"]["up"][0]
    assert res.dtype == jnp.float32 and float(jnp.abs(res).sum()) > 0.0
