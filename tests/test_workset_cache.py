"""Quantized workset cache + fused gather→dequant→weight sample path.

Covers: the storage codec (int8 / int4 / bf16 at rest, fp32
bit-exactness), nibble pack/unpack roundtrips at odd row widths,
kernel-vs-oracle parity for the fused sample megakernel (fp32, int8, and
nibble-packed int4 rings; multi-tile grids, the unfusable-batch
fallback, the all-dead-slot edge), Algorithm-2 weight tolerance of the
lossy caches vs the fp32 cache (SR unbiasedness through the cosine),
the ``workset_stats`` pipeline-staleness regression, and the
``workset_pspecs`` sharding rule over quantized rings.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import CELUConfig
from repro.core import engine
from repro.core.workset import (QUANT_KEYS, CastLeaf, Quant4Leaf,
                                QuantLeaf, decode_entry, pack_nibbles,
                                sample_hbm_bytes, unpack_nibbles,
                                workset_draw, workset_entry, workset_init,
                                workset_insert, workset_nbytes,
                                workset_sample, workset_stats)
from repro.kernels import ops, ref

RNG = np.random.default_rng(7)


def _arr(shape, dtype="float32"):
    return jnp.asarray(RNG.normal(size=shape), jnp.dtype(dtype))


def _entry(B=64, F=8, v=None):
    z = _arr((B, F)) if v is None else jnp.full((B, F), float(v))
    dz = _arr((B, F)) if v is None else jnp.full((B, F), -float(v))
    return {"z": z, "dz": dz, "batch": {"x": jnp.zeros((B, 2), jnp.int32)}}


# --------------------------------------------------------------------------
# Storage codec
# --------------------------------------------------------------------------
def test_fp32_cache_layout_is_the_historical_table():
    """cache_dtype="float32" stores plain arrays — bit-identical layout
    (the golden traces in test_engine.py pin the numerics)."""
    e = _entry()
    ws = workset_init(3, e)
    assert isinstance(ws["buf"]["z"], jnp.ndarray)
    ws = workset_insert(ws, e, 0)
    _, got, _, valid = workset_sample(ws, 2, "consecutive")
    assert bool(valid)
    np.testing.assert_array_equal(np.asarray(got["z"]), np.asarray(e["z"]))
    np.testing.assert_array_equal(np.asarray(got["dz"]), np.asarray(e["dz"]))


@pytest.mark.parametrize("cache_dtype,leaf_cls,max_rel",
                         [("bfloat16", CastLeaf, 1 / 128),
                          ("int8", QuantLeaf, 1 / 64),
                          ("int4", Quant4Leaf, 1 / 6)])
def test_lossy_cache_roundtrip(cache_dtype, leaf_cls, max_rel):
    """Insert + sample through a lossy cache reconstructs the statistics
    to storage precision (int8: one LSB of the per-row absmax scale)."""
    e = _entry(B=64, F=32)
    ws = workset_init(2, e, cache_dtype=cache_dtype)
    assert isinstance(ws["buf"]["z"], leaf_cls)
    assert isinstance(ws["buf"]["batch"]["x"], jnp.ndarray)  # verbatim
    ws = workset_insert(ws, e, 0, rng=jax.random.PRNGKey(0))
    _, got, _, _ = workset_sample(ws, 2, "consecutive")
    assert got["z"].shape == e["z"].shape
    for k in QUANT_KEYS:
        err = np.abs(np.asarray(got[k]) - np.asarray(e[k]))
        amax = np.abs(np.asarray(e[k])).max(axis=1, keepdims=True)
        assert (err <= amax * max_rel + 1e-6).all()


def test_int8_cache_sr_unbiased():
    """E[decode] == value: the stochastic rounding noise averages out
    across insert keys (the property Algorithm-2's tolerance rides on)."""
    e = _entry(B=16, F=8)
    acc = np.zeros((16, 8), np.float64)
    n = 300
    for s in range(n):
        ws = workset_init(1, e, cache_dtype="int8")
        ws = workset_insert(ws, e, 0, rng=jax.random.PRNGKey(s))
        _, got, _, _ = workset_sample(ws, 2, "consecutive")
        acc += np.asarray(got["z"], np.float64)
    scale = np.abs(np.asarray(e["z"])).max(axis=1, keepdims=True) / 127
    bias = np.abs(acc / n - np.asarray(e["z"]))
    # SR residual is U(0,1)-driven: sem ~ scale/sqrt(12 n); 6 sigma margin
    assert (bias <= 6 * scale / np.sqrt(12 * n) + 1e-7).all()


def test_cache_footprint_ratio():
    """The int8 table holds the cut statistics in ~F/(F+4)x4 fewer bytes
    (codes + one fp32 scale per row); int4 nibble-packs two codes per
    byte on top of that."""
    e = _entry(B=256, F=32)
    fp32 = workset_nbytes(workset_init(5, e), QUANT_KEYS)
    int8 = workset_nbytes(workset_init(5, e, cache_dtype="int8"),
                          QUANT_KEYS)
    bf16 = workset_nbytes(workset_init(5, e, cache_dtype="bfloat16"),
                          QUANT_KEYS)
    int4 = workset_nbytes(workset_init(5, e, cache_dtype="int4"),
                          QUANT_KEYS)
    assert fp32 == 2 * 5 * 256 * 32 * 4
    assert int8 == 2 * 5 * 256 * (32 + 4)
    assert bf16 == fp32 // 2
    assert int4 == 2 * 5 * 256 * (32 // 2 + 4)
    assert fp32 / int8 > 3.0
    assert fp32 / int4 > 6.0


def test_int4_pack_roundtrip_odd_widths():
    """pack→unpack is the identity on codes in [-7, 7], with odd widths
    padded by one zero code (the pad nibble decodes to an exact 0), over
    one short lane group and over full 256-code groups plus a rest."""
    for B, F in ((4, 8), (3, 7), (5, 33), (2, 1), (3, 600), (2, 1025)):
        q = jnp.asarray(RNG.integers(-7, 8, size=(B, F)), jnp.int8)
        qp = jnp.pad(q, ((0, 0), (0, F & 1))) if F & 1 else q
        packed = pack_nibbles(qp)
        assert packed.dtype == jnp.uint8
        assert packed.shape == (B, (F + (F & 1)) // 2)
        back = unpack_nibbles(packed)
        np.testing.assert_array_equal(np.asarray(back[:, :F]), np.asarray(q))
        if F & 1:    # the pad nibble must decode to 0, not garbage
            np.testing.assert_array_equal(np.asarray(back[:, F]),
                                          np.zeros(B, np.int8))


def test_unknown_cache_dtype_rejected():
    with pytest.raises(ValueError, match="cache_dtype"):
        workset_init(2, _entry(), cache_dtype="fp16")


def test_quantized_table_survives_scan_carry():
    """QuantLeaf is a registered pytree node: the table rides a lax.scan
    carry (the engine's local-update loop) untouched."""
    e = _entry(B=8, F=4)
    ws = workset_init(2, e, cache_dtype="int8")
    ws = workset_insert(ws, e, 0)

    def body(carry, _):
        ws = carry
        ws, slot, _, valid = workset_draw(ws, 4, "round_robin")
        return ws, valid

    ws2, valids = jax.lax.scan(body, ws, None, length=3)
    assert isinstance(ws2["buf"]["z"], QuantLeaf)
    assert int(valids.sum()) >= 1


# --------------------------------------------------------------------------
# Fused sample kernel vs oracle
# --------------------------------------------------------------------------
@pytest.mark.parametrize("W,B,F", [(3, 64, 8), (5, 128, 32), (4, 256, 16),
                                   (2, 384, 96)])   # 384 = 3 grid tiles
@pytest.mark.parametrize("cos_xi", [0.0, 0.5])
def test_fused_sample_f32_matches_oracle(W, B, F, cos_xi):
    a = _arr((B, F))
    z_ring, dz_ring = _arr((W, B, F)), _arr((W, B, F))
    for slot in (0, W - 1):
        w, cot = ops.fused_gather_weight(jnp.int32(slot), a, z_ring,
                                         dz_ring, cos_xi)
        w_r, cot_r = ref.fused_sample_ref(slot, a, z_ring, dz_ring, cos_xi)
        tol = dict(rtol=3e-7, atol=3e-7)
        np.testing.assert_allclose(np.asarray(w), np.asarray(w_r), **tol)
        np.testing.assert_allclose(np.asarray(cot), np.asarray(cot_r),
                                   **tol)


@pytest.mark.parametrize("W,B,F", [(3, 64, 8), (4, 256, 16), (2, 384, 96),
                                   (3, 64, 9), (4, 128, 33)])  # odd F
def test_fused_sample_q4_matches_oracle(W, B, F):
    """int4 nibble-packed ring kernel vs the unpack→dequant→cosine oracle
    (multi-tile grids at B=384, odd row widths through the pad nibble)."""
    P = (F + 1) // 2
    a = _arr((B, F))
    zq = jnp.asarray(RNG.integers(0, 256, size=(W, B, P)), jnp.uint8)
    dzq = jnp.asarray(RNG.integers(0, 256, size=(W, B, P)), jnp.uint8)
    if F & 1:   # storage codec invariant: pad nibble holds code 0 (+8)
        zq = (zq & 0x0F) | jnp.uint8(0x80)
        dzq = (dzq & 0x0F) | jnp.uint8(0x80)
    zs = jnp.abs(_arr((W, B, 1))) + 0.01
    dzs = jnp.abs(_arr((W, B, 1))) + 0.01
    for slot in (0, W - 1):
        w, cot = ops.fused_gather_weight_q4(jnp.int32(slot), a, zq, zs,
                                            dzq, dzs, 0.3)
        w_r, cot_r = ref.fused_sample_q4_ref(slot, a, zq, zs, dzq, dzs, 0.3)
        assert cot.shape == a.shape
        np.testing.assert_allclose(np.asarray(w), np.asarray(w_r),
                                   rtol=3e-7, atol=3e-7)
        np.testing.assert_allclose(np.asarray(cot), np.asarray(cot_r),
                                   rtol=3e-6, atol=3e-6)


@pytest.mark.parametrize("W,B,F", [(3, 64, 8), (4, 256, 16), (2, 384, 96)])
def test_fused_sample_q8_matches_oracle(W, B, F):
    a = _arr((B, F))
    zq = jnp.asarray(RNG.integers(-127, 128, size=(W, B, F)), jnp.int8)
    dzq = jnp.asarray(RNG.integers(-127, 128, size=(W, B, F)), jnp.int8)
    zs = jnp.abs(_arr((W, B, 1))) + 0.01
    dzs = jnp.abs(_arr((W, B, 1))) + 0.01
    for slot in (0, W - 1):
        w, cot = ops.fused_gather_weight_q8(jnp.int32(slot), a, zq, zs,
                                            dzq, dzs, 0.3)
        w_r, cot_r = ref.fused_sample_q8_ref(slot, a, zq, zs, dzq, dzs, 0.3)
        np.testing.assert_allclose(np.asarray(w), np.asarray(w_r),
                                   rtol=3e-7, atol=3e-7)
        np.testing.assert_allclose(np.asarray(cot), np.asarray(cot_r),
                                   rtol=3e-6, atol=3e-6)


def _q8_ring(W, B, F):
    zq = jnp.asarray(RNG.integers(-127, 128, size=(W, B, F)), jnp.int8)
    return zq, jnp.abs(_arr((W, B, 1))) + 0.01


@pytest.mark.parametrize("kind", ["float32", "int8"])
def test_fused_sample_w5_bit_exact(kind):
    """At the paper's ring depth (W=5) a one-tile row runs the oracle's
    float32 operations on the same blocks: weights and cotangent are
    bit-identical to ``kernels.ref`` in the interpreter."""
    W, B, F = 5, 128, 256
    a = _arr((B, F))
    for slot in (0, 4):
        if kind == "float32":
            z, dz = _arr((W, B, F)), _arr((W, B, F))
            got = ops.fused_gather_weight(jnp.int32(slot), a, z, dz, 0.5)
            want = ref.fused_sample_ref(slot, a, z, dz, 0.5)
        else:
            (zq, zs), (dzq, dzs) = _q8_ring(W, B, F), _q8_ring(W, B, F)
            got = ops.fused_gather_weight_q8(jnp.int32(slot), a, zq, zs,
                                             dzq, dzs, 0.5)
            want = ref.fused_sample_q8_ref(slot, a, zq, zs, dzq, dzs, 0.5)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("kind", ["float32", "int8", "int4"])
def test_fused_sample_feature_tiles_match_oracle(kind):
    """A split-LLM-sized row (B=8, F=32768) is cut into two feature tiles:
    the row sums accumulate across tiles, which reassociates the float32
    reduction (weights agree to 1e-6 relative), and the cotangent is
    written tile by tile in the second phase."""
    from repro.kernels import feature_tile
    W, B, F = 3, 8, 32768
    assert F // feature_tile(F, B) == 2
    a = _arr((B, F))
    if kind == "float32":
        z, dz = _arr((W, B, F)), _arr((W, B, F))
        w, cot = ops.fused_gather_weight(jnp.int32(1), a, z, dz, -1.0)
        w_r, cot_r = ref.fused_sample_ref(1, a, z, dz, -1.0)
    elif kind == "int8":
        (zq, zs), (dzq, dzs) = _q8_ring(W, B, F), _q8_ring(W, B, F)
        w, cot = ops.fused_gather_weight_q8(jnp.int32(1), a, zq, zs, dzq,
                                            dzs, -1.0)
        w_r, cot_r = ref.fused_sample_q8_ref(1, a, zq, zs, dzq, dzs, -1.0)
    else:
        P = F // 2
        zq = jnp.asarray(RNG.integers(0, 256, size=(W, B, P)), jnp.uint8)
        dzq = jnp.asarray(RNG.integers(0, 256, size=(W, B, P)), jnp.uint8)
        zs, dzs = (jnp.abs(_arr((W, B, 1))) + 0.01 for _ in range(2))
        w, cot = ops.fused_gather_weight_q4(jnp.int32(1), a, zq, zs, dzq,
                                            dzs, -1.0)
        w_r, cot_r = ref.fused_sample_q4_ref(1, a, zq, zs, dzq, dzs, -1.0)
    np.testing.assert_allclose(np.asarray(w), np.asarray(w_r), rtol=1e-6,
                               atol=1e-7)
    np.testing.assert_allclose(np.asarray(cot), np.asarray(cot_r),
                               rtol=1e-6, atol=1e-7)


def test_quantizer_feature_tiles_bit_exact():
    """The SR quantizer over a row cut into feature tiles: the absmax is
    exact in any order, so codes and scales stay bit-identical to the
    oracle."""
    from repro.kernels import feature_tile
    B, F = 8, 32768
    assert F // feature_tile(F, B) == 2
    x = _arr((B, F))
    u = jax.random.uniform(jax.random.PRNGKey(3), (B, F), jnp.float32)
    for levels in (127, 7):
        got = ops.quantize_stochastic(x, u, levels)
        want = ref.quantize_sr_ref(x, u, levels)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_fused_sample_rank3_statistics():
    """Ranks > 2 flatten per instance exactly like the weighting path."""
    W, B, S, d = 3, 128, 4, 8
    a = _arr((B, S, d))
    z_ring, dz_ring = _arr((W, B, S, d)), _arr((W, B, S, d))
    w, cot = ops.fused_gather_weight(jnp.int32(1), a, z_ring, dz_ring, 0.2)
    assert cot.shape == (B, S, d)
    w_r, cot_r = ref.fused_sample_ref(1, a, z_ring, dz_ring, 0.2)
    np.testing.assert_allclose(np.asarray(cot), np.asarray(cot_r),
                               rtol=3e-7, atol=3e-7)


def test_fused_sample_all_dead_slot_yields_zero():
    """An invalid draw lands on a never-written ring slot (all zeros):
    the kernel's cosine denominator floors at EPS and every weight — and
    the cotangent — is exactly zero, so the masked no-op update costs
    nothing numerically."""
    W, B, F = 3, 64, 8
    a = _arr((B, F))
    zeros = jnp.zeros((W, B, F), jnp.float32)
    w, cot = ops.fused_gather_weight(jnp.int32(2), a, zeros, zeros, 0.5)
    assert (np.asarray(w) == 0.0).all() and (np.asarray(cot) == 0.0).all()
    # int8 ring: zero codes AND zero scales (the empty-table state)
    w, cot = ops.fused_gather_weight_q8(
        jnp.int32(0), a, jnp.zeros((W, B, F), jnp.int8),
        jnp.zeros((W, B, 1), jnp.float32), jnp.zeros((W, B, F), jnp.int8),
        jnp.zeros((W, B, 1), jnp.float32), 0.5)
    assert (np.asarray(w) == 0.0).all() and (np.asarray(cot) == 0.0).all()
    # int4 ring: the empty table is 0x88 bytes (code 0 in both nibbles)
    # with zero scales — decodes to exact zeros
    empty = jnp.full((W, B, F // 2), 0x88, jnp.uint8)
    w, cot = ops.fused_gather_weight_q4(
        jnp.int32(1), a, empty, jnp.zeros((W, B, 1), jnp.float32),
        empty, jnp.zeros((W, B, 1), jnp.float32), 0.5)
    assert (np.asarray(w) == 0.0).all() and (np.asarray(cot) == 0.0).all()


def test_local_grad_a_cached_fused_matches_reference():
    """The engine dispatcher: fused ring sample == materialize-then-weight
    on the same table, for fp32 (bitwise) and int8 (bitwise: the decode is
    the same math) caches — including the odd-batch fallback."""
    def forward(p, batch):
        return batch["x"] @ p

    for cache_dtype in ("float32", "int8", "int4"):
        for B, F in ((64, 8), (37, 8)):        # 37: unfusable, falls back
            p = _arr((4, F))
            e = {"z": _arr((B, F)), "dz": _arr((B, F)),
                 "batch": {"x": _arr((B, 4))}}
            ws = workset_init(3, e, cache_dtype=cache_dtype)
            ws = workset_insert(ws, e, 0, rng=jax.random.PRNGKey(1))
            ws, slot, _, valid = workset_draw(ws, 3, "consecutive")
            kw = dict(weighting=True, fused=True, mask=None,
                      pipeline_staleness=0)
            g_f, w_f = engine.local_grad_a_cached(forward, p, ws, slot, 0.3,
                                                  cache_fused=True, **kw)
            g_r, w_r = engine.local_grad_a_cached(forward, p, ws, slot, 0.3,
                                                  cache_fused=False, **kw)
            np.testing.assert_allclose(np.asarray(w_f), np.asarray(w_r),
                                       rtol=3e-7, atol=3e-7)
            np.testing.assert_allclose(np.asarray(g_f), np.asarray(g_r),
                                       rtol=3e-6, atol=3e-6)


def test_local_grad_a_cached_pipeline_staleness_post_scale():
    """The megakernel composes the depth-s pipeline discount exactly like
    weighted_cotangent: w -> w^(1+s), cotangent scaled once."""
    def forward(p, batch):
        return batch["x"] @ p

    B, F = 64, 8
    p = _arr((4, F))
    e = {"z": _arr((B, F)), "dz": _arr((B, F)), "batch": {"x": _arr((B, 4))}}
    ws = workset_init(2, e)
    ws = workset_insert(ws, e, 0)
    ws, slot, _, _ = workset_draw(ws, 3, "consecutive")
    kw = dict(weighting=True, fused=True, mask=None, pipeline_staleness=1)
    g_f, w_f = engine.local_grad_a_cached(forward, p, ws, slot, 0.3,
                                          cache_fused=True, **kw)
    g_r, w_r = engine.local_grad_a_cached(forward, p, ws, slot, 0.3,
                                          cache_fused=False, **kw)
    np.testing.assert_allclose(np.asarray(w_f), np.asarray(w_r),
                               rtol=3e-7, atol=3e-7)
    np.testing.assert_allclose(np.asarray(g_f), np.asarray(g_r),
                               rtol=3e-6, atol=3e-6)


# --------------------------------------------------------------------------
# Algorithm-2 weights: int8 cache vs fp32 cache tolerance
# --------------------------------------------------------------------------
def _weights_through_cache(z_stale, dz_stale, z_adhoc, cache_dtype, seed):
    e = {"z": z_stale, "dz": dz_stale, "batch": {}}
    ws = workset_init(1, e, cache_dtype=cache_dtype)
    ws = workset_insert(ws, e, 0, rng=jax.random.PRNGKey(seed))
    _, got, _, _ = workset_sample(ws, 4, "consecutive")
    from repro.core.weighting import row_cosine
    return np.asarray(row_cosine(z_adhoc, got["z"]))


@pytest.mark.parametrize("B,F,seed", [(8, 16, 0), (32, 64, 1), (64, 128, 2),
                                      (17, 33, 3)])
def test_int8_cache_weights_within_tolerance_fixed(B, F, seed):
    """Deterministic slice of the hypothesis sweep below (runs even where
    hypothesis is absent)."""
    rng = np.random.default_rng(seed)
    z = jnp.asarray(rng.normal(size=(B, F)), jnp.float32)
    a = z + 0.3 * jnp.asarray(rng.normal(size=(B, F)), jnp.float32)
    dz = jnp.asarray(rng.normal(size=(B, F)), jnp.float32)
    c32 = _weights_through_cache(z, dz, a, "float32", seed)
    c8 = _weights_through_cache(z, dz, a, "int8", seed)
    assert np.abs(c8 - c32).max() <= 0.06


@pytest.mark.parametrize("B,F,seed", [(8, 16, 0), (32, 64, 1), (64, 128, 2),
                                      (17, 33, 3)])
def test_int4_cache_weights_within_tolerance_fixed(B, F, seed):
    """int4 at rest: 7 levels per row absmax perturbs elements by up to
    ~14%, so the Algorithm-2 cosine moves more than under int8 — but
    stays bounded, and the SR noise is unbiased (the convergence claim is
    pinned end-to-end by test_lossy_cache_trains and BENCH_llm)."""
    rng = np.random.default_rng(seed)
    z = jnp.asarray(rng.normal(size=(B, F)), jnp.float32)
    a = z + 0.3 * jnp.asarray(rng.normal(size=(B, F)), jnp.float32)
    dz = jnp.asarray(rng.normal(size=(B, F)), jnp.float32)
    c32 = _weights_through_cache(z, dz, a, "float32", seed)
    c4 = _weights_through_cache(z, dz, a, "int4", seed)
    assert np.abs(c4 - c32).max() <= 0.25


def test_int8_cache_weights_within_tolerance():
    """Paper Algorithm-2 cosines computed against the int8-at-rest cache
    stay within quantization tolerance of the fp32-cache cosines."""
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=25, deadline=None)
    @given(st.integers(8, 64), st.integers(16, 128),
           st.integers(0, 2 ** 31 - 1))
    def check(B, F, seed):
        rng = np.random.default_rng(seed)
        z = jnp.asarray(rng.normal(size=(B, F)), jnp.float32)
        # ad-hoc statistics drift from the cached ones, like a local step
        drift = jnp.asarray(rng.normal(size=(B, F)), jnp.float32)
        a = z + 0.3 * drift
        dz = jnp.asarray(rng.normal(size=(B, F)), jnp.float32)
        c32 = _weights_through_cache(z, dz, a, "float32", seed)
        c8 = _weights_through_cache(z, dz, a, "int8", seed)
        # per-row int8 SR perturbs each element by <= 1/127 of the row
        # absmax; the cosine moves by O(that / rms) — generous 6% bound
        assert np.abs(c8 - c32).max() <= 0.06, (B, F, seed)

    check()


# --------------------------------------------------------------------------
# Engine integration: lossy caches train, fp32 stays bit-exact
# --------------------------------------------------------------------------
def _tiny_workload():
    from repro.data.synthetic import TabularSpec, aligned_batches, \
        make_tabular
    from repro.models.tabular import DLRMConfig, make_dlrm
    from repro.optim import make_optimizer
    spec = TabularSpec("criteo", fields_a=4, fields_b=3, vocab=32,
                       n_train=2048, n_test=512)
    data = make_tabular(spec, seed=0)
    cfg = DLRMConfig("wdl", 4, 3, vocab=32, embed_dim=4, z_dim=8,
                     hidden=(16, 8))
    init_fn, task, _ = make_dlrm(cfg)
    return data, init_fn(jax.random.PRNGKey(0), cfg), task, \
        make_optimizer("adagrad", 0.05), aligned_batches


def _trace(cache_dtype, cache_fused, rounds=8):
    data, params, task, opt, aligned_batches = _tiny_workload()
    celu = CELUConfig(R=3, W=3, xi_degrees=60.0, cache_dtype=cache_dtype,
                      cache_fused=cache_fused)
    etask = engine.lift_two_party(task)
    it = aligned_batches(data["train"], 64, seed=0)
    _, ba, bb = next(it)
    asj = lambda d: {k: jnp.asarray(v) for k, v in d.items()}
    state = engine.init_state(etask, engine.lift_two_party_params(params),
                              opt, celu, [asj(ba)], asj(bb))
    rnd = engine.make_round(etask, opt, celu)
    it = aligned_batches(data["train"], 64, seed=0)
    out = []
    for _ in range(rounds):
        bi, ba, bb = next(it)
        state, m = rnd(state, [asj(ba)], asj(bb), bi)
        out.append((float(np.float32(m["loss"])),
                    float(np.float32(m["w_mean"]))))
    return out


def test_fp32_fused_sample_bitwise_equals_materializing_path():
    """cache_fused=True over the fp32 table is the SAME trace as the
    materializing reference — the megakernel's gather is exact and its
    fp32 body reproduces the weighting kernel bit-for-bit."""
    assert _trace("float32", True) == _trace("float32", False)


@pytest.mark.parametrize("cache_dtype", ["bfloat16", "int8", "int4"])
def test_lossy_cache_trains(cache_dtype):
    rows = _trace(cache_dtype, True, rounds=10)
    losses = [l for l, _ in rows]
    assert np.isfinite(losses).all()
    assert any(w > 0 for _, w in rows)
    # lossy fused == lossy unfused (the kernel IS the decode + weight)
    assert rows == _trace(cache_dtype, False, rounds=10)


# --------------------------------------------------------------------------
# Satellites: stats staleness regression + roofline counters
# --------------------------------------------------------------------------
def test_workset_stats_respects_pipeline_staleness():
    """Regression: stats used to call _valid_mask with no offset, so
    n_alive overcounted by the retired slots under depth-1 pipelining."""
    W = 4
    ws = workset_init(W, _entry(B=2, F=2))
    for t in range(W):
        ws = workset_insert(ws, _entry(B=2, F=2, v=t), t)
    assert int(workset_stats(ws, R=2)["n_alive"]) == W
    for s in (1, 2):
        assert int(workset_stats(ws, R=2,
                                 pipeline_staleness=s)["n_alive"]) == W - s
    # and the count now matches what the sampler will actually serve
    served = 0
    w2 = dict(ws)
    for _ in range(W):
        w2, _, _, v = workset_sample(w2, 2, "round_robin",
                                     pipeline_staleness=1)
        served += int(v)
    assert served == int(workset_stats(ws, R=2,
                                       pipeline_staleness=1)["n_alive"])


def test_sample_hbm_bytes_counters():
    """The roofline counter: fused + int8 moves strictly fewer bytes than
    every other path, unfused fp32 the most."""
    e = _entry(B=256, F=32)
    unfused32 = sample_hbm_bytes(e, "float32", fused=False)
    fused32 = sample_hbm_bytes(e, "float32", fused=True)
    fused8 = sample_hbm_bytes(e, "int8", fused=True)
    fused4 = sample_hbm_bytes(e, "int4", fused=True)
    assert fused4 < fused8 < fused32 < unfused32
    # the fused int8 path moves > 2x fewer bytes than unfused fp32
    assert unfused32 / fused8 > 2.0
    # int4 halves the ring-read bytes again (codes at half a byte)
    assert unfused32 / fused4 > 3.0
    with pytest.raises(ValueError):
        sample_hbm_bytes(e, "fp16")


def test_decode_entry_identity_on_plain_trees():
    e = _entry(B=4, F=4)
    got = decode_entry(e)
    assert got["z"] is e["z"]


# --------------------------------------------------------------------------
# Party-B fused sample path (local_grad_b_cached) + its roofline counter
# --------------------------------------------------------------------------
def _b_entry(B=64, F=8, K=2):
    return {"z": [_arr((B, F)) for _ in range(K)],
            "dz": [_arr((B, F)) for _ in range(K)],
            "batch": {"y": jnp.asarray(RNG.integers(0, 2, B), jnp.float32)}}


def _b_workset(B=64, F=8, K=2, W=3, cache_dtype="float32"):
    ws = workset_init(W, _b_entry(B, F, K), cache_dtype=cache_dtype)
    for t in range(W):
        ws = workset_insert(ws, _b_entry(B, F, K), t)
    return ws


def _loss_b(p, zs, batch):
    logits = sum(z.astype(jnp.float32) @ p["w"] for z in zs) + p["c"]
    li = (jnp.maximum(logits, 0.0) - logits * batch["y"]
          + jnp.log1p(jnp.exp(-jnp.abs(logits))))
    return li, 0.0


@pytest.mark.parametrize("cache_dtype", ["float32", "int8", "int4"])
def test_party_b_fused_ring_weights_parity(cache_dtype):
    """The label party's dz-side cosine weighting through the fused
    gather→dequant→weight kernel (never materializing the decoded ∇Z
    list) must agree with the materialize-then-weight reference — to 2
    float32 ulps on the fp32 ring (the same operations, but separate
    XLA:CPU programs whose fused row reductions may round differently;
    1 ulp apart on JAX 0.9.0; the gradients carry that through one
    backward pass), to storage precision on int8."""
    ws = _b_workset(cache_dtype=cache_dtype)
    p = {"w": _arr((8,)), "c": jnp.float32(0.1)}
    outs = {}
    for cf in (True, False):
        g, w = engine.local_grad_b_cached(_loss_b, p, ws, 1, 0.5,
                                          fused=True, cache_fused=cf)
        outs[cf] = (g, w)
    (g1, w1), (g0, w0) = outs[True], outs[False]
    if cache_dtype == "float32":
        ulp2 = 2 * float(np.finfo(np.float32).eps)
        np.testing.assert_allclose(np.asarray(w1), np.asarray(w0),
                                   rtol=ulp2, atol=0)
        for a, b in zip(jax.tree_util.tree_leaves(g1),
                        jax.tree_util.tree_leaves(g0)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=4 * ulp2, atol=1e-9)
    else:
        np.testing.assert_allclose(np.asarray(w1), np.asarray(w0),
                                   rtol=1e-5, atol=1e-5)
        for a, b in zip(jax.tree_util.tree_leaves(g1),
                        jax.tree_util.tree_leaves(g0)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)
    # weights are the Algorithm-2 cosine gate: in [0, 1]
    assert float(w1.min()) >= 0.0 and float(w1.max()) <= 1.0


def test_sample_hbm_bytes_party_b_accounting():
    """Party B's counter: the decoded Z copy the loss consumes is paid on
    BOTH paths; fusion saves exactly the decoded fp32 ∇Z materialization
    (one f32 z/dz-sized buffer per party)."""
    B, F, K = 256, 32, 2
    e = _b_entry(B, F, K)
    f32 = B * F * 4
    a_fused = sample_hbm_bytes(e, "float32", fused=True, party="a")
    b_fused = sample_hbm_bytes(e, "float32", fused=True, party="b")
    b_unfused = sample_hbm_bytes(e, "float32", fused=False, party="b")
    # the z materialization is party B's unavoidable extra vs party A
    assert b_fused - a_fused == K * f32
    # fusing the dz side skips exactly the decoded dz copies
    assert b_unfused - b_fused == K * f32
    # int8 at rest beats fp32 at rest on either path
    assert sample_hbm_bytes(e, "int8", fused=True, party="b") < b_fused
    with pytest.raises(ValueError, match="party"):
        sample_hbm_bytes(e, "float32", party="c")


# --------------------------------------------------------------------------
# Sharding rules over quantized rings
# --------------------------------------------------------------------------
def test_workset_pspecs_shard_batch_never_ring():
    """``sharding.rules.workset_pspecs`` must shard the per-instance
    batch dim of every ring leaf — including Quant4Leaf's packed codes
    and scales — and never the W slot axis (a draw reads ONE slot)."""
    from types import SimpleNamespace

    from jax.sharding import PartitionSpec as P

    from repro.sharding.rules import make_sharding, workset_pspecs

    z = _arr((8, 16))
    ws = workset_init(5, {"z": z, "dz": z}, cache_dtype="int4")
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    specs = workset_pspecs(ws, mesh)
    for k in ("z", "dz"):
        assert specs["buf"][k].q == P(None, "data", None)
        assert specs["buf"][k].scale == P(None, "data", None)
    for k in ("insert_time", "use_count", "batch_idx", "cursor", "time"):
        assert specs[k] == P()
    # the specs tree must be placeable as-is
    placed = jax.device_put(ws, make_sharding(mesh, specs))
    assert placed["buf"]["z"].q.shape == ws["buf"]["z"].q.shape

    # non-divisible batch replicates — the rule never falls back to W,
    # even when W itself would divide the data axis
    fake = SimpleNamespace(shape={"data": 5})
    bad = workset_pspecs(ws, fake)
    assert bad["buf"]["z"].q == P()
    # a divisible batch shards under the same multi-way axis
    ok = workset_pspecs(ws, SimpleNamespace(shape={"data": 4}))
    assert ok["buf"]["z"].q == P(None, "data", None)
