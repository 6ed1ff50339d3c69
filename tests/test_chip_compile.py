"""Mosaic compiles of the main-path kernels for a described TPU v5e.

Nothing runs: each test lowers a kernel wrapper at a real width for one
chip of a described ``v5e:2x2`` topology, compiles it with the TPU
compiler that ships with jaxlib, and checks that the program holds the
Mosaic kernel (``tpu_custom_call``), not an interpreter or a jnp
fallback.  This is what the CPU interpreter cannot show: block shapes the
(8, 128) tiling rule refuses, layouts Mosaic cannot match, blocks that do
not fit VMEM.

Geometries: the paper's message (W=5, B=4096, F=z_dim=256) and the
smollm-360m split-LLM cut row (B=8, F=S*d=512*960).  The topology is
described inside a fixture, so importing this file loads no TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

W = 5
PAPER_B, PAPER_F = 4096, 256
LLM_B, LLM_F = 8, 512 * 960
SERVE_C, SERVE_D = 8, 960


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    return compiled


def _f32(*s):
    return (s, jnp.float32)


def _ring(B, F, kind):
    """The ring operands of one quantized/full-precision table."""
    if kind == "f32":
        return [_f32(W, B, F)]
    if kind == "q8":
        return [((W, B, F), jnp.int8), _f32(W, B, 1)]
    return [((W, B, F // 2), jnp.uint8), _f32(W, B, 1)]


_SAMPLE = {"f32": ops.fused_gather_weight, "q8": ops.fused_gather_weight_q8,
           "q4": ops.fused_gather_weight_q4}


@pytest.mark.parametrize("B,F", [(PAPER_B, PAPER_F), (LLM_B, LLM_F)],
                         ids=["paper", "smollm"])
@pytest.mark.parametrize("kind", ["f32", "q8", "q4"])
def test_fused_sample_compiles(one_chip, kind, B, F):
    ring = _ring(B, F, kind)
    n = len(ring)

    def fn(slot, a, *r):
        return _SAMPLE[kind](slot, a, *r[:n], *r[n:], 0.5)

    c = _compile(fn, [((), jnp.int32), _f32(B, F)] + ring + ring, one_chip)
    assert c.memory_analysis().temp_size_in_bytes < 2 ** 30


@pytest.mark.parametrize("B,F", [(PAPER_B, PAPER_F), (LLM_B, LLM_F)],
                         ids=["paper", "smollm"])
def test_quantizer_compiles(one_chip, B, F):
    _compile(lambda x, u: ops.quantize_stochastic(x, u, 127),
             [_f32(B, F), _f32(B, F)], one_chip)


def test_cosine_kernels_compile(one_chip):
    B, F = PAPER_B, PAPER_F
    _compile(lambda a, s: ops.cosine_weight(a, s, 0.5),
             [_f32(B, F), _f32(B, F)], one_chip)
    _compile(lambda a, s, dz: ops.weighted_cotangent(a, s, dz, 0.5),
             [_f32(B, F), _f32(B, F), _f32(B, F)], one_chip)


def test_adagrad_kernels_compile(one_chip):
    from repro.kernels.fused_adagrad import BLOCK
    _compile(lambda g, a: ops.fused_adagrad(g, a, 0.01, 1e-10),
             [_f32(512, 960), _f32(512, 960)], one_chip)
    R = 1024
    _compile(lambda g, q, s, u: ops.fused_adagrad_q8(g, q, s, u, 0.01,
                                                     1e-10),
             [_f32(R, BLOCK), ((R, BLOCK), jnp.int8), _f32(R, 1),
              _f32(R, BLOCK)], one_chip)


def test_flash_attention_compiles(one_chip):
    """Forward and the custom-VJP backward kernels at smollm's head
    geometry (15 heads of 64) over a 1024-token sequence."""
    qkv = [_f32(1, 1024, 15, 64)] * 3
    _compile(lambda q, k, v: ops.flash_attention(q, k, v), qkv, one_chip)
    _compile(lambda q, k, v: jax.grad(
        lambda *a: ops.flash_attention_trainable(*a).sum(),
        argnums=(0, 1, 2))(q, k, v), qkv, one_chip)


def test_serving_dequant_compiles(one_chip):
    _compile(lambda slot, q, s: ops.fused_gather_dequant_q8(slot, q, s),
             [((), jnp.int32), ((W, SERVE_C, SERVE_D), jnp.int8),
              _f32(W, SERVE_C, 1)], one_chip)


def test_moe_grouped_matmuls_compile(one_chip):
    """The dropless MoE layer forward and backward at lfm2-8b-a1b's widths
    (d 2048, 8 of 32 experts of 1792, top-4) over 512 tokens: the TPU
    compiler lowers ``ragged_dot`` to its grouped-matmul kernel."""
    from repro.configs.base import MoEConfig
    from repro.models import moe as M
    cfg = MoEConfig(n_experts=32, top_k=4, d_expert=1792, router="sigmoid",
                    router_aux_coef=0.0, n_held=8)
    p = jax.eval_shape(lambda k: M.moe_init(k, 2048, 7168, cfg),
                       jax.random.PRNGKey(0))
    leaves, tree = jax.tree_util.tree_flatten(p)

    def loss(x, *ws):
        y, _, _ = M.moe_apply(jax.tree_util.tree_unflatten(tree, ws), x,
                              cfg)
        return jnp.sum(y.astype(jnp.float32))

    c = _compile(lambda x, *ws: jax.grad(loss, argnums=tuple(
        range(1 + len(ws))))(x, *ws),
        [((1, 512, 2048), jnp.bfloat16)] + [(w.shape, w.dtype)
                                            for w in leaves], one_chip)
    assert "ragged-dot" in c.as_text()
