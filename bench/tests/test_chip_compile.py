"""Every cell's timed program compiles for a described TPU v5e, at the
cell's own shapes, without a chip: one chip of a described v5e host.
What the chip's compiler refuses is found here for no chip time.
Nothing runs; shapes come from ``jax.eval_shape``.

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests/test_chip_compile.py
"""
import os

import pytest

import harness

os.environ.setdefault("TPU_LOG_DIR", "disabled")


@pytest.fixture(scope="module")
def topo():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def _shaped(tree, sharding):
    import jax
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _round_text(cell_name, topo):
    import jax
    from jax.sharding import SingleDeviceSharding

    from repro.configs.base import CELUConfig
    from repro.core import engine
    from repro.optim import make_optimizer

    cell = harness.Cell(cell_name)
    job = cell.job
    one = SingleDeviceSharding(topo.devices[0])
    task, shapes = cell.family.program(cell.cfg)
    base = CELUConfig(R=job["R"], W=job["W"], xi_degrees=job["xi"],
                      cache_dtype=job["cache_dtype"])
    celu, n_local = engine.preset_config(job["protocol"], base)
    kw = {} if job["opt_state_dtype"] == "float32" else \
        {"state_dtype": job["opt_state_dtype"]}
    opt = make_optimizer("adagrad", job["lr"], **kw)
    import traffic
    _, ba, bb = next(traffic.stream(0, cell.data_spec()))
    a = [jax.tree_util.tree_map(lambda v: jax.ShapeDtypeStruct(
        v.shape, v.dtype), ba)]
    b = jax.tree_util.tree_map(lambda v: jax.ShapeDtypeStruct(
        v.shape, v.dtype), bb)
    etask = engine.lift_two_party(task)
    tp = engine.make_transport(celu)
    state = jax.eval_shape(
        lambda p: engine.init_state(etask, engine.lift_two_party_params(p),
                                    opt, celu, a, b, transport=tp), shapes)
    fn = engine.make_round(etask, opt, celu, local_steps=n_local,
                           transport=tp, donate=True)
    compiled = fn.lower(_shaped(state, one), _shaped(a, one),
                        _shaped(b, one), 0).compile()
    return compiled.as_text()


@pytest.mark.parametrize("cell,kernels", [
    ("wdl-criteo.celu-fp32", ["fused_sample"]),
    ("wdl-criteo.vanilla", []),
    ("smollm-360m.celu-int8", ["fused_sample_q8", "quantize_sr"]),
])
def test_round_compiles_for_v5e(topo, cell, kernels):
    text = _round_text(cell, topo)
    for k in kernels:
        assert f"{k}/pallas_call" in text, k
