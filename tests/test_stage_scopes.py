"""Every operation of a compiled CELU round sits under one of the round's
stage scopes (``engine.STAGE_SCOPES``), and each part scope
(``engine.LEAF_SCOPES``) under the stage that owns it.  A profile reader
charges device time by these names, so code added outside a stage later
fails here.

Checked on the CPU-compiled text: every ``op_name`` that starts with
``jit(<fn>)/`` (the program's own operations; arguments are named by
their path, reducer bodies by their primitive) continues with a stage
name, after ``shard_map/`` in the pod round.  Literals, and broadcasts of
literals, that XLA hoists out of every scope carry no device work and
are not checked.
"""
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import CELUConfig
from repro.core import engine
from repro.core.engine import (EXCHANGE_APPLY, EXCHANGE_COMPUTE, LEAF_SCOPES,
                               LOCAL_GRAD, LOCAL_SCAN, OPTIMIZER,
                               STAGE_SCOPES, WORKSET_DRAW, WORKSET_INSERT)
from repro.data.synthetic import aligned_batches
from repro.models.tabular import make_dlrm
from repro.optim import make_optimizer

from test_engine import _workload

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OP_NAME = re.compile(r'op_name="([^"]*)"')
HOISTED = re.compile(r"=\s*\S+\s+(constant\(|broadcast\(%constant)")

ROUND_PARTS = {(EXCHANGE_APPLY, OPTIMIZER), (EXCHANGE_APPLY, WORKSET_INSERT),
               (LOCAL_SCAN, WORKSET_DRAW), (LOCAL_SCAN, LOCAL_GRAD),
               (LOCAL_SCAN, OPTIMIZER)}


def stray_ops(hlo_text, fn, stages=STAGE_SCOPES):
    """The op_names of ``fn``'s own operations that sit under no stage."""
    scoped = re.compile(r"^jit\(%s\)/(shard_map/)?(%s)/" % (
        re.escape(fn), "|".join(stages)))
    out = []
    for line in hlo_text.splitlines():
        m = OP_NAME.search(line)
        if m and m.group(1).startswith("jit(") and not HOISTED.search(line) \
                and not scoped.match(m.group(1)):
            out.append(m.group(1))
    return out


def parts(hlo_text):
    """{(stage, part)} for every part scope found under a stage."""
    out = set()
    for name in OP_NAME.findall(hlo_text):
        comps = name.split("/")
        for i, c in enumerate(comps):
            if c in STAGE_SCOPES:
                out.update((c, p) for p in comps[i + 1:] if p in LEAF_SCOPES)
                break
    return out


def _two_party(K):
    """The engine tests' tiny DLRM with K feature parties: each party A_i
    runs party A's tower on the same fields, B sums the K cut tensors."""
    data, cfg = _workload()
    init_fn, task, _ = make_dlrm(cfg)
    params = init_fn(jax.random.PRNGKey(0), cfg)
    etask = engine.lift_two_party(task)
    if K > 1:
        etask = engine.KPartyTask(
            task.forward_a, lambda pb, zs, bb: task.loss_b(pb, sum(zs), bb))
    params = {"a": [params["a"]] * K, "b": params["b"]}
    bi, ba, bb = next(aligned_batches(data["train"], 64, seed=0))
    a = [{k: jnp.asarray(v) for k, v in ba.items()}] * K
    b = {k: jnp.asarray(v) for k, v in bb.items()}
    celu = CELUConfig(R=3, W=3, xi_degrees=60.0)
    opt = make_optimizer("adagrad", 0.05)
    state = engine.init_state(etask, params, opt, celu, a, b)
    return etask, opt, celu, state, a, b, bi


@pytest.mark.parametrize("K", [1, 3])
def test_make_round_ops_sit_under_stages(K):
    etask, opt, celu, state, a, b, bi = _two_party(K)
    fn = engine.make_round(etask, opt, celu)
    text = fn.lower(state, a, b, bi).compile().as_text()
    assert stray_ops(text, "round_fn") == []
    assert parts(text) == ROUND_PARTS


@pytest.mark.parametrize("stage,want", [
    (EXCHANGE_COMPUTE, set()),
    (EXCHANGE_APPLY, {(EXCHANGE_APPLY, OPTIMIZER),
                      (EXCHANGE_APPLY, WORKSET_INSERT)}),
    (LOCAL_SCAN, {(LOCAL_SCAN, WORKSET_DRAW), (LOCAL_SCAN, LOCAL_GRAD),
                  (LOCAL_SCAN, OPTIMIZER)}),
])
def test_pipelined_stages_carry_their_scope(stage, want):
    """The depth-1 scheduler jits each stage on its own; each compiled
    stage is all its own scope."""
    etask, opt, celu, state, a, b, bi = _two_party(1)
    eng = engine.make_pipeline(etask, opt, celu, depth=1)
    rs = eng.init(state)
    fresh_args = (rs.params, rs.transport, a, b, rs.comm_rounds)
    if stage == EXCHANGE_COMPUTE:
        low = eng._compute.lower(*fresh_args)
    elif stage == EXCHANGE_APPLY:
        fresh = eng._compute(*fresh_args)
        low = eng._apply.lower(rs.as_state(), fresh, a, b, bi)
    else:
        low = eng._scan.lower(rs.as_state())
    text = low.compile().as_text()
    assert stray_ops(text, stage, stages=(stage,)) == []
    assert parts(text) == want


@pytest.mark.parametrize("depth", [0, 1])
def test_fleet_step_ops_sit_under_stages(depth):
    from repro.fleet.scheduler import JobHyper, make_fleet_step
    etask, _, celu, state, a, b, bi = _two_party(1)
    init, step, _ = make_fleet_step(etask, celu, depth=depth)
    fs = init(state, a, b)
    hyper = JobHyper.for_spec(0.05, celu.xi_degrees)
    text = jax.jit(step).lower(fs, hyper, a, b, bi).compile().as_text()
    assert stray_ops(text, "step") == []
    assert parts(text) == ROUND_PARTS


POD_CODE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax, jax.numpy as jnp
from repro.core.pod_protocol import make_pod_round, init_pod_state
from repro.optim import adagrad
mesh = jax.make_mesh((2,), ("pod",))
opt = adagrad(0.05)
state = init_pod_state(jax.random.PRNGKey(0), mesh, opt, n_fields=4,
                       vocab=32, batch=16, W=2, z_dim=8, hidden=16)
x = jnp.zeros((2, 16, 4), jnp.int32)
y = jnp.zeros((2, 16), jnp.float32)
rnd = make_pod_round(mesh, opt, R=2, cos_xi=0.5, pipeline_depth={depth})
print(rnd.lower(*state, x, y).compile().as_text())
"""


@pytest.mark.parametrize("depth", [0, 1])
def test_pod_round_ops_sit_under_stages(depth):
    """The pod round on two forced CPU devices (a process of its own:
    the device count is fixed before JAX starts)."""
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", POD_CODE.format(depth=depth)],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    assert stray_ops(r.stdout, "exchange_and_local") == []
    assert parts(r.stdout) == ROUND_PARTS
