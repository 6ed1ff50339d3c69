"""stages.py against a hand-written compiled round and a trace whose
numbers are known, and against the tiny round compiled on the CPU."""
import os

import pytest

import harness
import stages

T = stages.T

# ENTRY holds a scoped op, a fusion with no metadata whose body votes
# 2 : 1 for local_scan/optimizer, a copy and a bitcast with no metadata, a
# broadcast literal that only its user places, an XLA-made loop whose
# body holds an unscoped dynamic-update-slice, and one op no rule reaches.
HLO = """HloModule jit_round_fn, is_scheduled=true

%fused_computation.1 (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  %multiply.11 = f32[8]{0} multiply(%param_0.1, %param_0.1), metadata={op_name="jit(round_fn)/local_scan/while/body/closed_call/optimizer/mul"}
  %add.12 = f32[8]{0} add(%multiply.11, %param_0.1), metadata={op_name="jit(round_fn)/exchange_apply/add"}
  ROOT %sqrt.13 = f32[8]{0} sqrt(%add.12), metadata={op_name="jit(round_fn)/local_scan/while/body/closed_call/optimizer/sqrt"}
}

%wide.body.sunk (wide.param.1: (u32[], f32[64])) -> (u32[], f32[64]) {
  %wide.param.1 = (u32[], f32[64]{0}) parameter(0)
  %get-tuple-element.21 = u32[] get-tuple-element(%wide.param.1), index=0
  %get-tuple-element.22 = f32[64]{0} get-tuple-element(%wide.param.1), index=1
  %constant.23 = f32[8]{0} constant({0, 0, 0, 0, 0, 0, 0, 0})
  %dynamic-update-slice.24 = f32[64]{0} dynamic-update-slice(%get-tuple-element.22, %constant.23, %get-tuple-element.21)
  ROOT %tuple.25 = (u32[], f32[64]{0}) tuple(%get-tuple-element.21, %dynamic-update-slice.24)
}

%wide.cond (wide.param.2: (u32[], f32[64])) -> pred[] {
  %wide.param.2 = (u32[], f32[64]{0}) parameter(0)
  %get-tuple-element.31 = u32[] get-tuple-element(%wide.param.2), index=0
  %constant.32 = u32[] constant(8)
  ROOT %compare.33 = pred[] compare(%get-tuple-element.31, %constant.32), direction=LT
}

ENTRY %main.9 (p0: f32[8], p1: u32[]) -> (u32[], f32[64]) {
  %p0 = f32[8]{0} parameter(0), metadata={op_name="state['x']"}
  %p1 = u32[] parameter(1)
  %add.2 = f32[8]{0} add(%p0, %p0), metadata={op_name="jit(round_fn)/exchange_compute/add"}
  %fusion.3 = f32[8]{0} fusion(%add.2), kind=kLoop, calls=%fused_computation.1
  %copy.4 = f32[8]{0} copy(%fusion.3)
  %bitcast.5 = f32[64]{0} bitcast(%add.2)
  %constant.11 = f32[] constant(0)
  %broadcast.10 = f32[8]{0} broadcast(%constant.11), dimensions={}
  %multiply.12 = f32[8]{0} multiply(%broadcast.10, %p0), metadata={op_name="jit(round_fn)/exchange_apply/optimizer/mul"}
  %tuple.6 = (u32[], f32[64]{0}) tuple(%p1, %bitcast.5)
  %while.7 = (u32[], f32[64]{0}) while(%tuple.6), condition=%wide.cond, body=%wide.body.sunk
  %negate.8 = f32[8]{0} negate(%p0)
  ROOT %get-tuple-element.9 = f32[64]{0} get-tuple-element(%while.7), index=1
}
"""

US = 1000        # ns


def _trace(hlo=HLO):
    """One device, window 0..100 us:
        add.2                   0..10   exchange_compute         (rule 1)
        fusion.3               10..30   local_scan/optimizer     (rule 2)
        copy.4                 30..35   local_scan/optimizer     (rule 3)
        broadcast.10           35..40   exchange_apply/optimizer (rule 4)
        while.7                40..80   enclosing, left out
          dynamic-update-slice.24  40..50, 55..65  exchange_compute (rule 5)
        negate.8               85..90   no stage
    busy 85 us: exchange_compute 30, local_scan 25, exchange_apply 5,
    unattributed 25 (negate 5 + loop control 50..55 and 65..80)."""
    ev = [(0, 10, "add.2"), (10, 30, "fusion.3"), (30, 35, "copy.4"),
          (35, 40, "broadcast.10"),
          (40, 80, "while.7"), (40, 50, "dynamic-update-slice.24"),
          (55, 65, "dynamic-update-slice.24"), (85, 90, "negate.8")]
    t = T.Trace([T.DeviceOps(0, [(s * US, e * US, n) for s, e, n in ev])],
                [(0, 100 * US, T.WINDOW_SPAN)])
    t.hlo = hlo
    return t


def test_each_rule_of_the_attribution_order():
    a = stages.Attribution(HLO)
    assert a.scope("add.2") == ("exchange_compute", "exchange_compute")
    assert a.scope("fusion.3") == ("local_scan", "optimizer")      # 2 : 1
    assert a.scope("copy.4") == ("local_scan", "optimizer")        # operand
    assert a.scope("bitcast.5") == ("exchange_compute",) * 2
    assert a.scope("broadcast.10") == ("exchange_apply", "optimizer")  # user
    # the loop: nothing scoped in its body, its nearest scoped operand is
    # add.2, three hops up; the body's write takes the loop's stage
    assert a.scope("while.7") == ("exchange_compute",) * 2
    assert a.scope("dynamic-update-slice.24") == ("exchange_compute",) * 2
    assert a.scope("negate.8") is None
    assert a.scope("no-such-op.1") is None


def test_split_adds_up_to_busy_time():
    t = _trace()
    s = stages.split(t, t.hlo)
    assert s.stages == pytest.approx({"exchange_compute": 30e-6,
                                      "local_scan": 25e-6,
                                      "exchange_apply": 5e-6})
    assert s.leaves == pytest.approx({"exchange_compute": 30e-6,
                                      "optimizer": 30e-6})
    assert s.unattributed == pytest.approx(25e-6)
    assert s.busy == pytest.approx(t.busy_s()) == pytest.approx(85e-6)
    assert sum(s.stages.values()) + s.unattributed == pytest.approx(s.busy)


def test_readers_per_round():
    rec = {"trace": _trace(), "traced_steps": 2}
    read = lambda name: harness.load_module(  # noqa: E731
        os.path.join(harness.BENCH, "metrics", name + ".py"),
        "metric_" + name.replace(".", "_")).read(rec)
    assert read("exchange_compute_ms.samples") == pytest.approx(0.015)
    assert read("exchange_apply_ms.tokens") == pytest.approx(0.0025)
    assert read("local_scan_ms.samples") == pytest.approx(0.0125)
    assert read("optimizer_ms.samples") == pytest.approx(0.015)
    assert read("stage_unattributed_share.tokens") == \
        pytest.approx(100 * 25 / 85)


def test_compiled_text_from_the_frame_that_holds_rec():
    """Without ``trace.hlo`` the text is the ``hlo`` held beside this
    very ``rec`` up the call stack, as in the harness's ``main``."""
    t = _trace()
    del t.hlo
    rec = {"trace": t, "traced_steps": 2}

    def main_like(rec):
        hlo = HLO                                        # noqa: F841
        return harness.load_module(
            os.path.join(harness.BENCH, "metrics",
                         "local_scan_ms.samples.py"),
            "metric_local_scan_ms_samples").read(rec)

    assert main_like(rec) == pytest.approx(0.0125)
    other = {"trace": _trace(), "traced_steps": 2}
    del other["trace"].hlo
    hlo = HLO                                            # noqa: F841
    assert stages.compiled_text(other) is None     # held beside another rec


def test_no_stage_named_reads_nothing():
    """A compiled text without the scopes (an old program, or a cache
    entry made before them) gives no numbers rather than zeros."""
    bare = HLO.replace("exchange_compute", "a").replace(
        "exchange_apply", "b").replace("local_scan", "c")
    rec = {"trace": _trace(bare), "traced_steps": 2}
    assert stages.ms_per_round(rec, stages.LOCAL_SCAN) is None
    assert stages.unattributed_share(rec) is None
    assert stages.ms_per_round({"trace": None}, stages.LOCAL_SCAN) is None


def test_tiny_round_leaves_nothing_that_computes_unplaced():
    """The CPU-compiled tiny round: whatever no rule places in ``ENTRY``
    reads nothing but the round's arguments and literals."""
    import re

    import jax
    import jax.numpy as jnp

    from repro.configs.base import CELUConfig
    from repro.core import engine
    from repro.models.tabular import DLRMConfig, make_dlrm
    from repro.optim import make_optimizer

    cfg = DLRMConfig("wdl", 4, 3, vocab=32, embed_dim=4, z_dim=8,
                     hidden=(16, 8))
    init_fn, task, _ = make_dlrm(cfg)
    etask = engine.lift_two_party(task)
    params = engine.lift_two_party_params(
        init_fn(jax.random.PRNGKey(0), cfg))
    a = [{"x_a": jnp.zeros((64, 4), jnp.int32)}]
    b = {"x_b": jnp.zeros((64, 3), jnp.int32),
         "y": jnp.zeros((64,), jnp.float32)}
    celu = CELUConfig(R=3, W=3, xi_degrees=60.0)
    opt = make_optimizer("adagrad", 0.05)
    state = engine.init_state(etask, params, opt, celu, a, b)
    text = engine.make_round(etask, opt, celu).lower(
        state, a, b, 0).compile().as_text()
    at = stages.Attribution(text)
    lines = {T.instruction(l): l for l in text.splitlines()
             if T.INSTR.match(l)}
    entry = re.search(r"^ENTRY %?([\w.\-]+)", text, re.M).group(1)
    todo = [n for n in at.instrs
            if at.instrs[n].comp == entry and at.scope(n) is None]
    read, seen = set(), set()
    while todo:
        n = todo.pop()
        if n not in seen:
            seen.add(n)
            todo.extend(at.instrs[n].operands)
            if not at.instrs[n].operands:
                read.add(n)
    assert all(" parameter(" in lines[n] or " constant(" in lines[n]
               for n in read)
    got = {at.scope(n)[0] for n in at.instrs if at.scope(n)}
    assert got == set(stages.STAGE_SCOPES)
