"""moe_scopes.py against a hand-written compiled round and a trace whose
numbers are known."""
import harness
import moe_scopes

T = moe_scopes.T

# A forward op under moe, a backward fusion under transpose(jvp(moe)) /
# moe_experts, the compiler's grouped-matmul kernel and its metadata
# kernel (their own op_name), a copy no metadata names whose operand is
# the kernel's output, and an op outside any MoE layer.
HLO = """HloModule jit_round_fn, is_scheduled=true

%fused_computation.1 (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  ROOT %multiply.11 = f32[8]{0} multiply(%param_0.1, %param_0.1), metadata={op_name="jit(round_fn)/local_scan/transpose(jvp(moe))/moe_experts/mul"}
}

ENTRY %main.9 (p0: f32[8], p1: s32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  %p1 = s32[8]{0} parameter(1)
  %sort.2 = f32[8]{0} sort(%p0), metadata={op_name="jit(round_fn)/exchange_compute/jvp(moe)/jit(argsort)/sort"}
  %ragged-dot-metadata = s32[8]{0} custom-call(%p1), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-metadata"}
  %ragged-dot-none = f32[8]{0} custom-call(%ragged-dot-metadata, %sort.2), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %copy.4 = f32[8]{0} copy(%ragged-dot-none)
  %fusion.3 = f32[8]{0} fusion(%copy.4), kind=kLoop, calls=%fused_computation.1
  ROOT %add.5 = f32[8]{0} add(%fusion.3, %p0), metadata={op_name="jit(round_fn)/exchange_compute/short_conv/add"}
}
"""

US = 1000        # ns


def _rec(hlo=HLO):
    """One device, window 0..100 us, two traced rounds:
        sort.2                 0..10   moe          (dispatch)
        ragged-dot-metadata   10..12   moe_experts
        ragged-dot-none       12..40   moe_experts
        copy.4                40..45   moe_experts  (its operand's)
        fusion.3              45..60   moe_experts  (its body's)
        add.5                 60..70   other
    per round: moe 30 us, of which experts 25."""
    ev = [(0, 10, "sort.2"), (10, 12, "ragged-dot-metadata"),
          (12, 40, "ragged-dot-none"), (40, 45, "copy.4"),
          (45, 60, "fusion.3"), (60, 70, "add.5")]
    t = T.Trace([T.DeviceOps(0, [(s * US, e * US, n) for s, e, n in ev])],
                [(0, 100 * US, T.WINDOW_SPAN)])
    t.hlo = hlo
    return {"trace": t, "traced_steps": 2}


def _read(name, rec):
    return harness.load_module(
        f"{harness.BENCH}/metrics/{name}.py",
        "metric_" + name.replace(".", "_")).read(rec)


def test_scopes_of_op_names():
    assert moe_scopes.scope_of(
        "jit(f)/local_scan/transpose(jvp(moe))/moe_experts/dot") == \
        ("moe", "moe_experts")
    assert moe_scopes.scope_of("jit(f)/jvp(moe)/top_k") == ("moe", "moe")
    assert moe_scopes.scope_of("ragged-dot-none") == ("moe", "moe_experts")
    assert moe_scopes.scope_of("jit(f)/short_conv/add")[0] == "other"
    assert moe_scopes.scope_of("jit(f)/moe_experts_x/add")[0] == "other"


def test_moe_and_dispatch_ms():
    rec = _rec()
    assert abs(_read("moe_ms.tokens", rec) - 0.030) < 1e-12
    assert abs(_read("moe_dispatch_ms.tokens", rec) - 0.005) < 1e-12


def test_nothing_to_read_without_moe():
    hlo = HLO.replace("moe", "mlp").replace("ragged-dot", "dot")
    rec = _rec(hlo)
    rec["trace"].devices[0].events = [
        (s, e, n.replace("ragged-dot", "dot"))
        for s, e, n in rec["trace"].devices[0].events]
    assert _read("moe_ms.tokens", rec) is None
    assert _read("moe_dispatch_ms.tokens", rec) is None
    assert _read("moe_ms.tokens", {"trace": None, "traced_steps": 0}) is None
