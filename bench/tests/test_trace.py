"""trace.py against a hand-built trace whose numbers are known."""
import os

import pytest

import harness

T = harness.load_module(os.path.join(harness.BENCH, "trace.py"),
                        "bench_trace")

# one device; times in microseconds from 0 (the window span is 0..100 us)
#   while.7          0..20   (encloses the next one)
#   fusion.1         0..20
#   custom-call.3   30..40   fused_sample
#   custom-call.4   40..50   fused_sample_q8
#   fusion.1        45..60   (overlaps: busy is the union)
#   collective-permute-start.2  80..90   (on the async line)
# host spans: host_prep 20..35, wait 50..100 (window 0..100)
TRACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 6 offset_ps: 0 duration_ps: 20000000 }
    events { metadata_id: 1 offset_ps: 0 duration_ps: 20000000 }
    events { metadata_id: 2 offset_ps: 30000000 duration_ps: 10000000 }
    events { metadata_id: 3 offset_ps: 40000000 duration_ps: 10000000 }
    events { metadata_id: 1 offset_ps: 45000000 duration_ps: 15000000 }
  }
  lines { id: 3 name: "Async XLA Ops" timestamp_ns: 0
    events { metadata_id: 4 offset_ps: 80000000 duration_ps: 10000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 5 offset_ps: 0 duration_ps: 100000000 } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop" } }
  event_metadata { key: 2 value { id: 2 name: "%custom-call.3 = f32[8]{0} custom-call(f32[8]{0} %a), custom_call_target=tpu_custom_call" } }
  event_metadata { key: 3 value { id: 3 name: "%custom-call.4 = f32[8]{0} custom-call(f32[8]{0} %b), custom_call_target=tpu_custom_call" } }
  event_metadata { key: 4 value { id: 4 name: "%collective-permute-start.2 = f32[8]{0} collective-permute-start(f32[8]{0} %z)" } }
  event_metadata { key: 5 value { id: 5 name: "jit_round_fn" } }
  event_metadata { key: 6 value { id: 6 name: "%while.7 = (s32[]) while((s32[]) %t), condition=%c, body=%b" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python3" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000000 }
    events { metadata_id: 2 offset_ps: 20000000 duration_ps: 15000000 }
    events { metadata_id: 3 offset_ps: 50000000 duration_ps: 50000000 } }
  event_metadata { key: 1 value { id: 1 name: "window" } }
  event_metadata { key: 2 value { id: 2 name: "host_prep" } }
  event_metadata { key: 3 value { id: 3 name: "wait" } }
}
"""


HLO = """
  %custom-call.3 = f32[8]{0} custom-call(f32[8]{0} %a), custom_call_target="tpu_custom_call", metadata={op_name="jit(round_fn)/while/body/fused_sample/pallas_call"}
  ROOT %custom-call.4 = f32[8]{0} custom-call(f32[8]{0} %b), custom_call_target="tpu_custom_call", metadata={op_name="jit(round_fn)/fused_sample_q8/pallas_call"}
  %fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, metadata={op_name="jit(round_fn)/fused_sample/mul"}
"""


@pytest.fixture(scope="module")
def trace():
    from jax.profiler import ProfileData
    t = T.from_profile(ProfileData.from_text_proto(TRACE))
    t.kernels = T.kernel_ops(HLO)
    return t


def test_kernel_ops_from_compiled_text():
    assert T.kernel_ops(HLO) == {"custom-call.3": "fused_sample",
                                 "custom-call.4": "fused_sample_q8"}


def test_window_busy_idle(trace):
    assert trace.window_s() == pytest.approx(100e-6)
    # union of the compute line: 0..20, 30..60 = 50 us (the async
    # collective is not compute)
    assert trace.busy_s() == pytest.approx(50e-6)
    assert trace.idle_share() == pytest.approx(0.5)


def test_kernels_by_stable_name(trace):
    assert trace.kernel("fused_sample") == (pytest.approx(10e-6), 1)
    assert trace.kernel("fused_sample_q8") == (pytest.approx(10e-6), 1)
    assert trace.kernel("quantize_sr") == (0.0, 0)


def test_collectives_and_ops(trace):
    assert trace.collective_seconds() == pytest.approx(10e-6)
    # the enclosing loop is left out, its body counted
    assert trace.op_seconds() == {"fusion": pytest.approx(35e-6),
                                  "fused_sample": pytest.approx(10e-6),
                                  "fused_sample_q8": pytest.approx(10e-6)}


def test_idle_gaps_by_host_span(trace):
    # gaps 20..30 (mid 25: host_prep), 60..100 (mid 80: wait)
    gaps = trace.idle_gaps_by_host()
    assert gaps == {"host_prep": pytest.approx(10e-6),
                    "wait": pytest.approx(40e-6)}


def test_instruction_names():
    assert T.instruction("%fusion.12 = f32[2]{0} fusion(%a)") == "fusion.12"
    assert T.instruction("  ROOT %tuple.3 = (f32[]) tuple()") == "tuple.3"
    assert T.opcode("collective-permute-start.3") == \
        "collective-permute-start"
    assert T.is_collective("collective-permute-done")
    assert not T.is_collective("fusion")
