"""The trace reduction against a small trace recorded on the CPU
(record_cpu_trace.py) and a hand-written trace in the TPU's layout."""
import os

import pytest
from jax.profiler import ProfileData

from bench import trace_reduce as T
from bench import work

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
PEAKS = {"hbm_bytes_per_s": 819e9, "int8_ops_per_s": 393e12}


@pytest.fixture(scope="module")
def cpu():
    pd = ProfileData.from_file(os.path.join(DATA, "cpu_trace.xplane.pb"))
    with open(os.path.join(DATA, "cpu_trace.hlo.txt")) as fh:
        hlo = T.HloIndex([fh.read()])
    return pd, hlo, T.reduce(pd, hlo, peaks=PEAKS)


def test_union_and_clip():
    assert T._union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]
    assert T._clip([(0, 4), (6, 9), (10, 12)], 2, 10) == [(2, 4), (6, 9)]


def test_busy_is_the_union_of_device_ops_in_the_window(cpu):
    pd, _hlo, out = cpu
    ops, spans, _t0 = T.read_trace(pd)
    (lo, hi), = [(s, e) for n, s, e, _t in spans if n == T.WINDOW_SPAN]
    assert ops and out["devices"] == sorted({o[0] for o in ops})
    covered = set()
    for _dev, s, e, *_rest in ops:
        covered.update(range(int(max(s, lo)), int(min(e, hi))))
    assert out["busy_s"] == pytest.approx(len(covered) * 1e-9, abs=2e-9
                                          * len(ops))
    assert 0 < out["busy_s"] < out["window_s"] == pytest.approx(
        (hi - lo) * 1e-9)


def test_per_op_time_sums_every_device_op(cpu):
    pd, _hlo, out = cpu
    ops, _spans, _t0 = T.read_trace(pd)
    total = sum(e - s for _d, s, e, *_r in ops) * 1e-9
    listed = dict(T.reduce(pd, cpu[1], top=1000)["device_ops"])
    assert sum(listed.values()) == pytest.approx(total)
    assert any("jit(ring_matmul)/dot_general" in k for k in listed)


def test_ring_ops_found_by_name_path_with_their_work(cpu):
    _pd, _hlo, out = cpu
    ring = out["ring"]
    one = work.ring_matmul_work(64, 32, 8)
    # four launches of one exact (64, 32) x (32, 8) ring product
    assert ring["bytes"] == pytest.approx(4 * one.bytes)
    assert ring["ops"] == pytest.approx(4 * one.ops)
    assert 0 < ring["seconds"] < out["busy_s"] + 1e-12
    assert ring["roofline_s"] > 0 and ring["bytes_bound"] > 0


def test_longest_gap_labelled_by_the_span_over_it(cpu):
    _pd, _hlo, out = cpu
    label, seconds = out["idle_gaps"][0]
    assert label == "bench.sleep|-"
    assert 0.05 <= seconds < 0.5


TPU_HLO = """HloModule jit_prog, entry_computation_layout={()}

%fused_computation.7 (param_0: s8[8,64,32], param_1: s8[8,32,8]) -> s32[8,64,8,8] {
  %param_0 = s8[8,64,32]{2,1,0} parameter(0)
  %param_1 = s8[8,32,8]{2,1,0} parameter(1)
  ROOT %convolution.3 = s32[8,64,8,8]{3,2,1,0} convolution(%param_0, %param_1), dim_labels=0bf_0io->0b1f, metadata={op_name="jit(prog)/jit(ring_matmul)/dot_general"}
}

ENTRY %main.1 (a: u64[64,8], b: u64[64,8]) -> u64[64,8] {
  %a = u64[64,8]{1,0} parameter(0)
  %b = u64[64,8]{1,0} parameter(1)
  %fusion.2 = s8[8,64,32]{2,1,0} fusion(%a), kind=kLoop, calls=%fused_computation.9, metadata={op_name="jit(prog)/jit(ring_matmul)/convert_element_type"}
  %fusion.3 = s8[8,32,8]{2,1,0} fusion(%b), kind=kLoop, calls=%fused_computation.9, metadata={op_name="jit(prog)/jit(ring_matmul)/convert_element_type"}
  %fusion.4 = s32[8,64,8,8]{3,2,1,0} fusion(%fusion.2, %fusion.3), kind=kOutput, calls=%fused_computation.7, metadata={op_name="jit(prog)/jit(ring_matmul)/dot_general"}
  ROOT %add.5 = u64[64,8]{1,0} add(%a, %b), metadata={op_name="jit(prog)/add"}
}
"""

TPU_TRACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 10 offset_ps: 0 duration_ps: 9000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 11 offset_ps: 1000000 duration_ps: 2000000 }
    events { metadata_id: 12 offset_ps: 4000000 duration_ps: 1000000 }
    events { metadata_id: 13 offset_ps: 4500000 duration_ps: 1000000 } }
  event_metadata { key: 10 value { id: 10 name: "jit_prog(123)" } }
  event_metadata { key: 11 value { id: 11 name: "%fusion.4 = s32[8,64,8,8]{3,2,1,0} fusion(s8[8,64,32]{2,1,0} %fusion.2, s8[8,32,8]{2,1,0} %fusion.3), kind=kOutput, calls=%fused_computation.7" } }
  event_metadata { key: 12 value { id: 12 name: "%add.5 = u64[64,8]{1,0} add(u64[64,8]{1,0} %a, u64[64,8]{1,0} %b)" } }
  event_metadata { key: 13 value { id: 13 name: "%fusion.2 = s8[8,64,32]{2,1,0} fusion(u64[64,8]{1,0} %a), kind=kLoop, calls=%fused_computation.9" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 3 name: "python3" timestamp_ns: 0
    events { metadata_id: 20 offset_ps: 0 duration_ps: 20000000 }
    events { metadata_id: 21 offset_ps: 9000000 duration_ps: 8000000 } }
  event_metadata { key: 20 value { id: 20 name: "bench.window" } }
  event_metadata { key: 21 value { id: 21 name: "bench.sleep" } }
}
"""


def test_tpu_layout_name_path_busy_and_gaps():
    out = T.reduce(ProfileData.from_text_proto(TPU_TRACE),
                   T.HloIndex([TPU_HLO]), peaks=PEAKS)
    assert out["devices"] == ["/device:TPU:0"]
    assert out["window_s"] == pytest.approx(20e-6)
    # ops at [2, 4), [5, 6), [5.5, 6.5) microseconds: a union of 3.5
    assert out["busy_s"] == pytest.approx(3.5e-6)
    ops = dict(out["device_ops"])
    assert ops["jit(prog)/jit(ring_matmul)/dot_general"] == \
        pytest.approx(2e-6)
    assert ops["jit(prog)/add"] == pytest.approx(1e-6)
    ring = out["ring"]
    assert ring["events"] == 2 and ring["seconds"] == pytest.approx(3e-6)
    assert ring["bytes"] == pytest.approx(
        work.ring_matmul_work(64, 32, 8).bytes)
    label, seconds = out["idle_gaps"][0]
    assert label == "bench.sleep|-" and seconds == pytest.approx(13.5e-6)


def test_an_event_the_programs_do_not_hold_is_not_a_ring_op():
    hlo = T.HloIndex([TPU_HLO])
    path, w = hlo.lookup("%fusion.4 = s32[8,64,8,16]{3,2,1,0} fusion("
                         "s8[8,64,32]{2,1,0} %x)", {})
    assert path == "" and w.ops == 0
