"""Trace reduction: busy and idle time, top ops, qsgd kernel matching and
the attribution of idle gaps to the benchmark's host spans, on hand-made
events and on a small trace recorded on a TPU v5e (``fixtures/``: 10 ms of
a traced window of the vgg11 qsgd+EF cell at 3 levels, about two rounds;
the kernels and their shapes do not depend on the level count)."""
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

from chipbench import cells, trace  # noqa: E402


def ev(plane, name, start, dur):
    line = "XLA Ops" if plane.startswith("/device") else "python"
    return {"plane": plane, "line": line, "name": name, "start": float(start),
            "dur": float(dur)}


TPU0, HOST = "/device:TPU:0", "/host:CPU"
HAND = [
    ev(HOST, "bench.window", 100, 1000),
    ev(HOST, "bench.dispatch", 100, 150),
    ev(HOST, "bench.wait", 250, 700),
    ev(HOST, "bench.dispatch", 950, 100),
    ev(TPU0, "convolution.3", 50, 100),  # clipped to [100, 150)
    ev(TPU0, "convolution_add_fusion.7", 300, 200),
    ev(TPU0, "qsgd_quantize.42", 450, 100),  # overlaps the conv by 50
    ev(TPU0, "all-gather.5", 700, 100),
    ev(TPU0, "fusion.12", 1050, 200),  # clipped to [1050, 1100)
]


def test_union_and_gaps():
    assert trace.union_ns([(0, 10), (5, 15), (20, 30), (21, 22)]) == 25
    assert trace.gaps([(0, 10), (5, 15), (20, 30)], -5, 40) == [(-5, 0), (15, 20), (30, 40)]
    assert trace.gaps([], 0, 5) == [(0, 5)]


def test_hand_events_reduce_as_read_by_hand():
    t = trace.Trace.from_events(HAND)
    assert t.window_s == pytest.approx(1000e-9)
    # busy: [100,150) + [300,550) + [700,800) + [1050,1100) = 50 + 250 + 100 + 50
    assert t.busy_s() == pytest.approx(450e-9)
    assert t.top_ops(1) == [["convolution_add_fusion", pytest.approx(200e-9)]]
    assert dict(t.top_ops()) == pytest.approx({
        "convolution_add_fusion": 200e-9, "all-gather": 100e-9, "qsgd_quantize": 100e-9,
        "convolution": 50e-9, "fusion": 50e-9})
    qsgd = cells.load_module(HERE / "metrics" / "qsgd_kernel_ms.py").is_qsgd
    assert t.op_s(qsgd) == pytest.approx(100e-9)
    # idle: [150,300) mostly in the first dispatch (100 of 150), [550,700)
    # in the wait, [800,1050) mostly in the wait (150 of 250)
    gaps = t.idle_gaps()
    assert [round(s * 1e9) for s, _ in gaps] == [150, 150, 250]
    assert [label for _, label in gaps] == ["dispatch", "wait", "wait"]
    assert t.breakdown(1)["idle_gaps"] == [["wait", pytest.approx(250e-9)]]


def test_a_trace_without_one_window_is_refused():
    with pytest.raises(ValueError):
        trace.Trace.from_events(HAND[1:])


def test_tpu_op_names_are_read_from_their_hlo_text():
    text = ("%qsgd_quantize.41 = (s8[32768,512]{1,0:T(8,128)(4,1)S(1)}, f32[32768,1]{1,0:T(8,128)}) "
            "custom-call(f32[32768,512]{1,0:T(8,128)} %pad.1, f32[32768,512]{1,0:T(8,128)} %pad.2)")
    assert trace.op_label(text) == "qsgd_quantize"
    assert trace.op_label("%fusion.374.clone.clone = f32[8] fusion(f32[8] %a)") == "fusion"


def test_a_recorded_tpu_trace_reduces_as_read_by_hand():
    t = trace.Trace.from_events(trace.load_events(HERE / "fixtures" / "vgg11-qsgd-b64m1.trace.json.gz"))
    assert t.devices == [0] and t.window_s == pytest.approx(0.01)
    busy = t.busy_s()
    assert 0.0085 < busy < 0.0095  # the device was idle 5-15% of these 10 ms
    qsgd = cells.load_module(HERE / "metrics" / "qsgd_kernel_ms.py").is_qsgd
    # 22 leaves, three kernels each per round: quantize, the EF's dequantize,
    # dequant-reduce; a little over two rounds in the slice
    assert t.count(qsgd) == 145
    assert {trace.op_label(n) for _, _, n in t.ops[0] if qsgd(n)} == {
        "qsgd_quantize", "qsgd_dequantize", "qsgd_dequant_reduce"}
    assert 0.0020 < t.op_s(qsgd) < 0.0030
    assert {name for _, _, name in t.spans} == {"dispatch", "wait"}
    longest = t.breakdown(1)["idle_gaps"][0]
    assert longest[0] == "dispatch" and longest[1] > 1e-4
