"""The benchmark's own counts: model FLOPs from the published layer shapes,
the bytes the qsgd codec must move, the peaks table, and the shares built
from them."""
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

from chipbench import cells  # noqa: E402

VGG11_MACS = {  # by hand, one 32x32x3 sample: H * W * 3 * 3 * Cin * Cout per conv
    "conv1": 32 * 32 * 9 * 3 * 64, "conv2": 16 * 16 * 9 * 64 * 128,
    "conv3": 8 * 8 * 9 * 128 * 256, "conv4": 8 * 8 * 9 * 256 * 256,
    "conv5": 4 * 4 * 9 * 256 * 512, "conv6": 4 * 4 * 9 * 512 * 512,
    "conv7": 2 * 2 * 9 * 512 * 512, "conv8": 2 * 2 * 9 * 512 * 512,
    "fc1": 512 * 4096, "fc2": 4096 * 4096, "fc3": 4096 * 10,
}


def test_vgg11_forward_macs_match_the_hand_count():
    cell = cells.load_cell("vgg11-qsgd127ef-b64m1")
    macs = cell.model.layer_macs(cell.config)
    assert macs == VGG11_MACS
    assert [round(macs[f"conv{i}"] / 1e6, 2) for i in range(1, 9)] == [
        1.77, 18.87, 18.87, 37.75, 18.87, 37.75, 9.44, 9.44]
    assert [round(macs[f"fc{i}"] / 1e6, 2) for i in range(1, 4)] == [2.10, 16.78, 0.04]
    assert round(sum(macs.values()) / 1e6, 1) == 171.7


def test_qsgd_bytes_for_one_small_leaf_by_hand():
    q = cells.kernel_counts("qsgd")
    # 1000 values in buckets of 512: 2 buckets, 1024 values with the padding
    quantize = 1024 * 4 + 1024 * 4 + 1024 + 2 * 4  # x and u in, levels and norms out
    dequantize = 1024 + 2 * 4 + 1024 * 4  # levels and norms in, values out
    reduce1 = 1024 + 2 * 4 + 4 + 1024 * 4  # one peer's bank and weight in, mean out
    assert q.round_bytes([1000], peers=1, bucket=512, ef=True) == quantize + dequantize + reduce1
    assert q.round_bytes([1000], peers=1, bucket=512, ef=False) == quantize + reduce1
    reduce4 = 4 * 1024 + 4 * 2 * 4 + 4 * 4 + 1024 * 4
    assert q.round_bytes([1000], peers=4, bucket=512, ef=True) == quantize + dequantize + reduce4


def test_peaks_table_knows_the_v5e_and_refuses_other_chips():
    p = cells.peaks("TPU v5 lite")
    assert p["flops_bf16"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        cells.peaks("TPU v4")


class FakeTrace:
    def __init__(self, qsgd_s, window_s=1.0):
        self.ops, self.qsgd_s, self.window_s = {0: []}, qsgd_s, window_s

    def op_s(self, pred):
        return self.qsgd_s


def _run(cell, **kw):
    base = dict(cell=cell, traffic=cell.traffic, chips=cell.chips, window_s=1.0, rounds=1,
                samples=0, trace=None, leaf_sizes=[16_777_216],
                peaks=lambda: cells.peaks("TPU v5 lite"), kernel=cells.kernel_counts)
    base.update(kw)
    return SimpleNamespace(**base)


def test_mfu_reaches_100_only_at_the_peak():
    cell = cells.load_cell("vgg11-qsgd127ef-b64m1")
    flops_per_sample = 6 * sum(VGG11_MACS.values())  # 1.03 GFLOP
    at_peak = 197e12 / flops_per_sample  # samples in one second at the peak
    mfu = cells.reader("mfu")
    assert mfu(_run(cell, samples=at_peak)) == pytest.approx(100.0)
    # 15,000 samples/s on one chip is about 7.8%
    assert mfu(_run(cell, samples=15_000)) == pytest.approx(15_000 / at_peak * 100)
    assert mfu(_run(cell, samples=15_000)) < 100


def test_qsgd_roofline_reaches_100_only_at_the_bandwidth():
    cell = cells.load_cell("vgg11-qsgd127ef-b64m1")
    q = cells.kernel_counts("qsgd")
    least = q.round_bytes([16_777_216], peers=1, bucket=512, ef=True) / 819e9
    roof = cells.reader("qsgd_roofline")
    assert roof(_run(cell, trace=FakeTrace(least))) == pytest.approx(100.0)
    assert roof(_run(cell, trace=FakeTrace(2 * least))) == pytest.approx(50.0)
    assert roof(_run(cell, trace=FakeTrace(0.0))) is None
    allgather = cells.load_cell("vgg11-allgather-b64m235")
    assert roof(_run(allgather, trace=FakeTrace(least))) is None
