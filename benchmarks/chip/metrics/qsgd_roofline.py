"""The qsgd kernels' share of their roofline: the least time for the bytes
the codec must move on the cell's leaves (``kernels/qsgd.py``) at the
chip's HBM bandwidth, over the kernels' device time. Memory-bound: the
bytes bound it."""

from pathlib import Path

from chipbench.cells import load_module


def read(run):
    q = run.traffic.get("qsgd")
    if q is None or run.trace is None or not run.trace.ops:
        return None
    is_qsgd = load_module(Path(__file__).with_name("qsgd_kernel_ms.py")).is_qsgd
    kernel_s = run.trace.op_s(is_qsgd)
    if kernel_s <= 0:
        return None
    per_round = run.kernel("qsgd").round_bytes(run.leaf_sizes, peers=run.traffic["peers"],
                                               bucket=q["bucket"], ef=run.traffic["ef"])
    least_s = per_round * run.rounds / run.peaks()["hbm_bytes_per_s"]
    return 100 * least_s / kernel_s
