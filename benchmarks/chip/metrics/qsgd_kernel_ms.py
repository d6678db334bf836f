"""Device milliseconds per round of the three qsgd Pallas kernels
(quantize, dequantize, dequant-reduce). A trace names a Pallas call after
the jitted entry that makes it: ``%qsgd_quantize.41 = (...) custom-call(...)``."""

import re

from chipbench.trace import op_label

QSGD = re.compile(r"^qsgd_(quantize|dequantize|dequant_reduce)$")


def is_qsgd(name):
    return bool(QSGD.match(op_label(name)))


def read(run):
    if run.trace is None or not run.trace.ops:
        return None
    s = run.trace.op_s(is_qsgd)
    return s / run.rounds * 1e3 if s > 0 else None
