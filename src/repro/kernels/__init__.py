"""Pallas TPU kernels for the framework's compute hot-spots.

  qsgd.py            — QSGD quantize/dequantize + fused decode-reduce (§III-B.4)
  topk.py            — top-k select+pack (bisection threshold search)
  ssd_scan.py        — Mamba-2 chunked SSD scan (SSM archs' hot loop)
  flash_attention.py — blocked online-softmax attention forward
  ops.py             — jit'd public wrappers (interpret on CPU, compiled on TPU)
  ref.py             — pure-jnp oracles every kernel is validated against
"""
from repro.kernels import ops

__all__ = ["ops"]
