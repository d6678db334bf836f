"""Production mesh construction.

Single pod: (16, 16) = 256 chips, axes ("data", "model").
Multi-pod:  (2, 16, 16) = 512 chips, axes ("pod", "data", "model") — the
"pod" axis crosses the inter-pod DCN/ICI boundary; the P2P gradient
exchange runs over ("pod", "data") (or just "pod" for FSDP archs, where a
whole pod acts as one peer).

Defined as functions so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before any jax initialization).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(shape))


def make_host_mesh(data: Optional[int] = None, model: int = 1):
    """A small mesh over whatever devices exist (CPU tests / examples)."""
    n = len(jax.devices())
    if data is None:
        data = n // model
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto, AxisType.Auto))


# Hardware constants for the roofline analysis (TPU v5e).
PEAK_FLOPS_BF16 = 197e12  # per chip
HBM_BW = 819e9  # bytes/s per chip
ICI_BW = 50e9  # bytes/s per link
