"""Jit'd public wrappers for the Pallas kernels.

Every kernel entry takes ``interpret`` as a required keyword;
:func:`default_interpret` is the one place that decides it: compiled on a
TPU backend, interpreted everywhere else (CPU tests).

A Mosaic kernel cannot be partitioned by GSPMD. The codec kernels run
inside the P2P step, where the peer axes are manual but the lambda axis
stays automatic, so each codec call is wrapped in a ``shard_map`` over the
whole mesh with replicated operands: every device of a peer runs the
kernel on the whole (already reduced) gradient. The top-k decoder is an
XLA scatter-add, not a kernel.
"""
from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, PartitionSpec

from repro.kernels import flash_attention as _fa
from repro.kernels import qsgd as _qsgd
from repro.kernels import ref as _ref
from repro.kernels import ssd_scan as _ssd
from repro.kernels import topk as _topk


def default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _manual(fn: Callable, *args):
    """``fn(*args)``, manual over every axis of the current mesh.

    Axes that are already manual are named too: the Mosaic lowering checks
    that the whole mesh is manual, and a nested ``shard_map`` records only
    the axes it names.
    """
    am = jax.sharding.get_abstract_mesh()
    if all(t == AxisType.Manual for t in am.axis_types):
        return fn(*args)
    return jax.shard_map(
        fn, in_specs=PartitionSpec(), out_specs=PartitionSpec(),
        axis_names=frozenset(am.axis_names), check_vma=False,
    )(*args)


def qsgd_quantize(buckets: jnp.ndarray, u: jnp.ndarray, s: int):
    return _manual(
        lambda b, r: _qsgd.qsgd_quantize(b, r, s, interpret=default_interpret()),
        buckets, u,
    )


def qsgd_dequantize(levels: jnp.ndarray, norms: jnp.ndarray, s: int):
    return _manual(
        lambda l, n: _qsgd.qsgd_dequantize(l, n, s, interpret=default_interpret()),
        levels, norms,
    )


def qsgd_dequant_reduce(
    levels: jnp.ndarray, norms: jnp.ndarray, w: jnp.ndarray, s: int
):
    """Fused decode: (P, nb, B) int8 banks -> weighted dense sum (nb, B) f32."""
    return _manual(
        lambda l, n, ww: _qsgd.qsgd_dequant_reduce(
            l, n, ww, s, interpret=default_interpret()
        ),
        levels, norms, w,
    )


def topk_select_pack(x: jnp.ndarray, k: int):
    return _manual(
        lambda v: _topk.topk_select_pack(v, k, interpret=default_interpret()), x
    )


def topk_scatter_accum(vals: jnp.ndarray, idx: jnp.ndarray, w: jnp.ndarray, n: int):
    """Top-k decode: (P, k) banks -> weighted dense sum (n,) f32.

    XLA's scatter-add: Mosaic has no scatter, and the scatter-add already
    folds the P*k weighted entries into the dense sum in one pass.
    """
    return _ref.topk_scatter_ref(vals, idx, w, n)


def ssd_scan(
    x: jnp.ndarray,
    dt: jnp.ndarray,
    A: jnp.ndarray,
    Bm: jnp.ndarray,
    Cm: jnp.ndarray,
    *,
    chunk: int = 256,
) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    y = _ssd.ssd_scan_pallas(x, dt, A, Bm, Cm, chunk=chunk, interpret=default_interpret())
    return y, None


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    softcap: float = 0.0,
    window: int = 0,
    block_q: int = 512,
    block_kv: int = 512,
) -> jnp.ndarray:
    return _fa.flash_attention(
        q, k, v,
        causal=causal, softcap=softcap, window=window,
        block_q=block_q, block_kv=block_kv,
        interpret=default_interpret(),
    )
