"""QSGD quantize/dequantize Pallas TPU kernels.

The gradient tensor is pre-bucketed to (nb, BUCKET) f32. Each grid step
processes a (rows, BUCKET) block resident in VMEM: one fp32 L2-norm
reduction per bucket row plus elementwise stochastic rounding, VPU work
(BUCKET is a multiple of 128 lanes). Per-bucket norms travel as (nb, 1)
columns so every block's last two dims obey the TPU tiling rule. The level
count ``s`` is static and closed over. Uniform randoms are passed in as an
operand so the kernel is a pure function (deterministic vs the oracle;
on-chip PRNG would break bit-reproducibility between interpret mode and
the jnp reference).

Block size. Each kernel takes its rows per grid step from its operands'
shapes through :func:`tile_rows`: the largest multiple of 32 rows (one
int8 sublane tile) whose blocks, double-buffered, fit ``VMEM_BUDGET``
(8 MiB, half of v5e's 16 MiB default scoped VMEM), and no more than
``nb`` rounded up to 32, so a one-bucket bias leaf still takes one
32-row block. A (rows, 1) f32 norm column is counted 128 lanes wide, as
VMEM holds it; dequant-reduce counts its P level banks and P norm
columns. At BUCKET 512 a large leaf gets 800 rows a step in quantize,
1,344 in dequantize and 1,344 / 672 in dequant-reduce at P = 1 / 4; a
larger bucket or more peers get fewer rows by the same rule.
The grid is ``cdiv(nb, rows)``: the last block may be ragged, and since
every kernel works row by row, its rows past ``nb`` are computed on
whatever the buffer holds and never written back. Nothing is padded or
sliced in HBM.
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROW_ALIGN = 32  # rows of one int8 sublane tile: the least block height
VMEM_BUDGET = 8 * 1024 * 1024  # double-buffered block bytes per grid step
NORM_ROW_BYTES = 128 * 4  # a (rows, 1) f32 column fills 128 lanes in VMEM


def tile_rows(nb: int, bucket: int, itemsizes: Sequence[int], norm_columns: int) -> int:
    """Bucket rows per grid step for a kernel over (nb, bucket) buckets.

    ``itemsizes`` holds the bytes per element of every (rows, bucket) block
    the kernel reads or writes (a (P, rows, bucket) bank counts P times);
    ``norm_columns`` the number of (rows, 1) f32 norm columns. Returns the
    largest multiple of ``ROW_ALIGN`` whose blocks, double-buffered, fit
    ``VMEM_BUDGET``, capped at ``nb`` rounded up to ``ROW_ALIGN``; never
    less than ``ROW_ALIGN``.
    """
    row_bytes = 2 * (bucket * sum(itemsizes) + norm_columns * NORM_ROW_BYTES)
    fit = VMEM_BUDGET // row_bytes // ROW_ALIGN * ROW_ALIGN
    cap = pl.cdiv(nb, ROW_ALIGN) * ROW_ALIGN
    return max(ROW_ALIGN, min(fit, cap))


def _quantize_kernel(x_ref, u_ref, lev_ref, nrm_ref, *, s: float):
    x = x_ref[...].astype(jnp.float32)  # (rows, BUCKET)
    u = u_ref[...].astype(jnp.float32)
    norms = jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True))  # (rows, 1)
    safe = jnp.maximum(norms, 1e-30)
    r = jnp.abs(x) / safe * s
    l = jnp.floor(r)
    xi = l + (u < (r - l)).astype(jnp.float32)
    lev = jnp.clip(xi, 0.0, s) * jnp.sign(x)
    lev_ref[...] = lev.astype(jnp.int32).astype(jnp.int8)
    nrm_ref[...] = norms


def _dequantize_kernel(lev_ref, nrm_ref, out_ref, *, s: float):
    lev = lev_ref[...].astype(jnp.int32).astype(jnp.float32)
    out_ref[...] = lev * (nrm_ref[...] / s)


def _dequant_reduce_kernel(w_ref, lev_ref, nrm_ref, out_ref, *, s: float, P: int):
    """Fused decode-dequantize-reduce over the gathered peer banks.

    One VMEM pass: every peer's int8 levels tile is dequantized and folded
    into the mixing-weighted sum without ever materializing the P dense
    fp32 gradients in HBM (the unfused path vmap-dequantizes all P banks,
    then reduces — P x the fp32 traffic). The P mixing weights are SMEM
    scalars.
    """
    acc = jnp.zeros(out_ref.shape, jnp.float32)
    for p in range(P):
        scale = (w_ref[p] * nrm_ref[p]) / s  # (rows, 1)
        acc = acc + lev_ref[p].astype(jnp.int32).astype(jnp.float32) * scale
    out_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("s", "interpret"))
def qsgd_quantize(buckets: jnp.ndarray, u: jnp.ndarray, s: int, *, interpret: bool):
    """buckets, u: (nb, BUCKET) f32 -> (levels int8 (nb, BUCKET), norms f32 (nb,))."""
    nb, bucket = buckets.shape
    assert bucket % 128 == 0, f"bucket {bucket} must be lane-aligned (128)"
    rows = tile_rows(nb, bucket, (4, 4, 1), 1)
    lev, nrm = pl.pallas_call(
        functools.partial(_quantize_kernel, s=float(s)),
        grid=(pl.cdiv(nb, rows),),
        in_specs=[
            pl.BlockSpec((rows, bucket), lambda i: (i, 0)),
            pl.BlockSpec((rows, bucket), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((rows, bucket), lambda i: (i, 0)),
            pl.BlockSpec((rows, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb, bucket), jnp.int8),
            jax.ShapeDtypeStruct((nb, 1), jnp.float32),
        ],
        interpret=interpret,
    )(buckets, u)
    return lev, nrm[:, 0]


@functools.partial(jax.jit, static_argnames=("s", "interpret"))
def qsgd_dequantize(levels: jnp.ndarray, norms: jnp.ndarray, s: int, *, interpret: bool):
    """levels (nb, BUCKET) int8, norms (nb,) -> f32 (nb, BUCKET)."""
    nb, bucket = levels.shape
    assert bucket % 128 == 0
    rows = tile_rows(nb, bucket, (1, 4), 1)
    return pl.pallas_call(
        functools.partial(_dequantize_kernel, s=float(s)),
        grid=(pl.cdiv(nb, rows),),
        in_specs=[
            pl.BlockSpec((rows, bucket), lambda i: (i, 0)),
            pl.BlockSpec((rows, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((rows, bucket), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, bucket), jnp.float32),
        interpret=interpret,
    )(levels, norms.astype(jnp.float32)[:, None])


@functools.partial(jax.jit, static_argnames=("s", "interpret"))
def qsgd_dequant_reduce(
    levels: jnp.ndarray,
    norms: jnp.ndarray,
    w: jnp.ndarray,
    s: int,
    *,
    interpret: bool,
):
    """Fused decode-dequantize-reduce over P gathered peer banks.

    levels (P, nb, BUCKET) int8, norms (P, nb) f32, w (P,) f32 mixing
    weights -> (nb, BUCKET) f32 = sum_p w[p] * dequantize(levels[p], norms[p]).
    Replaces the unfused vmap-dequantize-then-reduce path with a single
    VMEM pass per block (the dense fp32 per-peer banks are never built).
    """
    P, nb, bucket = levels.shape
    assert bucket % 128 == 0
    assert norms.shape == (P, nb) and w.shape == (P,)
    rows = tile_rows(nb, bucket, (1,) * P + (4,), P)
    return pl.pallas_call(
        functools.partial(_dequant_reduce_kernel, s=float(s), P=P),
        grid=(pl.cdiv(nb, rows),),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((P, rows, bucket), lambda i: (0, i, 0)),
            pl.BlockSpec((P, rows, 1), lambda i: (0, i, 0)),
        ],
        out_specs=pl.BlockSpec((rows, bucket), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, bucket), jnp.float32),
        interpret=interpret,
    )(w.astype(jnp.float32), levels, norms.astype(jnp.float32)[:, :, None])
