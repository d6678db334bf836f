"""QSGD quantize/dequantize Pallas TPU kernels.

The gradient tensor is pre-bucketed to (nb, BUCKET) f32. Each grid step
processes a (TILE_NB, BUCKET) tile resident in VMEM: one fp32 L2-norm
reduction per bucket row plus elementwise stochastic rounding — VPU work,
tile-aligned (BUCKET is a multiple of 128 lanes; TILE_NB = 32 rows is a
whole int8 tile and four f32 tiles). Per-bucket norms travel as (nb, 1)
columns so every block's last two dims obey the TPU tiling rule. The level
count ``s`` is static and closed over. Uniform randoms are passed in as an
operand so the kernel is a pure function (deterministic vs the oracle;
on-chip PRNG would break bit-reproducibility between interpret mode and
the jnp reference).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE_NB = 32  # bucket rows per grid step (one int8 sublane tile)


def _quantize_kernel(x_ref, u_ref, lev_ref, nrm_ref, *, s: float):
    x = x_ref[...].astype(jnp.float32)  # (TILE_NB, BUCKET)
    u = u_ref[...].astype(jnp.float32)
    norms = jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True))  # (TILE_NB, 1)
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
        scale = (w_ref[p] * nrm_ref[p]) / s  # (TILE_NB, 1)
        acc = acc + lev_ref[p].astype(jnp.int32).astype(jnp.float32) * scale
    out_ref[...] = acc


def _pad_rows(x: jnp.ndarray, axis: int, value=0) -> jnp.ndarray:
    pad = (-x.shape[axis]) % TILE_NB
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


@functools.partial(jax.jit, static_argnames=("s", "interpret"))
def qsgd_quantize(buckets: jnp.ndarray, u: jnp.ndarray, s: int, *, interpret: bool):
    """buckets, u: (nb, BUCKET) f32 -> (levels int8 (nb, BUCKET), norms f32 (nb,))."""
    nb, bucket = buckets.shape
    assert bucket % 128 == 0, f"bucket {bucket} must be lane-aligned (128)"
    buckets = _pad_rows(buckets, 0)
    u = _pad_rows(u, 0, value=1.0)
    nbp = buckets.shape[0]
    lev, nrm = pl.pallas_call(
        functools.partial(_quantize_kernel, s=float(s)),
        grid=(nbp // TILE_NB,),
        in_specs=[
            pl.BlockSpec((TILE_NB, bucket), lambda i: (i, 0)),
            pl.BlockSpec((TILE_NB, bucket), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((TILE_NB, bucket), lambda i: (i, 0)),
            pl.BlockSpec((TILE_NB, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nbp, bucket), jnp.int8),
            jax.ShapeDtypeStruct((nbp, 1), jnp.float32),
        ],
        interpret=interpret,
    )(buckets, u)
    return lev[:nb], nrm[:nb, 0]


@functools.partial(jax.jit, static_argnames=("s", "interpret"))
def qsgd_dequantize(levels: jnp.ndarray, norms: jnp.ndarray, s: int, *, interpret: bool):
    """levels (nb, BUCKET) int8, norms (nb,) -> f32 (nb, BUCKET)."""
    nb, bucket = levels.shape
    assert bucket % 128 == 0
    levels = _pad_rows(levels, 0)
    norms = _pad_rows(norms.astype(jnp.float32)[:, None], 0)
    nbp = levels.shape[0]
    out = pl.pallas_call(
        functools.partial(_dequantize_kernel, s=float(s)),
        grid=(nbp // TILE_NB,),
        in_specs=[
            pl.BlockSpec((TILE_NB, bucket), lambda i: (i, 0)),
            pl.BlockSpec((TILE_NB, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((TILE_NB, bucket), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nbp, bucket), jnp.float32),
        interpret=interpret,
    )(levels, norms)
    return out[:nb]


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
    VMEM pass per tile (the dense fp32 per-peer banks are never built).
    """
    P, nb, bucket = levels.shape
    assert bucket % 128 == 0
    assert norms.shape == (P, nb) and w.shape == (P,)
    levels = _pad_rows(levels, 1)
    norms = _pad_rows(norms.astype(jnp.float32)[:, :, None], 1)
    nbp = levels.shape[1]
    out = pl.pallas_call(
        functools.partial(_dequant_reduce_kernel, s=float(s), P=P),
        grid=(nbp // TILE_NB,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((P, TILE_NB, bucket), lambda i: (0, i, 0)),
            pl.BlockSpec((P, TILE_NB, 1), lambda i: (0, i, 0)),
        ],
        out_specs=pl.BlockSpec((TILE_NB, bucket), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nbp, bucket), jnp.float32),
        interpret=interpret,
    )(w.astype(jnp.float32), levels, norms)
    return out[:nb]
