"""Chunked SSD (Mamba-2) scan as a Pallas TPU kernel.

TPU adaptation of the SSD algorithm (arXiv:2405.21060 §6): the GPU version
leans on warp-level parallel prefix scans; on TPU we restructure the
computation around the MXU — each chunk is processed with dense
(chunk x chunk) and (chunk x state) matmuls, and the inter-chunk recurrence
is carried in a VMEM scratch accumulator across sequential grid steps
(the TPU grid is executed in order, which *is* the scan).

Grid: (B, H, num_chunks) — chunks innermost, so the state scratch carries
the running (P, N) state for one (batch, head) pair and is reset whenever a
new (b, h) pair begins.

Blocks (per grid step, all VMEM, f32):
  x   (Q, P)   Q = chunk (default 256, multiple of 8), P = headdim
  dt, cum (Q, 1) and cum (1, Q) — the chunk-local log-decay prefix sum,
      computed by XLA around the kernel
  B/C (Q, N)   group-mapped via the index_map (no repeat in HBM)
  L   (Q, Q)   intra-chunk decay matrix, built on the fly
  y   (Q, P)   output block
  state scratch (P, N)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, cc_ref, cr_ref, b_ref, c_ref, y_ref, st_ref):
    c_idx = pl.program_id(2)

    @pl.when(c_idx == 0)
    def _():
        st_ref[...] = jnp.zeros_like(st_ref)

    x = x_ref[0, 0, 0].astype(jnp.float32)  # (Q, P)
    dt = dt_ref[0, 0, 0]  # (Q, 1)
    cum = cc_ref[0, 0, 0]  # (Q, 1) inclusive log-decay prefix sum
    cum_row = cr_ref[0, 0, 0]  # (1, Q) the same, laid out along lanes
    Bm = b_ref[0, 0, 0].astype(jnp.float32)  # (Q, N)
    Cm = c_ref[0, 0, 0].astype(jnp.float32)  # (Q, N)

    Q = x.shape[0]
    # L[i, j] = exp(cum_i - cum_j) for i >= j else 0
    diff = cum - cum_row
    ii = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    # mask before exp (upper triangle would overflow; see models/ssm.py)
    Lmat = jnp.exp(jnp.where(ii >= jj, diff, -jnp.inf))

    xdt = x * dt  # (Q, P)

    # intra-chunk (dual / "attention" form): (C B^T . L) @ xdt  -> MXU matmuls
    scores = jax.lax.dot_general(
        Cm, Bm, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * Lmat
    y = jnp.dot(scores, xdt, preferred_element_type=jnp.float32)

    # inter-chunk: contribution of the carried state
    state = st_ref[...]  # (P, N)
    y += jax.lax.dot_general(
        Cm, state, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * jnp.exp(cum)

    # update the carried state: S <- exp(sum a) S + sum_j exp(cum_Q - cum_j) B_j xdt_j
    lane = jax.lax.broadcasted_iota(jnp.int32, cum_row.shape, 1)
    total = jnp.sum(jnp.where(lane == Q - 1, cum_row, 0.0), axis=1, keepdims=True)  # (1, 1)
    new_state = jax.lax.dot_general(
        xdt * jnp.exp(total - cum), Bm, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (P, N)
    st_ref[...] = state * jnp.exp(total) + new_state

    y_ref[0, 0, 0] = y.astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan_pallas(
    x: jnp.ndarray,  # (B, S, H, P)
    dt: jnp.ndarray,  # (B, S, H)
    A: jnp.ndarray,  # (H,)
    Bm: jnp.ndarray,  # (B, S, G, N)
    Cm: jnp.ndarray,  # (B, S, G, N)
    *,
    chunk: int = 256,
    interpret: bool,
):
    """Returns y (B, S, H, P) f32. (Final state is recoverable but not
    returned — training/prefill is the kernel's role; decode uses the O(1)
    recurrent step which needs no kernel.)"""
    Bsz, S, H, Pd = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    pad = (-S) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0), (0, 0)))
    Sp = S + pad
    nc = Sp // chunk

    # The chunk-local log-decay prefix sum is an XLA cumsum (Mosaic has no
    # cumsum); it goes in twice, as a (Q, 1) column and a (1, Q) lane row,
    # so the kernel builds the (Q, Q) decay matrix without a transpose.
    a = dt.astype(jnp.float32) * A.astype(jnp.float32)  # (B, S, H)
    cum = jnp.cumsum(a.reshape(Bsz, nc, chunk, H), axis=2).reshape(Bsz, Sp, H)

    # kernel layouts: x (B,H,nc,Q,P); dt/cum (B,H,nc,Q,1); cum row
    # (B,H,nc,1,Q); B/C (B,G,nc,Q,N)
    xk = x.transpose(0, 2, 1, 3).reshape(Bsz, H, nc, chunk, Pd)
    dtk = dt.astype(jnp.float32).transpose(0, 2, 1).reshape(Bsz, H, nc, chunk, 1)
    cck = cum.transpose(0, 2, 1).reshape(Bsz, H, nc, chunk, 1)
    crk = cum.transpose(0, 2, 1).reshape(Bsz, H, nc, 1, chunk)
    Bk = Bm.transpose(0, 2, 1, 3).reshape(Bsz, G, nc, chunk, N)
    Ck = Cm.transpose(0, 2, 1, 3).reshape(Bsz, G, nc, chunk, N)

    rep = H // G

    col = pl.BlockSpec((1, 1, 1, chunk, 1), lambda b, h, c: (b, h, c, 0, 0))
    y = pl.pallas_call(
        _ssd_kernel,
        grid=(Bsz, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, 1, chunk, Pd), lambda b, h, c: (b, h, c, 0, 0)),
            col,
            col,
            pl.BlockSpec((1, 1, 1, 1, chunk), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, chunk, N), lambda b, h, c: (b, h // rep, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, chunk, N), lambda b, h, c: (b, h // rep, c, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, chunk, Pd), lambda b, h, c: (b, h, c, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((Bsz, H, nc, chunk, Pd), jnp.float32),
        scratch_shapes=[pltpu.VMEM((Pd, N), jnp.float32)],
        interpret=interpret,
    )(xk, dtk, cck, crk, Bk, Ck)

    y = y.reshape(Bsz, H, Sp, Pd).transpose(0, 2, 1, 3)[:, :S]
    return y
