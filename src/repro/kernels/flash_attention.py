"""Flash attention (forward) Pallas TPU kernel with GQA, causal masking,
sliding window and logit softcap.

Layout: q (B, H, nq, Qb, D), k/v (B, K, nk, Kb, D); grid (B, H, nq, nk) with
the KV block index innermost — sequential on TPU, so the online-softmax
running state (m, l, acc) lives in VMEM scratch across KV steps. Block sizes
default to 512x512 (MXU-aligned; D is the lane dim and must be >= 128-friendly,
padded if needed by the wrapper).

Causal + window masks are computed from global positions reconstructed with
iota off the block indices — no mask tensors in HBM.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
    *, scale: float, causal: bool, softcap: float, window: int,
    block_q: int, block_kv: int, nk: int, kv_len: int,
):
    kv_idx = pl.program_id(3)
    q_idx = pl.program_id(2)

    @pl.when(kv_idx == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0, 0].astype(jnp.float32) * scale  # (Qb, D)
    k = k_ref[0, 0, 0].astype(jnp.float32)  # (Kb, D)
    v = v_ref[0, 0, 0].astype(jnp.float32)

    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # (Qb, Kb)
    if softcap:
        s = jnp.tanh(s / softcap) * softcap

    q_pos = q_idx * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    k_pos = kv_idx * block_kv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    valid = k_pos < kv_len
    if causal:
        rel = q_pos - k_pos
        valid &= rel >= 0
        if window:
            valid &= rel < window
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jnp.dot(
        p, v, preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new

    @pl.when(kv_idx == nk - 1)
    def _():
        o_ref[0, 0, 0] = (
            acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[:, None]
        ).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "softcap", "window", "block_q", "block_kv", "interpret"),
)
def flash_attention(
    q: jnp.ndarray,  # (B, Sq, H, D)
    k: jnp.ndarray,  # (B, Skv, K, D)
    v: jnp.ndarray,
    *,
    causal: bool = True,
    softcap: float = 0.0,
    window: int = 0,
    block_q: int = 512,
    block_kv: int = 512,
    interpret: bool,
) -> jnp.ndarray:
    B, Sq, H, D = q.shape
    _, Skv, K, _ = k.shape
    rep = H // K
    scale = 1.0 / math.sqrt(D)

    block_q = min(block_q, Sq)
    block_kv = min(block_kv, Skv)
    pad_q = (-Sq) % block_q
    pad_kv = (-Skv) % block_kv
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_kv:
        k = jnp.pad(k, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
    nq = (Sq + pad_q) // block_q
    nk = (Skv + pad_kv) // block_kv

    qk = q.transpose(0, 2, 1, 3).reshape(B, H, nq, block_q, D)
    kk = k.transpose(0, 2, 1, 3).reshape(B, K, nk, block_kv, D)
    vk = v.transpose(0, 2, 1, 3).reshape(B, K, nk, block_kv, D)

    out = pl.pallas_call(
        functools.partial(
            _flash_kernel,
            scale=scale, causal=causal, softcap=softcap, window=window,
            block_q=block_q, block_kv=block_kv, nk=nk, kv_len=Skv,
        ),
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0, 0)),
            pl.BlockSpec((1, 1, 1, block_kv, D), lambda b, h, i, j: (b, h // rep, j, 0, 0)),
            pl.BlockSpec((1, 1, 1, block_kv, D), lambda b, h, i, j: (b, h // rep, j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, nq, block_q, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        interpret=interpret,
    )(qk, kk, vk)
    out = out.reshape(B, H, Sq + pad_q, D).transpose(0, 2, 1, 3)[:, :Sq]
    return out
