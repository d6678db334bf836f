"""Pure-JAX model primitives shared by every architecture.

Parameters are nested dicts of ``jnp.ndarray`` (fp32 storage, bf16 compute
by default). Every layer has an ``init_*`` (returns the param pytree) and an
apply function. Sharding is expressed through *logical axis* constraints
(:func:`shard`) resolved against the active mesh by the launcher; with no
mesh active they are no-ops, so the same code runs single-device smoke tests
and 512-chip dry-runs.
"""
from __future__ import annotations

import math
from contextlib import contextmanager
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import AxisType, PartitionSpec as P

Params = Dict[str, Any]

# ---------------------------------------------------------------------------
# Logical-axis sharding hints
# ---------------------------------------------------------------------------

_AXIS_RULES: Dict[str, Any] = {}


@contextmanager
def axis_rules(rules: Dict[str, Any]):
    """Install logical-axis -> mesh-axis rules (used inside ``mesh`` scopes)."""
    global _AXIS_RULES
    old = _AXIS_RULES
    _AXIS_RULES = dict(rules)
    try:
        yield
    finally:
        _AXIS_RULES = old


def logical_to_spec(*names: Optional[str]) -> P:
    am = jax.sharding.get_abstract_mesh()
    # mesh axes GSPMD owns; with no mesh context every rule applies
    auto = None if am.empty else frozenset(
        n for n, t in zip(am.axis_names, am.axis_types) if t == AxisType.Auto
    )

    def resolve(n):
        if not n:
            return None
        ax = _AXIS_RULES.get(n)
        if ax is None:
            return None
        axes = (ax,) if isinstance(ax, str) else tuple(ax)
        if auto is not None:
            axes = tuple(a for a in axes if a in auto)
        if not axes:
            return None
        return axes[0] if len(axes) == 1 else axes

    return P(*[resolve(n) for n in names])


def shard(x: jnp.ndarray, *names: Optional[str]) -> jnp.ndarray:
    """Constrain ``x`` to the logical axes ``names`` (no-op without rules).

    Axis references that resolve to *manual* mesh axes (inside a shard_map
    region) are dropped — the manual axes already partition those dims.
    """
    if not _AXIS_RULES:
        return x
    spec = logical_to_spec(*names)
    if all(s is None for s in spec):
        return x
    return lax.with_sharding_constraint(x, spec)


# ---------------------------------------------------------------------------
# Initializers / numerics
# ---------------------------------------------------------------------------


def _dense_init(key, in_dim, out_dim, dtype) -> jnp.ndarray:
    scale = 1.0 / math.sqrt(in_dim)
    return (jax.random.normal(key, (in_dim, out_dim)) * scale).astype(dtype)


def softcap(x: jnp.ndarray, cap: float) -> jnp.ndarray:
    return jnp.tanh(x / cap) * cap if cap else x


def activation(x: jnp.ndarray, kind: str) -> jnp.ndarray:
    if kind == "silu":
        return jax.nn.silu(x)
    if kind == "gelu":
        return jax.nn.gelu(x)
    if kind == "relu":
        return jax.nn.relu(x)
    raise ValueError(f"unknown activation {kind}")


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def init_rmsnorm(dim: int, dtype=jnp.float32) -> Params:
    return {"scale": jnp.ones((dim,), dtype)}


def rmsnorm(params: Params, x: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)).astype(dt)


def init_layernorm(dim: int, dtype=jnp.float32) -> Params:
    return {"scale": jnp.ones((dim,), dtype), "bias": jnp.zeros((dim,), dtype)}


def layernorm(params: Params, x: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * lax.rsqrt(var + eps)
    return (y * params["scale"] + params["bias"]).astype(dt)


# ---------------------------------------------------------------------------
# Rotary position embeddings (GPT-NeoX half-rotation convention)
# ---------------------------------------------------------------------------


def rope_tables(positions: jnp.ndarray, head_dim: int, theta: float):
    """positions: (..., S) int32 -> (sin, cos) of shape (..., S, head_dim//2)."""
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    angles = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.sin(angles), jnp.cos(angles)


def apply_rope(x: jnp.ndarray, sin: jnp.ndarray, cos: jnp.ndarray) -> jnp.ndarray:
    """x: (B, S, H, D); sin/cos: (B, S, D/2) or (S, D/2)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    if sin.ndim == 2:  # (S, half) -> (1, S, half)
        sin, cos = sin[None], cos[None]
    sin, cos = sin[:, :, None, :], cos[:, :, None, :]  # insert head axis
    sin, cos = sin.astype(x.dtype), cos.astype(x.dtype)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


# ---------------------------------------------------------------------------
# Attention (GQA, optional sliding window / softcap / cross-attention),
# flash-style blockwise for long sequences, direct path for decode.
# ---------------------------------------------------------------------------


def init_attention(key, cfg, *, cross: bool = False) -> Params:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    h, k = cfg.num_heads, cfg.num_kv_heads
    pdt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 4)
    p = {
        "wq": _dense_init(ks[0], d, h * hd, pdt),
        "wk": _dense_init(ks[1], d, k * hd, pdt),
        "wv": _dense_init(ks[2], d, k * hd, pdt),
        "wo": _dense_init(ks[3], h * hd, d, pdt),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * hd,), pdt)
        p["bk"] = jnp.zeros((k * hd,), pdt)
        p["bv"] = jnp.zeros((k * hd,), pdt)
    return p


def _mask_value(dtype):
    return jnp.asarray(jnp.finfo(jnp.float32).min, jnp.float32)


def attend(
    q: jnp.ndarray,  # (B, Sq, H, D)
    k: jnp.ndarray,  # (B, Skv, K, D)
    v: jnp.ndarray,
    *,
    causal: bool,
    q_positions: jnp.ndarray,  # (Sq,) absolute positions of queries
    kv_positions: jnp.ndarray,  # (Skv,) absolute positions of keys (-1 = invalid)
    window: int = 0,
    softcap_val: float = 0.0,
    block_kv: int = 1024,
) -> jnp.ndarray:
    """Masked multi-head attention with GQA and online-softmax blocking.

    Query/key validity and locality are driven entirely by *positions*, which
    makes the same code path serve full causal attention, sliding windows,
    rolling decode caches and cross attention (``causal=False``).
    """
    B, Sq, H, D = q.shape
    _, Skv, K, _ = k.shape
    assert H % K == 0, (H, K)
    G = H // K
    qf = q.reshape(B, Sq, K, G, D).astype(jnp.float32) / math.sqrt(D)
    scale_dtype = jnp.float32

    def block(kb, vb, kpos):
        s = jnp.einsum("bqkgd,bskd->bkgqs", qf, kb.astype(jnp.float32))
        s = softcap(s, softcap_val)
        valid = (kpos >= 0)[None, None, None, None, :]
        if causal:
            rel = q_positions[:, None] - kpos[None, :]  # (Sq, Skv_b)
            ok = rel >= 0
            if window:
                ok &= rel < window
            valid = valid & ok[None, None, None, :, :]
        elif window:
            rel = jnp.abs(q_positions[:, None] - kpos[None, :])
            valid = valid & (rel < window)[None, None, None, :, :]
        return jnp.where(valid, s, _mask_value(scale_dtype)), vb

    if Skv <= block_kv:
        s, vb = block(k, v, kv_positions)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bkgqs,bskd->bqkgd", p, vb.astype(jnp.float32))
        return o.reshape(B, Sq, H, D).astype(q.dtype)

    # Online-softmax over kv blocks (flash-style; memory O(block)).
    nblocks = (Skv + block_kv - 1) // block_kv
    pad = nblocks * block_kv - Skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_positions = jnp.pad(kv_positions, (0, pad), constant_values=-1)
    kb = k.reshape(B, nblocks, block_kv, K, D).swapaxes(0, 1)
    vb = v.reshape(B, nblocks, block_kv, K, D).swapaxes(0, 1)
    pb = kv_positions.reshape(nblocks, block_kv)

    def step(carry, blk):
        m, l, acc = carry
        kb_i, vb_i, pos_i = blk
        s, vv = block(kb_i, vb_i, pos_i)  # (B,K,G,Sq,bkv)
        m_new = jnp.maximum(m, s.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * alpha + p.sum(axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bkgqs,bskd->bkgqd", p, vv.astype(jnp.float32)
        )
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, K, G, Sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, K, G, Sq), jnp.float32)
    a0 = jnp.zeros((B, K, G, Sq, D), jnp.float32)
    (m, l, acc), _ = lax.scan(step, (m0, l0, a0), (kb, vb, pb))
    o = acc / jnp.maximum(l, 1e-30)[..., None]
    o = o.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, D)
    return o.astype(q.dtype)


def attention_apply(
    params: Params,
    x: jnp.ndarray,  # (B, S, d)
    cfg,
    *,
    positions: jnp.ndarray,  # (S,) absolute positions of x
    causal: bool = True,
    window: int = 0,
    cache: Optional[Params] = None,  # decode: {"k","v"} rolling/absolute buffers
    cache_pos: Optional[jnp.ndarray] = None,  # scalar: current decode position
    cross_kv: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
) -> Tuple[jnp.ndarray, Optional[Params]]:
    B, S, d = x.shape
    hd, H, K = cfg.resolved_head_dim, cfg.num_heads, cfg.num_kv_heads
    dt = x.dtype

    q = (x @ params["wq"].astype(dt)).reshape(B, S, H, hd)
    if "bq" in params:
        q = q + params["bq"].astype(dt).reshape(H, hd)

    if cross_kv is not None:
        kx, vx = cross_kv  # precomputed encoder K/V: (B, Senc, K, hd)
        q = shard(q, "batch", None, "heads", None)
        o = attend(
            q, kx, vx,
            causal=False,
            q_positions=positions,
            kv_positions=jnp.arange(kx.shape[1]),
            softcap_val=cfg.attn_logit_softcap,
        )
        y = o.reshape(B, S, H * hd) @ params["wo"].astype(dt)
        return shard(y, "batch", "seq", "embed"), cache

    k = (x @ params["wk"].astype(dt)).reshape(B, S, K, hd)
    v = (x @ params["wv"].astype(dt)).reshape(B, S, K, hd)
    if "bk" in params:
        k = k + params["bk"].astype(dt).reshape(K, hd)
        v = v + params["bv"].astype(dt).reshape(K, hd)

    if cfg.rope_theta:
        sin, cos = rope_tables(positions, hd, cfg.rope_theta)
        q = apply_rope(q, sin, cos)
        k = apply_rope(k, sin, cos)

    q = shard(q, "batch", None, "heads", None)

    new_cache = None
    if cache is not None and S > 1:
        # Prefill: fill the cache with the whole prompt's K/V in one pass
        # and attend causally over the prompt itself.
        import numpy as np

        Sc = cache["k"].shape[1]
        kc, vc = k, v
        if Sc < S:  # rolling window cache: keep the last Sc tokens,
            # written at slot t % Sc so decode's rolling scheme continues.
            kc = kc[:, S - Sc :]
            vc = vc[:, S - Sc :]
            slots = np.array([(S - Sc + i) % Sc for i in range(Sc)])
            perm = np.argsort(slots)
            kc = kc[:, perm]
            vc = vc[:, perm]
            ck = kc.astype(cache["k"].dtype)
            cv = vc.astype(cache["v"].dtype)
        else:
            ck = lax.dynamic_update_slice(
                cache["k"], kc.astype(cache["k"].dtype), (0, 0, 0, 0)
            )
            cv = lax.dynamic_update_slice(
                cache["v"], vc.astype(cache["v"].dtype), (0, 0, 0, 0)
            )
        ck = shard(ck, "batch", "kv_seq", "kv_heads", None)
        cv = shard(cv, "batch", "kv_seq", "kv_heads", None)
        new_cache = {"k": ck, "v": cv}
        k = shard(k, "batch", None, "kv_heads", None)
        v = shard(v, "batch", None, "kv_heads", None)
        o = attend(
            q, k, v,
            causal=causal,
            q_positions=positions,
            kv_positions=positions,
            window=window,
            softcap_val=cfg.attn_logit_softcap,
        )
        y = o.reshape(B, S, H * hd) @ params["wo"].astype(dt)
        return shard(y, "batch", "seq", "embed"), new_cache

    if cache is not None:
        # Decode: write this step's K/V into the cache, attend over the cache.
        Sc = cache["k"].shape[1]
        if window and Sc == window:
            slot = (cache_pos % window).astype(jnp.int32)
            # slot j holds absolute position p - ((p - j) mod W)
            j = jnp.arange(Sc)
            kv_pos = cache_pos - ((cache_pos - j) % window)
        else:
            slot = cache_pos.astype(jnp.int32)
            j = jnp.arange(Sc)
            kv_pos = jnp.where(j <= cache_pos, j, -1)
        ck = lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype), (0, slot, 0, 0))
        cv = lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype), (0, slot, 0, 0))
        ck = shard(ck, "batch", "kv_seq", "kv_heads", None)
        cv = shard(cv, "batch", "kv_seq", "kv_heads", None)
        new_cache = {"k": ck, "v": cv}
        kv_pos = jnp.where(kv_pos >= 0, kv_pos, -1)
        o = attend(
            q, ck, cv,
            causal=True,
            q_positions=positions,
            kv_positions=kv_pos,
            window=window,
            softcap_val=cfg.attn_logit_softcap,
        )
    else:
        k = shard(k, "batch", None, "kv_heads", None)
        v = shard(v, "batch", None, "kv_heads", None)
        o = attend(
            q, k, v,
            causal=causal,
            q_positions=positions,
            kv_positions=positions,
            window=window,
            softcap_val=cfg.attn_logit_softcap,
        )

    y = o.reshape(B, S, H * hd) @ params["wo"].astype(dt)
    return shard(y, "batch", "seq", "embed"), new_cache


def init_decode_cache(cfg, batch: int, seq_len: int, layer_window: int, dtype) -> Params:
    """Cache buffers for one attention layer (rolling if windowed)."""
    size = min(seq_len, layer_window) if layer_window else seq_len
    shape = (batch, size, cfg.num_kv_heads, cfg.resolved_head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


# ---------------------------------------------------------------------------
# Dense MLP (SwiGLU / GeGLU)
# ---------------------------------------------------------------------------


def init_mlp(key, d: int, f: int, dtype) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": _dense_init(k1, d, f, dtype),
        "w_up": _dense_init(k2, d, f, dtype),
        "w_down": _dense_init(k3, f, d, dtype),
    }


def mlp_apply(params: Params, x: jnp.ndarray, act: str) -> jnp.ndarray:
    dt = x.dtype
    mid = (None,) * (x.ndim - 2)  # rank-agnostic: (B,S,d) or flat (T,d)
    g = activation(x @ params["w_gate"].astype(dt), act)
    u = x @ params["w_up"].astype(dt)
    h = shard(g * u, "batch", *mid, "ff")
    return shard(h @ params["w_down"].astype(dt), "batch", *mid, "embed")


# ---------------------------------------------------------------------------
# Mixture of Experts (token-choice top-k)
#
# Baseline path: dense einsum over the expert dimension (every expert sees
# every token, gates zero out unrouted pairs). Memory-bounded by scanning
# token chunks; expert dim shards over the `experts` logical axis. This is
# compile-robust and exactly matches the reference semantics; the
# capacity-based dispatch (`moe_dispatch="capacity"`) is the optimized path
# measured in EXPERIMENTS.md §Perf.
# ---------------------------------------------------------------------------


def init_moe(key, cfg) -> Params:
    d, f, E = cfg.d_model, cfg.d_ff, cfg.num_experts
    pdt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 5)
    p = {
        "router": _dense_init(ks[0], d, E, pdt),
        "w_gate": (jax.random.normal(ks[1], (E, d, f)) / math.sqrt(d)).astype(pdt),
        "w_up": (jax.random.normal(ks[2], (E, d, f)) / math.sqrt(d)).astype(pdt),
        "w_down": (jax.random.normal(ks[3], (E, f, d)) / math.sqrt(f)).astype(pdt),
    }
    if cfg.moe_shared_ff:
        p["shared"] = init_mlp(ks[4], d, cfg.moe_shared_ff, pdt)
    return p


def router_topk(logits: jnp.ndarray, k: int) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Return (dense_gates (T,E), aux_loss, raw probs)."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    vals, idx = lax.top_k(probs, k)
    vals = vals / jnp.maximum(vals.sum(-1, keepdims=True), 1e-9)
    onehot = jax.nn.one_hot(idx, probs.shape[-1], dtype=jnp.float32)  # (T,k,E)
    dense_gates = (onehot * vals[..., None]).sum(axis=-2)  # (T,E)
    # Switch-style load-balance loss.
    E = probs.shape[-1]
    frac_tokens = (onehot.sum(-2) > 0).astype(jnp.float32).mean(axis=0)
    frac_probs = probs.mean(axis=0)
    aux = E * jnp.sum(frac_tokens * frac_probs)
    return dense_gates, aux, probs


def moe_apply(
    params: Params,
    x: jnp.ndarray,  # (B, S, d)
    cfg,
    *,
    dispatch: str = "dense",
    token_chunk: int = 4096,
    capacity_factor: float = 1.25,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (y, aux_loss)."""
    B, S, d = x.shape
    dt = x.dtype
    T = B * S
    xt = x.reshape(T, d)
    logits = xt @ params["router"].astype(dt)  # (T, E)
    gates, aux, _ = router_topk(logits, cfg.experts_per_token)
    gates = gates.astype(dt)

    wg = params["w_gate"].astype(dt)
    wu = params["w_up"].astype(dt)
    wd = params["w_down"].astype(dt)

    if dispatch == "dense":
        nchunks = max(1, T // max(token_chunk, 1)) if T > token_chunk else 1
        while T % nchunks:
            nchunks -= 1
        xc = xt.reshape(nchunks, T // nchunks, d)
        gc = gates.reshape(nchunks, T // nchunks, -1)

        def chunk_fn(carry, inp):
            xi, gi = inp  # (Tc, d), (Tc, E)
            h1 = jnp.einsum("td,edf->etf", xi, wg)
            h2 = jnp.einsum("td,edf->etf", xi, wu)
            h = activation(h1, cfg.act) * h2
            h = shard(h, "experts", None, None)
            yi = jnp.einsum("etf,efd,te->td", h, wd, gi)
            return carry, yi

        _, yc = lax.scan(chunk_fn, 0, (xc, gc))
        y = yc.reshape(T, d)
    elif dispatch == "capacity":
        y = _moe_capacity(xt, gates, wg, wu, wd, cfg, capacity_factor)
    else:
        raise ValueError(f"unknown moe dispatch {dispatch!r}")

    if "shared" in params:
        y = y + mlp_apply(params["shared"], xt, cfg.act)
    return shard(y.reshape(B, S, d), "batch", "seq", "embed"), aux.astype(jnp.float32)


def _moe_capacity(xt, gates, wg, wu, wd, cfg, capacity_factor) -> jnp.ndarray:
    """Capacity-based gather/scatter dispatch: compute only routed tokens.

    Each (token, expert) pair with a non-zero gate is assigned a slot in the
    expert's buffer (capacity C ~= k*T/E * factor); overflow tokens are
    dropped (standard token-choice capacity semantics).
    """
    T, E = gates.shape
    k = cfg.experts_per_token
    C = max(int(math.ceil(k * T / E * capacity_factor)), 1)
    routed = gates > 0  # (T, E)
    # slot index = exclusive cumsum of routed within each expert column
    pos = jnp.cumsum(routed.astype(jnp.int32), axis=0) - 1  # (T, E)
    keep = routed & (pos < C)
    # Build (E, C) gather indices: token index occupying each slot.
    slot_token = jnp.zeros((E, C), jnp.int32)
    t_idx = jnp.broadcast_to(jnp.arange(T)[:, None], (T, E))
    flat_dest = jnp.where(keep, jnp.arange(E)[None, :] * C + pos, E * C)
    slot_token = (
        jnp.zeros((E * C + 1,), jnp.int32)
        .at[flat_dest.reshape(-1)]
        .max(t_idx.reshape(-1))[: E * C]
        .reshape(E, C)
    )
    occupied = (
        jnp.zeros((E * C + 1,), jnp.bool_)
        .at[flat_dest.reshape(-1)]
        .max(keep.reshape(-1))[: E * C]
        .reshape(E, C)
    )
    xe = jnp.take(xt, slot_token, axis=0)  # (E, C, d)
    xe = jnp.where(occupied[..., None], xe, 0)
    xe = shard(xe, "experts", None, None)
    h = activation(jnp.einsum("ecd,edf->ecf", xe, wg), cfg.act) * jnp.einsum(
        "ecd,edf->ecf", xe, wu
    )
    h = shard(h, "experts", None, None)
    ye = jnp.einsum("ecf,efd->ecd", h, wd)  # (E, C, d)
    g = gates[slot_token, jnp.arange(E)[:, None]]  # (E, C)
    ye = ye * (g * occupied)[..., None]
    y = jnp.zeros_like(xt).at[slot_token.reshape(-1)].add(ye.reshape(E * C, -1))
    return y
