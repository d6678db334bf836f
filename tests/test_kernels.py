"""Per-kernel validation: shape/dtype sweeps, assert_allclose vs ref.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, qsgd
from repro.kernels.flash_attention import flash_attention
from repro.kernels.qsgd import qsgd_dequantize, qsgd_quantize
from repro.kernels.ref import (
    attention_ref,
    qsgd_dequantize_ref,
    qsgd_quantize_ref,
    ssd_scan_ref,
)
from repro.kernels.ssd_scan import ssd_scan_pallas


# ---------------------------------------------------------------------------
# QSGD
# ---------------------------------------------------------------------------

# rows per grid step at bucket 512 (800 and 1,344)
QUANT_TILE = qsgd.tile_rows(10**6, 512, (4, 4, 1), 1)
DEQUANT_TILE = qsgd.tile_rows(10**6, 512, (1, 4), 1)
QUANT_CASES = [
    (nb, bucket, s)
    for s in (1, 15, 127) for bucket in (128, 256, 2048) for nb in (1, 7, 8, 33)
] + [  # a ragged last block at the tiles of bucket 512
    (nb, 512, s)
    for s in (15, 127)
    for nb in (QUANT_TILE + 1, 2 * QUANT_TILE - 1, DEQUANT_TILE + 1)
]


@pytest.mark.parametrize(
    "nb,bucket,s",
    [pytest.param(nb, b, s, id=f"{s}-{b}-{nb}") for nb, b, s in QUANT_CASES],
)
def test_qsgd_quantize_matches_ref(nb, bucket, s):
    key = jax.random.PRNGKey(nb * 1000 + bucket + s)
    x = jax.random.normal(key, (nb, bucket)) * 3.0
    u = jax.random.uniform(jax.random.fold_in(key, 1), (nb, bucket))
    lev_k, nrm_k = qsgd_quantize(x, u, s, interpret=True)
    lev_r, nrm_r = qsgd_quantize_ref(x, u, s)
    np.testing.assert_array_equal(np.asarray(lev_k), np.asarray(lev_r))
    np.testing.assert_allclose(np.asarray(nrm_k), np.asarray(nrm_r), rtol=1e-6)
    dq_k = qsgd_dequantize(lev_k, nrm_k, s, interpret=True)
    dq_r = qsgd_dequantize_ref(lev_r, nrm_r, s)
    np.testing.assert_allclose(np.asarray(dq_k), np.asarray(dq_r), rtol=1e-6)


def _kernel_outputs(nb, bucket, P):
    key = jax.random.PRNGKey(nb + P)
    x = jax.random.normal(key, (nb, bucket)) * 3.0
    u = jax.random.uniform(jax.random.fold_in(key, 1), (nb, bucket))
    lev, nrm = qsgd.qsgd_quantize.__wrapped__(x, u, 127, interpret=True)
    banks = jax.random.randint(jax.random.fold_in(key, 2), (P, nb, bucket), -127, 128, jnp.int8)
    bank_nrm = jax.random.uniform(jax.random.fold_in(key, 3), (P, nb), jnp.float32, 0.1, 2.0)
    w = jax.random.uniform(jax.random.fold_in(key, 4), (P,), jnp.float32)
    return (
        lev, nrm,
        qsgd.qsgd_dequantize.__wrapped__(lev, nrm, 127, interpret=True),
        qsgd.qsgd_dequant_reduce.__wrapped__(banks, bank_nrm, w, 127, interpret=True),
    )


@pytest.mark.parametrize("nb,P", [(801, 1), (1345, 4)])
def test_qsgd_kernels_bit_identical_to_32_row_blocks(monkeypatch, nb, P):
    """The block height enters no arithmetic: every output of the three
    kernels equals, bit for bit, what 32-row blocks compute."""
    tiled = _kernel_outputs(nb, 512, P)
    monkeypatch.setattr(qsgd, "tile_rows", lambda *a: qsgd.ROW_ALIGN)
    rows32 = _kernel_outputs(nb, 512, P)
    for got, want in zip(tiled, rows32):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _vgg11_leaf_sizes():
    from repro.configs import get_config
    from repro.models.cnn import init_cnn

    shapes = jax.eval_shape(lambda k: init_cnn(k, get_config("vgg11")), jax.random.PRNGKey(0))
    return [int(np.prod(l.shape)) for l in jax.tree.leaves(shapes)]


def _kernel_blocks(P):
    """Each kernel's (itemsizes, norm columns), as its entry sizes its blocks."""
    return {
        "quantize": ((4, 4, 1), 1),
        "dequantize": ((1, 4), 1),
        "dequant_reduce": ((1,) * P + (4,), P),
    }


@pytest.mark.parametrize("P", [1, 4])
def test_qsgd_tile_rows_on_vgg11_leaves(P):
    sizes = _vgg11_leaf_sizes()
    assert len(sizes) == 22 and sum(sizes) == 28_144_010
    nbs = [-(-n // 512) for n in sizes]
    assert sum(-(-nb // 32) for nb in nbs) == 1730  # grid steps of 32-row blocks
    for name, (items, cols) in _kernel_blocks(P).items():
        steps = 0
        for nb in nbs:
            rows = qsgd.tile_rows(nb, 512, items, cols)
            assert rows % 32 == 0 and 32 <= rows <= -(-nb // 32) * 32, (name, nb, rows)
            steps += -(-nb // rows)
        assert steps <= 130, (name, steps)


@pytest.mark.parametrize("bucket", [128, 512, 2048])
@pytest.mark.parametrize("P", [1, 4, 16])
@pytest.mark.parametrize("nb", [1, 33, 8192, 32768])
def test_qsgd_tile_rows_fit_vmem_budget(bucket, P, nb):
    cap = -(-nb // 32) * 32
    for name, (items, cols) in _kernel_blocks(P).items():
        rows = qsgd.tile_rows(nb, bucket, items, cols)
        # every block twice (double-buffered); a norm column fills 128 f32 lanes
        step_bytes = 2 * (bucket * sum(items) + cols * 128 * 4)
        assert rows * step_bytes <= qsgd.VMEM_BUDGET, (name, rows)
        assert rows % 32 == 0 and rows <= cap, (name, rows)
        # the largest such: 32 rows more would overflow the budget or pass nb
        assert (rows + 32) * step_bytes > qsgd.VMEM_BUDGET or rows == cap, (name, rows)


def test_qsgd_zero_bucket():
    x = jnp.zeros((4, 128))
    u = jnp.full((4, 128), 0.5)
    lev, nrm = qsgd_quantize(x, u, 15, interpret=True)
    assert np.all(np.asarray(lev) == 0)
    dq = qsgd_dequantize(lev, nrm, 15, interpret=True)
    assert np.all(np.asarray(dq) == 0)


# ---------------------------------------------------------------------------
# SSD scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize(
    "B,S,H,P,G,N,chunk",
    [
        (1, 32, 2, 16, 1, 8, 16),
        (2, 96, 4, 32, 2, 16, 32),
        (2, 64, 4, 64, 1, 32, 64),  # single chunk
        (1, 80, 8, 32, 4, 16, 32),  # padded last chunk
    ],
)
def test_ssd_kernel_matches_ref(B, S, H, P, G, N, chunk):
    key = jax.random.PRNGKey(B * S + H)
    x = jax.random.normal(key, (B, S, H, P)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 1), (B, S, H))) * 0.2
    A = -jnp.exp(jax.random.normal(jax.random.fold_in(key, 2), (H,)) * 0.3)
    Bm = jax.random.normal(jax.random.fold_in(key, 3), (B, S, G, N)) * 0.3
    Cm = jax.random.normal(jax.random.fold_in(key, 4), (B, S, G, N)) * 0.3
    y_ref, _ = ssd_scan_ref(x, dt, A, Bm, Cm)
    y_k = ssd_scan_pallas(x, dt, A, Bm, Cm, chunk=chunk, interpret=True)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_ref), atol=2e-5, rtol=2e-4)


def test_ssd_kernel_bf16_inputs():
    B, S, H, P, G, N = 1, 64, 2, 32, 1, 16
    key = jax.random.PRNGKey(0)
    x = (jax.random.normal(key, (B, S, H, P)) * 0.5).astype(jnp.bfloat16)
    dt = jax.nn.softplus(jax.random.normal(key, (B, S, H))) * 0.2
    A = -jnp.exp(jnp.zeros((H,)))
    Bm = (jax.random.normal(key, (B, S, G, N)) * 0.3).astype(jnp.bfloat16)
    Cm = (jax.random.normal(key, (B, S, G, N)) * 0.3).astype(jnp.bfloat16)
    y_ref, _ = ssd_scan_ref(x, dt, A, Bm, Cm)
    y_k = ssd_scan_pallas(x, dt, A, Bm, Cm, chunk=32, interpret=True)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_ref), atol=3e-2, rtol=3e-2)


# ---------------------------------------------------------------------------
# Flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize(
    "B,S,H,K,D,softcap,window,bq,bkv",
    [
        (2, 64, 4, 2, 32, 0.0, 0, 32, 32),
        (1, 128, 4, 4, 64, 50.0, 0, 64, 32),
        (2, 96, 8, 2, 32, 0.0, 32, 32, 32),   # sliding window
        (1, 100, 4, 1, 32, 0.0, 0, 32, 32),   # padded seq (100 % 32 != 0)
        (1, 64, 8, 8, 128, 0.0, 0, 64, 64),   # MHA, lane-sized head_dim
    ],
)
def test_flash_attention_matches_ref(B, S, H, K, D, softcap, window, bq, bkv):
    key = jax.random.PRNGKey(S + H + D)
    q = jax.random.normal(key, (B, S, H, D)) * 0.5
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, K, D)) * 0.5
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, K, D)) * 0.5
    o_k = flash_attention(
        q, k, v, causal=True, softcap=softcap, window=window, block_q=bq, block_kv=bkv,
        interpret=True,
    )
    o_r = attention_ref(q, k, v, causal=True, softcap=softcap, window=window)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r), atol=2e-5, rtol=2e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(dtype):
    B, S, H, K, D = 1, 64, 4, 2, 32
    key = jax.random.PRNGKey(3)
    q = (jax.random.normal(key, (B, S, H, D)) * 0.5).astype(dtype)
    k = (jax.random.normal(jax.random.fold_in(key, 1), (B, S, K, D)) * 0.5).astype(dtype)
    v = (jax.random.normal(jax.random.fold_in(key, 2), (B, S, K, D)) * 0.5).astype(dtype)
    o_k = flash_attention(q, k, v, block_q=32, block_kv=32, interpret=True)
    assert o_k.dtype == dtype
    o_r = attention_ref(q, k, v)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-4
    np.testing.assert_allclose(
        np.asarray(o_k, np.float32), np.asarray(o_r), atol=tol, rtol=tol
    )


# ---------------------------------------------------------------------------
# ops wrappers
# ---------------------------------------------------------------------------

def test_ops_default_interpret_on_cpu():
    assert ops.default_interpret() is True
