"""Interpret-mode parity suite for the fused compressed-exchange kernels.

Covers the PR's kernel surface against the ``kernels/ref.py`` oracles:
fused decode-dequantize-reduce (qsgd), topk select+pack and the fused
scatter-accumulate decoder — plus the impl-routing regression (the device
``combine`` must actually take the kernel path when ``impl="kernel"``),
packed-wire-format accounting asserts, and the EF-SGD convergence /
equivalence rails on the host cluster and the 4-device mesh.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import compression as C
from repro.core.compression import QSGDConfig
from repro.core.exchange import ExchangeContext, get_exchange
from repro.kernels import ops as kops
from repro.kernels import ref as kref

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


# ---------------------------------------------------------------------------
# fused decode-dequantize-reduce vs the unfused oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("P", [1, 2, 4])
# the last three leave a ragged last block at bucket 512's tiles of
# 672 (P = 4), 1024 (P = 2) and 1344 (P = 1) rows
@pytest.mark.parametrize(
    "nb,bucket",
    [(1, 128), (5, 256), (8, 128), (13, 512), (673, 512), (1343, 512), (1345, 512)],
)
@pytest.mark.parametrize("s", [3, 127])
def test_dequant_reduce_matches_unfused_ref(P, nb, bucket, s):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(nb * 1000 + bucket + s), 3)
    lev = jax.random.randint(k1, (P, nb, bucket), -s, s + 1, jnp.int8)
    nrm = jax.random.uniform(k2, (P, nb), jnp.float32, 0.1, 2.0)
    w = jax.random.uniform(k3, (P,), jnp.float32)
    got = kops.qsgd_dequant_reduce(lev, nrm, w, s)
    want = kref.qsgd_dequant_reduce_ref(lev, nrm, w, s)
    assert got.shape == (nb, bucket)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-6
    )


def test_dequant_reduce_uniform_weights_is_mean_of_dequant():
    P, nb, bucket, s = 4, 6, 128, 7
    lev = jax.random.randint(jax.random.PRNGKey(0), (P, nb, bucket), -s, s + 1, jnp.int8)
    nrm = jax.random.uniform(jax.random.PRNGKey(1), (P, nb), jnp.float32, 0.1, 1.0)
    w = jnp.full((P,), 1.0 / P, jnp.float32)
    fused = kops.qsgd_dequant_reduce(lev, nrm, w, s)
    unfused = jnp.stack(
        [C.qsgd_dequantize_ref(lev[p], nrm[p], s) for p in range(P)]
    ).mean(axis=0)
    np.testing.assert_allclose(
        np.asarray(fused), np.asarray(unfused), rtol=1e-6, atol=1e-6
    )


def test_compression_dequant_reduce_routes_impl():
    """C.dequant_reduce(impl="kernel") must call the Pallas wrapper."""
    P, nb, bucket, s = 2, 4, 128, 15
    lev = jax.random.randint(jax.random.PRNGKey(2), (P, nb, bucket), -s, s + 1, jnp.int8)
    nrm = jnp.ones((P, nb), jnp.float32)
    w = jnp.full((P,), 0.5, jnp.float32)
    calls = []
    orig = kops.qsgd_dequant_reduce
    kops.qsgd_dequant_reduce = lambda *a, **k: (calls.append(1), orig(*a, **k))[1]
    try:
        out_k = C.dequant_reduce(lev, nrm, w, QSGDConfig(levels=s, impl="kernel"))
        assert calls, "impl='kernel' did not reach the Pallas wrapper"
        out_j = C.dequant_reduce(lev, nrm, w, QSGDConfig(levels=s, impl="jnp"))
        assert len(calls) == 1, "impl='jnp' must NOT take the kernel path"
    finally:
        kops.qsgd_dequant_reduce = orig
    np.testing.assert_allclose(
        np.asarray(out_k), np.asarray(out_j), rtol=1e-6, atol=1e-6
    )


# ---------------------------------------------------------------------------
# topk select+pack / scatter-accumulate vs the oracles
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "n,k",
    [(7, 1), (128, 128), (129, 4), (513, 5), (1000, 10), (4096, 1), (300, 300)],
)
def test_topk_select_pack_matches_lax_top_k(n, k):
    x = jax.random.normal(jax.random.PRNGKey(n * 7 + k), (n,), jnp.float32)
    v, i = kops.topk_select_pack(x, k)
    rv, ri = kref.topk_select_ref(x, k)
    # Same selected index SET (order may differ) and values = x at indices.
    assert set(np.asarray(i).tolist()) == set(np.asarray(ri).tolist())
    np.testing.assert_array_equal(np.asarray(v), np.asarray(x)[np.asarray(i)])
    assert i.dtype == jnp.int32 and v.dtype == jnp.float32


def test_topk_select_pack_exact_k_under_ties():
    # all-equal magnitudes: the two-tier threshold must still emit exactly
    # k unique indices with the tied value
    for x, k in [(jnp.ones((300,)), 7), (jnp.zeros((64,)), 5),
                 (-jnp.ones((200,)) * 2.5, 3)]:
        v, i = kops.topk_select_pack(x, k)
        idx = np.asarray(i).tolist()
        assert len(set(idx)) == k
        np.testing.assert_array_equal(np.asarray(v), np.asarray(x)[idx])


@pytest.mark.parametrize("P,k,n", [(1, 1, 1), (2, 9, 200), (4, 33, 1000)])
def test_topk_scatter_accum_matches_ref(P, k, n):
    vals = jax.random.normal(jax.random.PRNGKey(P), (P, k), jnp.float32)
    idx = jax.random.randint(jax.random.PRNGKey(k), (P, k), 0, n, jnp.int32)
    w = jax.random.uniform(jax.random.PRNGKey(n), (P,), jnp.float32)
    got = kops.topk_scatter_accum(vals, idx, w, n)
    want = kref.topk_scatter_ref(vals, idx, w, n)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-6)


def test_topk_select_scatter_roundtrip_is_projection():
    """scatter(select(x)) == x masked to its top-k coordinates."""
    n, k = 777, 31
    x = jax.random.normal(jax.random.PRNGKey(5), (n,), jnp.float32)
    v, i = kops.topk_select_pack(x, k)
    dense = kops.topk_scatter_accum(v[None], i[None], jnp.ones((1,)), n)
    rv, ri = kref.topk_select_ref(x, k)
    ref_dense = np.zeros((n,), np.float32)
    ref_dense[np.asarray(ri)] = np.asarray(rv)
    np.testing.assert_allclose(np.asarray(dense), ref_dense, rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# seeded shape sweeps: arbitrary lengths incl. non-multiple-of-bucket sizes
# (deterministic stand-in for the hypothesis property tests — hypothesis is
# an optional dependency here, same as tests/test_compression.py)
# ---------------------------------------------------------------------------

_SWEEP = [
    # (n, bucket, s, P) — n deliberately NOT a multiple of bucket except one
    (1, 128, 3, 1),
    (97, 128, 15, 2),
    (128, 128, 127, 4),
    (200, 256, 3, 3),
    (511, 256, 127, 2),
    (513, 512, 15, 4),
    (700, 512, 3, 1),
]


@pytest.mark.parametrize("n,bucket,s,P", _SWEEP)
def test_fused_decode_matches_host_codec_sweep(n, bucket, s, P):
    """Quantize an arbitrary-length (non-multiple-of-bucket) vector per
    peer, then: fused kernel reduce == mean of per-peer host dequantize."""
    cfg = QSGDConfig(levels=s, bucket=bucket, impl="jnp")
    x = jax.random.normal(jax.random.PRNGKey(n * 31 + bucket + P), (P, n))
    payloads = [
        C.quantize(x[p], jax.random.PRNGKey(p), cfg) for p in range(P)
    ]
    lev = jnp.stack([p["levels"] for p in payloads])  # (P, nb, bucket)
    nrm = jnp.stack([p["norms"] for p in payloads])
    w = jnp.full((P,), 1.0 / P, jnp.float32)
    fused = kops.qsgd_dequant_reduce(lev, nrm, w, s).reshape(-1)[:n]
    unfused = jnp.stack(
        [C.dequantize(p, cfg) for p in payloads]
    ).mean(axis=0)
    np.testing.assert_allclose(
        np.asarray(fused), np.asarray(unfused), rtol=1e-5, atol=1e-5
    )


@pytest.mark.parametrize("n", [2, 13, 128, 129, 500, 900])
@pytest.mark.parametrize("frac", [1e-3, 0.01, 0.1, 1.0])
def test_topk_kernel_selects_same_set_sweep(n, frac):
    k = max(1, min(n, int(round(n * frac))))
    x = jax.random.normal(jax.random.PRNGKey(n), (n,), jnp.float32)
    v, i = kops.topk_select_pack(x, k)
    rv, ri = kref.topk_select_ref(x, k)
    assert set(np.asarray(i).tolist()) == set(np.asarray(ri).tolist())
    assert float(jnp.abs(v).min()) >= float(jnp.abs(rv).min()) - 1e-6


# ---------------------------------------------------------------------------
# satellite 1: device combine must route QSGDConfig.impl / ctx.topk_impl
# ---------------------------------------------------------------------------


def _vmap_combine(proto, ctx, grads, key=None):
    """Run a device combine under vmap-with-axis-name (a cheap stand-in
    for the shard_map manual region: all_gather/axis_index resolve)."""

    def body(g):
        avg, _ = proto.combine(g, ctx, key=key)
        return avg

    return jax.vmap(body, axis_name="data")(grads)


def test_qsgd_device_combine_takes_kernel_path():
    """Regression (PR-7 satellite): combine() ignored QSGDConfig.impl and
    always dequantized through the jnp ref. Assert the Pallas wrappers are
    reached when impl='kernel' — for encode AND the fused decode-reduce."""
    P = 4
    grads = {"w": jax.random.normal(jax.random.PRNGKey(0), (P, 2, 200))}
    proto = get_exchange("qsgd")
    calls = {"quant": 0, "reduce": 0}
    oq, orr = kops.qsgd_quantize, kops.qsgd_dequant_reduce

    def cq(*a, **k):
        calls["quant"] += 1
        return oq(*a, **k)

    def cr(*a, **k):
        calls["reduce"] += 1
        return orr(*a, **k)

    kops.qsgd_quantize, kops.qsgd_dequant_reduce = cq, cr
    try:
        ctx = ExchangeContext(
            axis="data", num_peers=P,
            qsgd=QSGDConfig(levels=7, bucket=128, impl="kernel"),
        )
        out_k = _vmap_combine(proto, ctx, grads, key=jax.random.PRNGKey(3))
        assert calls["quant"] >= 1, "impl='kernel' quantize not routed"
        assert calls["reduce"] >= 1, "impl='kernel' fused decode not routed"
        calls["quant"] = calls["reduce"] = 0
        ctx_j = ExchangeContext(
            axis="data", num_peers=P,
            qsgd=QSGDConfig(levels=7, bucket=128, impl="jnp"),
        )
        out_j = _vmap_combine(proto, ctx_j, grads, key=jax.random.PRNGKey(3))
        assert calls["quant"] == 0 and calls["reduce"] == 0
    finally:
        kops.qsgd_quantize, kops.qsgd_dequant_reduce = oq, orr
    # same key -> identical stochastic rounding -> paths agree to float eps
    np.testing.assert_allclose(
        np.asarray(out_k["w"]), np.asarray(out_j["w"]), rtol=1e-6, atol=1e-6
    )


def test_topk_device_combine_takes_kernel_path():
    P = 2
    grads = {"w": jax.random.normal(jax.random.PRNGKey(1), (P, 300))}
    proto = get_exchange("topk")
    calls = {"sel": 0, "scat": 0}
    osel, oscat = kops.topk_select_pack, kops.topk_scatter_accum

    def cs(*a, **k):
        calls["sel"] += 1
        return osel(*a, **k)

    def cc(*a, **k):
        calls["scat"] += 1
        return oscat(*a, **k)

    kops.topk_select_pack, kops.topk_scatter_accum = cs, cc
    try:
        ctx = ExchangeContext(
            axis="data", num_peers=P, topk_frac=0.05, topk_impl="kernel"
        )
        out_k = _vmap_combine(proto, ctx, grads)
        assert calls["sel"] >= 1 and calls["scat"] >= 1
        calls["sel"] = calls["scat"] = 0
        ctx_j = ExchangeContext(
            axis="data", num_peers=P, topk_frac=0.05, topk_impl="jnp"
        )
        out_j = _vmap_combine(proto, ctx_j, grads)
        assert calls["sel"] == 0 and calls["scat"] == 0
    finally:
        kops.topk_select_pack, kops.topk_scatter_accum = osel, oscat
    np.testing.assert_allclose(
        np.asarray(out_k["w"]), np.asarray(out_j["w"]), rtol=1e-6, atol=1e-6
    )


# ---------------------------------------------------------------------------
# satellite 2: wire accounting == the encoded payload's actual nbytes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shape", [(3, 33), (1000,), (7, 11, 13)])
@pytest.mark.parametrize("impl", ["jnp", "kernel"])
def test_qsgd_wire_bytes_match_encoded_payload(shape, impl):
    grads = {"w": jax.random.normal(jax.random.PRNGKey(0), shape)}
    cfg = QSGDConfig(levels=7, bucket=128, impl=impl)
    ctx = ExchangeContext(num_peers=4, qsgd=cfg)
    proto = get_exchange("qsgd")
    payload, nbytes = proto.host_encode(grads, ctx, key=jax.random.PRNGKey(1))
    # actual packed wire format: int8 level banks + fp32 bucket norms
    actual = int(payload["w"]["levels"].nbytes + payload["w"]["norms"].nbytes)
    assert payload["w"]["levels"].dtype == jnp.int8
    assert payload["w"]["norms"].dtype == jnp.float32
    assert nbytes == actual
    assert proto.wire_bytes_per_edge(grads, ctx) == actual
    # roundtrip: decode reproduces the leaf shape
    dec = proto.host_decode(payload, grads, ctx)
    assert dec["w"].shape == shape


@pytest.mark.parametrize("wire_dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("impl", ["jnp", "kernel"])
def test_topk_wire_bytes_match_encoded_payload(wire_dtype, impl):
    grads = {"w": jax.random.normal(jax.random.PRNGKey(0), (3, 77)),
             "b": jax.random.normal(jax.random.PRNGKey(1), (13,))}
    ctx = ExchangeContext(
        num_peers=4, topk_frac=0.1, topk_impl=impl, wire_dtype=wire_dtype
    )
    proto = get_exchange("topk")
    payload, nbytes = proto.host_encode(grads, ctx)
    # actual packed wire format: wire-dtype values + int32 index pairs
    actual = sum(
        int(p["values"].nbytes + p["idx"].nbytes)
        for p in jax.tree.leaves(
            payload, is_leaf=lambda x: isinstance(x, dict) and "values" in x
        )
    )
    for p in jax.tree.leaves(
        payload, is_leaf=lambda x: isinstance(x, dict) and "values" in x
    ):
        assert p["idx"].dtype == jnp.int32
        assert p["values"].dtype == wire_dtype
    assert nbytes == actual
    assert proto.wire_bytes_per_edge(grads, ctx) == actual
    dec = proto.host_decode(payload, grads, ctx)
    assert dec["w"].shape == (3, 77) and dec["b"].shape == (13,)


def test_qsgd_wire_bytes_le_30pct_of_raw():
    grads = {"w": jnp.zeros((64, 64)), "b": jnp.zeros((100,))}
    raw = sum(x.size * 4 for x in jax.tree.leaves(grads))
    q = get_exchange("qsgd").wire_bytes_per_edge(
        grads, ExchangeContext(num_peers=4, qsgd=QSGDConfig(levels=3, bucket=512))
    )
    t = get_exchange("topk").wire_bytes_per_edge(
        grads, ExchangeContext(num_peers=4, topk_frac=1e-3)
    )
    assert q <= 0.30 * raw
    assert t <= 0.30 * raw


# ---------------------------------------------------------------------------
# EF-SGD: equivalence + convergence rails
# ---------------------------------------------------------------------------


def test_combine_ef_lossless_residual_is_zero():
    """For a lossless protocol the local image IS the gradient, so the
    EF residual stays identically zero (the no-regression rail)."""
    P = 2
    grads = {"w": jax.random.normal(jax.random.PRNGKey(0), (P, 64))}
    proto = get_exchange("allgather_mean")
    ctx = ExchangeContext(axis="data", num_peers=P)

    def body(g):
        avg, local, _ = proto.combine_ef(g, ctx)
        res = jax.tree.map(lambda a, b: a - b, g, local)
        return avg, res

    avg, res = jax.vmap(body, axis_name="data")(grads)
    np.testing.assert_array_equal(np.asarray(res["w"]), 0.0)
    np.testing.assert_allclose(
        np.asarray(avg["w"][0]), np.asarray(grads["w"]).mean(0), rtol=1e-6
    )


def test_combine_ef_qsgd_local_image_is_own_decode():
    P = 2
    s, bucket = 7, 128
    grads = {"w": jax.random.normal(jax.random.PRNGKey(0), (P, 200))}
    cfg = QSGDConfig(levels=s, bucket=bucket)
    proto = get_exchange("qsgd")
    ctx = ExchangeContext(axis="data", num_peers=P, qsgd=cfg)
    key = jax.random.PRNGKey(9)

    def body(g):
        _, local, _ = proto.combine_ef(g, ctx, key=key)
        return local

    local = jax.vmap(body, axis_name="data")(grads)
    # re-derive each peer's decode with the same per-peer folded key
    for r in range(P):
        kr = jax.random.fold_in(key, r)
        (leafkey,) = jax.random.split(kr, 1)
        payload = C.quantize(grads["w"][r], leafkey, cfg)
        np.testing.assert_allclose(
            np.asarray(local["w"][r]),
            np.asarray(C.dequantize(payload, cfg)),
            rtol=1e-6, atol=1e-6,
        )


@pytest.mark.slow
def test_ef_convergence_device_path():
    """EF-SGD retains convergence at the aggressive settings on the
    device exchange path (every contribution compressed — the semantics
    ``build_p2p_train_step`` runs on the mesh), on a seeded least-squares
    problem:

      * top-k frac=1e-3 (k=1 of 512, a contractive but biased
        sparsifier) STALLS without EF and converges >= 10x lower with it;
      * qsgd levels=3 is UNBIASED and converges without EF — which is
        why no EF-beats-no-EF claim exists for qsgd: aggressive qsgd is
        also non-contractive (noise ~ sqrt(bucket)/levels of the input),
        outside EF theory, and EF-qsgd finiteness is covered by the
        multidevice test above.
    """
    P, B, D = 4, 64, 512
    key = jax.random.PRNGKey(0)
    w_true = jax.random.normal(key, (D,)) / jnp.sqrt(D)
    X = jax.random.normal(jax.random.fold_in(key, 1), (P, B, D))
    y = jnp.einsum("pbd,d->pb", X, w_true) + 0.01 * jax.random.normal(
        jax.random.fold_in(key, 2), (P, B)
    )

    def lossf(w):
        return float(jnp.mean((jnp.einsum("pbd,d->pb", X, w) - y) ** 2))

    def train(name, ef, lr, n, **ctx_kw):
        proto = get_exchange(name) if name else None
        ctx = ExchangeContext(axis="data", num_peers=P, **ctx_kw)

        def step(w, e, Xr, yr, k):
            g = Xr.T @ (Xr @ w - yr) / B
            if proto is None:
                return w - lr * jax.lax.pmean(g, "data"), e
            if ef:
                c = g + e
                avg, local, _ = proto.combine_ef(c, ctx, key=k)
                return w - lr * avg, c - local
            avg, _ = proto.combine(g, ctx, key=k)
            return w - lr * avg, e

        vstep = jax.jit(
            jax.vmap(step, in_axes=(0, 0, 0, 0, None), axis_name="data")
        )
        w = jnp.zeros((P, D))
        e = jnp.zeros((P, D))
        for t in range(n):
            w, e = vstep(w, e, X, y, jax.random.fold_in(key, 100 + t))
        return lossf(w[0])

    no_ef = train("topk", False, 0.02, 1500, topk_frac=1e-3)
    with_ef = train("topk", True, 0.02, 1500, topk_frac=1e-3)
    assert no_ef >= 0.1, f"top-k frac=1e-3 should stall without EF: {no_ef}"
    assert with_ef <= no_ef / 10.0, (with_ef, no_ef)

    qsgd_no_ef = train(
        "qsgd", False, 0.1, 300, qsgd=QSGDConfig(levels=3, bucket=512)
    )
    assert qsgd_no_ef <= 1e-3, f"unbiased qsgd should converge: {qsgd_no_ef}"


@pytest.mark.slow
def test_fused_kernel_paths_equivalence_multidevice():
    """Acceptance rail: kernel == jnp combine paths <= 1e-6 on the 4-device
    mesh (interpret mode), and EF threading through build_p2p_train_step
    is a no-op for a lossless protocol."""
    script = textwrap.dedent(
        """
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.core.compression import QSGDConfig
        from repro.core.exchange import ExchangeContext, get_exchange

        mesh = jax.make_mesh((4,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
        g_global = {
            "w": jax.random.normal(jax.random.PRNGKey(0), (4, 6, 33)),
            "b": jax.random.normal(jax.random.PRNGKey(1), (4, 170)),
        }

        def run(name, **ctx_kw):
            proto = get_exchange(name)
            ctx = ExchangeContext(axis="data", num_peers=4, **ctx_kw)

            def body(g):
                per_peer = jax.tree.map(lambda x: x[0], g)
                key = jax.random.PRNGKey(7) if proto.requires_key else None
                avg, _ = proto.combine(per_peer, ctx, key=key)
                return avg

            fn = jax.shard_map(
                body, mesh=mesh,
                in_specs=(jax.tree.map(lambda _: P("data"), g_global),),
                out_specs=jax.tree.map(lambda _: P(), g_global),
                axis_names={"data"}, check_vma=False,
            )
            with jax.set_mesh(mesh):
                return jax.jit(fn)(g_global)

        def maxerr(a, b):
            return max(
                float(jnp.abs(x - y).max())
                for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))
            )

        # fused Pallas decode path == unfused jnp reference (same rng key
        # -> identical stochastic rounding, so only decode order differs)
        for kw_k, kw_j in [
            (
                {"qsgd": QSGDConfig(levels=3, bucket=128, impl="kernel")},
                {"qsgd": QSGDConfig(levels=3, bucket=128, impl="jnp")},
            ),
            (
                {"qsgd": QSGDConfig(levels=127, bucket=256, impl="kernel")},
                {"qsgd": QSGDConfig(levels=127, bucket=256, impl="jnp")},
            ),
        ]:
            err = maxerr(run("qsgd", **kw_k), run("qsgd", **kw_j))
            assert err <= 1e-6, ("qsgd", err)
            print("qsgd kernel==jnp err", err)

        for frac in (0.05, 1.0):
            err = maxerr(
                run("topk", topk_frac=frac, topk_impl="kernel"),
                run("topk", topk_frac=frac, topk_impl="jnp"),
            )
            assert err <= 1e-6, ("topk", frac, err)
            print("topk kernel==jnp err", frac, err)

        # EF threading through the step builder: lossless protocol ->
        # bit-equal params and an all-zero residual bank
        from repro.core.p2p import Topology, build_p2p_train_step, init_ef
        from repro.core.p2p import TrainState
        from repro.optim import sgd

        opt = sgd(momentum=0.9)
        params = {"w": jax.random.normal(jax.random.PRNGKey(2), (8, 16))}
        batch = {"x": jax.random.normal(jax.random.PRNGKey(3), (8, 16))}

        def loss_fn(p, b):
            l = jnp.mean((b["x"] @ p["w"].T) ** 2)
            return l, l

        def make_state(ef):
            s = TrainState(
                params=params, opt_state=opt.init(params),
                step=jnp.zeros((), jnp.int32), key=jax.random.PRNGKey(0),
            )
            return s.replace(ef=init_ef(params, 4)) if ef else s

        def run_steps(topo, ef):
            step = build_p2p_train_step(
                loss_fn, opt, topo, mesh, lambda s: 0.05
            )
            st = make_state(ef)
            with jax.set_mesh(mesh):
                for _ in range(3):
                    st, _m = jax.jit(step)(st, batch)
            return st

        topo = Topology(peer_axes=("data",), lambda_axis=None,
                        exchange="allgather_mean")
        a = run_steps(topo, ef=False)
        b = run_steps(Topology(peer_axes=("data",), lambda_axis=None,
                               exchange="allgather_mean", ef=True), ef=True)
        assert maxerr(a.params, b.params) == 0.0, "EF must be a lossless no-op"
        assert all(
            float(jnp.abs(x).max()) == 0.0 for x in jax.tree.leaves(b.ef)
        ), "lossless residual must stay zero"

        # EF + qsgd(levels=3, kernel impl) runs end-to-end and stays finite
        topo_q = Topology(
            peer_axes=("data",), lambda_axis=None, exchange="qsgd",
            qsgd=QSGDConfig(levels=3, bucket=128, impl="kernel"), ef=True,
        )
        c = run_steps(topo_q, ef=True)
        assert all(
            bool(jnp.isfinite(x).all()) for x in jax.tree.leaves(c.params)
        )
        assert any(
            float(jnp.abs(x).max()) > 0.0 for x in jax.tree.leaves(c.ef)
        ), "lossy codec must accumulate a residual"
        print("OK")
        """
    )
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = SRC
    r = subprocess.run(
        [sys.executable, "-c", script], env=env, capture_output=True, text=True,
        timeout=600,
    )
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout


@pytest.mark.slow
def test_host_cluster_kernel_impl_equivalence():
    """Acceptance rail: host cluster final params, kernel vs jnp impl,
    <= 1e-6 for both codecs."""
    from repro.configs import get_config
    from repro.core import LocalP2PCluster
    from repro.optim import sgd

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "benchmarks"))
    from common import small_mnist

    cfg = get_config("squeezenet1.1")

    def run(**kw):
        cl = LocalP2PCluster(
            cfg, small_mnist(size=128, hw=8), num_peers=4, batch_size=8,
            batches_per_epoch=1, optimizer=sgd(momentum=0.9), lr=0.05,
            sync=True, seed=0, **kw,
        )
        cl.run_epoch_sync(0)
        return cl.peers[0].params

    def maxerr(a, b):
        return max(
            float(jnp.abs(x - y).max())
            for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))
        )

    q = maxerr(
        run(exchange="qsgd", qsgd=QSGDConfig(levels=7, bucket=256, impl="jnp")),
        run(exchange="qsgd", qsgd=QSGDConfig(levels=7, bucket=256, impl="kernel")),
    )
    assert q <= 1e-6, q
    t = maxerr(
        run(exchange="topk", topk_frac=0.01, topk_impl="jnp"),
        run(exchange="topk", topk_frac=0.01, topk_impl="kernel"),
    )
    assert t <= 1e-6, t
