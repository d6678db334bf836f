"""ExchangeProtocol registry: enumeration, errors, byte accounting, host
codec roundtrips, checkpoint versioning — plus sync-protocol equivalence
with the reference mean on a 4-device CPU mesh (subprocess)."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import TrainState, Topology, as_train_state
from repro.core.compression import QSGDConfig
from repro.core.exchange import (
    ExchangeContext,
    ExchangeProtocol,
    available_exchanges,
    get_exchange,
    register_exchange,
)

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def test_registry_enumerates_all_protocols():
    names = available_exchanges()
    assert {
        "allgather_mean", "psum_mean", "qsgd", "topk", "async",
        "reduce_scatter", "trimmed_mean", "median", "krum",
    } <= set(names)
    for n in names:
        proto = get_exchange(n)
        assert isinstance(proto, ExchangeProtocol)
        assert proto.name == n


def test_parameterized_exchange_specs():
    # NAME:ARG mirrors the graph registry's gossip:K idiom
    assert get_exchange("trimmed_mean:0.25").frac == 0.25
    assert get_exchange("trimmed_mean").frac is None  # falls back to ctx
    assert get_exchange("krum:2").m == 2
    with pytest.raises(ValueError, match=r"\[0, 0.5\)"):
        get_exchange("trimmed_mean:0.7")
    with pytest.raises(ValueError, match=">= 1"):
        get_exchange("krum:0")
    with pytest.raises(ValueError, match="does not take"):
        get_exchange("allgather_mean:3")
    with pytest.raises(ValueError, match="unknown exchange protocol"):
        get_exchange("nope:1")
    # krum's pairwise distances need every contribution
    assert get_exchange("krum").requires_full_graph
    assert not get_exchange("median").requires_full_graph


def test_unknown_exchange_raises_helpful_error():
    with pytest.raises(ValueError, match="unknown exchange protocol"):
        get_exchange("carrier_pigeon")
    with pytest.raises(ValueError, match="allgather_mean"):
        get_exchange("carrier_pigeon")  # message lists registered names
    # Topology resolves through the same registry
    with pytest.raises(ValueError, match="registered protocols"):
        Topology(exchange="carrier_pigeon").protocol()


def test_register_exchange_extends_topology_names():
    @register_exchange("_test_identity")
    class Identity(ExchangeProtocol):
        def combine(self, grads, ctx, *, key=None, state=None):
            return grads, state

    assert "_test_identity" in available_exchanges()
    assert isinstance(Topology(exchange="_test_identity").protocol(), Identity)


def test_wire_byte_accounting():
    grads = {"a": jnp.zeros((128, 64)), "b": jnp.zeros((100,))}
    n = 128 * 64 + 100
    ctx = ExchangeContext(num_peers=4, qsgd=QSGDConfig(levels=127, bucket=128),
                          topk_frac=0.1)
    # per-edge payload is the old publish-side figure; the per-peer total
    # scales by the overlay degree (no graph set => full mesh, P-1 = 3)
    raw = get_exchange("allgather_mean").wire_bytes_per_edge(grads, ctx)
    assert raw == n * 4
    assert get_exchange("allgather_mean").wire_bytes(grads, ctx) == 3 * raw
    # ring all-reduce: fused collective, 2(P-1)/P of raw regardless of
    # degree; the host mailbox publishes the dense payload
    assert get_exchange("psum_mean").wire_bytes(grads, ctx) == int(raw * 2 * 3 / 4)
    assert get_exchange("psum_mean").host_wire_bytes(grads, ctx) == raw
    assert not get_exchange("psum_mean").decomposes_per_edge
    # qsgd: ~1 byte/elt + norms, > 3x compression (per edge)
    q = get_exchange("qsgd").wire_bytes_per_edge(grads, ctx)
    assert q < raw / 3
    assert get_exchange("qsgd").wire_bytes(grads, ctx) == 3 * q
    # topk: k entries x (4B value + 4B index) per edge
    t = get_exchange("topk").wire_bytes_per_edge(grads, ctx)
    expect = (round(128 * 64 * 0.1)) * 8 + (round(100 * 0.1)) * 8
    assert t == expect
    # bf16 wire dtype halves value bytes
    half = ExchangeContext(num_peers=4, wire_dtype=jnp.bfloat16)
    assert get_exchange("allgather_mean").wire_bytes_per_edge(grads, half) == n * 2
    # a sparse overlay shrinks the per-peer total: ring degree is 2
    from repro.core.graph import get_graph

    rg = get_graph("ring", 8)
    rctx = ExchangeContext(num_peers=8, graph=rg, mixing=rg.mixing_matrix())
    assert get_exchange("allgather_mean").wire_bytes(grads, rctx) == 2 * n * 4


def test_qsgd_host_roundtrip_close():
    proto = get_exchange("qsgd")
    ctx = ExchangeContext(qsgd=QSGDConfig(levels=127, bucket=128))
    grads = {"w": jax.random.normal(jax.random.PRNGKey(0), (300,))}
    payload, nbytes = proto.host_encode(grads, ctx, key=jax.random.PRNGKey(1))
    assert 0 < nbytes < 300 * 4
    back = proto.host_decode(payload, grads, ctx)
    err = float(jnp.abs(back["w"] - grads["w"]).max())
    assert 0 < err < 0.5  # bounded quantization error, not exact


def test_topk_host_roundtrip_keeps_largest():
    proto = get_exchange("topk")
    ctx = ExchangeContext(topk_frac=0.2)
    g = {"w": jnp.asarray([0.1, -5.0, 0.2, 4.0, -0.3, 0.05, 0.0, 1.0, -0.2, 0.15])}
    payload, nbytes = proto.host_encode(g, ctx)
    assert nbytes == 2 * 8  # k=2 entries x 8 bytes
    back = proto.host_decode(payload, g, ctx)["w"]
    np.testing.assert_allclose(
        np.asarray(back),
        [0, -5.0, 0, 4.0, 0, 0, 0, 0, 0, 0],
        atol=1e-6,
    )


def test_async_init_state_ring_shape():
    proto = get_exchange("async")
    ring = proto.init_state(
        {"w": jnp.zeros((3, 2))}, ExchangeContext(num_peers=4, staleness=3)
    )
    assert jax.tree.leaves(ring)[0].shape == (3, 4, 3, 2)


def test_train_state_dict_compat_and_pytree():
    s = TrainState(params={"w": jnp.ones(2)}, opt_state=(), step=jnp.int32(3),
                   key=jax.random.PRNGKey(0))
    assert s["step"] == 3 and s.get("mailbox") is None
    assert "mailbox" not in dict(s)
    # absent mailbox behaves like the legacy dict: not a member, KeyError on lookup
    assert "mailbox" not in s and "params" in s
    assert list(iter(s)) == s.keys()
    with pytest.raises(KeyError):
        s["mailbox"]
    legacy = as_train_state({"params": s.params, "opt_state": (), "step": s.step,
                             "key": s.key})
    assert isinstance(legacy, TrainState)
    doubled = jax.tree.map(lambda x: x * 2, s)
    assert isinstance(doubled, TrainState)
    assert float(doubled.params["w"][0]) == 2.0
    with pytest.raises(KeyError):
        s["nope"]


def test_checkpoint_versioning(tmp_path):
    from repro.train import checkpoint as ckpt

    state = TrainState(
        params={"w": jnp.arange(4.0)},
        opt_state={"momentum": {"w": jnp.ones(4)}},
        step=jnp.int32(7),
        key=jax.random.PRNGKey(0),
    )
    # v2: full state roundtrip
    p2 = str(tmp_path / "state_v2")
    ckpt.save_state(p2, state)
    like = jax.tree.map(jnp.zeros_like, state)
    back, meta = ckpt.restore_state(p2, like)
    assert meta["format"] == ckpt.STATE_FORMAT and meta["step"] == 7
    np.testing.assert_array_equal(np.asarray(back.params["w"]), np.arange(4.0))
    assert int(back.step) == 7
    # sync-protocol v2 checkpoint restores into an async `like`: the cold
    # mailbox ring from `like` is kept, everything else comes from disk
    ring = {"w": jnp.zeros((1, 2, 4))}
    back_a, _ = ckpt.restore_state(p2, like.replace(mailbox=ring))
    np.testing.assert_array_equal(np.asarray(back_a.params["w"]), np.arange(4.0))
    assert back_a.mailbox is ring
    # v1 (params-only) restores into .params and keeps the rest fresh
    p1 = str(tmp_path / "params_v1")
    ckpt.save(p1, state.params, step=3)
    back1, meta1 = ckpt.restore_state(p1, like)
    np.testing.assert_array_equal(np.asarray(back1.params["w"]), np.arange(4.0))
    assert int(back1.step) == 0  # from `like`, not the checkpoint
    assert float(back1.opt_state["momentum"]["w"][0]) == 0.0


@pytest.mark.slow
def test_sync_protocols_match_reference_mean_multidevice():
    """psum_mean / allgather_mean / topk(frac=1) == the P-peer mean, and
    qsgd is within the quantization error bound — on a 4-device CPU mesh."""
    script = textwrap.dedent(
        """
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.core.compression import QSGDConfig
        from repro.core.exchange import ExchangeContext, get_exchange

        mesh = jax.make_mesh((4,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
        g_global = {
            "w": jax.random.normal(jax.random.PRNGKey(0), (4, 6, 33)),
            "b": jax.random.normal(jax.random.PRNGKey(1), (4, 17)),
        }
        ref = jax.tree.map(lambda x: x.mean(axis=0), g_global)

        def run(name, **ctx_kw):
            proto = get_exchange(name)
            ctx = ExchangeContext(axis="data", num_peers=4, **ctx_kw)

            def body(g):
                per_peer = jax.tree.map(lambda x: x[0], g)  # drop peer dim
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

        for name, kw, tol in [
            ("allgather_mean", {}, 1e-6),
            ("psum_mean", {}, 1e-6),
            ("reduce_scatter", {}, 1e-6),  # sharded ring, same mean
            ("tree", {}, 1e-6),  # binary tree reduce, same mean
            ("tree:3", {}, 1e-6),  # non-dyadic fanout at P=4
            ("topk", {"topk_frac": 1.0}, 1e-6),  # k=n: lossless
            ("qsgd", {"qsgd": QSGDConfig(levels=127, bucket=64)}, 0.5),
            ("trimmed_mean:0", {}, 1e-6),  # zero trim IS the mean
            ("trimmed_mean", {}, 1e-6),  # ctx default trim_frac=0.0
        ]:
            avg = run(name, **kw)
            err = max(
                float(jnp.abs(a - b).max())
                for a, b in zip(jax.tree.leaves(avg), jax.tree.leaves(ref))
            )
            assert err <= tol, (name, err)
            print(name, "err", err)

        # sparsified topk deviates but preserves the largest coordinates
        sparse = run("topk", topk_frac=0.25)
        err = float(jnp.abs(sparse["w"] - ref["w"]).max())
        assert err > 0, "frac<1 must be lossy on dense gradients"

        # coordinate median == numpy median over the peer axis
        med = run("median")
        med_ref = jax.tree.map(lambda x: jnp.median(x, axis=0), g_global)
        err = max(
            float(jnp.abs(a - b).max())
            for a, b in zip(jax.tree.leaves(med), jax.tree.leaves(med_ref))
        )
        assert err <= 1e-6, ("median", err)

        # krum picks the row with the lowest summed distance to its
        # P - f - 2 nearest peers (f defaults to (P-3)//2 = 0 at P=4)
        flat = np.concatenate(
            [np.asarray(g_global[k]).reshape(4, -1) for k in ("w", "b")], 1
        )
        d2 = ((flat[:, None, :] - flat[None, :, :]) ** 2).sum(-1)
        np.fill_diagonal(d2, np.inf)
        scores = np.sort(d2, axis=1)[:, :2].sum(1)
        kref = flat[int(np.argmin(scores))]
        kr = run("krum")
        kflat = np.concatenate(
            [np.asarray(kr[k]).reshape(-1) for k in ("w", "b")]
        )
        err = float(np.abs(kflat - kref).max())
        assert err <= 1e-5, ("krum", err)
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
