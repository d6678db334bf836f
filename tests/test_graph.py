"""PeerGraph registry + overlay-aware exchange: mixing-matrix properties
(row-stochasticity, symmetry, spectral-gap sanity) for every registered
graph at P in {2, 4, 8}; device- and host-path equivalence of
``graph="full"`` with the legacy allgather_mean math; Metropolis–Hastings
mixing on the host path; HostMailbox edge enforcement under churn; the
``exchange_gradients`` num_peers fix; the ``async_mode`` deprecation."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import LocalP2PCluster, Topology, exchange_context
from repro.core.exchange import ExchangeContext, get_exchange
from repro.core.graph import (
    PeerGraph,
    StaticGraph,
    available_graphs,
    get_graph,
    register_graph,
)
from repro.core.mailbox import HostMailbox
from repro.core.p2p import exchange_gradients, init_mailbox
from repro.data import BatchKey, make_dataset
from repro.optim import sgd

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


# ---------------------------------------------------------------------------
# Registry + construction
# ---------------------------------------------------------------------------

def test_registry_enumerates_graphs():
    names = available_graphs()
    assert {"full", "ring", "gossip", "hierarchical", "static"} <= set(names)
    for name in ("full", "ring", "gossip", "hierarchical"):
        g = get_graph(name, 4)
        assert isinstance(g, PeerGraph) and g.name == name


def test_unknown_graph_and_bad_param_raise():
    with pytest.raises(ValueError, match="unknown peer graph"):
        get_graph("smallworld", 4)
    with pytest.raises(ValueError, match="registered graphs"):
        get_graph("smallworld", 4)
    with pytest.raises(ValueError, match="must be an int"):
        get_graph("gossip:many", 4)
    with pytest.raises(ValueError, match="explicit adjacency"):
        get_graph("static", 4)  # programmatic-only
    with pytest.raises(ValueError, match="built for 4 peers"):
        get_graph(get_graph("ring", 4), 8)


def test_register_graph_extends_topology_names():
    @register_graph("_test_line")
    class Line(PeerGraph):
        def __init__(self, num_peers, *, seed=0):
            super().__init__(num_peers)

        def build_adjacency(self):
            P = self.num_peers
            adj = np.zeros((P, P), dtype=bool)
            for r in range(P - 1):
                adj[r, r + 1] = adj[r + 1, r] = True
            return adj

    assert "_test_line" in available_graphs()
    topo = Topology(peer_axes=("data",), graph="_test_line")
    assert topo.peer_graph(4).neighbors(0) == (1,)


@pytest.mark.parametrize("P", [2, 4, 8])
@pytest.mark.parametrize("spec", ["full", "ring", "gossip:3", "hierarchical"])
def test_mixing_matrix_properties(spec, P):
    if spec == "gossip:3" and P <= 3:
        pytest.skip("gossip:k now validates k < P")
    g = get_graph(spec, P, seed=1)
    W = g.mixing_matrix()
    # row-stochastic, symmetric => doubly stochastic
    np.testing.assert_allclose(W.sum(axis=1), np.ones(P), atol=1e-12)
    np.testing.assert_allclose(W, W.T, atol=1e-12)
    assert (W >= -1e-12).all()
    # connected graph => spectral gap strictly positive, <= 1
    assert g.is_connected()
    gap = g.spectral_gap()
    assert 0.0 < gap <= 1.0 + 1e-12
    # off-diagonal support matches adjacency exactly
    off = W.copy()
    np.fill_diagonal(off, 0.0)
    np.testing.assert_array_equal(off > 0, g.adjacency)


def test_full_graph_mixing_is_uniform_mean():
    for P in (2, 4, 8):
        W = get_graph("full", P).mixing_matrix()
        np.testing.assert_allclose(W, np.full((P, P), 1.0 / P), atol=1e-12)
    assert get_graph("full", 8).spectral_gap() == pytest.approx(1.0)


def test_spectral_gap_orders_density():
    # denser overlays mix faster: full >= gossip:3 >= ring at P=8
    gaps = {s: get_graph(s, 8, seed=0).spectral_gap()
            for s in ("full", "gossip:3", "ring")}
    assert gaps["full"] >= gaps["gossip:3"] >= gaps["ring"] > 0


def test_hierarchical_structure():
    g = get_graph("hierarchical:4", 8)
    hubs = (0, 4)
    assert g.adjacency[0, 4]  # hub mesh
    for spoke in (1, 2, 3):
        assert g.neighbors(spoke) == (0,)  # spokes see only their hub
    for spoke in (5, 6, 7):
        assert g.neighbors(spoke) == (4,)
    assert set(g.neighbors(0)) == {1, 2, 3, 4}
    assert g.max_degree == 4 and g.is_connected()


def test_gossip_is_seeded_and_min_degree():
    a = get_graph("gossip:3", 16, seed=7)
    b = get_graph("gossip:3", 16, seed=7)
    c = get_graph("gossip:3", 16, seed=8)
    np.testing.assert_array_equal(a.adjacency, b.adjacency)
    assert not np.array_equal(a.adjacency, c.adjacency)  # seed matters
    assert int(a.degrees.min()) >= 3 and a.is_connected()


def test_gossip_degree_validated_against_num_peers():
    # regression: k >= P used to degrade silently (the round loop could
    # never reach min-degree k); now it is a clean spec error naming both
    with pytest.raises(ValueError, match=r"k=3.*num_peers=2"):
        get_graph("gossip:3", 2)
    with pytest.raises(ValueError, match=r"k=8.*num_peers=8"):
        get_graph("gossip:8", 8)
    with pytest.raises(ValueError, match="must be >= 1"):
        get_graph("gossip:0", 8)
    assert int(get_graph("gossip:7", 8).degrees.min()) >= 7  # k = P-1 is fine


def test_static_graph_from_edges():
    g = StaticGraph.from_edges(4, [(0, 1), (1, 2), (2, 3)])
    assert g.neighbors(1) == (0, 2) and not g.is_full
    assert get_graph(g, 4) is g
    with pytest.raises(ValueError, match="symmetric"):
        StaticGraph(2, np.array([[False, True], [False, False]]))


# ---------------------------------------------------------------------------
# Context resolution + degree-aware accounting
# ---------------------------------------------------------------------------

def test_exchange_context_resolves_graph_and_mixing():
    ctx = exchange_context(
        Topology(peer_axes=("data",), graph="ring"), num_peers=4
    )
    assert ctx.graph.name == "ring" and ctx.degree == 2.0
    np.testing.assert_allclose(ctx.mixing.sum(axis=1), np.ones(4), atol=1e-6)
    # full graph keeps the legacy bit-exact mean path: no mixing matrix
    ctx_full = exchange_context(Topology(peer_axes=("data",)), num_peers=4)
    assert ctx_full.graph.name == "full" and ctx_full.mixing is None
    assert ctx_full.degree == 3.0


def test_wire_bytes_scale_with_degree():
    grads = {"w": jnp.zeros((128, 64), jnp.float32)}
    proto = get_exchange("allgather_mean")
    per_edge = 128 * 64 * 4
    for P, spec, degree in [(8, "ring", 2), (8, "full", 7), (16, "full", 15)]:
        g = get_graph(spec, P)
        ctx = ExchangeContext(num_peers=P, graph=g,
                              mixing=None if g.is_full else g.mixing_matrix())
        assert proto.wire_bytes_per_edge(grads, ctx) == per_edge
        assert proto.wire_bytes(grads, ctx) == per_edge * degree
        # the host mailbox publish is one payload regardless of degree
        assert proto.host_wire_bytes(grads, ctx) == per_edge


def test_psum_mean_rejects_sparse_graph():
    g = get_graph("ring", 4)
    ctx = ExchangeContext(axis="data", num_peers=4, graph=g,
                          mixing=g.mixing_matrix())
    with pytest.raises(ValueError, match="only supports graph='full'"):
        get_exchange("psum_mean").combine({"w": jnp.zeros(3)}, ctx)
    # ...and at construction time, not just inside the jitted step trace
    with pytest.raises(ValueError, match="fused global collective"):
        exchange_context(
            Topology(peer_axes=("data",), exchange="psum_mean", graph="ring"),
            num_peers=4,
        )
    with pytest.raises(ValueError, match="fused global collective"):
        _tiny_cluster(sync=True, exchange="psum_mean", graph="ring")
    # the full graph stays fine for fused collectives
    assert exchange_context(
        Topology(peer_axes=("data",), exchange="psum_mean"), num_peers=4
    ).mixing is None


# ---------------------------------------------------------------------------
# Satellite: exchange_gradients num_peers plumbing
# ---------------------------------------------------------------------------

def test_exchange_gradients_requires_explicit_num_peers():
    topo = Topology(peer_axes=("data",), exchange="async")
    grads = {"w": jnp.ones((3,))}
    # sync/no-mailbox: peer count is no longer silently inferred as 1
    with pytest.raises(ValueError, match="num_peers"):
        exchange_gradients(grads, Topology(peer_axes=("data",)))
    # async mailbox fallback still works (ring leaves are (K, P, *grad))
    mb = init_mailbox(grads, num_peers=4)
    assert jax.tree.leaves(mb)[0].shape[:2] == (1, 4)
    # no-peer topologies pass through untouched
    out, mb2 = exchange_gradients(grads, Topology(peer_axes=()), mailbox=None)
    assert out is grads and mb2 is None


def test_topology_async_mode_deprecated():
    with pytest.warns(DeprecationWarning, match='exchange="async"'):
        topo = Topology(peer_axes=("data",), async_mode=True)
    assert topo.exchange_name == "async"  # behavior kept


# ---------------------------------------------------------------------------
# HostMailbox: deliveries respect graph edges (incl. under churn)
# ---------------------------------------------------------------------------

def test_mailbox_blocks_non_edge_consumption():
    g = get_graph("ring", 4)
    mb = HostMailbox(4, graph=g)
    mb.publish(2, "g2", nbytes=8, time=0.0, epoch=0)
    # 0-2 is not a ring edge: refused and counted
    assert mb.consume(2, consumer=0) is None
    assert mb.stats["blocked"] == 1
    # 1-2 is an edge: delivered and recorded
    assert mb.consume(2, consumer=1).payload == "g2"
    assert (1, 2) in mb.delivered_edges
    # anonymous consumers (legacy callers) keep broker semantics
    assert mb.consume(2).payload == "g2"


def _tiny_cluster(**kw):
    return LocalP2PCluster(
        get_config("squeezenet1.1"),
        make_dataset("mnist", size=128, image_hw=8, channels=1),
        num_peers=4,
        batch_size=8,
        batches_per_epoch=1,
        optimizer=sgd(momentum=0.0),
        lr=0.05,
        seed=0,
        **kw,
    )


def test_host_deliveries_respect_edges_under_churn():
    cl = _tiny_cluster(
        sync=False, graph="ring", churn_prob=0.4, churn_downtime_s=0.5,
        peer_speeds=[1.0, 2.0, 3.0, 4.0],
    )
    for e in range(3):
        cl.run_epoch_async(e)
    assert sum(p.drops for p in cl.peers) > 0  # churn actually fired
    assert cl.mailbox.delivered_edges  # gradients actually flowed
    for consumer, producer in cl.mailbox.delivered_edges:
        assert cl.graph.adjacency[consumer, producer], (consumer, producer)
    assert cl.mailbox.stats["blocked"] == 0  # cluster never even tried


# ---------------------------------------------------------------------------
# Host-path equivalence + MH mixing correctness
# ---------------------------------------------------------------------------

def test_host_full_graph_matches_legacy_bit_for_bit():
    a = _tiny_cluster(sync=True)
    b = _tiny_cluster(sync=True, graph="full")
    a.run_epoch_sync(0)
    b.run_epoch_sync(0)
    for pa, pb in zip(a.peers, b.peers):
        for x, y in zip(jax.tree.leaves(pa.params), jax.tree.leaves(pb.params)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_host_ring_applies_metropolis_hastings_weights():
    cl = _tiny_cluster(sync=True, graph="ring")
    ref = _tiny_cluster(sync=True)  # identical init (same seed)
    W = cl.graph.mixing_matrix()
    grads = {}
    for peer in ref.peers:
        b = jax.tree.map(jnp.asarray, peer.loader.load(BatchKey(peer.rank, 0, 0)))
        grads[peer.rank], _, _ = ref._grad(peer.params, b)
    cl.run_epoch_sync(0)
    for r in range(4):
        ranks = sorted([r] + list(cl.graph.neighbors(r)))
        mixed = jax.tree.map(
            lambda *xs: sum(
                float(W[r, j]) * x.astype(jnp.float32)
                for j, x in zip(ranks, xs)
            ),
            *[grads[j] for j in ranks],
        )
        want, _ = ref._apply(
            ref.peers[r].params, ref.peers[r].opt_state, mixed, jnp.float32(0.05)
        )
        for x, y in zip(jax.tree.leaves(cl.peers[r].params), jax.tree.leaves(want)):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=1e-6)


# ---------------------------------------------------------------------------
# Device-path equivalence (4-device subprocess)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_device_full_graph_bit_exact_and_ring_mixes():
    """graph='full' reproduces allgather_mean bit-for-bit; graph='ring'
    applies the MH row weights; async mixing reduces to the legacy math on
    the full graph — on a 4-device CPU mesh."""
    script = textwrap.dedent(
        """
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.core.p2p import Topology, exchange_context

        mesh = jax.make_mesh((4,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
        g_global = {
            "w": jax.random.normal(jax.random.PRNGKey(0), (4, 6, 33)),
            "b": jax.random.normal(jax.random.PRNGKey(1), (4, 17)),
        }

        def run(name="allgather_mean", **topo_kw):
            topo = Topology(peer_axes=("data",), lambda_axis=None,
                            exchange=name, **topo_kw)
            ctx = exchange_context(topo, mesh)
            proto = topo.protocol()

            def body(g):
                per = jax.tree.map(lambda x: x[0], g)
                avg, _ = proto.combine(per, ctx, key=None)
                return jax.tree.map(lambda x: x[None], avg)

            fn = jax.shard_map(
                body, mesh=mesh,
                in_specs=(jax.tree.map(lambda _: P("data"), g_global),),
                out_specs=jax.tree.map(lambda _: P("data"), g_global),
                axis_names={"data"}, check_vma=False,
            )
            with jax.set_mesh(mesh):
                return jax.jit(fn)(g_global), ctx

        legacy, _ = run()
        full, _ = run(graph="full")
        for a, b in zip(jax.tree.leaves(legacy), jax.tree.leaves(full)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

        ring, rctx = run(graph="ring")
        W = np.asarray(rctx.mixing, np.float32)
        for kname in ("w", "b"):
            want = np.einsum(
                "rp,p...->r...", W, np.asarray(g_global[kname], np.float32)
            )
            err = np.abs(np.asarray(ring[kname]) - want).max()
            assert err < 1e-5, (kname, err)

        # topk(frac=1) under ring == exact MH mix (lossless sparsification)
        ringt, _ = run("topk", graph="ring", topk_frac=1.0)
        want = np.einsum("rp,p...->r...", W,
                         np.asarray(g_global["w"], np.float32))
        assert np.abs(np.asarray(ringt["w"]) - want).max() < 1e-5
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
