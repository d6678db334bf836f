"""ShardPlan + sharded exchange coverage: flatten/unflatten roundtrips
(property-tested across mixed-dtype pytrees), padding edge cases, the
shard-addressed mailbox with latest-wins compaction, reduce_scatter wire
accounting and full-graph gating, the parallel serverless aggregation
stage, and reduce_scatter == allgather_mean equivalence on the host
cluster and a 4-device CPU mesh (subprocess)."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Topology, exchange_context, exchange_gradients
from repro.core.events import LinkModel, RuntimeConfig
from repro.core.exchange import ExchangeContext, get_exchange
from repro.core.graph import get_graph
from repro.core.mailbox import HostMailbox
from repro.core.serverless import ServerlessExecutor
from repro.core.shard import ShardPlan

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


# ---------------------------------------------------------------------------
# ShardPlan: flatten/unflatten roundtrip
# ---------------------------------------------------------------------------

def _assert_roundtrip(tree, P):
    plan = ShardPlan.for_tree(tree, P)
    shards = plan.shards(tree)
    assert shards.shape == (plan.num_shards, plan.shard_size)
    back = plan.unflatten(shards)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a, np.float64),
                                      np.asarray(b, np.float64))
    # the 1-D buffer is accepted too
    back2 = plan.unflatten(plan.flatten(tree))
    for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(back2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_roundtrip_mixed_dtypes_and_shapes():
    tree = {
        "w": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
        "b": (jnp.ones((5,), jnp.bfloat16) * 1.5,
              jnp.asarray(-2.0, jnp.float16)),
        "scalar": jnp.asarray(7.25, jnp.float32),
    }
    for P in (1, 2, 3, 4, 8, 19):
        _assert_roundtrip(tree, P)


def test_roundtrip_property_hypothesis():
    hyp = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    dtypes = [jnp.float32, jnp.bfloat16, jnp.float16]

    @settings(max_examples=30, deadline=None)
    @given(
        sizes=st.lists(st.integers(0, 7), min_size=1, max_size=4),
        dts=st.lists(st.sampled_from(range(len(dtypes))), min_size=4,
                     max_size=4),
        num_shards=st.integers(1, 9),
        seed=st.integers(0, 2**31 - 1),
    )
    def prop(sizes, dts, num_shards, seed):
        rng = np.random.default_rng(seed)
        leaves = []
        for i, n in enumerate(sizes):
            dt = dtypes[dts[i % len(dts)]]
            # values exactly representable in every float dtype here
            vals = rng.integers(-8, 8, size=(n,)).astype(np.float32) / 4.0
            leaves.append(jnp.asarray(vals).astype(dt))
        tree = dict(enumerate(leaves))
        _assert_roundtrip(tree, num_shards)

    prop()


def test_padding_more_shards_than_params():
    tree = {"w": jnp.arange(3, dtype=jnp.float32)}
    plan = ShardPlan.for_tree(tree, 8)
    assert plan.shard_size == 1 and plan.pad == 5 and plan.padded_size == 8
    shards = plan.shards(tree)
    # trailing shards are pure padding, zero-filled
    np.testing.assert_array_equal(np.asarray(shards[3:]).ravel(), np.zeros(5))
    _assert_roundtrip(tree, 8)
    # slices tile the buffer contiguously
    assert plan.shard_slice(0) == (0, 1) and plan.shard_slice(7) == (7, 8)
    with pytest.raises(IndexError):
        plan.shard_slice(8)


def test_plan_validation_errors():
    with pytest.raises(ValueError, match="num_shards"):
        ShardPlan.for_tree({"w": jnp.zeros(4)}, 0)
    plan = ShardPlan.for_tree({"w": jnp.zeros(4)}, 2)
    with pytest.raises(ValueError, match="leaves"):
        plan.flatten({"w": jnp.zeros(4), "extra": jnp.zeros(1)})
    with pytest.raises(ValueError, match="elements"):
        plan.unflatten(jnp.zeros(5))


def test_shard_bytes_tracks_wire_dtype():
    plan = ShardPlan.for_tree({"w": jnp.zeros((16, 16), jnp.float32)}, 4)
    assert plan.shard_bytes() == 64 * 4
    assert plan.shard_bytes(jnp.bfloat16) == 64 * 2


# ---------------------------------------------------------------------------
# reduce_scatter: accounting + gating
# ---------------------------------------------------------------------------

def test_reduce_scatter_wire_accounting_shrinks_with_P():
    g = {"w": jnp.zeros((64, 64), jnp.float32)}
    proto = get_exchange("reduce_scatter")
    assert proto.sharded and proto.requires_full_graph
    per_edge = {
        P: proto.wire_bytes_per_edge(g, ExchangeContext(num_peers=P))
        for P in (4, 8, 16)
    }
    # one shard per edge: model/P bytes, halving as P doubles
    assert per_edge[4] == 64 * 64 * 4 // 4
    assert per_edge[8] == per_edge[4] // 2 and per_edge[16] == per_edge[8] // 2
    ctx = ExchangeContext(num_peers=8)
    # ring reduce-scatter + allgather: 2(P-1) shard sends per peer
    assert proto.wire_bytes(g, ctx) == 2 * 7 * per_edge[8]
    # mailbox publishes: P-1 pieces + 1 aggregated shard
    assert proto.host_wire_bytes(g, ctx) == 8 * per_edge[8]


def test_reduce_scatter_rejects_sparse_graph():
    g = get_graph("ring", 4)
    ctx = ExchangeContext(axis="data", num_peers=4, graph=g,
                          mixing=g.mixing_matrix())
    with pytest.raises(ValueError, match="only supports graph='full'"):
        get_exchange("reduce_scatter").combine({"w": jnp.zeros(3)}, ctx)
    with pytest.raises(ValueError, match="sharded global reduce-scatter"):
        exchange_context(
            Topology(peer_axes=("data",), exchange="reduce_scatter",
                     graph="ring"),
            num_peers=4,
        )


# ---------------------------------------------------------------------------
# Satellites: context/num_peers validation + mailbox compaction
# ---------------------------------------------------------------------------

def test_context_rejects_mismatched_graph():
    with pytest.raises(ValueError, match="does not match its overlay graph"):
        ExchangeContext(num_peers=4, graph=get_graph("full", 8))


def test_exchange_gradients_validates_num_peers_against_mailbox():
    from repro.core import init_mailbox

    topo = Topology(peer_axes=("data",), exchange="async")
    mailbox = init_mailbox({"w": jnp.zeros(3)}, num_peers=4)
    with pytest.raises(ValueError, match="spans 4 peers"):
        exchange_gradients(
            {"w": jnp.zeros(3)}, topo, mailbox=mailbox, num_peers=8
        )


def test_mailbox_shard_addressing_and_compaction():
    mb = HostMailbox(3)
    # shard-addressed registers are independent of the classic one
    mb.publish(0, "dense", nbytes=8, time=0.0, epoch=0)
    mb.publish(0, "piece1", nbytes=4, time=0.0, epoch=0, shard=("piece", 1))
    mb.publish(0, "agg", nbytes=4, time=0.0, epoch=0, shard=("agg",))
    assert mb.consume(0).payload == "dense"
    assert mb.consume(0, shard=("piece", 1)).payload == "piece1"
    assert mb.consume(0, shard=("agg",)).payload == "agg"
    assert mb.consume(0, shard=("piece", 2)) is None
    assert mb.live_messages == 3
    # same (peer, epoch) cell republished -> compacted, latest wins
    assert mb.stats["compacted"] == 0
    mb.publish(0, "piece1b", nbytes=4, time=1.0, epoch=0, shard=("piece", 1))
    assert mb.stats["compacted"] == 1
    assert mb.consume(0, shard=("piece", 1)).payload == "piece1b"
    # a NEW epoch replaces without counting as same-epoch compaction
    mb.publish(0, "piece1c", nbytes=4, time=2.0, epoch=1, shard=("piece", 1))
    assert mb.stats["compacted"] == 1
    # memory bound: registers replace, live count never grows with epochs
    for e in range(2, 30):
        mb.publish(0, f"e{e}", nbytes=4, time=float(e), epoch=e,
                   shard=("agg",))
    assert mb.live_messages == 3
    with pytest.raises(IndexError):
        mb.consume(7)
    with pytest.raises(IndexError):
        mb.publish(7, "orphan", nbytes=4, time=0.0, epoch=0)


# ---------------------------------------------------------------------------
# Parallel serverless aggregation stage
# ---------------------------------------------------------------------------

def test_simulate_aggregation_memory_sized_from_shard_bytes():
    ex = ServerlessExecutor(
        backend="serverless", invoke_overhead_s=0.0,
        orchestration_overhead_s=0.0,
    )
    small = ex.simulate_aggregation(
        [0.01] * 8, shard_bytes=1_000_000, num_contributions=8
    )
    big = ex.simulate_aggregation(
        [0.01], shard_bytes=100_000_000, num_contributions=8, peer="mono"
    )
    assert small.num_batches == 8 and big.num_batches == 1
    assert small.lambda_memory_mb < big.lambda_memory_mb
    # parallel shard aggregators: wall ~= one shard's time, not the sum
    assert small.wall_time_s < small.measured_compute_s


def test_simulate_aggregation_runs_on_the_event_engine():
    ex = ServerlessExecutor(
        backend="serverless",
        runtime=RuntimeConfig(cold_start_s=2.0, concurrency_limit=2),
    )
    rep = ex.simulate_aggregation(
        [0.5] * 4, shard_bytes=500_000, num_contributions=4,
        link=LinkModel(bandwidth_bps=1e9),
    )
    # 2 concurrency slots -> 2 cold containers, reused warm by the queued
    # pair once the first wave releases them
    assert rep.num_cold_starts == 2
    assert rep.queue_wait_s > 0.0  # 4 invocations through 2 slots
    assert rep.download_s > 0.0  # 3 foreign pieces fetched per aggregator
    assert rep.egress_bytes == 4 * 3 * 500_000


def test_aggregation_history_feeds_allocation_policy():
    ex = ServerlessExecutor(backend="serverless", allocation="aimd")
    r0 = ex.simulate_aggregation(
        [5.0] * 2, shard_bytes=1_000_000, num_contributions=2, epoch=0
    )
    r1 = ex.simulate_aggregation(
        [5.0] * 2, shard_bytes=1_000_000, num_contributions=2, epoch=1
    )
    # slow epoch-0 aggregators push AIMD to a bigger tier at epoch 1,
    # observed through the ("agg", peer) history key
    assert r1.lambda_memory_mb > r0.lambda_memory_mb


# ---------------------------------------------------------------------------
# Host-path equivalence + sharded cluster plumbing
# ---------------------------------------------------------------------------

def _cluster(exchange, sync=True, **kw):
    from repro.configs import get_config
    from repro.core import LocalP2PCluster
    from repro.data import make_dataset
    from repro.optim import sgd

    return LocalP2PCluster(
        get_config("squeezenet1.1"),
        make_dataset("mnist", size=96, image_hw=8, channels=1),
        num_peers=3,
        batch_size=8,
        batches_per_epoch=1,
        optimizer=sgd(momentum=0.9),
        lr=0.05,
        sync=sync,
        exchange=exchange,
        seed=0,
        **kw,
    )


@pytest.mark.slow
def test_host_reduce_scatter_matches_allgather_mean():
    ref = _cluster("allgather_mean")
    shd = _cluster("reduce_scatter")
    for _ in range(2):
        ref.run_epoch_sync(_)
        shd.run_epoch_sync(_)
    for r in range(3):
        err = max(
            float(jnp.abs(a - b).max())
            for a, b in zip(
                jax.tree.leaves(ref.peers[r].params),
                jax.tree.leaves(shd.peers[r].params),
            )
        )
        assert err <= 1e-6, (r, err)
    # shard-addressed traffic: P-1 pieces + 1 agg per peer per epoch
    assert shd.mailbox.live_messages == 3 * 3  # (P-1) pieces + 1 agg, x P
    # epoch 2 republished every register: all compacted? no — new epoch
    # replaces, same-epoch republish never happens in the sync flow
    assert shd.mailbox.stats["compacted"] == 0
    cc = shd.comm_cost()
    assert cc.num_shards == 3 and cc.shard_bytes == cc.bytes_per_edge > 0
    assert cc.wire_bytes_per_step == 2 * 2 * cc.bytes_per_edge


@pytest.mark.slow
def test_host_tree_matches_allgather_mean():
    """tree[:fanout] hierarchical host exchange lands on the same mean:
    hub fan-in + down-sweep relay == the flat all-gather average."""
    ref = _cluster("allgather_mean")
    trc = _cluster("tree")
    for _ in range(2):
        ref.run_epoch_sync(_)
        trc.run_epoch_sync(_)
    for r in range(3):
        err = max(
            float(jnp.abs(a - b).max())
            for a, b in zip(
                jax.tree.leaves(ref.peers[r].params),
                jax.tree.leaves(trc.peers[r].params),
            )
        )
        assert err <= 1e-6, (r, err)
    # register traffic: P=3, fanout 2 -> ranks 1,2 publish up, the root
    # publishes one down register; nothing else stays live
    assert trc.mailbox.live_messages == 3
    assert trc.mailbox.stats["blocked"] == 0
    cc = trc.comm_cost()
    # one tree hop carries the whole buffer: per-edge == P x shard bytes
    assert cc.bytes_per_edge == 3 * cc.shard_bytes
    assert cc.wire_bytes_per_step == 2 * 2 * cc.bytes_per_edge


def test_tree_cluster_prices_per_level_aggregation():
    shd = _cluster("tree", executor=ServerlessExecutor(backend="serverless"))
    shd.run_epoch_sync(0)
    # P=3 fanout 2: one hub level (the root fans in both children)
    assert len(shd.aggregation_reports) == 1
    rep = shd.aggregation_reports[0]
    assert rep.num_batches == 1  # one hub invocation at that level
    assert rep.backend == "serverless"


def test_sharded_cluster_rejects_async_mode():
    with pytest.raises(ValueError, match="sync"):
        _cluster("reduce_scatter", sync=False)
    with pytest.raises(ValueError, match="sync"):
        _cluster("tree", sync=False)


def test_sharded_cluster_prices_parallel_aggregators():
    shd = _cluster(
        "reduce_scatter",
        executor=ServerlessExecutor(backend="serverless"),
    )
    shd.run_epoch_sync(0)
    assert len(shd.aggregation_reports) == 1
    rep = shd.aggregation_reports[0]
    assert rep.num_batches == 3  # one aggregator invocation per shard
    assert rep.backend == "serverless"
    assert rep.lambda_memory_mb >= 128


# ---------------------------------------------------------------------------
# Device-path equivalence (4-device subprocess)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_reduce_scatter_matches_mean_multidevice():
    """ppermute ring reduce-scatter + allgather == the P-peer mean on a
    4-device CPU mesh, including a padded (size % P != 0) pytree."""
    script = textwrap.dedent(
        """
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.core.exchange import ExchangeContext, get_exchange

        mesh = jax.make_mesh((4,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
        # 6*33 + 17 = 215 elements: not divisible by 4 -> padding exercised
        g_global = {
            "w": jax.random.normal(jax.random.PRNGKey(0), (4, 6, 33)),
            "b": jax.random.normal(jax.random.PRNGKey(1), (4, 17)),
        }
        ref = jax.tree.map(lambda x: x.mean(axis=0), g_global)
        proto = get_exchange("reduce_scatter")
        ctx = ExchangeContext(axis="data", num_peers=4)

        def body(g):
            per_peer = jax.tree.map(lambda x: x[0], g)
            avg, _ = proto.combine(per_peer, ctx)
            return avg

        fn = jax.shard_map(
            body, mesh=mesh,
            in_specs=(jax.tree.map(lambda _: P("data"), g_global),),
            out_specs=jax.tree.map(lambda _: P(), g_global),
            axis_names={"data"}, check_vma=False,
        )
        with jax.set_mesh(mesh):
            avg = jax.jit(fn)(g_global)
        err = max(
            float(jnp.abs(a - b).max())
            for a, b in zip(jax.tree.leaves(avg), jax.tree.leaves(ref))
        )
        assert err <= 1e-6, err
        print("OK", err)
        """
    )
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = SRC
    r = subprocess.run(
        [sys.executable, "-c", script], env=env, capture_output=True,
        text=True, timeout=600,
    )
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout
