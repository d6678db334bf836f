"""P2P exchange semantics. Multi-device collective behaviour runs in a
subprocess (so the 8-device XLA flag never leaks into this process);
host-level Algorithm-1 semantics run in-process via LocalP2PCluster."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import LocalP2PCluster, QSGDConfig
from repro.data import make_dataset
from repro.optim import sgd

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


@pytest.mark.slow
def test_exchange_modes_equivalent_multidevice():
    """allgather_mean (paper) == psum_mean (optimized) bit-for-bit, and the
    qsgd + async exchanges lower and run — on an 8-device mesh."""
    script = textwrap.dedent(
        """
        import jax, jax.numpy as jnp
        from jax import make_mesh, set_mesh
        from jax.sharding import AxisType
        from repro.configs import get_config, reduced
        from repro.core.p2p import Topology
        from repro.core.compression import QSGDConfig
        from repro.train import build_train_step, init_train_state
        from repro.optim import sgd
        from repro.optim.schedules import constant
        from repro.models.layers import axis_rules

        mesh = make_mesh((4, 2), ("data", "model"), axis_types=(AxisType.Auto,)*2)
        cfg = reduced(get_config("qwen2.5-3b"))
        opt = sgd(momentum=0.9)
        state = init_train_state(jax.random.PRNGKey(0), cfg, opt)
        batch = {"tokens": jnp.zeros((8, 32), jnp.int32),
                 "labels": jnp.ones((8, 32), jnp.int32)}
        rules = {"batch": ("data",), "embed": None, "ff": None, "heads": None,
                 "kv_heads": None, "experts": None, "vocab": None, "kv_seq": None}
        outs = {}
        for mode in ("allgather_mean", "psum_mean", "qsgd"):
            topo = Topology(peer_axes=("data",), lambda_axis="model", exchange=mode,
                            qsgd=QSGDConfig(levels=127, bucket=256))
            step = build_train_step(cfg, opt, topo, mesh, constant(1e-2))
            with set_mesh(mesh):
                with axis_rules(rules):
                    s2, m = jax.jit(step)(state, batch)
            outs[mode] = s2["params"]
            assert bool(jnp.isfinite(m["loss"])), mode
        d = max(float(jnp.abs(a - b).max()) for a, b in zip(
            jax.tree.leaves(outs["allgather_mean"]), jax.tree.leaves(outs["psum_mean"])))
        assert d == 0.0, f"allgather vs psum diff {d}"
        dq = max(float(jnp.abs(a - b).max()) for a, b in zip(
            jax.tree.leaves(outs["allgather_mean"]), jax.tree.leaves(outs["qsgd"])))
        assert 0 < dq < 0.1, f"qsgd should be close but not identical: {dq}"
        print("OK")
        """
    )
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    r = subprocess.run(
        [sys.executable, "-c", script], env=env, capture_output=True, text=True,
        timeout=600,
    )
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout


def test_sync_p2p_equals_pooled_sgd():
    """With equal partitions and a sync exchange, P peers stepping together
    must equal single-worker SGD on the pooled batch (Algorithm 1's goal)."""
    cfg = get_config("squeezenet1.1")
    ds = make_dataset("mnist", size=256, image_hw=8, channels=1)
    # 2 peers x 1 batch of 16
    cl2 = LocalP2PCluster(
        cfg, ds, num_peers=2, batch_size=16, batches_per_epoch=1,
        optimizer=sgd(momentum=0.0), lr=0.1, sync=True, seed=3,
    )
    cl2.run_epoch_sync(0)
    # Reference: single peer with both peers' batches
    import jax

    cl1 = LocalP2PCluster(
        cfg, ds, num_peers=2, batch_size=16, batches_per_epoch=1,
        optimizer=sgd(momentum=0.0), lr=0.1, sync=True, seed=3,
    )
    b0 = cl1.peers[0].loader.load(__import__("repro.data", fromlist=["BatchKey"]).BatchKey(0, 0, 0))
    b1 = cl1.peers[1].loader.load(__import__("repro.data", fromlist=["BatchKey"]).BatchKey(1, 0, 0))
    g0, _, _ = cl1._grad(cl1.peers[0].params, jax.tree.map(jnp.asarray, b0))
    g1, _, _ = cl1._grad(cl1.peers[1].params, jax.tree.map(jnp.asarray, b1))
    avg = jax.tree.map(lambda a, b: (a + b) / 2, g0, g1)
    ref_params, _ = cl1._apply(
        cl1.peers[0].params, cl1.peers[0].opt_state, avg, jnp.float32(0.1)
    )
    for a, b in zip(jax.tree.leaves(cl2.peers[0].params), jax.tree.leaves(ref_params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
    # and all peers hold identical models after a sync epoch
    for a, b in zip(
        jax.tree.leaves(cl2.peers[0].params), jax.tree.leaves(cl2.peers[1].params)
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_async_uses_stale_gradients():
    """Async peers consume what's visible at their clock — peers diverge."""
    cfg = get_config("squeezenet1.1")
    ds = make_dataset("mnist", size=256, image_hw=8, channels=1)
    cl = LocalP2PCluster(
        cfg, ds, num_peers=3, batch_size=8, batches_per_epoch=1,
        optimizer=sgd(momentum=0.0), lr=0.05, sync=False,
        peer_speeds=[1.0, 3.0, 9.0], seed=0,
    )
    cl.run_epoch_async(0)
    cl.run_epoch_async(1)
    p0 = jax.tree.leaves(cl.peers[0].params)
    p2 = jax.tree.leaves(cl.peers[2].params)
    diff = max(float(jnp.abs(a - b).max()) for a, b in zip(p0, p2))
    assert diff > 0  # stale consumption -> models diverge between peers


def test_qsgd_cluster_reduces_wire_bytes():
    cfg = get_config("squeezenet1.1")
    ds = make_dataset("mnist", size=128, image_hw=8, channels=1)
    cl = LocalP2PCluster(
        cfg, ds, num_peers=2, batch_size=8, batches_per_epoch=1,
        optimizer=sgd(momentum=0.9), lr=0.05,
        qsgd=QSGDConfig(levels=127, bucket=512), seed=0,
    )
    cl.run_epoch_sync(0)
    assert cl.peers[0].comm_bytes_sent < cl._model_bytes / 3
