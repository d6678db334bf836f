"""Async (staleness-K) P2P exchange in the distributed JAX path —
multi-device semantics run in a subprocess (8 fake devices)."""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


@pytest.mark.slow
def test_async_mailbox_exchange_multidevice():
    script = textwrap.dedent(
        """
        import jax, jax.numpy as jnp
        from jax import make_mesh, set_mesh
        from jax.sharding import AxisType
        from repro.configs import get_config, reduced
        from repro.core.p2p import Topology, init_mailbox
        from repro.train import build_train_step, init_train_state
        from repro.optim import sgd
        from repro.optim.schedules import constant
        from repro.models.layers import axis_rules

        mesh = make_mesh((4, 2), ("data", "model"), axis_types=(AxisType.Auto,)*2)
        cfg = reduced(get_config("qwen2.5-3b"), num_layers=1, d_model=64, vocab_size=64)
        opt = sgd(momentum=0.0)
        state = init_train_state(jax.random.PRNGKey(0), cfg, opt)
        batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, 64),
                 "labels": jax.random.randint(jax.random.PRNGKey(2), (8, 16), 0, 64)}
        rules = {"batch": ("data",), "embed": None, "ff": None, "heads": None,
                 "kv_heads": None, "experts": None, "vocab": None, "kv_seq": None,
                 "seq": None}

        # async topology with a staleness-1 mailbox ring in the train state
        topo = Topology(peer_axes=("data",), lambda_axis="model", exchange="async")
        astate = state.replace(mailbox=init_mailbox(state.params, 4))
        step_a = build_train_step(cfg, opt, topo, mesh, constant(1e-2))

        # sync reference
        topo_s = Topology(peer_axes=("data",), lambda_axis="model", exchange="psum_mean")
        step_s = build_train_step(cfg, opt, topo_s, mesh, constant(1e-2))

        with set_mesh(mesh):
            with axis_rules(rules):
                s1, m1 = jax.jit(step_a)(astate, batch)
                s2, m2 = jax.jit(step_a)(s1, batch)
                ss, ms = jax.jit(step_s)(state, batch)

        # step 1: mailbox was zeros -> effective grad = own/P, so async
        # params differ from sync (which averages fresh gradients)
        d = max(float(jnp.abs(a - b).max()) for a, b in zip(
            jax.tree.leaves(s1["params"]), jax.tree.leaves(ss["params"])))
        assert d > 0, "async step should differ from sync on a cold mailbox"
        # mailbox ring was refreshed with the step's gradients: (K=1, P=4, ...)
        mb = jax.tree.leaves(s1["mailbox"])[0]
        assert mb.shape[:2] == (1, 4), mb.shape
        assert float(jnp.abs(mb).max()) > 0
        assert bool(jnp.isfinite(m2["loss"]))

        # staleness-2: the bank consumed at step t was published at t-2, so
        # after one step the ring's oldest slot is still the zero bank and
        # the fresh bank sits in slot 1
        topo2 = Topology(peer_axes=("data",), lambda_axis="model", exchange="async",
                         staleness=2)
        astate2 = state.replace(mailbox=init_mailbox(state.params, 4, staleness=2))
        step_2 = build_train_step(cfg, opt, topo2, mesh, constant(1e-2))
        with set_mesh(mesh):
            with axis_rules(rules):
                t1, _ = jax.jit(step_2)(astate2, batch)
        ring = jax.tree.leaves(t1["mailbox"])[0]
        assert ring.shape[:2] == (2, 4), ring.shape
        assert float(jnp.abs(ring[0]).max()) == 0.0  # still the cold bank
        assert float(jnp.abs(ring[1]).max()) > 0     # fresh publication
        # step-1 params agree with staleness-1 (both consumed a zero bank)
        dk = max(float(jnp.abs(a - b).max()) for a, b in zip(
            jax.tree.leaves(t1["params"]), jax.tree.leaves(s1["params"])))
        assert dk == 0.0, dk
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
