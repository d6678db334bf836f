"""End-to-end driver: distributed P2P training of a ~100M-parameter LM for a
few hundred steps with the TPU-native serverless-P2P train step.

Peers = the `data` mesh axis (each holds a disjoint partition); the `model`
axis is the serverless lambda pool (micro-batch fan-out). On this CPU
container the mesh is 1x1 and the arch is a ~100M-param variant; on a TPU
slice the same code runs the full configs on the production mesh.

    PYTHONPATH=src python examples/p2p_serverless_train.py --steps 200
"""
import sys, os
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core.compression import QSGDConfig
from repro.core.convergence import ConvergenceDetector
from repro.core.exchange import available_exchanges
from repro.core.p2p import Topology
from repro.data import BatchKey, DataLoader, Partitioner, make_dataset
from repro.launch.mesh import make_host_mesh
from repro.launch.sharding import activation_rules
from repro.configs.base import ShapeConfig
from repro.models.layers import axis_rules
from repro.optim import adam
from repro.optim.schedules import warmup_cosine
from repro.train import P2PTrainer


def hundred_m_config():
    """~100M-param decoder LM in the qwen2.5 family (107M params)."""
    base = get_config("qwen2.5-3b")
    return dataclasses.replace(
        base, name="qwen-100m", num_layers=10, d_model=640, num_heads=10,
        num_kv_heads=2, d_ff=2560, vocab_size=32_768, head_dim=64, remat=False,
        serve_window=0,
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--exchange", default="qsgd",
                    choices=list(available_exchanges()))
    ap.add_argument("--checkpoint", default="/tmp/p2p_lm_ckpt")
    args = ap.parse_args()

    cfg = hundred_m_config()
    mesh = make_host_mesh()
    npeers = mesh.shape["data"]
    topo = Topology(
        peer_axes=("data",) if npeers > 1 else (),
        lambda_axis="model" if mesh.shape["model"] > 1 else None,
        exchange=args.exchange,
        qsgd=QSGDConfig(levels=127, bucket=2048),
        serverless=mesh.shape["model"] > 1,
        grad_clip=1.0,
    )
    opt = adam()
    sched = warmup_cosine(1e-3, 20, args.steps)
    trainer = P2PTrainer(cfg, opt, topo, mesh, sched)
    state = trainer.init_state(jax.random.PRNGKey(0))
    nparams = sum(x.size for x in jax.tree.leaves(state.params))
    print(f"model: {cfg.name} ({nparams/1e6:.1f}M params), "
          f"peers={npeers}, exchange={args.exchange}")
    if topo.peer_axes:
        print(f"wire: {trainer.comm_cost(state.params).summary()}")

    ds = make_dataset("lm", size=100_000, vocab_size=cfg.vocab_size, seq_len=args.seq)
    loader = DataLoader(Partitioner(ds, 1), 0, args.batch)
    detector = ConvergenceDetector(1e-3, mode="min", plateau_patience=5,
                                   stop_patience=20, max_epochs=10**6)

    rules = activation_rules(cfg, ShapeConfig("ex", args.seq, args.batch, "train"), mesh)
    t0 = time.time()
    with jax.set_mesh(mesh):
        with axis_rules(rules):
            for i in range(args.steps):
                b = loader.load(BatchKey(0, i // loader.num_batches, i % loader.num_batches))
                batch = {"tokens": jnp.asarray(b["tokens"]), "labels": jnp.asarray(b["labels"])}
                state, m = trainer.step(state, batch)
                if (i + 1) % 20 == 0 or i == 0:
                    ce = float(m["aux"])
                    dt = (time.time() - t0) / (i + 1)
                    toks = args.batch * args.seq / dt
                    print(f"step {i+1:4d}  ce={ce:.4f}  {dt*1e3:.0f} ms/step "
                          f"({toks:,.0f} tok/s)")
                    if detector.step(ce):
                        print("converged — early stop")
                        break
    trainer.save(args.checkpoint, state)
    print(f"checkpoint saved: {args.checkpoint}.npz")


if __name__ == "__main__":
    main()
