"""The system under test: the program's P2P trainer for one cell. The only
module of the benchmark that imports the program.

It builds ``P2PTrainer(...).step_fn`` for the cell's configuration and
traffic, jitted with explicit shardings on a (peers, lambdas) mesh, and
reads what the correctness check needs from the program's train state.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from repro.configs import get_config
from repro.core.compression import QSGDConfig
from repro.core.p2p import Topology
from repro.launch.compile_cache import enable_compile_cache
from repro.optim import sgd
from repro.optim.schedules import constant
from repro.train import P2PTrainer


class System:
    def __init__(self, mcfg, traffic, devices):
        peers, lambdas = traffic["peers"], traffic["lambdas"]
        self.cache_dir = enable_compile_cache()
        self.mesh = jax.make_mesh((peers, lambdas), ("data", "model"),
                                  axis_types=(AxisType.Auto,) * 2,
                                  devices=devices[:peers * lambdas])
        q = traffic.get("qsgd")
        topo = Topology(
            peer_axes=("data",), lambda_axis="model" if lambdas > 1 else None,
            exchange=traffic["exchange"], ef=traffic["ef"],
            qsgd=QSGDConfig(levels=q["levels"], bucket=q["bucket"], impl=q["impl"]) if q else None,
            accum_steps=traffic["accum_steps"], serverless=lambdas > 1,
        )
        opt = mcfg["optimizer"]
        self.trainer = P2PTrainer(get_config(mcfg["program_config"]),
                                  sgd(momentum=opt["momentum"]), topo, self.mesh,
                                  constant(opt["lr"]))
        self.rep = NamedSharding(self.mesh, P())
        self.data = NamedSharding(self.mesh, P("data"))
        self.shapes = jax.eval_shape(self.trainer.init_state, jax.random.PRNGKey(0))
        self.state_sh = jax.tree.map(lambda _: self.rep, self.shapes)
        self.step = None

    def make_state(self, params, key):
        """The program's train state around the benchmark's weights and key."""
        want = jax.tree.map(lambda s: (s.shape, s.dtype), self.shapes.params)
        got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
        if jax.tree.structure(want) != jax.tree.structure(got) or want != got:
            raise ValueError("the benchmark's weights do not match the program's parameters")

        def build(params, key):
            zeros = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), self.shapes)
            return zeros.replace(params=params, key=key,
                                 opt_state=self.trainer.optimizer.init(params))

        return jax.jit(build, out_shardings=self.state_sh)(params, key)

    def compile(self, batch):
        """Compile the step for this cell's batch: the one program the
        window drives."""
        fn = jax.jit(self.trainer.step_fn,
                     in_shardings=(self.state_sh, jax.tree.map(lambda _: self.data, batch)),
                     out_shardings=(self.state_sh, self.rep))
        shapes = jax.tree.map(lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
                              self.shapes, self.state_sh)
        with jax.set_mesh(self.mesh):
            self.step = fn.lower(shapes, batch).compile()
        return self.step

    def leaf_norms(self):
        """jit: (tree, tree | None) -> (devices, leaves) norms of every
        device's own copy of ``a`` (or of ``a - b``)."""
        axes = self.mesh.axis_names

        def local(a, b):
            if b is not None:
                a = jax.tree.map(jnp.subtract, a, b)
            return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                              for x in jax.tree.leaves(a)])[None]

        fn = jax.shard_map(local, mesh=self.mesh, in_specs=(P(), P()), out_specs=P(axes),
                           check_vma=False)
        return jax.jit(fn)

    @staticmethod
    def momentum(state):
        """The optimizer's state: after the first step, the first gradient
        as the optimizer got it (SGD momentum starts from zeros)."""
        return state.opt_state

    @staticmethod
    def loss(metrics):
        return metrics["loss"]
