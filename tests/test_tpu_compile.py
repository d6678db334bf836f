"""Compile-only rails against a described TPU v5e (no chip attached).

Every Pallas kernel on the exchange path, the top-k decoder (XLA's
scatter-add), and the vgg11 P2P train step with the codec kernels selected
are compiled for a ``v5e:2x2`` topology with ``interpret=False``. This is
what the TPU compiler refuses and interpret mode cannot see: block shapes that break the (8, 128) tiling rule, lowerings
Mosaic lacks, more VMEM than a kernel may use, a kernel GSPMD would have to
partition. Nothing runs, so nothing here is a result or a time.

The topology is described inside a module fixture, never at import: only
one process may load the TPU compiler library, and the xdist worker that
runs this file is the one that loads it.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.compression import QSGDConfig
from repro.core.p2p import Topology
from repro.kernels import flash_attention as fa
from repro.kernels import ops
from repro.kernels import qsgd, ssd_scan, topk
from repro.optim import sgd
from repro.optim.schedules import constant
from repro.train import P2PTrainer

FC2 = 4096 * 4096  # vgg11's largest leaf
CONV = 3 * 3 * 512 * 512  # its largest conv leaf
BUCKET = 512


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around these
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


# ---------------------------------------------------------------------------
# the kernels alone, at vgg11 leaf sizes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,bucket", [
    pytest.param(FC2, BUCKET, id=str(FC2)),
    pytest.param(CONV, BUCKET, id=str(CONV)),
    pytest.param(40960 + 7, BUCKET, id=str(40960 + 7)),
    pytest.param(FC2, 2048, id=f"{FC2}-2048"),  # QSGDConfig's default bucket
])
def test_qsgd_kernels_compile(one_chip, n, bucket):
    """Blocks sized by ``qsgd.tile_rows`` (up to 1,344 rows of 512, ragged
    last block) lower and fit VMEM."""
    nb = -(-n // bucket)
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    x = S((nb, bucket), jnp.float32)
    _assert_kernel(_compile(lambda b, u: qsgd.qsgd_quantize(b, u, 127, interpret=False), x, x))
    _assert_kernel(_compile(
        lambda l, m: qsgd.qsgd_dequantize(l, m, 3, interpret=False),
        S((nb, bucket), jnp.int8), S((nb,), jnp.float32),
    ))
    _assert_kernel(_compile(
        lambda l, m, w: qsgd.qsgd_dequant_reduce(l, m, w, 3, interpret=False),
        S((4, nb, bucket), jnp.int8), S((4, nb), jnp.float32), S((4,), jnp.float32),
    ))


@pytest.mark.parametrize("n", [FC2, 40960 + 7])
def test_topk_kernels_compile(one_chip, n):
    k = max(1, round(0.01 * n))
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    _assert_kernel(_compile(
        lambda x: topk.topk_select_pack(x, k, interpret=False), S((n,), jnp.float32)
    ))
    # the decoder is XLA's scatter-add, no kernel: it only has to compile
    _compile(
        lambda v, i, w: ops.topk_scatter_accum(v, i, w, n),
        S((4, k), jnp.float32), S((4, k), jnp.int32), S((4,), jnp.float32),
    )


def test_flash_attention_compiles(one_chip):
    S = lambda shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    _assert_kernel(_compile(
        lambda q, k, v: fa.flash_attention(q, k, v, interpret=False),
        S((1, 2048, 8, 128)), S((1, 2048, 2, 128)), S((1, 2048, 2, 128)),
    ))


def test_ssd_scan_compiles(one_chip):
    B, L, H, Pd, G, N = 1, 2048, 32, 64, 1, 128
    S = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    _assert_kernel(_compile(
        lambda *a: ssd_scan.ssd_scan_pallas(*a, chunk=256, interpret=False),
        S((B, L, H, Pd)), S((B, L, H)), S((H,)), S((B, L, G, N)), S((B, L, G, N)),
    ))


# ---------------------------------------------------------------------------
# the vgg11 P2P train step with the codec kernels selected
# ---------------------------------------------------------------------------

# (peers, lambdas, exchange): one described chip, then the 2x2 host as
# 2 peers x 2 lambdas (the lambda axis stays automatic around the kernels)
# and as 4 peers x 1 lambda
STEPS = [(1, 1, "qsgd"), (1, 1, "topk"), (2, 2, "qsgd"), (4, 1, "topk")]


def _step_program(topo_desc, peers, lambdas, exchange):
    devs = np.array(topo_desc.devices[: peers * lambdas]).reshape(peers, lambdas)
    mesh = Mesh(devs, ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    kw = (
        {"qsgd": QSGDConfig(levels=3, bucket=BUCKET, impl="kernel")}
        if exchange == "qsgd" else {"topk_frac": 0.01, "topk_impl": "kernel"}
    )
    tp = Topology(
        peer_axes=("data",), lambda_axis="model" if lambdas > 1 else None,
        exchange=exchange, ef=True, serverless=lambdas > 1, **kw,
    )
    trainer = P2PTrainer(get_config("vgg11"), sgd(), tp, mesh, constant(0.01))
    rep, data = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    state = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=rep),
        jax.eval_shape(trainer.init_state, jax.random.PRNGKey(0)),
    )
    b = 64 * peers * lambdas
    batch = {
        "images": jax.ShapeDtypeStruct((b, 32, 32, 3), jnp.float32, sharding=data),
        "labels": jax.ShapeDtypeStruct((b,), jnp.int32, sharding=data),
    }
    with jax.set_mesh(mesh):
        return jax.jit(trainer.step_fn).lower(state, batch).compile()


@pytest.fixture(scope="module")
def step_programs(topo):
    # steer the kernel entries to their compiled form (the CPU backend
    # would pick interpret mode); compile the four programs concurrently
    mp = pytest.MonkeyPatch()
    mp.setattr(ops, "default_interpret", lambda: False)
    try:
        with ThreadPoolExecutor(len(STEPS)) as pool:
            futs = {s: pool.submit(_step_program, topo, *s) for s in STEPS}
            return {s: f.result() for s, f in futs.items()}
    finally:
        mp.undo()


@pytest.mark.parametrize("peers,lambdas,exchange", STEPS)
def test_vgg11_p2p_step_compiles_with_kernels(step_programs, peers, lambdas, exchange):
    compiled = step_programs[(peers, lambdas, exchange)]
    _assert_kernel(compiled)
    mem = compiled.memory_analysis()
    # params + EF bank + batch, per device, fit a 16 GB v5e with room to spare
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 4 * 2**30


@pytest.mark.parametrize("peers,lambdas,exchange", STEPS)
def test_vgg11_p2p_step_pools_without_select_and_scatter(step_programs, peers, lambdas,
                                                          exchange):
    """vgg11's 2x2 pools tile their maps, so their backward is an
    elementwise select and the step holds no ``select-and-scatter``."""
    assert "select-and-scatter" not in step_programs[(peers, lambdas, exchange)].as_text()
