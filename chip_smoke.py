"""Bring-up smoke of the P2P train step on a TPU. It checks results and
states no speed.

    python chip_smoke.py              # one chip
    python chip_smoke.py --four-chip  # one v5e 2x2 host, the mesh phase only

One chip, vgg11 at its published widths (32x32x3 input, convs 64-512,
fc 4096), 64 procedural CIFAR images per peer, random weights from a seed:

  (a) a few steps through the training CLI (``repro.launch.train.main``);
  (b) the qsgd (levels 127 and 3) and top-k (1%) exchanges with error
      feedback inside the P2P step: each step with the Pallas codec
      kernels, compiled, against the same step with the ``jnp`` codecs from
      the same state;
  (c) one ``LocalP2PCluster`` epoch of four vgg11 peers (the paper's
      Algorithm-1 host path) with its serverless and instance reports.

Four chips: vgg11 as 4 peers x 1 lambda (allgather_mean, psum_mean,
allgather_mean on a ring, qsgd and top-k kernels with error feedback) and as
2 peers x 2 lambdas (allgather_mean over the lambda fan-out). Lossless
exchanges are checked against a one-device reference that applies the
overlay's mixing matrix to per-peer gradients of the same batches; the
kernels against the ``jnp`` codecs on the same mesh, step by step as above.

Lines before the last are bring-up facts (device, losses, compile seconds).
The last line is ``{"ok": true, "device": {...}}``. Without a TPU, or when a
phase fails, the script exits non-zero and prints no such line.
"""
from __future__ import annotations

import argparse
import contextlib
import io
import json
import math
import re
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from repro.configs import get_config
from repro.core import (
    InstanceConfig,
    LocalP2PCluster,
    ServerlessExecutor,
    compare_backends,
)
from repro.core.compression import QSGDConfig, qsgd_dequantize_ref, qsgd_quantize_ref
from repro.core.p2p import Topology
from repro.data import BatchKey, DataLoader, Partitioner, make_dataset
from repro.kernels import ops
from repro.kernels import ref as kref
from repro.launch.compile_cache import enable_compile_cache
from repro.optim import sgd
from repro.optim.schedules import constant
from repro.train import P2PTrainer

ARCH = "vgg11"
SEED = 0
BATCH_PER_PEER = 64  # the paper's batch size
STEPS = 3
# plain SGD at a rate where vgg11 (no batch norm) descends smoothly: at 1e-2
# the second step overshoots (loss 4.2 -> 43) and amplifies any rounding
# difference between two runs into a different trajectory
LR = 3e-4
# bounds on rel_dist (see below)
LOSSLESS_TOL = 1e-3  # after STEPS steps: same math, other reduction order
# Pallas codec vs jnp codec in the train step, one step at a time: at every
# step both take the state the jnp run reached, so only the codecs'
# arithmetic differs (a bucket norm summed in another order, which can move
# a stochastic-rounding draw across its threshold). Bounds the parameters'
# distance relative to that step's move, and the EF residual's relative to
# its norm. A wrong scale, sign or residual puts them a whole step apart.
KERNEL_TOL = 2e-3
LOSS_TOL = 1e-3  # |loss_a - loss_b| per step
# Codecs on one vgg11 fc2-sized input: at most this share of qsgd levels
# may differ from the jnp codec, each by exactly one level (a flip at a
# rounding threshold, never a wrong value)
CODEC_N = 4096 * 4096
FLIP_SHARE = 1e-3


class SmokeFailure(AssertionError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def fact(msg: str) -> None:
    print(f"[bring-up] {msg}", flush=True)


# ---------------------------------------------------------------------------
# shared pieces
# ---------------------------------------------------------------------------


def peer_batches(num_peers: int, steps: int, per_peer: int):
    """steps x num_peers procedural CIFAR batches, one partition per peer."""
    ds = make_dataset("cifar", seed=SEED)
    part = Partitioner(ds, num_peers, shuffle_seed=SEED)
    loaders = [DataLoader(part, r, per_peer) for r in range(num_peers)]
    return [[loaders[r].load(BatchKey(r, 0, t)) for r in range(num_peers)]
            for t in range(steps)]


def global_batch(per_peer_batches, sharding):
    """Concatenate per-peer batches in peer order and place them on the mesh."""
    return {
        k: jax.device_put(np.concatenate([b[k] for b in per_peer_batches]), sharding)
        for k in ("images", "labels")
    }


def make_topo(exchange: str, impl: str = "jnp", *, levels: int = 0,
              graph: str = "full", lambda_axis=None) -> Topology:
    kw = {}
    if exchange == "qsgd":
        kw["qsgd"] = QSGDConfig(levels=levels, bucket=512, impl=impl)
    if exchange == "topk":
        kw.update(topk_frac=0.01, topk_impl=impl)
    return Topology(
        peer_axes=("data",), lambda_axis=lambda_axis, exchange=exchange,
        graph=graph, ef=exchange in ("qsgd", "topk"),
        serverless=lambda_axis is not None, **kw,
    )


class Run:
    """One P2PTrainer on a mesh: its state, its compiled step, its results."""

    def __init__(self, name: str, topo: Topology, mesh):
        self.name = name
        self.mesh = mesh
        self.trainer = P2PTrainer(get_config(ARCH), sgd(), topo, mesh, constant(LR))
        self.rep = NamedSharding(mesh, P())
        self.data = NamedSharding(mesh, P("data"))
        shapes = jax.eval_shape(self.trainer.init_state, jax.random.PRNGKey(SEED))
        self.state_sh = jax.tree.map(lambda _: self.rep, shapes)
        self.state_shapes = jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            shapes, self.state_sh,
        )
        self.compiled = None
        self.compile_s = 0.0
        self.losses = []
        self.state = self.init_params = None

    def init_state(self):
        return jax.device_put(
            self.trainer.init_state(jax.random.PRNGKey(SEED)), self.state_sh
        )

    def compile(self, batch_shapes) -> "Run":
        t0 = time.perf_counter()
        step = jax.jit(
            self.trainer.step_fn,
            in_shardings=(self.state_sh, jax.tree.map(lambda _: self.data, batch_shapes)),
            out_shardings=(self.state_sh, self.rep),
        )
        with jax.set_mesh(self.mesh):
            self.compiled = step.lower(self.state_shapes, batch_shapes).compile()
        self.compile_s = time.perf_counter() - t0
        return self

    def run(self, batches):
        state = self.init_state()
        self.init_params = state.params
        for per_peer in batches:
            state, metrics = self.compiled(state, global_batch(per_peer, self.data))
            self.losses.append(float(metrics["loss"]))
        self.state = state
        check(all(math.isfinite(x) for x in self.losses), f"{self.name}: loss {self.losses}")
        fact(f"{self.name}: losses {self.losses} (compile {self.compile_s:.1f} s)")
        return self

    def release(self) -> None:
        """Drop the device copies of this run's parameters."""
        self.state = self.init_params = None


def batch_shapes(num_peers: int, per_peer: int, sharding):
    b = num_peers * per_peer
    return {
        "images": jax.ShapeDtypeStruct((b, 32, 32, 3), jnp.float32, sharding=sharding),
        "labels": jax.ShapeDtypeStruct((b,), jnp.int32, sharding=sharding),
    }


def compile_all(runs, shapes):
    """Compile every run's step at once: XLA compiles release the GIL."""
    with ThreadPoolExecutor(len(runs)) as pool:
        return list(pool.map(lambda r: r.compile(shapes), runs))


def per_device_params(params, device):
    """The copy of every (replicated) parameter that ``device`` holds."""
    return [
        np.asarray(next(s.data for s in leaf.addressable_shards if s.device == device))
        for leaf in jax.tree.leaves(params)
    ]


def rel_dist(a, b, base=None) -> float:
    """||a - b|| / ||b - base|| over all leaves (base 0 when None): 0 when a
    and b agree, 1 when they differ by as much as b differs from base."""
    f64 = lambda x: np.asarray(x, np.float64)
    base = base if base is not None else [0.0] * len(b)
    num = sum(float(np.sum((f64(x) - f64(y)) ** 2)) for x, y in zip(a, b))
    den = sum(float(np.sum((f64(y) - f64(z)) ** 2)) for y, z in zip(b, base))
    return math.sqrt(num / den)


def lockstep(name: str, kern: Run, ref: Run, batches) -> None:
    """The kernel-codec step against the jnp-codec step, both taken from the
    state the jnp run reached, at every step and on every device's copy, so
    a difference is caught at the step it arises and never compounds."""
    state = ref.init_state()
    dp, de = [], []  # per step, worst over devices
    for per_peer in batches:
        batch = global_batch(per_peer, ref.data)
        got, got_m = kern.compiled(state, batch)
        want, want_m = ref.compiled(state, batch)
        kern.losses.append(float(got_m["loss"]))
        ref.losses.append(float(want_m["loss"]))
        devs = list(ref.mesh.devices.flat)
        dp.append(max(rel_dist(per_device_params(got.params, d),
                               per_device_params(want.params, d),
                               per_device_params(state.params, d)) for d in devs))
        de.append(max(rel_dist(per_device_params(got.ef, d),
                               per_device_params(want.ef, d)) for d in devs))
        state = want
    dl = max(abs(a - b) for a, b in zip(kern.losses, ref.losses))
    fact(f"{name}: losses {kern.losses} (kernel), {ref.losses} (jnp); compile "
         f"{kern.compile_s:.1f} s / {ref.compile_s:.1f} s")
    check(all(math.isfinite(x) for x in kern.losses + ref.losses),
          f"{name}: loss not finite")
    fmt = lambda xs: "[" + ", ".join(f"{x:.3e}" for x in xs) + "]"
    fact(f"{name}: rel distance per step of the params {fmt(dp)}, of the EF "
         f"residual {fmt(de)} (tol {KERNEL_TOL:g}), max |dloss| {dl:.3e} "
         f"(tol {LOSS_TOL:g})")
    check(max(dp + de) <= KERNEL_TOL and dl <= LOSS_TOL,
          f"{name}: kernel and jnp disagree")


def kernel_calls(run: Run) -> int:
    return run.compiled.as_text().count("tpu_custom_call")


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------


def phase_cli() -> None:
    from repro.launch import train

    out = io.StringIO()
    try:
        with contextlib.redirect_stdout(out):
            state = train.main([
                "--arch", ARCH, "--full", "--exchange", "allgather_mean",
                "--steps", "4", "--batch", str(BATCH_PER_PEER), "--log-every", "1",
                "--data-parallel", "1",
            ])
    finally:
        print(out.getvalue(), end="")
    text = out.getvalue()
    losses = [float(x) for x in re.findall(r"^step\s+\d+ loss (\S+)", text, re.M)]
    check(len(losses) == 4 and all(math.isfinite(x) for x in losses),
          f"CLI losses {losses}")
    check(all(bool(jnp.isfinite(x).all()) for x in jax.tree.leaves(state.params)),
          "CLI params not finite")
    fact(f"(a) CLI {ARCH} --full allgather_mean: losses {losses}")


def codec_agreement() -> None:
    """The compiled codec kernels against the jnp codecs on the same input."""
    key = jax.random.PRNGKey(SEED)
    x = jax.random.normal(key, (CODEC_N // 512, 512), jnp.float32)
    u = jax.random.uniform(jax.random.fold_in(key, 1), x.shape)
    for s in (127, 3):
        lk, nk = ops.qsgd_quantize(x, u, s)
        lr, nr = qsgd_quantize_ref(x, u, s)
        dl = np.abs(np.asarray(lk, np.int32) - np.asarray(lr, np.int32))
        dn = float(jnp.max(jnp.abs(nk - nr) / nr))
        fact(f"(b) qsgd_quantize levels={s}: {int((dl != 0).sum())} of {dl.size} "
             f"levels differ (max by {int(dl.max())}), norms within {dn:.1e}")
        check(dl.max() <= 1 and (dl != 0).mean() <= FLIP_SHARE and dn <= 1e-5,
              f"qsgd_quantize levels={s} disagrees with the jnp codec")
        # the dequantize kernel builds the EF residual's local image
        got, want = ops.qsgd_dequantize(lk, nk, s), qsgd_dequantize_ref(lk, nk, s)
        err = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
        fact(f"(b) qsgd_dequantize levels={s}: max error {err:.1e} of the largest value")
        check(err <= 1e-6, f"qsgd_dequantize levels={s} disagrees with the jnp codec")
    levels = jnp.stack([qsgd_quantize_ref(x * (p + 1), u, 3)[0] for p in range(4)])
    norms = jnp.stack([qsgd_quantize_ref(x * (p + 1), u, 3)[1] for p in range(4)])
    w = jnp.full((4,), 0.25, jnp.float32)
    got = ops.qsgd_dequant_reduce(levels, norms, w, 3)
    want = kref.qsgd_dequant_reduce_ref(levels, norms, w, 3)
    err = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
    fact(f"(b) qsgd_dequant_reduce: max error {err:.1e} of the largest value")
    check(err <= 1e-6, "qsgd_dequant_reduce disagrees with the jnp codec")
    flat = x.reshape(-1)
    k = CODEC_N // 100
    v, i = ops.topk_select_pack(flat, k)
    _, ri = kref.topk_select_ref(flat, k)
    same = bool(jnp.all(jnp.sort(i) == jnp.sort(ri))) and bool(jnp.all(v == flat[i]))
    fact(f"(b) topk_select_pack k={k}: same index set as lax.top_k: {same}")
    check(same, "topk_select_pack disagrees with lax.top_k")
    vals = jnp.stack([v * (p + 1) for p in range(4)])
    idx = jnp.stack([jnp.roll(i, p) for p in range(4)])
    got = ops.topk_scatter_accum(vals, idx, w, CODEC_N)
    want = kref.topk_scatter_ref(vals, idx, w, CODEC_N)
    err = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
    fact(f"(b) topk_scatter_accum: max error {err:.1e} of the largest value")
    check(err <= 1e-6, "topk_scatter_accum disagrees with the jnp codec")


def phase_codecs() -> None:
    check(not ops.default_interpret(), "kernels would run in interpret mode")
    codec_agreement()
    mesh = jax.make_mesh((1, 1), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    cases = [("qsgd levels=127", "qsgd", 127), ("qsgd levels=3", "qsgd", 3),
             ("topk frac=0.01", "topk", 0)]
    runs = [Run(f"(b) {name} {impl}+EF", make_topo(ex, impl, levels=lv), mesh)
            for name, ex, lv in cases for impl in ("kernel", "jnp")]
    compile_all(runs, batch_shapes(1, BATCH_PER_PEER, NamedSharding(mesh, P("data"))))
    batches = peer_batches(1, STEPS, BATCH_PER_PEER)
    for i, (name, _, _) in enumerate(cases):
        kern, ref = runs[2 * i], runs[2 * i + 1]
        nk, nr = kernel_calls(kern), kernel_calls(ref)
        fact(f"(b) {name}: tpu_custom_call in the kernel step {nk}, in the jnp step {nr}")
        check(nk > nr, f"{name}: no compiled Pallas kernel in the step")
        lockstep(f"(b) {name} kernel vs jnp", kern, ref, batches)


def phase_cluster() -> None:
    cluster = LocalP2PCluster(
        get_config(ARCH), make_dataset("cifar", seed=SEED), num_peers=4,
        batch_size=BATCH_PER_PEER, batches_per_epoch=2, optimizer=sgd(momentum=0.9),
        lr=LR, sync=True, exchange="allgather_mean",
        executor=ServerlessExecutor(backend="serverless"), seed=SEED,
    )
    hist = cluster.run(epochs=1)
    loss = hist[-1]["loss"]
    check(math.isfinite(loss) and math.isfinite(hist[-1]["val_loss"]),
          f"cluster loss {hist[-1]}")
    reports = [p.reports[-1] for p in cluster.peers]
    check(len(reports) == 4 and all(r.num_batches == 2 for r in reports),
          "missing serverless reports")
    inst = ServerlessExecutor(
        backend="instance", instance="t2.large", instance_config=InstanceConfig.ideal(),
    ).simulate_instance(reports[0].per_batch_s)
    cmp = compare_backends(reports[0].cost_report(), inst.cost_report())
    check(all(math.isfinite(float(cmp[k])) for k in ("speedup_pct", "cost_multiple")),
          f"backend comparison {cmp}")
    fact(f"(c) LocalP2PCluster 4 x {ARCH}: loss {loss:.4f} val_loss "
         f"{hist[-1]['val_loss']:.4f}; serverless report {reports[0].num_batches} "
         f"invocations x {reports[0].lambda_memory_mb} MB; instance report "
         f"{inst.num_batches} batches on t2.large")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------


def reference_params(run: Run, batches, mixing: np.ndarray):
    """One-device reference of the P2P step with plain SGD: every peer takes
    its gradient on its own batch, then applies row r of the overlay's mixing
    matrix. Returns each peer's parameters as a list of numpy leaves."""
    loss_fn = run.trainer.loss_fn
    grad = jax.jit(jax.grad(lambda p, b: loss_fn(p, b)[0]))
    init = jax.device_get(run.init_params)
    peers = [init] * mixing.shape[0]
    for per_peer in batches:
        gs = [jax.device_get(grad(peers[r], {k: jnp.asarray(v) for k, v in b.items()}))
              for r, b in enumerate(per_peer)]
        peers = [
            jax.tree.map(lambda p, *g: p - LR * sum(w * x for w, x in zip(row, g)),
                         peers[r], *gs)
            for r, row in enumerate(mixing.astype(np.float32))
        ]
    return [jax.tree.leaves(p) for p in peers], jax.tree.leaves(init)


def phase_four_chip() -> None:
    devs = jax.devices()
    check(len(devs) == 4, f"--four-chip needs 4 devices, found {len(devs)}")
    mesh41 = jax.make_mesh((4, 1), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    mesh22 = jax.make_mesh((2, 2), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    lossless = [
        Run("4x1 allgather_mean", make_topo("allgather_mean"), mesh41),
        Run("4x1 psum_mean", make_topo("psum_mean"), mesh41),
        Run("4x1 allgather_mean ring", make_topo("allgather_mean", graph="ring"), mesh41),
    ]
    codecs = [Run(f"4x1 {ex} {impl}+EF", make_topo(ex, impl, levels=3), mesh41)
              for ex in ("qsgd", "topk") for impl in ("kernel", "jnp")]
    fanout = Run("2x2 allgather_mean lambda fan-out",
                 make_topo("allgather_mean", lambda_axis="model"), mesh22)
    with ThreadPoolExecutor(2) as pool:
        jobs = [
            pool.submit(compile_all, lossless + codecs,
                        batch_shapes(4, BATCH_PER_PEER, NamedSharding(mesh41, P("data")))),
            pool.submit(fanout.compile,
                        batch_shapes(2, 2 * BATCH_PER_PEER, NamedSharding(mesh22, P("data")))),
        ]
        for job in jobs:
            job.result()  # re-raises a compile failure

    b4 = peer_batches(4, STEPS, BATCH_PER_PEER)
    b2 = peer_batches(2, STEPS, 2 * BATCH_PER_PEER)
    gb = global_batch(b4[0], NamedSharding(mesh41, P("data")))
    check(all(len(x.sharding.device_set) == 4 for x in gb.values()),
          "the batch does not span 4 devices")

    for run in lossless + [fanout]:
        run.run(b2 if run is fanout else b4)
        outs = jax.tree.leaves(run.state.params)
        check(all(len(x.sharding.device_set) == 4 for x in outs),
              f"{run.name}: outputs do not span 4 devices")
        P_ = run.trainer.num_peers
        peers, init = reference_params(
            run, b2 if run is fanout else b4, run.trainer.graph.mixing_matrix()
        )
        worst = 0.0
        for r in range(P_):
            for dev in run.mesh.devices[r]:
                d = rel_dist(per_device_params(run.state.params, dev), peers[r], init)
                worst = max(worst, d)
        fact(f"{run.name}: {P_} peers on {len(run.mesh.devices.flat)} devices, "
             f"worst rel param distance to the one-device reference {worst:.3e} "
             f"(tol {LOSSLESS_TOL:g})")
        check(worst <= LOSSLESS_TOL, f"{run.name}: disagrees with the reference")
        run.release()

    for i in (0, 2):
        kern, ref = codecs[i], codecs[i + 1]
        check(kernel_calls(kern) > kernel_calls(ref),
              f"{kern.name}: no compiled Pallas kernel in the step")
        lockstep(f"{kern.name} vs jnp", kern, ref, b4)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the 2x2 mesh phase (needs 4 TPU chips)")
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform!r}", file=sys.stderr)
        return 2
    fact(f"cache dir {enable_compile_cache()}")
    fact(f"device_kind {dev.device_kind!r}, {len(jax.devices())} device(s), "
         f"jax {jax.__version__}")
    phases = [phase_four_chip] if args.four_chip else [phase_cli, phase_codecs, phase_cluster]
    for phase in phases:
        t0 = time.perf_counter()
        phase()
        fact(f"{phase.__name__} passed ({time.perf_counter() - t0:.1f} s wall, "
             "including compilation)")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices()),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
