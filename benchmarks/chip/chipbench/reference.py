"""Plain reference of the P2P round (the paper's Algorithm 1) that the
benchmark compares the program with, and the numbers it compares.

One round, for every peer r of P on its own batches: the loss and its
gradient (the mean over the round's ``accum`` micro-batches), then the
exchange, then SGD with momentum. Exchanges:

- ``allgather_mean``: the mean of the P gradients.
- ``qsgd`` (Alistarh et al. 2017): each peer adds its error-feedback
  residual e_r to its gradient, cuts the sum into buckets of ``bucket``
  values, and rounds |v_i| / ||bucket|| * s stochastically to an integer
  level with the uniform draw u_i; the round's gradient is the mean of the
  P decoded payloads, and e_r becomes the sum minus its own decoded image.
  The draws come from the train state's key: folded with the step, then
  with the peer, then split once per parameter leaf in the pytree's order.

The arithmetic is an ``Ops``. The reference computes as the configuration
states: float32 values, each convolution and matrix product at XLA's
default precision (on a TPU one bfloat16 pass, float32 accumulation). The
control, the precision below, takes the products' operands in fp8.

Nothing here imports the program under test. Everything runs one peer and
one micro-batch at a time, so it fits beside nothing else on one chip.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

Z_LOSS = 1e-4
PRECISION = lax.Precision.DEFAULT
FP8, FP8_MAX = jnp.float8_e4m3fn, 448.0  # e4m3 and its largest finite value


def _round(x):
    """x on the fp8 grid with one scale for the tensor (its largest
    magnitude maps to fp8's largest value), back in float32."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / s).astype(FP8).astype(jnp.float32) * s


@jax.custom_vjp
def _rounded(x):
    """Rounded going forward; the gradient passes straight through."""
    return _round(x)


_rounded.defvjp(lambda x: (_round(x), None), lambda _, g: (g,))


@jax.custom_vjp
def _grad_rounded(y):
    """Unchanged going forward; the gradient is rounded going back, so the
    backward pass's products take rounded operands too."""
    return y


_grad_rounded.defvjp(lambda y: (y, None), lambda _, g: (_round(g),))


@dataclass(frozen=True)
class Ops:
    """How the reference computes each convolution and matrix product: on
    float32 values at XLA's default precision, and with ``fp8`` its operands
    rounded to fp8 (one scale per tensor) in the forward and the backward
    pass. fp8 values are exact in bfloat16, so those products are exact."""

    fp8: bool = False

    def _product(self, f, x, w):
        if not self.fp8:
            return f(x, w)
        return _grad_rounded(f(_rounded(x), _rounded(w)))

    def conv(self, x, w):
        return self._product(
            lambda a, b: lax.conv_general_dilated(
                a, b, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
                precision=PRECISION), x, w)

    def dot(self, x, w):
        return self._product(lambda a, b: jnp.dot(a, b, precision=PRECISION), x, w)


REFERENCE = Ops()
# The control: the precision below the configuration's, put in the
# program's place. The products run at XLA's default precision, one
# bfloat16 pass on a TPU, so the control takes their operands in fp8.
CONTROL = Ops(fp8=True)


def as_program(readings):
    """``run_rounds``'s readings in the form ``compare`` takes a program's."""
    losses, first, change, grad = readings
    return {"losses": losses, "grad_norms": first, "change_norms": change, "grad": grad}


def loss_fn(params, images, labels, model, mcfg, ops):
    logits = model.forward(params, images, mcfg, ops=ops).astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(lse - gold) + Z_LOSS * jnp.mean(jnp.square(lse))


def peer_gradient(params, images, labels, *, model, mcfg, ops):
    """Mean loss and gradient over micro-batches: images (m, b, H, W, C)."""
    vg = jax.value_and_grad(partial(loss_fn, model=model, mcfg=mcfg, ops=ops))
    m = images.shape[0]

    def body(carry, xy):
        l, g = vg(params, *xy)
        acc_g, acc_l = carry
        return (jax.tree.map(lambda a, b: a + b.astype(jnp.float32) / m, acc_g, g),
                acc_l + l / m), None

    zeros = jax.tree.map(jnp.zeros_like, params)
    (g, l), _ = lax.scan(body, (zeros, jnp.zeros((), jnp.float32)), (images, labels))
    return l, g


def qsgd_encode(x, u, s):
    """x, u: (nb, bucket) -> int levels in [-s, s] and bucket norms."""
    norms = jnp.sqrt(jnp.sum(x * x, axis=-1))
    r = jnp.abs(x) / jnp.maximum(norms, 1e-30)[:, None] * s
    low = jnp.floor(r)
    lev = jnp.clip(low + (u < r - low), 0, s) * jnp.sign(x)
    return lev, norms


def qsgd_decode(lev, norms, s):
    return lev * (norms / s)[:, None]


def buckets_of(x, bucket):
    flat = x.reshape(-1)
    return jnp.pad(flat, (0, -flat.size % bucket)).reshape(-1, bucket)


@partial(jax.jit, static_argnames=("s", "bucket"))
def qsgd_peer(grads, ef_r, key, *, s, bucket):
    """One peer's payload: (decoded payload, new residual), leaf by leaf."""
    leaves, tdef = jax.tree.flatten(grads)
    keys = jax.random.split(key, len(leaves))
    dec, res = [], []
    for g, e, k in zip(leaves, jax.tree.leaves(ef_r), keys):
        c = g + e
        b = buckets_of(c, bucket)
        u = jax.random.uniform(k, b.shape, jnp.float32)
        img = qsgd_decode(*qsgd_encode(b, u, s), s).reshape(-1)[: c.size].reshape(c.shape)
        dec.append(img)
        res.append(c - img)
    return tdef.unflatten(dec), tdef.unflatten(res)


@jax.jit
def sgd_momentum(params, mom, avg, lr, momentum):
    mom = jax.tree.map(lambda m, g: momentum * m + g, mom, avg)
    return jax.tree.map(lambda p, m: p - lr * m, params, mom), mom


def run_rounds(params, key, peer_batches, rounds, *, model, mcfg, traffic, opt,
               ops=REFERENCE, fault=None):
    """``rounds`` P2P rounds from ``params`` (f32) and the state key.

    ``peer_batches(r, t)`` gives peer r's (images (m, b, ...), labels (m, b))
    of round t. ``fault="half_batch"`` plants the fault the comparison must
    catch: each peer uses the first half of every micro-batch.

    Returns the per-round losses (mean over peers), the per-leaf norms of
    the first round's exchanged gradient and of the parameters' change over
    all rounds: float64 arrays (rounds,), (1, leaves) and (1, leaves); and
    the first exchanged gradient itself, as a list of numpy leaves.
    """
    grad = jax.jit(partial(peer_gradient, model=model, mcfg=mcfg, ops=ops))
    P = traffic["peers"]
    q = traffic.get("qsgd")
    mom = jax.tree.map(jnp.zeros_like, params)
    ef = [jax.tree.map(jnp.zeros_like, params) for _ in range(P)]
    p0, losses, first = params, [], None
    for t in range(rounds):
        step_key = jax.random.fold_in(key, t)
        outs, ls = [], []
        for r in range(P):
            x, y = peer_batches(r, t)
            if fault == "half_batch":
                x, y = x[:, : x.shape[1] // 2], y[:, : y.shape[1] // 2]
            l, g = grad(params, x, y)
            ls.append(float(l))
            if q:
                g, ef[r] = qsgd_peer(g, ef[r], jax.random.fold_in(step_key, r),
                                     s=q["levels"], bucket=q["bucket"])
            outs.append(g)
        losses.append(float(np.mean(ls)))
        mean = jax.tree.map(lambda *gs: sum(gs) / P, *outs)
        if first is None:
            first = leaf_norms(mean)[None]
            first_grad = [np.asarray(x) for x in jax.tree.leaves(mean)]
        params, mom = sgd_momentum(params, mom, mean, opt["lr"], opt["momentum"])
    change = leaf_norms(jax.tree.map(jnp.subtract, params, p0))[None]
    return np.asarray(losses), first, change, first_grad


@jax.jit
def _leaf_norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


def leaf_norms(tree):
    return np.asarray(_leaf_norms(tree), np.float64)


# ---------------------------------------------------------------------------
# The numbers compared
# ---------------------------------------------------------------------------

QUIET = 1e-3  # a leaf whose reference gradient is under this share of the
# median leaf's is nought to rounding (a conv bias before batch norm): its
# change is left out of change_norm_gap


def leaf_gaps(got, want):
    """Per leaf, the worst row's |got - want| over max(want, median of want)."""
    scale = np.maximum(want, np.median(want))
    return np.max(np.abs(np.atleast_2d(got) - want) / scale, axis=0)


def grad_dists(got, want):
    """Per leaf, the norm of the difference between two gradients (lists of
    leaves)."""
    return np.asarray([np.linalg.norm(np.asarray(a, np.float64) - np.asarray(b, np.float64))
                       for a, b in zip(got, want)])


def compare(prog, ref):
    """The numbers that may decide ``correct`` (``limits/<cell>.json`` says
    which do in a cell). ``prog`` holds the program's readings: ``losses``
    (rounds,), ``grad_norms`` and ``change_norms`` (devices, leaves), every
    device's copy, and ``grad``, device 0's first exchanged gradient; ``ref``
    is what ``run_rounds`` returned for the sound reference.

    - ``loss_gap``: the worst round's relative loss gap;
    - ``grad_norm_gap``: the worst leaf's gap between the norms of the
      first exchanged gradient;
    - ``change_norm_gap``: the worst leaf's gap between the norms of the
      parameters' change over the checked rounds, quiet leaves left out;
    - ``grad_dist_gap``: the worst leaf's norm of the difference between the
      first exchanged gradients, over the same scale as the norm gaps.
    """
    losses_r, grad_r, change_r = ref[0], ref[1][0], ref[2][0]
    loud = grad_r >= QUIET * np.median(grad_r)
    dist = grad_dists(prog["grad"], ref[3])
    return {
        "loss_gap": float(np.max(np.abs(np.asarray(prog["losses"]) - losses_r)
                                 / np.abs(losses_r))),
        "grad_norm_gap": float(leaf_gaps(prog["grad_norms"], grad_r).max()),
        "change_norm_gap": float(leaf_gaps(np.asarray(prog["change_norms"])[:, loud],
                                           change_r[loud]).max()),
        "grad_dist_gap": float(np.max(dist / np.maximum(grad_r, np.median(grad_r)))),
    }
