"""Weights and data from the seed: the one generator every traffic file
feeds.

A traffic file gives ``peers``, ``batch_per_peer``, ``batches_per_peer``
(n, a peer's partition) and ``accum_steps`` (m, the batches a peer trains
on in one round). Each peer's partition is n batches of CIFAR-shaped
images, drawn around one template per class so the labels can be learned;
the templates depend on the seed alone, the noise and labels on the seed
and the peer. Round t trains every peer on its block t mod (n / m) of m
consecutive batches.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def seed_keys(seed: int):
    """Keys for the weights, the train state and the data, from a seed of
    any size, 32 bits and more."""
    if seed < 0:
        raise ValueError(f"seed must be a whole number >= 0, got {seed}")
    base = jax.random.PRNGKey(np.uint32(seed & 0xFFFFFFFF))
    base = jax.random.fold_in(base, np.uint32((seed >> 32) & 0xFFFFFFFF))
    return {name: jax.random.fold_in(base, i)
            for i, name in enumerate(("weights", "state", "data"))}


def blocks(traffic) -> int:
    n, m = traffic["batches_per_peer"], traffic["accum_steps"]
    if n % m:
        raise ValueError(f"accum_steps {m} must divide batches_per_peer {n}")
    return n // m


def peer_pool(key, r, *, traffic, mcfg):
    """Peer r's partition: images (n, b, H, W, C) f32, labels (n, b) i32."""
    n, b = traffic["batches_per_peer"], traffic["batch_per_peer"]
    side, ch, classes = mcfg["image_size"], mcfg["image_channels"], mcfg["num_classes"]
    kt, kp = jax.random.split(key)
    templates = jax.random.normal(kt, (classes, side, side, ch), jnp.float32)
    kl, kx = jax.random.split(jax.random.fold_in(kp, r))
    labels = jax.random.randint(kl, (n, b), 0, classes, jnp.int32)
    images = jax.random.normal(kx, (n, b, side, side, ch), jnp.float32) + templates[labels]
    return images, labels


def peer_block(key, r, j, *, traffic, mcfg):
    """Peer r's block j: (m, b, ...) images and (m, b) labels."""
    m = traffic["accum_steps"]
    x, y = peer_pool(key, r, traffic=traffic, mcfg=mcfg)
    return (jax.lax.dynamic_slice_in_dim(x, j * m, m),
            jax.lax.dynamic_slice_in_dim(y, j * m, m))


def make_blocks(key, *, traffic, mcfg, sharding):
    """Every round's batch, on the device in one call: a tuple of n / m
    dicts of (P * m * b, ...) arrays, peer r's m * b rows contiguous, laid
    out with ``sharding`` (one peer's rows on its own devices)."""
    P, nb = traffic["peers"], blocks(traffic)

    def gen(key):
        xs, ys = zip(*[peer_pool(key, r, traffic=traffic, mcfg=mcfg) for r in range(P)])
        x, y = jnp.stack(xs), jnp.stack(ys)  # (P, n, b, ...)
        x = x.reshape((P, nb, -1) + x.shape[3:]).swapaxes(0, 1)  # (nb, P, m*b, ...)
        y = y.reshape(P, nb, -1).swapaxes(0, 1)
        return tuple({"images": x[j].reshape((-1,) + x.shape[3:]), "labels": y[j].reshape(-1)}
                     for j in range(nb))

    return jax.jit(gen, out_shardings=sharding)(key)


def make_params(key, model, mcfg, sharding):
    """The model's weights, on the device in one call."""
    return jax.jit(partial(model.init, cfg=mcfg), out_shardings=sharding)(key)
