"""The CNNs' max pool against ``lax.reduce_window(max)`` and its autodiff.

Windows that tile the map take ``cnn._tiled_max_pool``, whose backward is an
elementwise select on the first maximum's window index. Its values and its
``jax.vjp`` must equal ``reduce_window``'s bit for bit, ties included; every
other shape keeps ``reduce_window`` itself.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from repro.configs import get_config
from repro.core.simulate import cnn_loss
from repro.models import cnn


def reference_pool(x, window=2, stride=2):
    return lax.reduce_window(
        x, -jnp.inf, lax.max, (1, window, window, 1), (1, stride, stride, 1), "VALID"
    )


def bits(a):
    return np.asarray(a, np.float32).view(np.uint32)


def assert_bit_equal(got, want):
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_array_equal(bits(got), bits(want))


def random_maps(key, shape):
    return jax.random.normal(key, shape, jnp.float32)


def post_relu_maps(key, shape):
    """ReLU outputs where whole 2x2 windows are zero (about half of them)."""
    x = jax.nn.relu(jax.random.normal(key, shape, jnp.float32))
    n, h, w, c = shape
    dead = jax.random.bernoulli(jax.random.fold_in(key, 1), 0.5, (n, h // 2, w // 2, c))
    dead = jnp.repeat(jnp.repeat(dead, 2, axis=1), 2, axis=2)
    return jnp.where(dead, 0.0, x)


def tied_maps(key, shape):
    """Positive maps whose 2x2 windows hold their maximum at 1..4 positions:
    window number i (counted over n, h, w, c) ties at the positions set in
    the 4-bit pattern 1 + i % 15, so every non-empty subset of the window
    positions, and so every position as first maximum, occurs."""
    n, h, w, c = shape
    low = jax.random.uniform(key, (n, h, w, c), jnp.float32, 0.5, 1.5)
    pattern = 1 + jnp.arange(n * (h // 2) * (w // 2) * c).reshape(n, h // 2, w // 2, c) % 15
    up = lambda a: jnp.repeat(jnp.repeat(a, 2, axis=1), 2, axis=2)
    pos = (jnp.arange(h)[:, None] % 2) * 2 + jnp.arange(w)[None, :] % 2  # (h, w)
    at_max = (up(pattern) >> pos[None, :, :, None]) & 1
    return jnp.where(at_max == 1, 2.0, low)


MAPS = {"random": random_maps, "post_relu": post_relu_maps, "ties": tied_maps}


def _wrapped(how, fn):
    if how == "eager":
        return fn
    if how == "jit":
        return jax.jit(fn)
    # inside a lax.scan over micro-batches, as the accumulation path runs it
    def scanned(xs):
        return lax.scan(lambda c, x: (c, fn(x)), 0, xs)[1]

    return lambda x: jax.jit(scanned)(x[None])[0]


@pytest.mark.parametrize("how", ["eager", "jit", "scan"])
@pytest.mark.parametrize("n,c", [(1, 3), (64, 3), (1, 64), (64, 64), (1, 512), (64, 512)])
@pytest.mark.parametrize("maps", list(MAPS))
def test_tiled_pool_matches_reduce_window(maps, n, c, how):
    key = jax.random.PRNGKey(n * 1000 + c)
    x = MAPS[maps](key, (n, 8, 6, c))
    y, vjp = jax.vjp(_wrapped(how, cnn.max_pool), x)
    y_ref, vjp_ref = jax.vjp(reference_pool, x)
    assert_bit_equal(y, y_ref)
    g = jax.random.normal(jax.random.fold_in(key, 2), y.shape, jnp.float32)
    assert_bit_equal(vjp(g)[0], vjp_ref(g)[0])


def test_tied_windows_route_to_the_first_maximum():
    """A window of four equal maxima sends the whole gradient to its
    top-left element, as ``select_and_scatter`` with a ``ge`` select does."""
    x = jnp.ones((1, 2, 2, 1), jnp.float32)
    _, vjp = jax.vjp(cnn.max_pool, x)
    g = vjp(jnp.full((1, 1, 1, 1), 3.0))[0]
    np.testing.assert_array_equal(np.asarray(g)[0, :, :, 0], [[3.0, 0.0], [0.0, 0.0]])


@pytest.mark.parametrize("shape,window,stride", [
    ((2, 9, 9, 5), 3, 2),   # SqueezeNet 1.1's overlapping pool
    ((2, 8, 8, 5), 3, 2),
    ((2, 7, 8, 5), 2, 2),   # odd H
    ((2, 8, 7, 5), 2, 2),   # odd W
])
def test_other_shapes_keep_reduce_window(shape, window, stride):
    x = jax.nn.relu(random_maps(jax.random.PRNGKey(3), shape))
    pool = lambda x: cnn.max_pool(x, window, stride)
    y, vjp = jax.vjp(pool, x)
    y_ref, vjp_ref = jax.vjp(lambda x: reference_pool(x, window, stride), x)
    assert_bit_equal(y, y_ref)
    g = jax.random.normal(jax.random.PRNGKey(4), y.shape, jnp.float32)
    assert_bit_equal(vjp(g)[0], vjp_ref(g)[0])
    hlo = jax.jit(jax.grad(lambda x: pool(x).sum())).lower(x).as_text()
    assert "select_and_scatter" in hlo


def _batch(cfg, n=4):
    k1, k2 = jax.random.split(jax.random.PRNGKey(5))
    return {
        "images": jax.random.normal(k1, (n, cfg.image_size, cfg.image_size, 3), jnp.float32),
        "labels": jax.random.randint(k2, (n,), 0, cfg.num_classes),
    }


def test_vgg11_gradient_is_bit_identical_with_reduce_window(monkeypatch):
    """Op by op. Under ``jit``, XLA's CPU backend splits a fused reduction
    over threads by the fusion's cost, so a different fusion around conv 0's
    bias sum may reassociate it (1 ulp); the pool's own vjp is compared
    under ``jit`` above."""
    cfg = get_config("vgg11")
    params = cnn.init_cnn(jax.random.PRNGKey(6), cfg)
    batch = _batch(cfg)
    grad = jax.grad(lambda p: cnn_loss(p, batch, cfg)[0])
    got = grad(params)
    monkeypatch.setattr(cnn, "max_pool", reference_pool)
    want = grad(params)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert_bit_equal(a, b)


@pytest.mark.parametrize("arch,count", [("vgg11", 0), ("squeezenet1.1", 2)])
def test_select_and_scatter_only_where_windows_overlap(arch, count):
    """vgg11's five 2x2 pools lower with no ``select_and_scatter``;
    SqueezeNet 1.1 at 32 px keeps one for each of its two 3/2 pools (after
    fires 1 and 3)."""
    cfg = get_config(arch)
    params = jax.eval_shape(lambda k: cnn.init_cnn(k, cfg), jax.random.PRNGKey(0))
    batch = jax.eval_shape(lambda: _batch(cfg))
    hlo = jax.jit(jax.grad(lambda p, b: cnn_loss(p, b, cfg)[0])).lower(params, batch).as_text()
    assert hlo.count("stablehlo.select_and_scatter") == count
