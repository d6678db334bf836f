"""The paper's own CNNs — VGG-11, MobileNetV3-Small, SqueezeNet 1.1 — in
pure JAX (NHWC, ``lax.conv_general_dilated``).

BatchNorm is applied in batch-statistics mode: every peer normalizes with
its own batch moments, which is what the paper's per-peer PyTorch training
does in its training stages. No running averages are kept, because they
never enter a training step's loss or gradient (only evaluation reads them)
and keeping them would add state that every exchange and checkpoint carries.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
from jax import lax

Params = Dict[str, Any]


def _conv_w(key, kh, kw, cin, cout, dtype=jnp.float32):
    """A conv without bias (He-normal over its fan-in)."""
    fan_in = kh * kw * cin
    w = jax.random.normal(key, (kh, kw, cin, cout)) * math.sqrt(2.0 / fan_in)
    return {"w": w.astype(dtype)}


def _conv_init(key, kh, kw, cin, cout, dtype=jnp.float32):
    return {**_conv_w(key, kh, kw, cin, cout, dtype), "b": jnp.zeros((cout,), dtype)}


def conv2d(p: Params, x, stride=1, padding="SAME", groups=1):
    """``p["b"]`` is optional: a conv that feeds a batch norm has none."""
    y = lax.conv_general_dilated(
        x, p["w"],
        window_strides=(stride, stride),
        padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups,
    )
    return y + p["b"] if "b" in p else y


def _bn_init(c, dtype=jnp.float32):
    return {"scale": jnp.ones((c,), dtype), "bias": jnp.zeros((c,), dtype)}


def batchnorm(p: Params, x, eps=1e-3):  # torchvision's MobileNetV3 eps
    mu = x.mean(axis=(0, 1, 2), keepdims=True)
    var = x.var(axis=(0, 1, 2), keepdims=True)
    return (x - mu) * lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def max_pool(x, window=2, stride=2):
    """VALID max pool of NHWC maps. Windows that tile the map exactly
    (``window == stride``, both sizes divisible by it) take
    ``_tiled_max_pool``, whose backward is elementwise; any other shape
    keeps ``reduce_window`` and its ``select_and_scatter`` backward."""
    h, w = x.shape[1:3]
    if window == stride and h % window == 0 and w % window == 0:
        return _tiled_max_pool(x, window)
    return _reduce_window_max(x, window, stride)


def _reduce_window_max(x, window, stride):
    return lax.reduce_window(
        x, -jnp.inf, lax.max, (1, window, window, 1), (1, stride, stride, 1), "VALID"
    )


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def _tiled_max_pool(x, k):
    return _reduce_window_max(x, k, k)


def _tiled_max_pool_fwd(x, k):
    """The pooled maps and, as the only residual, each window's index
    (row-major, 0..k*k-1) of its first maximum: the element that
    ``select_and_scatter`` with JAX's ``ge`` select routes the gradient to."""
    y = _reduce_window_max(x, k, k)
    dtype = jnp.int8 if k * k <= 128 else jnp.int32
    first = jnp.full(y.shape, k * k - 1, dtype)
    for p in reversed(range(k * k - 1)):
        i, j = divmod(p, k)
        view = lax.slice(x, (0, i, j, 0), x.shape, (1, k, k, 1))
        first = jnp.where(view == y, jnp.asarray(p, dtype), first)
    return y, first


def _tiled_max_pool_bwd(k, first, g):
    """g_x[n, h, w, c] = g[n, h // k, w // k, c] where ``first`` there is
    (h % k) * k + w % k, else 0. H and W are untiled dims of the chip's
    NHWC layout, so the upsampling reshape is a bitcast."""
    n, ho, wo, c = g.shape

    def up(a):
        a = lax.broadcast_in_dim(a, (n, ho, k, wo, k, c), (0, 1, 3, 5))
        return a.reshape(n, ho * k, wo * k, c)

    hw = (1, ho * k, wo * k, 1)
    at = (lax.broadcasted_iota(jnp.int32, hw, 1) % k * k
          + lax.broadcasted_iota(jnp.int32, hw, 2) % k).astype(first.dtype)
    return (jnp.where(up(first) == at, up(g), jnp.zeros((), g.dtype)),)


_tiled_max_pool.defvjp(_tiled_max_pool_fwd, _tiled_max_pool_bwd)


def avg_pool_to(x, out_hw: int):
    h = x.shape[1]
    if h == out_hw:
        return x
    win = max(h // out_hw, 1)
    return lax.reduce_window(
        x, 0.0, lax.add, (1, win, win, 1), (1, win, win, 1), "VALID"
    ) / (win * win)


def _linear_init(key, din, dout, dtype=jnp.float32):
    w = jax.random.normal(key, (din, dout)) * math.sqrt(2.0 / din)
    return {"w": w.astype(dtype), "b": jnp.zeros((dout,), dtype)}


def linear(p, x):
    return x @ p["w"] + p["b"]


# ---------------------------------------------------------------------------
# VGG-11
# ---------------------------------------------------------------------------

_VGG11_PLAN = [64, "M", 128, "M", 256, 256, "M", 512, 512, "M", 512, 512, "M"]


def init_vgg11(key, cfg) -> Params:
    ks = iter(jax.random.split(key, 16))
    cin = cfg.image_channels
    convs: List[Params] = []
    for item in _VGG11_PLAN:
        if item == "M":
            continue
        convs.append(_conv_init(next(ks), 3, 3, cin, item))
        cin = item
    pool_hw = 7 if cfg.image_size >= 64 else 1
    flat = 512 * pool_hw * pool_hw
    return {
        "convs": convs,
        "fc1": _linear_init(next(ks), flat, 4096),
        "fc2": _linear_init(next(ks), 4096, 4096),
        "fc3": _linear_init(next(ks), 4096, cfg.num_classes),
    }


def vgg11_forward(params: Params, images: jnp.ndarray, cfg) -> jnp.ndarray:
    x = images
    ci = 0
    for item in _VGG11_PLAN:
        if item == "M":
            x = max_pool(x)
        else:
            x = jax.nn.relu(conv2d(params["convs"][ci], x))
            ci += 1
    x = avg_pool_to(x, 7 if cfg.image_size >= 64 else 1)
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(linear(params["fc1"], x))
    x = jax.nn.relu(linear(params["fc2"], x))
    return linear(params["fc3"], x)


# ---------------------------------------------------------------------------
# SqueezeNet 1.1
# ---------------------------------------------------------------------------

# (squeeze, expand1x1, expand3x3)
_FIRE_PLAN = [
    (16, 64, 64), (16, 64, 64),
    (32, 128, 128), (32, 128, 128),
    (48, 192, 192), (48, 192, 192), (64, 256, 256), (64, 256, 256),
]
_FIRE_POOL_AFTER = {1, 3}  # maxpool after these fire indices (v1.1)


def init_squeezenet(key, cfg) -> Params:
    ks = iter(jax.random.split(key, 4 + 3 * len(_FIRE_PLAN)))
    p: Params = {"stem": _conv_init(next(ks), 3, 3, cfg.image_channels, 64)}
    cin = 64
    fires = []
    for (s, e1, e3) in _FIRE_PLAN:
        fires.append(
            {
                "squeeze": _conv_init(next(ks), 1, 1, cin, s),
                "e1": _conv_init(next(ks), 1, 1, s, e1),
                "e3": _conv_init(next(ks), 3, 3, s, e3),
            }
        )
        cin = e1 + e3
    p["fires"] = fires
    p["head"] = _conv_init(next(ks), 1, 1, cin, cfg.num_classes)
    return p


def squeezenet_forward(params: Params, images: jnp.ndarray, cfg) -> jnp.ndarray:
    small = cfg.image_size < 64
    x = jax.nn.relu(conv2d(params["stem"], images, stride=1 if small else 2))
    if not small:
        x = max_pool(x, 3, 2)
    for i, f in enumerate(params["fires"]):
        s = jax.nn.relu(conv2d(f["squeeze"], x))
        x = jnp.concatenate(
            [jax.nn.relu(conv2d(f["e1"], s)), jax.nn.relu(conv2d(f["e3"], s))], axis=-1
        )
        if i in _FIRE_POOL_AFTER:
            x = max_pool(x, 3, 2)
    x = jax.nn.relu(conv2d(params["head"], x))
    return x.mean(axis=(1, 2))  # global average pool -> logits


# ---------------------------------------------------------------------------
# MobileNetV3-Small
# ---------------------------------------------------------------------------
# Howard et al. 2019 (arXiv:1905.02244), Table 2, as torchvision's
# ``mobilenet_v3_small`` builds it: convs that feed a batch norm have no
# bias, a block has no expand conv where its expansion equals its input,
# the SE squeeze is ``_make_divisible(exp // 4)`` wide with a hard-sigmoid
# gate, and every conv pads (k - 1) // 2 on each side. Below 64 px the stem
# takes stride 1, so 32 px maps run 32 -> 16 -> 8 -> 4 -> 2. The
# classifier's dropout is left out: the step is deterministic. Each block's
# depthwise conv, its batch norm and activation sit in one ``cnn.depthwise``
# scope, which a device trace reads back through the HLO's op names.

# (kernel, exp, out, SE, activation, stride)
_MBV3_PLAN = [
    (3, 16, 16, True, "relu", 2),
    (3, 72, 24, False, "relu", 2),
    (3, 88, 24, False, "relu", 1),
    (5, 96, 40, True, "hswish", 2),
    (5, 240, 40, True, "hswish", 1),
    (5, 240, 40, True, "hswish", 1),
    (5, 120, 48, True, "hswish", 1),
    (5, 144, 48, True, "hswish", 1),
    (5, 288, 96, True, "hswish", 2),
    (5, 576, 96, True, "hswish", 1),
    (5, 576, 96, True, "hswish", 1),
]


def _make_divisible(v, divisor=8):
    """The nearest multiple of ``divisor`` to v, at least divisor and never
    more than 10% below v (torchvision's rounding of the SE widths)."""
    new = max(divisor, int(v + divisor / 2) // divisor * divisor)
    return new + divisor if new < 0.9 * v else new


def _hsigmoid(x):
    return jax.nn.relu6(x + 3) / 6


def _act(x, kind):
    return jax.nn.relu(x) if kind == "relu" else x * _hsigmoid(x)


def _same(k):
    p = (k - 1) // 2
    return ((p, p), (p, p))


def init_mobilenet_v3_small(key, cfg) -> Params:
    ks = iter(jax.random.split(key, 8 + 8 * len(_MBV3_PLAN)))
    p: Params = {
        "stem": _conv_w(next(ks), 3, 3, cfg.image_channels, 16),
        "stem_bn": _bn_init(16),
    }
    cin = 16
    blocks = []
    for (k, exp, out, se, actk, stride) in _MBV3_PLAN:
        b: Params = {}
        if exp != cin:
            b["expand"] = _conv_w(next(ks), 1, 1, cin, exp)
            b["expand_bn"] = _bn_init(exp)
        b["dw"] = _conv_w(next(ks), k, k, 1, exp)
        b["dw_bn"] = _bn_init(exp)
        if se:
            sq = _make_divisible(exp // 4)
            b["se_fc1"] = _conv_init(next(ks), 1, 1, exp, sq)
            b["se_fc2"] = _conv_init(next(ks), 1, 1, sq, exp)
        b["project"] = _conv_w(next(ks), 1, 1, exp, out)
        b["project_bn"] = _bn_init(out)
        blocks.append(b)
        cin = out
    p["blocks"] = blocks
    p["head_conv"] = _conv_w(next(ks), 1, 1, cin, 576)
    p["head_bn"] = _bn_init(576)
    p["fc1"] = _linear_init(next(ks), 576, 1024)
    p["fc2"] = _linear_init(next(ks), 1024, cfg.num_classes)
    return p


def mobilenet_v3_small_forward(params: Params, images: jnp.ndarray, cfg) -> jnp.ndarray:
    def conv_bn(p, bn, x, **kw):
        return batchnorm(bn, conv2d(p, x, **kw))

    x = conv_bn(params["stem"], params["stem_bn"], images,
                stride=1 if cfg.image_size < 64 else 2, padding=_same(3))
    x = _act(x, "hswish")
    for b, (k, exp, out, se, actk, stride) in zip(params["blocks"], _MBV3_PLAN):
        h = x
        if "expand" in b:
            h = _act(conv_bn(b["expand"], b["expand_bn"], h), actk)
        with jax.named_scope("cnn.depthwise"):
            h = conv_bn(b["dw"], b["dw_bn"], h, stride=stride, padding=_same(k), groups=exp)
            h = _act(h, actk)
        if se:
            s = h.mean(axis=(1, 2), keepdims=True)
            s = jax.nn.relu(conv2d(b["se_fc1"], s))
            h = h * _hsigmoid(conv2d(b["se_fc2"], s))
        h = conv_bn(b["project"], b["project_bn"], h)
        x = h + x if stride == 1 and x.shape[-1] == out else h
    x = _act(conv_bn(params["head_conv"], params["head_bn"], x), "hswish")
    x = x.mean(axis=(1, 2))
    x = _act(linear(params["fc1"], x), "hswish")
    return linear(params["fc2"], x)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

CNN_ZOO = {
    "vgg11": (init_vgg11, vgg11_forward),
    "squeezenet1_1": (init_squeezenet, squeezenet_forward),
    "mobilenet_v3_small": (init_mobilenet_v3_small, mobilenet_v3_small_forward),
}


def init_cnn(key, cfg) -> Params:
    return CNN_ZOO[cfg.cnn_variant][0](key, cfg)


def cnn_forward(params: Params, images: jnp.ndarray, cfg) -> jnp.ndarray:
    return CNN_ZOO[cfg.cnn_variant][1](params, images, cfg)
