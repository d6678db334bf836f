"""Plain reference of MobileNetV3-Small (Howard et al. 2019, arXiv:1905.02244,
Table 2) at the sizes of ``mobilenet-v3-small.json``, NHWC.

Written from the published network as torchvision's ``mobilenet_v3_small``
builds it:

- stem: 3x3 conv to ``stem_width``, batch norm, hard-swish;
- each bneck block: a 1x1 expand conv, batch norm and activation where the
  expansion differs from the block's input (not in block 1); a k x k
  depthwise conv at the block's stride, batch norm and activation; where
  the table says so a squeeze-and-excite gate: global mean, 1x1 conv with
  bias to ``make_divisible(exp // 4)``, ReLU, 1x1 conv with bias back,
  hard sigmoid ``relu6(x + 3) / 6``, times the map; a 1x1 project conv and
  batch norm; the input added back where the stride is 1 and the widths
  match;
- head: 1x1 conv to ``head_width``, batch norm, hard-swish, global mean;
  then ``classifier_width`` with hard-swish, and the classes.

Convs that feed a batch norm have no bias; every conv pads (k - 1) // 2 on
each side; batch norm takes eps ``bn_eps`` (torchvision's 1e-3), and the
batch's own statistics over (N, H, W) with the biased variance.

Departures from the published network: the stem takes the
configuration's ``stem_stride``, 1 where the published network has 2 (its
``reduced_why``); the classifier's dropout is left out, and no running
statistics are kept, since neither enters a training step's loss or
gradient.

``init`` lays the parameters out as the program under test keeps them.
``forward`` takes every convolution and product from ``ops``
(``chipbench.reference.Ops``): the 1x1 convs and the classifier through
``ops.dot``, stride-1 dense convs through ``ops.conv``, and strided or
depthwise convs through ``ops._product``, the hook through which the
control rounds operands to fp8.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from chipbench.reference import PRECISION


def make_divisible(v, divisor):
    """torchvision's ``_make_divisible``: the nearest multiple of divisor,
    at least divisor, and not more than 10% below v."""
    new = max(divisor, int(v + divisor / 2) // divisor * divisor)
    return new + divisor if new < 0.9 * v else new


def hard_sigmoid(x):
    """The SE gate (hard-swish writes its own, so the gate stands alone)."""
    return jnp.clip(x + 3.0, 0.0, 6.0) / 6.0


def activation(x, kind):
    if kind == "relu":
        return jnp.maximum(x, 0.0)
    return x * jnp.clip(x + 3.0, 0.0, 6.0) / 6.0  # hard-swish


def blocks(cfg):
    """The block table as dicts, each with its input width ``cin``."""
    out, cin = [], cfg["stem_width"]
    for row in cfg["blocks"]:
        b = dict(zip(cfg["block_columns"], row), cin=cin)
        b["squeeze"] = make_divisible(b["expansion"] // 4, cfg["se_divisor"]) if b["se"] else 0
        out.append(b)
        cin = b["out"]
    return out


def has_expand(b):
    """A block expands with a 1x1 conv only where its expansion differs
    from its input (not block 1)."""
    return b["expansion"] != b["cin"]


def _he(key, shape, fan_in):
    return jax.random.normal(key, shape, jnp.float32) * math.sqrt(2.0 / fan_in)


def _bn(c):
    return {"scale": jnp.ones((c,), jnp.float32), "bias": jnp.zeros((c,), jnp.float32)}


def init(key, cfg):
    keys = iter(jax.random.split(key, 4 + 5 * len(cfg["blocks"])))
    cin, stem = cfg["image_channels"], cfg["stem_width"]
    params = {"stem": {"w": _he(next(keys), (3, 3, cin, stem), 9 * cin)}, "stem_bn": _bn(stem)}
    bs = []
    for b in blocks(cfg):
        k, e, o = b["kernel"], b["expansion"], b["out"]
        p = {}
        if has_expand(b):
            p["expand"] = {"w": _he(next(keys), (1, 1, b["cin"], e), b["cin"])}
            p["expand_bn"] = _bn(e)
        p["dw"] = {"w": _he(next(keys), (k, k, 1, e), k * k)}
        p["dw_bn"] = _bn(e)
        if b["se"]:
            s = b["squeeze"]
            p["se_fc1"] = {"w": _he(next(keys), (1, 1, e, s), e), "b": jnp.zeros((s,))}
            p["se_fc2"] = {"w": _he(next(keys), (1, 1, s, e), s), "b": jnp.zeros((e,))}
        p["project"] = {"w": _he(next(keys), (1, 1, e, o), e)}
        p["project_bn"] = _bn(o)
        bs.append(p)
    params["blocks"] = bs
    last, head, width = blocks(cfg)[-1]["out"], cfg["head_width"], cfg["classifier_width"]
    params["head_conv"] = {"w": _he(next(keys), (1, 1, last, head), last)}
    params["head_bn"] = _bn(head)
    params["fc1"] = {"w": _he(next(keys), (head, width), head), "b": jnp.zeros((width,))}
    params["fc2"] = {"w": _he(next(keys), (width, cfg["num_classes"]), width),
                     "b": jnp.zeros((cfg["num_classes"],))}
    return params


def conv(x, w, ops, *, stride=1, groups=1):
    """A bias-free conv, padded (k - 1) // 2 on each side."""
    k = w.shape[0]
    if k == 1 and stride == 1 and groups == 1:
        return ops.dot(x, w[0, 0])
    if stride == 1 and groups == 1:
        return ops.conv(x, w)  # "SAME" at stride 1 and odd k pads (k - 1) // 2 each side
    pad = (k - 1) // 2
    return ops._product(
        lambda a, b: lax.conv_general_dilated(
            a, b, (stride, stride), ((pad, pad), (pad, pad)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"), feature_group_count=groups,
            precision=PRECISION), x, w)


def batch_norm(p, x, eps):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def forward(params, images, cfg, *, ops):
    eps = cfg["bn_eps"]
    x = conv(images, params["stem"]["w"], ops, stride=cfg["stem_stride"])
    x = activation(batch_norm(params["stem_bn"], x, eps), "hswish")
    for p, b in zip(params["blocks"], blocks(cfg)):
        act, h = b["activation"], x
        if has_expand(b):
            h = activation(batch_norm(p["expand_bn"], conv(h, p["expand"]["w"], ops), eps), act)
        h = conv(h, p["dw"]["w"], ops, stride=b["stride"], groups=b["expansion"])
        h = activation(batch_norm(p["dw_bn"], h, eps), act)
        if b["se"]:
            s = jnp.mean(h, axis=(1, 2))
            s = jnp.maximum(ops.dot(s, p["se_fc1"]["w"][0, 0]) + p["se_fc1"]["b"], 0.0)
            s = hard_sigmoid(ops.dot(s, p["se_fc2"]["w"][0, 0]) + p["se_fc2"]["b"])
            h = h * s[:, None, None, :]
        h = batch_norm(p["project_bn"], conv(h, p["project"]["w"], ops), eps)
        x = x + h if b["stride"] == 1 and b["cin"] == b["out"] else h
    x = conv(x, params["head_conv"]["w"], ops)
    x = jnp.mean(activation(batch_norm(params["head_bn"], x, eps), "hswish"), axis=(1, 2))
    x = activation(ops.dot(x, params["fc1"]["w"]) + params["fc1"]["b"], "hswish")
    return ops.dot(x, params["fc2"]["w"]) + params["fc2"]["b"]


def layer_macs(cfg):
    """Multiply-accumulates of one sample's forward pass, per layer: the
    stem, every block's expand, depthwise, SE and project convs, the head
    conv and the classifier. Batch norms, activations and pools are not
    counted."""

    def out_side(side, k, stride):
        return (side + 2 * ((k - 1) // 2) - k) // stride + 1

    side = out_side(cfg["image_size"], 3, cfg["stem_stride"])
    out = {"stem": side * side * 9 * cfg["image_channels"] * cfg["stem_width"]}
    for i, b in enumerate(blocks(cfg), 1):
        e = b["expansion"]
        if has_expand(b):
            out[f"block{i}.expand"] = side * side * b["cin"] * e
        side = out_side(side, b["kernel"], b["stride"])
        out[f"block{i}.depthwise"] = side * side * b["kernel"] ** 2 * e
        if b["se"]:
            out[f"block{i}.se"] = 2 * e * b["squeeze"]
        out[f"block{i}.project"] = side * side * e * b["out"]
    out["head"] = side * side * b["out"] * cfg["head_width"]
    out["classifier"] = cfg["head_width"] * cfg["classifier_width"] + \
        cfg["classifier_width"] * cfg["num_classes"]
    return out
