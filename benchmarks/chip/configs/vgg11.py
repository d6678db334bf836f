"""Plain reference of VGG-11 (configuration A, arXiv:1409.1556) at the
sizes of ``vgg11.json``: NHWC, 3x3 'SAME' convolutions with bias and ReLU,
2x2 max pools, then fc1/fc2 of ``fc_width`` with ReLU and fc3 to the
classes. At 32 px the last pool leaves a 1x1 map, so fc1 reads 512
features.

``init`` lays the parameters out as the program under test keeps them, so
the benchmark can hand the same weights to both. ``forward`` takes its
convolutions and matrix products from ``ops`` (``chipbench.reference.Ops``).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax


def _convs(cfg):
    return [c for c in cfg["conv_plan"] if c != "M"]


def init(key, cfg):
    k = cfg["conv_kernel"]
    widths = _convs(cfg)
    keys = jax.random.split(key, len(widths) + 3)
    convs, cin = [], cfg["image_channels"]
    for kk, cout in zip(keys, widths):
        w = jax.random.normal(kk, (k, k, cin, cout), jnp.float32)
        convs.append({"w": w * math.sqrt(2.0 / (k * k * cin)),
                      "b": jnp.zeros((cout,), jnp.float32)})
        cin = cout
    side = cfg["image_size"] // 2 ** cfg["conv_plan"].count("M")
    dims = [cin * side * side, cfg["fc_width"], cfg["fc_width"], cfg["num_classes"]]
    fcs = {}
    for i, kk in enumerate(keys[len(widths):]):
        w = jax.random.normal(kk, (dims[i], dims[i + 1]), jnp.float32)
        fcs[f"fc{i + 1}"] = {"w": w * math.sqrt(2.0 / dims[i]),
                             "b": jnp.zeros((dims[i + 1],), jnp.float32)}
    return {"convs": convs, **fcs}


def forward(params, images, cfg, *, ops):
    p, x, i = params, images, 0
    for item in cfg["conv_plan"]:
        if item == "M":
            x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
            continue
        c = p["convs"][i]
        x = jax.nn.relu(ops.conv(x, c["w"]) + c["b"])
        i += 1
    x = x.reshape(x.shape[0], -1)
    for name in ("fc1", "fc2"):
        x = jax.nn.relu(ops.dot(x, p[name]["w"]) + p[name]["b"])
    return ops.dot(x, p["fc3"]["w"]) + p["fc3"]["b"]


def layer_macs(cfg):
    """Multiply-accumulates of one sample's forward pass, per layer."""
    k, side, cin = cfg["conv_kernel"], cfg["image_size"], cfg["image_channels"]
    out, i = {}, 0
    for item in cfg["conv_plan"]:
        if item == "M":
            side //= 2
            continue
        out[f"conv{i + 1}"] = side * side * k * k * cin * item
        cin, i = item, i + 1
    dims = [cin * side * side, cfg["fc_width"], cfg["fc_width"], cfg["num_classes"]]
    for j in range(3):
        out[f"fc{j + 1}"] = dims[j] * dims[j + 1]
    return out
