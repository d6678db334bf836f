"""MobileNetV3-Small (arXiv:1905.02244, Table 2; torchvision's
``mobilenet_v3_small``) in the program against its published counts and
against the plain reference the chip benchmark keeps
(``benchmarks/chip/configs/mobilenet-v3-small.py``), on the CPU at 32 px."""
import dataclasses
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.cnn import cnn_forward, init_cnn
from repro.train.steps import lm_loss

CHIP = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
sys.path.insert(0, str(CHIP))

from chipbench import cells, reference  # noqa: E402

CFG = get_config("mobilenet-v3-small")
REF_CFG = json.loads((CHIP / "configs" / "mobilenet-v3-small.json").read_text())
BATCH = 4

# Both sides compute in float32 with products at "highest" precision, so
# they differ only by the order of float32 sums: a 1x1 conv against a dot,
# the program's batch norm (mean, var) against the reference's. That is
# ~1e-5 of the largest logit and of a leaf's gradient norm; the bounds
# leave 20-50x room above what a sound program reads, while a wrong gate or
# an extra conv moves both by over 10% (test_a_planted_departure_fails).
LOGIT_TOL = 1e-4  # max |logit gap| over the reference's max |logit|
GRAD_TOL = 1e-3  # worst leaf's |grad gap| over max(its norm, median leaf's)


def shapes(cfg):
    return jax.eval_shape(lambda k: init_cnn(k, cfg), jax.random.PRNGKey(0))


def count(tree):
    leaves = jax.tree.leaves(tree)
    return len(leaves), sum(int(np.prod(x.shape)) for x in leaves)


def test_published_parameter_count_at_224px_and_1000_classes():
    cfg = dataclasses.replace(CFG, image_size=224, num_classes=1000)
    assert count(shapes(cfg))[1] == 2_542_856  # torchvision's mobilenet_v3_small


def test_cifar_cut_has_142_leaves_and_1_528_106_parameters():
    assert count(shapes(CFG)) == (142, 1_528_106)


def test_se_widths_are_make_divisible_of_a_quarter():
    se = [b["se_fc1"]["w"].shape[-1] for b in shapes(CFG)["blocks"] if "se_fc1" in b]
    assert se == [8, 24, 64, 64, 32, 40, 72, 144, 144]


def test_only_block_one_has_no_expand_and_bn_convs_have_no_bias():
    blocks = shapes(CFG)["blocks"]
    assert ["expand" in b for b in blocks] == [False] + [True] * 10
    for b in blocks:
        assert all("b" not in b[k] for k in ("expand", "dw", "project") if k in b)
        assert all("b" in b[k] for k in ("se_fc1", "se_fc2") if k in b)


def test_feature_maps_run_32_16_8_4_2():
    x = jnp.zeros((BATCH, 32, 32, 3))
    eqns = jax.make_jaxpr(lambda p: cnn_forward(p, x, CFG))(shapes(CFG)).jaxpr.eqns
    convs = [(e.outvars[0].aval.shape[1], e.params["feature_group_count"] > 1)
             for e in eqns if e.primitive.name == "conv_general_dilated"]
    assert [side for side, dw in convs if dw] == [16, 8, 8, 4, 4, 4, 4, 4, 2, 2, 2]
    sides = [side for side, _ in convs if side > 1]  # SE convs see a 1x1 map
    assert sorted(set(sides), reverse=True) == [32, 16, 8, 4, 2]
    assert sides[0] == 32 and sides[-1] == 2  # the stem and the head


def load_reference():
    return cells.load_module(CHIP / "configs" / "mobilenet-v3-small.py")


def gaps(ref, ref_params, prog_params):
    """(logit gap, gradient gap) of the program against ``ref`` on seeded
    images; the gradient gap over the leaves the program has."""
    x = jax.random.normal(jax.random.PRNGKey(1), (BATCH, 32, 32, 3))
    y = jnp.arange(BATCH) % CFG.num_classes
    ops = reference.REFERENCE
    with jax.default_matmul_precision("highest"):
        lp = jax.jit(lambda p: cnn_forward(p, x, CFG))(prog_params)
        lr = jax.jit(lambda p: ref.forward(p, x, REF_CFG, ops=ops))(ref_params)
        gp = jax.jit(jax.grad(lambda p: lm_loss(p, {"images": x, "labels": y}, CFG)[0]))(
            prog_params)
        gr = jax.jit(jax.grad(lambda p: reference.loss_fn(p, x, y, ref, REF_CFG, ops)))(
            ref_params)
    # a planted block-1 expand has leaves the program lacks: compare the rest
    gr["blocks"][0] = {k: v for k, v in gr["blocks"][0].items() if k in gp["blocks"][0]}
    logit = float(jnp.max(jnp.abs(lp - lr)) / jnp.max(jnp.abs(lr)))
    want = [np.asarray(g, np.float64) for g in jax.tree.leaves(gr)]
    got = [np.asarray(g, np.float64) for g in jax.tree.leaves(gp)]
    norms = np.array([np.linalg.norm(g) for g in want])
    dist = np.array([np.linalg.norm(a - b) for a, b in zip(got, want)])
    return logit, float(np.max(dist / np.maximum(norms, np.median(norms))))


def test_program_matches_the_plain_reference():
    ref = load_reference()
    params = ref.init(jax.random.PRNGKey(3), REF_CFG)
    assert jax.tree.map(np.shape, params) == jax.tree.map(np.shape, shapes(CFG))
    logit, grad = gaps(ref, params, params)
    assert logit < LOGIT_TOL and grad < GRAD_TOL, (logit, grad)


@pytest.mark.parametrize("departure", ["sigmoid_gate", "block1_expand"])
def test_a_planted_departure_fails(monkeypatch, departure):
    """A reference with a sigmoid SE gate, or with block 1's expand conv put
    back, is told apart from the program by both tolerances."""
    ref = load_reference()
    if departure == "sigmoid_gate":
        monkeypatch.setattr(ref, "hard_sigmoid", jax.nn.sigmoid)
    else:
        monkeypatch.setattr(ref, "has_expand", lambda b: True)
    ref_params = ref.init(jax.random.PRNGKey(3), REF_CFG)
    prog_params = dict(ref_params, blocks=list(ref_params["blocks"]))
    prog_params["blocks"][0] = {k: v for k, v in ref_params["blocks"][0].items()
                                if not k.startswith("expand")}
    logit, grad = gaps(ref, ref_params, prog_params)
    assert logit > LOGIT_TOL and grad > GRAD_TOL, (logit, grad)
