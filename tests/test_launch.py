"""Launch-layer unit tests that need no devices: sharding policy,
activation rules, shape handling, skip logic."""
import dataclasses

import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import SHAPES, get_config
from repro.configs.base import ShapeConfig
from repro.launch import sharding as SH


class FakeMesh:
    def __init__(self, shape):
        self.shape = shape
        self.axis_names = tuple(shape)


SINGLE = FakeMesh({"data": 16, "model": 16})
MULTI = FakeMesh({"pod": 2, "data": 16, "model": 16})


def test_cfg_for_shape_window_only_for_long():
    from repro.launch.dryrun import cfg_for_shape

    qwen = get_config("qwen2.5-3b")
    assert cfg_for_shape(qwen, SHAPES["decode_32k"]).serve_window == 0
    assert cfg_for_shape(qwen, SHAPES["long_500k"]).serve_window == 4096
    gem = get_config("gemma2-2b")
    assert cfg_for_shape(gem, SHAPES["decode_32k"]).sliding_window == 4096


def test_regime_a_train_rules_pin_batch_over_model():
    cfg = get_config("qwen2.5-3b")
    rules = SH.activation_rules(cfg, SHAPES["train_4k"], SINGLE)
    assert rules["batch"][-1] == "model"
    assert rules["heads"] is None and rules["ff"] is None


def test_regime_b_train_rules_are_tp():
    cfg = get_config("dbrx-132b")
    rules = SH.activation_rules(cfg, SHAPES["train_4k"], SINGLE)
    assert "model" not in (rules["batch"] or ())
    assert rules["heads"] == "model" and rules["ff"] == "model"
    assert rules["experts"] == "model"


def test_decode_rules_shard_cache():
    cfg = get_config("qwen2.5-3b")  # kv=2: heads can't shard 16 ways
    rules = SH.activation_rules(cfg, SHAPES["decode_32k"], SINGLE)
    assert rules["kv_heads"] is None
    assert rules["kv_seq"] == ("model",)
    # long-context single request: spare batch axes join the seq shard
    rules = SH.activation_rules(cfg, SHAPES["long_500k"], SINGLE)
    assert set(rules["kv_seq"]) == {"model", "data"}


def test_expert_fallback_megatron_split():
    granite = get_config("granite-moe-3b-a800m")  # 40 experts % 16 != 0
    s = SH.param_spec(("stack", "0", "ffn", "w_gate"), (32, 40, 1536, 512), granite, SINGLE)
    assert s == P(None, None, None, "model")  # column-parallel on f
    s = SH.param_spec(("stack", "0", "ffn", "w_down"), (32, 40, 512, 1536), granite, SINGLE)
    assert s == P(None, None, "model", None)  # row-parallel on f


def test_embed_single_axis_workaround():
    cfg = get_config("dbrx-132b")  # fsdp arch
    s = SH.param_spec(("embed",), (100352, 6144), cfg, SINGLE)
    assert sum(e is not None for e in s) <= 1  # never 2D-sharded


def test_topology_regimes():
    from repro.launch.dryrun import topology_for

    t = topology_for(get_config("qwen2.5-3b"), SINGLE)
    assert t.peer_axes == ("data",) and t.serverless
    t = topology_for(get_config("qwen2.5-3b"), MULTI)
    assert t.peer_axes == ("pod", "data")
    t = topology_for(get_config("dbrx-132b"), MULTI)
    assert t.peer_axes == ("pod",) and not t.serverless
    t = topology_for(get_config("dbrx-132b"), SINGLE)
    assert t.peer_axes == ()


def test_skip_registry():
    from repro.launch.dryrun import SKIPS

    assert ("whisper-base", "long_500k") in SKIPS


def test_batch_specs_sanitized_for_odd_batches():
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    # B=1 can't shard over anything; spec must collapse to replicated
    cfg = get_config("qwen2.5-3b")
    shape = ShapeConfig("x", 128, 1, "prefill")

    class M(FakeMesh):
        def __init__(self):
            super().__init__({"data": 16, "model": 16})

    rules = SH.activation_rules(cfg, shape, M())
    assert rules["batch"] is None


def test_train_main_runs_a_cnn_on_images(monkeypatch, tmp_path, capsys):
    """--arch <cnn> trains on procedural images through the CLI."""
    import math

    import numpy as np

    from repro.launch.train import main

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    state = main(["--arch", "squeezenet1.1", "--steps", "2", "--batch", "4",
                  "--data-parallel", "1", "--log-every", "1"])
    assert int(state.step) == 2
    losses = [float(line.split()[3]) for line in capsys.readouterr().out.splitlines()
              if line.startswith("step ")]
    assert len(losses) == 2 and all(math.isfinite(x) for x in losses)
    assert all(np.isfinite(np.asarray(x)).all() for x in jax.tree.leaves(state.params))


@pytest.mark.parametrize("env_dir", [True, False])
def test_compile_cache_lands_in_its_directory(tmp_path, env_dir):
    """JAX_COMPILATION_CACHE_DIR wins; unset, the cache is <repo>/.jax_cache."""
    import os
    import subprocess
    import sys
    from pathlib import Path

    from repro.launch.compile_cache import REPO_CACHE_DIR

    repo = Path(__file__).resolve().parents[1]
    assert REPO_CACHE_DIR == repo / ".jax_cache"
    want = tmp_path / "cache" if env_dir else REPO_CACHE_DIR
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(repo / "src"))
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = str(want)
    # a function no other test compiles, so its cache entry is new here
    tag = f"cache_probe_{os.getpid()}_{int(env_dir)}"
    script = (
        "import jax, jax.numpy as jnp\n"
        "from repro.launch.compile_cache import enable_compile_cache\n"
        "print(enable_compile_cache())\n"
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
        f"def {tag}(x):\n    return x * 3 + 1\n"
        f"jax.jit({tag})(jnp.ones(5))\n"
    )
    out = subprocess.run([sys.executable, "-c", script], env=env, check=True,
                         capture_output=True, text=True).stdout
    assert out.strip() == str(want)
    assert any(p.name.startswith(f"jit_{tag}-") for p in want.iterdir())
