"""Serving driver: batched greedy decode with a KV cache.

    PYTHONPATH=src python -m repro.launch.serve --arch gemma2-2b --reduced \
        --batch 4 --prompt-len 16 --gen 32
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import models
from repro.configs import get_config, reduced
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.launch.sharding import activation_rules
from repro.models.layers import axis_rules
from repro.configs.base import ShapeConfig
from repro.train import checkpoint as ckpt


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma2-2b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--checkpoint", default=None)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg, vocab_size=512)
    if cfg.family == "cnn":
        raise SystemExit("CNNs are not served autoregressively")
    mesh = make_host_mesh()
    max_len = args.prompt_len + args.gen

    params = models.init_model(jax.random.PRNGKey(0), cfg)
    if args.checkpoint:
        params, meta = ckpt.restore(args.checkpoint, params)
        print(f"restored checkpoint (step {meta.get('step')})")

    B = args.batch
    key = jax.random.PRNGKey(1)
    prompts = jax.random.randint(key, (B, args.prompt_len), 0, cfg.vocab_size)
    state = models.init_decode_state(cfg, B, max_len)

    @jax.jit
    def step(params, state, token, key):
        logits, state = models.decode_step(params, state, token, cfg)
        if args.temperature > 0:
            tok = jax.random.categorical(key, logits / args.temperature, axis=-1)
        else:
            tok = logits.argmax(-1)
        return tok[:, None].astype(jnp.int32), state

    shape = ShapeConfig("serve", max_len, B, "decode")
    rules = activation_rules(cfg, shape, mesh)
    out_tokens = []

    @jax.jit
    def do_prefill(params, state, prompt):
        return models.prefill(params, state, {"tokens": prompt}, cfg)

    with jax.set_mesh(mesh):
        with axis_rules(rules):
            t0 = time.time()
            logits, state = do_prefill(params, state, prompts)  # one-shot prefill
            tok = logits.argmax(-1)[:, None].astype(jnp.int32)
            out_tokens.append(np.asarray(tok)[:, 0])
            for i in range(args.gen - 1):
                key, sub = jax.random.split(key)
                tok, state = step(params, state, tok, sub)
                out_tokens.append(np.asarray(tok)[:, 0])
            dt = time.time() - t0
    gen = np.stack(out_tokens, axis=1)
    toks_per_s = B * (args.prompt_len + args.gen) / dt
    print(f"generated {gen.shape} in {dt:.2f}s ({toks_per_s:.1f} tok/s incl. prefill)")
    for b in range(min(B, 2)):
        print(f"request {b}: {gen[b][:24].tolist()}")
    return gen


if __name__ == "__main__":
    main()
