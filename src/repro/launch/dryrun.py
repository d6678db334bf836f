"""Multi-pod dry-run: lower + compile every (arch x input-shape) combination
on the production meshes, print memory/cost analysis, and extract the
roofline terms (FLOPs / HBM bytes / collective bytes).

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2.5-3b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--json out.json]

This is the ONLY entry point that forces 512 host devices; smoke tests and
benchmarks see the real device count.
"""
import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
).strip()

import argparse
import dataclasses
import json
import re
import sys
import time
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import models
from repro.configs import ASSIGNED_ARCHS, SHAPES, get_config
from repro.configs.base import ModelConfig, ShapeConfig
from repro.core.p2p import TrainState, Topology
from repro.launch import sharding as SH
from repro.launch.mesh import HBM_BW, ICI_BW, PEAK_FLOPS_BF16, make_production_mesh
from repro.models.layers import axis_rules
from repro.optim import adam, sgd
from repro.train import build_train_step, lm_loss

# (arch, shape) pairs that are skipped by design — see DESIGN.md §Arch-applicability
SKIPS = {
    ("whisper-base", "long_500k"): "enc-dec audio decoder; 500k autoregressive decode is meaningless",
}


def topology_for(
    cfg: ModelConfig, mesh, *,
    exchange: str = "allgather_mean",
    exchange_dtype: str = "float32",
    cast_params_once: bool = False,
) -> Topology:
    axes = set(mesh.axis_names)
    if cfg.fsdp:
        peer_axes = ("pod",) if "pod" in axes else ()
    else:
        peer_axes = ("pod", "data") if "pod" in axes else ("data",)
    return Topology(
        peer_axes=peer_axes,
        lambda_axis="model",
        exchange=exchange,
        exchange_dtype=exchange_dtype,
        cast_params_once=cast_params_once,
        # Regime A only: fan micro-batches over the lambda axis. Regime B
        # (fsdp) uses the model axis for tensor parallelism instead.
        serverless=not cfg.fsdp,
    )


def cfg_for_shape(cfg: ModelConfig, shape: ShapeConfig) -> ModelConfig:
    """serve_window (the SWA serving variant) applies only to long_500k."""
    if shape.name != "long_500k" and cfg.serve_window:
        return dataclasses.replace(cfg, serve_window=0)
    return cfg


def input_specs(cfg: ModelConfig, shape: ShapeConfig, mesh, rules):
    """ShapeDtypeStruct stand-ins + shardings for one (arch, shape)."""
    batch, batch_sh = SH.batch_specs(cfg, shape, mesh, rules)
    if shape.mode in ("train", "prefill"):
        return batch, batch_sh
    # decode: single token + cache state
    B = shape.global_batch
    token = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    token_sh = NamedSharding(mesh, P(rules["batch"]) if rules["batch"] else P())
    state_shapes = jax.eval_shape(
        lambda: models.init_decode_state(cfg, B, shape.seq_len)
    )
    state_sh = SH.decode_state_shardings(state_shapes, cfg, mesh, rules)
    return (token, state_shapes), (token_sh, state_sh)


def lower_one(
    arch: str,
    shape_name: str,
    *,
    multi_pod: bool = False,
    exchange: str = "allgather_mean",
    exchange_dtype: str = "float32",
    cast_params_once: bool = False,
    moe_dispatch: str = "dense",
    optimizer: str = "adam",
    donate: bool = True,
):
    """Lower + compile one combination. Returns (lowered, compiled, meta)."""
    cfg = cfg_for_shape(get_config(arch), SHAPES[shape_name])
    shape = SHAPES[shape_name]
    if (arch, shape_name) in SKIPS:
        raise SkipCombo(SKIPS[(arch, shape_name)])
    mesh = make_production_mesh(multi_pod=multi_pod)
    topo = topology_for(
        cfg, mesh, exchange=exchange, exchange_dtype=exchange_dtype,
        cast_params_once=cast_params_once,
    )
    rules = SH.activation_rules(cfg, shape, mesh, peer_axes=topo.peer_axes)

    with jax.set_mesh(mesh):
        with axis_rules(rules):
            if shape.mode == "train":
                opt = adam() if optimizer == "adam" else sgd(momentum=0.9)
                params_shapes = jax.eval_shape(
                    lambda: models.init_model(jax.random.PRNGKey(0), cfg)
                )
                opt_shapes = jax.eval_shape(opt.init, params_shapes)
                p_sh = SH.param_shardings(params_shapes, cfg, mesh)
                o_sh = SH.param_shardings(opt_shapes, cfg, mesh)
                state_shapes = TrainState(
                    params=params_shapes,
                    opt_state=opt_shapes,
                    step=jax.ShapeDtypeStruct((), jnp.int32),
                    key=jax.ShapeDtypeStruct((2,), jnp.uint32),
                )
                state_sh = TrainState(
                    params=p_sh,
                    opt_state=o_sh,
                    step=NamedSharding(mesh, P()),
                    key=NamedSharding(mesh, P()),
                )
                batch, batch_sh = input_specs(cfg, shape, mesh, rules)
                step = build_train_step(
                    cfg, opt, topo, mesh,
                    schedule=lambda s: jnp.float32(1e-3),
                    moe_dispatch=moe_dispatch,
                )
                fn = jax.jit(
                    step,
                    in_shardings=(state_sh, batch_sh),
                    donate_argnums=(0,) if donate else (),
                )
                lowered = fn.lower(state_shapes, batch)
            elif shape.mode == "prefill":
                params_shapes = jax.eval_shape(
                    lambda: models.init_model(jax.random.PRNGKey(0), cfg)
                )
                p_sh = SH.param_shardings(params_shapes, cfg, mesh)
                batch, batch_sh = input_specs(cfg, shape, mesh, rules)

                def prefill(params, batch):
                    logits, _ = models.forward(
                        params, batch, cfg, moe_dispatch=moe_dispatch
                    )
                    return logits

                fn = jax.jit(prefill, in_shardings=(p_sh, batch_sh))
                lowered = fn.lower(params_shapes, batch)
            else:  # decode
                params_shapes = jax.eval_shape(
                    lambda: models.init_model(jax.random.PRNGKey(0), cfg)
                )
                p_sh = SH.param_shardings(params_shapes, cfg, mesh)
                (token, state_shapes), (token_sh, state_sh) = input_specs(
                    cfg, shape, mesh, rules
                )

                def serve_step(params, state, token):
                    return models.decode_step(
                        params, state, token, cfg, moe_dispatch=moe_dispatch
                    )

                fn = jax.jit(
                    serve_step,
                    in_shardings=(p_sh, state_sh, token_sh),
                    out_shardings=(None, state_sh),
                    donate_argnums=(1,) if donate else (),
                )
                lowered = fn.lower(params_shapes, state_shapes, token)

            compiled = lowered.compile()
    meta = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "x".join(str(s) for s in mesh.devices.shape),
        "mode": shape.mode,
        "exchange": exchange if shape.mode == "train" else "-",
        "peers": int(np.prod([mesh.shape[a] for a in topo.peer_axes])) if topo.peer_axes else 1,
        "moe_dispatch": moe_dispatch if cfg.num_experts else "-",
    }
    return lowered, compiled, meta


class SkipCombo(Exception):
    pass


# ---------------------------------------------------------------------------
# Roofline extraction
# ---------------------------------------------------------------------------

def roofline(compiled, mesh, cfg: ModelConfig, shape: ShapeConfig) -> Dict[str, Any]:
    """Three-term roofline from the compiled per-partition HLO.

    ``cost_analysis()`` counts while bodies once (useless for scanned
    stacks), so FLOPs / dot-traffic / collective bytes come from the HLO
    analyzer, which scales loop bodies by their trip counts. All analyzer
    numbers are per-device; totals multiply by chip count.
    """
    from repro.launch import hlo_analysis as HA

    chips = int(np.prod(list(mesh.devices.shape)))
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    hlo = compiled.as_text()
    st = HA.analyze(hlo)
    flops = st.flops * chips  # totals across the mesh
    bytes_accessed = st.dot_bytes * chips
    coll = {k: v * chips for k, v in st.collective_bytes.items()}
    coll_total = float(sum(coll.values()))

    t_compute = flops / (chips * PEAK_FLOPS_BF16)
    t_memory = bytes_accessed / (chips * HBM_BW)
    t_coll = coll_total / (chips * ICI_BW)
    terms = {"compute": t_compute, "memory": t_memory, "collective": t_coll}
    dominant = max(terms, key=terms.get)

    # MODEL_FLOPS: 6*N*D for train (fwd+bwd), 2*N*D for inference
    n_active = cfg.active_param_count() if cfg.family != "cnn" else 0
    tokens = shape.global_batch * (shape.seq_len if shape.mode != "decode" else 1)
    mult = 6 if shape.mode == "train" else 2
    model_flops = mult * n_active * tokens
    mem = {}
    try:
        ma = compiled.memory_analysis()
        mem = {
            "argument_bytes": getattr(ma, "argument_size_in_bytes", None),
            "output_bytes": getattr(ma, "output_size_in_bytes", None),
            "temp_bytes": getattr(ma, "temp_size_in_bytes", None),
            "peak_bytes": getattr(ma, "peak_memory_in_bytes", None),
        }
    except Exception as e:  # pragma: no cover
        mem = {"error": str(e)}
    return {
        "chips": chips,
        "hlo_flops": flops,
        "hlo_bytes": bytes_accessed,
        "collective_bytes": coll_total,
        "collectives": coll,
        "terms_s": terms,
        "dominant": dominant,
        "model_flops": float(model_flops),
        "useful_flops_ratio": float(model_flops / flops) if flops else 0.0,
        "raw_cost_analysis": {
            "flops": float(ca.get("flops", 0.0)),
            "bytes accessed": float(ca.get("bytes accessed", 0.0)),
        },
        "memory": mem,
    }


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def run_one(arch: str, shape_name: str, *, multi_pod: bool, verbose: bool = True,
            **kw) -> Optional[Dict[str, Any]]:
    t0 = time.time()
    try:
        lowered, compiled, meta = lower_one(
            arch, shape_name, multi_pod=multi_pod, **kw
        )
    except SkipCombo as e:
        if verbose:
            print(f"SKIP {arch} x {shape_name}: {e}")
        return {"arch": arch, "shape": shape_name, "skipped": str(e)}
    cfg = cfg_for_shape(get_config(arch), SHAPES[shape_name])
    mesh = make_production_mesh(multi_pod=multi_pod)
    rf = roofline(compiled, mesh, cfg, SHAPES[shape_name])
    rec = {**meta, **rf, "lower_compile_s": round(time.time() - t0, 1)}
    if verbose:
        mem = rf["memory"]
        peak = mem.get("peak_bytes") or 0
        args = mem.get("argument_bytes") or 0
        print(
            f"OK {arch} x {shape_name} [{meta['mesh']}] peers={meta['peers']} "
            f"flops={rf['hlo_flops']:.3e} bytes={rf['hlo_bytes']:.3e} "
            f"coll={rf['collective_bytes']:.3e} dom={rf['dominant']} "
            f"useful={rf['useful_flops_ratio']:.2f} "
            f"mem(arg={args/1e9:.2f}GB peak={peak/1e9:.2f}GB) "
            f"t={rec['lower_compile_s']}s"
        )
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--exchange", default="allgather_mean")
    ap.add_argument("--exchange-dtype", default="float32")
    ap.add_argument("--cast-params", action="store_true")
    ap.add_argument("--moe-dispatch", default="dense")
    ap.add_argument("--optimizer", default="adam")
    ap.add_argument("--json", default=None)
    args = ap.parse_args(argv)

    combos = []
    if args.all:
        for a in ASSIGNED_ARCHS:
            for s in SHAPES:
                combos.append((a, s))
    else:
        combos.append((args.arch, args.shape))

    records = []
    failed = []
    for a, s in combos:
        try:
            rec = run_one(
                a, s,
                multi_pod=args.multi_pod,
                exchange=args.exchange,
                exchange_dtype=args.exchange_dtype,
                cast_params_once=args.cast_params,
                moe_dispatch=args.moe_dispatch,
                optimizer=args.optimizer,
            )
            records.append(rec)
        except Exception as e:
            failed.append((a, s, repr(e)))
            print(f"FAIL {a} x {s}: {e!r}")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(records, f, indent=1, default=str)
    print(f"\n{len([r for r in records if 'skipped' not in r])} ok, "
          f"{len([r for r in records if 'skipped' in r])} skipped, {len(failed)} failed")
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
