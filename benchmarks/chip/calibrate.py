"""Readings that set a cell's correctness limits (``limits/<cell>.json``).

    python3 benchmarks/chip/calibrate.py --workload <name> --seeds 1,2,... \\
        --control-seeds 1,2,3 --out <file.json>

In one process, with one compiled step: for every seed the program's
first rounds against the plain reference (the lower readings); for the
control seeds, the reference computed in the precision below the program's
(``reference.CONTROL``) put in the program's place, and the reference with
half of every batch left out. A step that returns its state unchanged reads 1 in
``change_norm_gap`` by construction and needs no run. The benchmark's own
runs never run this. Needs a TPU, as ``run.py`` does.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parents[1] / "src"), str(HERE)]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from chipbench import cells, data, harness, reference  # noqa: E402


def raw(readings, ref):
    """The readings themselves, as lists, for a later look; the gradient as
    its per-leaf distance from the reference's."""
    out = {k: [float(x) for x in np.asarray(v, np.float64).reshape(-1)]
           for k, v in readings.items() if k != "grad"}
    out["grad_dist"] = reference.grad_dists(readings["grad"], ref[3]).tolist()
    return out


def calibrate(cell, seeds, control_seeds, *, require_tpu=True, log=harness.log):
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise harness.NoChip(f"needs a TPU; JAX found {devices[0].platform!r}")
    from chipbench.system import System

    traffic, mcfg = cell.traffic, cell.config
    rounds = traffic["checked_rounds"]
    system = System(mcfg, traffic, devices)
    norms, step, records = system.leaf_norms(), None, []
    for seed in seeds:
        keys = data.seed_keys(seed)
        params = data.make_params(keys["weights"], cell.model, mcfg, system.rep)
        state = system.make_state(params, keys["state"])
        batches = data.make_blocks(keys["data"], traffic=traffic, mcfg=mcfg,
                                   sharding=system.data)
        if step is None:
            step = system.compile(batches[0])
        _, prog = harness.check_rounds(step, state, batches, rounds, norms,
                                       system.momentum, system.loss)
        del params, state, batches
        ref = harness.reference_readings(cell, keys, rounds)
        rec = {"seed": seed, "program": reference.compare(prog, ref),
               "raw": {"reference": raw(reference.as_program(ref), ref),
                       "program": raw(prog, ref)}}
        if seed in control_seeds:
            ctl = reference.as_program(harness.reference_readings(cell, keys, rounds,
                                                                  ops=reference.CONTROL))
            rec["control"] = reference.compare(ctl, ref)
            rec["raw"]["control"] = raw(ctl, ref)
            half = reference.as_program(harness.reference_readings(cell, keys, rounds,
                                                                   fault="half_batch"))
            rec["half_batch"] = reference.compare(half, ref)
            rec["raw"]["half_batch"] = raw(half, ref)
        log(f"[calibrate] {json.dumps({k: v for k, v in rec.items() if k != 'raw'})}")
        records.append(rec)
    return records


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated program seeds")
    ap.add_argument("--control-seeds", required=True,
                    help="comma-separated seeds (among --seeds) for the control and faults")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    control = {int(s) for s in args.control_seeds.split(",")}
    if not control <= set(seeds):
        ap.error("--control-seeds must be among --seeds")
    cell = cells.load_cell(args.workload)
    try:
        records = calibrate(cell, seeds, control)
    except harness.NoChip as e:
        harness.log(f"calibrate: {e}")
        return 2
    out = {"workload": args.workload, "device": jax.devices()[0].device_kind,
           "seconds": time.perf_counter() - T0, "records": records}
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    Path(args.out).write_text(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
