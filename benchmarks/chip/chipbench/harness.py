"""One run of one cell: set up, check the first rounds, measure a window,
compare with the reference, print one result line.

Set-up builds the program's compiled step and its train state around
weights and data made on the device from the seed, and drives the step
through its first rounds (``checked_rounds`` of the traffic), which warms
up the only program the window runs. The window then drives the same step
in a closed loop with one round in flight: round t+1 is dispatched before
round t's loss is read, and a round is complete when its loss reaches the
host. After the window the program's state is freed and the plain
reference recomputes the first rounds from the seed; the comparison
decides ``correct``.
"""
from __future__ import annotations

import contextlib
import gc
import json
import math
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass
from functools import partial

import jax
import numpy as np

from chipbench import cells, data, reference
from chipbench.trace import Trace, events_from_xplane, find_xplane


# A traced run traces a second window of at most this many seconds after
# the measured one: a long trace loses events (a 51 s trace of the qsgd cell
# read half the kernel time and 53% idle against 14-19% in 10 s traces) and
# took over five minutes to write and read; 10 s took two to three.
TRACE_SECONDS = 5.0


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclass
class Run:
    """What the metric readers read (``metrics/<name>.py``)."""

    cell: cells.Cell
    device_kind: str
    chips: int
    setup_s: float
    window_s: float
    rounds: int  # rounds completed in the window
    samples: int  # training samples in those rounds, over all peers
    dispatch_s: float  # host seconds inside the step's calls
    dispatches: int
    leaf_sizes: list  # number of values in each parameter leaf
    trace: Trace | None = None

    @property
    def traffic(self):
        return self.cell.traffic

    def peaks(self):
        return cells.peaks(self.device_kind)

    def kernel(self, name):
        return cells.kernel_counts(name)


class CompileCounter:
    """Counts JAX compilations (traces and backend compiles) while on."""

    def __init__(self):
        self.on, self.n = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, duration, **_):
        if self.on and event in ("/jax/core/compile/jaxpr_trace_duration",
                                 "/jax/core/compile/backend_compile_duration"):
            self.n += 1


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def samples_per_round(traffic) -> int:
    return traffic["peers"] * traffic["accum_steps"] * traffic["batch_per_peer"]


def check_rounds(step, state, batches, rounds, norms, momentum, loss):
    """Drive the step through its first rounds and read what the reference
    is compared with: every round's loss, every device's per-leaf norms of
    the first exchanged gradient and of the parameters' change, and device
    0's first exchanged gradient itself."""
    p0, losses, grad_norms, grad = state.params, [], None, None
    for t in range(rounds):
        state, m = step(state, batches[t % len(batches)])
        losses.append(float(loss(m)))
        if t == 0:
            grad_norms = np.asarray(norms(momentum(state), None), np.float64)
            grad = [np.asarray(x) for x in jax.tree.leaves(momentum(state))]
    change = np.asarray(norms(state.params, p0), np.float64)
    return state, {"losses": losses, "grad_norms": grad_norms, "change_norms": change,
                   "grad": grad}


def window(step, state, batches, t, seconds, span):
    """The measured closed loop. Returns the state, the completion times,
    the dispatch seconds and count, the rounds whose loss was not finite,
    and the window's start."""
    nb = len(batches)
    done, dispatch_s, dispatches, failed = [], 0.0, 0, 0

    def dispatch(state, t):
        nonlocal dispatch_s, dispatches
        with span("bench.dispatch"):
            d0 = time.perf_counter()
            out = step(state, batches[t % nb])
            dispatch_s += time.perf_counter() - d0
        dispatches += 1
        return out

    with span("bench.window"):
        start = time.perf_counter()
        state, inflight = dispatch(state, t)
        while True:
            t += 1
            state, nxt = dispatch(state, t)
            with span("bench.wait"):
                loss = float(inflight["loss"])
            now = time.perf_counter()
            done.append(now)
            failed += not math.isfinite(loss)
            inflight = nxt
            if now - start >= seconds:
                break
    float(inflight["loss"])  # the round still in flight, outside the window
    return state, done, dispatch_s, dispatches, failed, start


def no_span(_):
    return contextlib.nullcontext()


def log_window(name, done, start, setup_s):
    """The window's rounds and the spread of the intervals between their
    completions, so a stall or a slow stretch shows."""
    gaps = np.diff([start] + done) * 1e3
    worst = int(np.argmax(gaps))
    log(f"[chipbench] {name} window: {len(done)} rounds in {done[-1] - start:.3f} s; "
        f"round intervals ms median {np.median(gaps):.3f}, p95 {np.percentile(gaps, 95):.3f}, "
        f"max {gaps[worst]:.3f} ending at {done[worst] - start:.3f} s"
        + ("" if setup_s is None else f"; set-up {setup_s:.3f} s"))


def run(cell: cells.Cell, seed: int, seconds: float, trace: bool, *,
        t0: float, require_tpu: bool = True):
    """One run; returns the result line as a dict."""
    devices = jax.devices()
    dev = devices[0]
    if require_tpu and dev.platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {dev.platform!r}")
    if len(devices) < cell.chips:
        raise NoChip(f"{cell.name} needs {cell.chips} chips; JAX found {len(devices)}")
    from chipbench.system import System  # the program under test

    traffic, mcfg = cell.traffic, cell.config
    counter = CompileCounter()
    system = System(mcfg, traffic, devices)
    keys = data.seed_keys(seed)
    params = data.make_params(keys["weights"], cell.model, mcfg, system.rep)
    state = system.make_state(params, keys["state"])
    del params
    batches = data.make_blocks(keys["data"], traffic=traffic, mcfg=mcfg, sharding=system.data)
    step = system.compile(batches[0])
    rounds = traffic["checked_rounds"]
    state, prog = check_rounds(step, state, batches, rounds, system.leaf_norms(),
                               system.momentum, system.loss)
    leaf_sizes = [int(np.prod(s.shape)) for s in jax.tree.leaves(system.shapes.params)]

    compiles_before = counter.n
    counter.on = True
    state, done, dispatch_s, dispatches, failed, start = window(
        step, state, batches, rounds, seconds, no_span)
    setup_s = start - t0
    window_s = done[-1] - start
    log_window("measured", done, start, setup_s)
    attempted, tr = len(done), None
    if trace:
        tdir = tempfile.mkdtemp(prefix="chipbench-trace-")
        jax.profiler.start_trace(tdir)
        state, done, dispatch_s, dispatches, tfailed, tstart = window(
            step, state, batches, rounds + 1 + attempted, min(seconds, TRACE_SECONDS),
            jax.profiler.TraceAnnotation)
        jax.profiler.stop_trace()
        window_s = done[-1] - tstart
        log_window("traced", done, tstart, None)
        attempted, failed = attempted + len(done), failed + tfailed
        tr = Trace.from_events(events_from_xplane(find_xplane(tdir)))
        shutil.rmtree(tdir, ignore_errors=True)
    counter.on = False
    log(f"[chipbench] compilations inside the windows: {counter.n - compiles_before}")
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)
    log(f"[chipbench] first-round losses {prog['losses']}")

    r = Run(cell=cell, device_kind=dev.device_kind, chips=cell.chips, setup_s=setup_s,
            window_s=window_s, rounds=len(done), samples=len(done) * samples_per_round(traffic),
            dispatch_s=dispatch_s, dispatches=dispatches, leaf_sizes=leaf_sizes, trace=tr)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = cells.reader(m["name"])(r)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    del state, batches, step, system
    gc.collect()
    r0 = time.perf_counter()
    ref = reference_readings(cell, keys, rounds)
    log(f"[chipbench] reference: {rounds} rounds in {time.perf_counter() - r0:.3f} s")
    numbers = reference.compare(prog, ref)
    log("[chipbench] readings " + json.dumps({
        "numbers": numbers,
        "program": {k: np.asarray(v).tolist() for k, v in prog.items() if k != "grad"},
        "reference": {"losses": ref[0].tolist(), "grad_norms": ref[1][0].tolist(),
                      "change_norms": ref[2][0].tolist()}}))
    checks = {k: {"value": numbers[k], "limit": limit} for k, limit in cell.limits.items()}
    correct = failed == 0 and all(c["value"] <= c["limit"] for c in checks.values())
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if trace:
        device.update(busy_s=tr.busy_s(), window_s=tr.window_s)
        out["breakdown"] = tr.breakdown()
    out["checks"] = checks
    return out


def reference_readings(cell, keys, rounds, *, ops=reference.REFERENCE, fault=None):
    """The plain reference's readings for the cell's first rounds, from the
    same seed keys (weights, state key, data) the run used."""
    mcfg, traffic = cell.config, cell.traffic
    nb = data.blocks(traffic)
    block = jax.jit(partial(data.peer_block, traffic=traffic, mcfg=mcfg))
    params = data.make_params(keys["weights"], cell.model, mcfg, None)
    return reference.run_rounds(
        params, keys["state"], lambda r, t: block(keys["data"], r, t % nb), rounds,
        model=cell.model, mcfg=mcfg, traffic=traffic, opt=mcfg["optimizer"],
        ops=ops, fault=fault)


def print_result(out):
    for k, c in out["checks"].items():
        log(f"check {k} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(out), flush=True)


def main(argv, t0):
    import argparse

    ap = argparse.ArgumentParser(description="Run one cell of the chip benchmark.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = cells.load_cell(args.workload)
    try:
        out = run(cell, args.seed, args.seconds, bool(args.trace), t0=t0)
    except NoChip as e:
        log(f"chipbench: {e}")
        return 2
    print_result(out)
    return 0
