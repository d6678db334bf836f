"""Record a traced window of a cell with the compiled step's scope map, and
read what the host runtime does inside the step's calls.

    python3 benchmarks/chip/record_trace.py --workload <name> --seed <n> \\
        --seconds 5 --slice-ms 10 --out <fixture.json.gz>

Sets the cell up as ``run.py`` does (no reference, no result line), traces
one window of ``--seconds``, and prints one JSON summary: device seconds by
scope (``chipbench/scopes.py``) and busy time, the host runtime's events
inside the ``bench.dispatch`` spans by name (seconds, count, share of the
dispatch time), and the longest device idle gaps, each labelled with its
benchmark span and the innermost runtime event that covers most of it
(``dispatch/<event>``). ``--out`` saves ``--slice-ms`` of the window, from
the 20th dispatch on, with the map of the instructions in it: the fixture
``test_chipbench_scopes.py`` reads. Needs a TPU, as ``run.py`` does.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import bisect  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from collections import defaultdict  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parents[1] / "src"), str(HERE)]

import jax  # noqa: E402

from chipbench import cells, data, harness, scopes  # noqa: E402
from chipbench.trace import (  # noqa: E402
    SPAN, WINDOW, Trace, events_from_xplane, find_xplane, gaps)


def host_events(path, within):
    """Host events (every line of the host planes, the benchmark's own spans
    left out) that lie inside one of the ``within`` (start, end) spans, which
    do not overlap."""
    within = sorted(within)
    starts = [s for s, _ in within]
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                s, d = float(e.start_ns), float(e.duration_ns)
                if e.name.startswith(SPAN) or d <= 0:
                    continue
                i = bisect.bisect_right(starts, s) - 1
                if i >= 0 and s + d <= within[i][1]:
                    out.append((s, s + d, e.name))
    return sorted(out)


def label_gaps(tr, runtime, k=10):
    """The k longest idle gaps of device 0, labelled ``<span>/<event>``:
    the innermost runtime event that covers most of the gap (the span's
    label alone where no runtime event lies in the gap)."""
    if not tr.ops:
        return []
    spans = gaps([(s, e) for s, e, _ in tr.ops[tr.devices[0]]], tr.lo, tr.hi)
    longest = sorted(zip(tr.idle_gaps(), spans), key=lambda x: -x[0][0])[:k]
    starts = [s for s, _, _ in runtime]
    reach = max((e - s for s, e, _ in runtime), default=0.0)
    out = []
    for (sec, label), (gs, ge) in longest:
        near = runtime[bisect.bisect_left(starts, gs - reach):bisect.bisect_left(starts, ge)]
        best = max(((min(e, ge) - max(s, gs), -(e - s), n) for s, e, n in near
                    if e > gs), default=None)
        out.append([label if best is None else f"{label}/{best[2]}", sec])
    return out


def fixture(events, hlo_map, lo, ms):
    """``ms`` of the events from ``lo`` on, with a window of that length, and
    the map of the instructions in it."""
    hi = lo + ms * 1e6
    keep = [e for e in events if e["name"] != WINDOW
            and e["start"] < hi and e["start"] + e["dur"] > lo]
    keep.append({"plane": "/host:CPU", "line": "python", "name": WINDOW,
                 "start": lo, "dur": hi - lo})
    names = {scopes.instr_name(e["name"]) for e in keep}
    return {"events": keep, "hlo_map": {n: s for n, s in hlo_map.items() if n in names}}


def record(cell, seed, seconds, slice_ms):
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise harness.NoChip(f"needs a TPU; JAX found {devices[0].platform!r}")
    from chipbench.system import System

    traffic, mcfg = cell.traffic, cell.config
    system = System(mcfg, traffic, devices)
    keys = data.seed_keys(seed)
    params = data.make_params(keys["weights"], cell.model, mcfg, system.rep)
    state = system.make_state(params, keys["state"])
    batches = data.make_blocks(keys["data"], traffic=traffic, mcfg=mcfg, sharding=system.data)
    step = system.compile(batches[0])
    rounds = traffic["checked_rounds"]
    state, _ = harness.check_rounds(step, state, batches, rounds, system.leaf_norms(),
                                    system.momentum, system.loss)
    state, *_ = harness.window(step, state, batches, rounds, 1.0, harness.no_span)
    tdir = tempfile.mkdtemp(prefix="chipbench-record-")
    jax.profiler.start_trace(tdir)
    state, done, *_ = harness.window(step, state, batches, rounds, seconds,
                                     jax.profiler.TraceAnnotation)
    jax.profiler.stop_trace()
    path = find_xplane(tdir)
    events = events_from_xplane(path)
    tr = Trace.from_events(events)
    dispatch = [(s, e) for s, e, n in tr.spans if n == "dispatch"]
    runtime = host_events(path, dispatch)
    shutil.rmtree(tdir, ignore_errors=True)
    hlo_map = scopes.hlo_scopes(step.as_text())

    in_dispatch = sum(e - s for s, e in dispatch)
    by_name = defaultdict(lambda: [0.0, 0])
    for s, e, n in runtime:
        by_name[n][0] += (e - s) * 1e-9
        by_name[n][1] += 1
    summary = {
        "rounds": len(done), "window_s": tr.window_s, "busy_s": tr.busy_s(),
        "scope_s": scopes.scope_seconds(tr, hlo_map),
        "collective_s": tr.op_s(scopes.is_collective),
        "dispatch_s": in_dispatch * 1e-9, "dispatches": len(dispatch),
        "runtime_in_dispatch": sorted(
            ([n, s, c, s / (in_dispatch * 1e-9)] for n, (s, c) in by_name.items()),
            key=lambda x: -x[1])[:25],
        "idle_gaps": label_gaps(tr, runtime),
    }
    lo = sorted(dispatch)[min(20, len(dispatch) - 1)][0]
    return summary, fixture(events, hlo_map, lo, slice_ms)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--slice-ms", type=float, default=10.0)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    try:
        summary, fix = record(cells.load_cell(args.workload), args.seed, args.seconds,
                              args.slice_ms)
    except harness.NoChip as e:
        harness.log(f"record_trace: {e}")
        return 2
    if args.out:
        with gzip.open(args.out, "wt") as f:
            json.dump(fix, f)
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
