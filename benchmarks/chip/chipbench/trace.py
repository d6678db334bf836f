"""Reduction of a profiler trace to what the per-layer metrics read.

A trace is reduced to plain events first: ``{"plane", "line", "name",
"start", "dur"}`` with times in nanoseconds. Device operations
are the events on a TPU plane's ``XLA Ops`` line; the benchmark's own host
spans (``jax.profiler.TraceAnnotation``) carry names that start with
``bench.``. The span ``bench.window`` marks the measured window, so host
and device times are read on the profiler's one clock.
"""
from __future__ import annotations

import bisect
import glob
import gzip
import json
import re
from collections import defaultdict
from dataclasses import dataclass, field

SPAN = "bench."
WINDOW = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)")
OPS_LINE = "XLA Ops"


def events_from_xplane(path: str) -> list:
    """Plain events of the device planes' ops and of the benchmark's spans."""
    import jax

    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            for e in line.events:
                if not device and not e.name.startswith(SPAN):
                    continue
                out.append({"plane": plane.name, "line": line.name, "name": e.name,
                            "start": float(e.start_ns), "dur": float(e.duration_ns)})
    return out


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {trace_dir}, found {paths}")
    return paths[0]


def load_events(path):
    """Plain events saved as gzipped JSON (``events_from_xplane``'s list)."""
    with gzip.open(path, "rt") as f:
        return json.load(f)


def union_ns(intervals) -> float:
    """Length of the union of (start, end) intervals."""
    total, end = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if e <= end:
            continue
        total += e - max(s, end)
        end = e
    return total


def gaps(intervals, lo, hi):
    """The (start, end) stretches of [lo, hi] that no interval covers."""
    out, cur = [], lo
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(s, e) for s, e in out if e > s]


@dataclass
class Trace:
    """Device ops per device and host spans, clipped to the window."""

    lo: float
    hi: float
    ops: dict = field(default_factory=dict)  # device -> [(start, end, name)]
    spans: list = field(default_factory=list)  # [(start, end, name)]

    @classmethod
    def from_events(cls, events):
        windows = [e for e in events if e["name"] == WINDOW]
        if len(windows) != 1:
            raise ValueError(f"expected one {WINDOW} span, found {len(windows)}")
        lo = windows[0]["start"]
        hi = lo + windows[0]["dur"]
        t = cls(lo, hi)
        ops = defaultdict(list)
        for e in events:
            s, end = max(e["start"], lo), min(e["start"] + e["dur"], hi)
            if end <= s and e["dur"] > 0:
                continue
            m = DEVICE_PLANE.match(e["plane"])
            if m:
                ops[int(m.group(1))].append((s, end, e["name"]))
            elif e["name"] != WINDOW:
                t.spans.append((s, end, e["name"][len(SPAN):]))
        t.ops = dict(ops)
        return t

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    @property
    def devices(self):
        return sorted(self.ops)

    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the devices."""
        if not self.ops:
            return 0.0
        return sum(union_ns([(s, e) for s, e, _ in self.ops[d]])
                   for d in self.devices) / len(self.ops) * 1e-9

    def op_s(self, pred) -> float:
        """Device seconds of the ops ``pred(name)`` picks, averaged over the
        devices."""
        if not self.ops:
            return 0.0
        return sum(e - s for d in self.devices for s, e, n in self.ops[d]
                   if pred(n)) / len(self.ops) * 1e-9

    def count(self, pred) -> float:
        """Ops ``pred`` picks, averaged over the devices."""
        if not self.ops:
            return 0.0
        return sum(1 for d in self.devices for _, _, n in self.ops[d]
                   if pred(n)) / len(self.ops)

    def top_ops(self, k=10):
        """The k op names that took most device time, seconds averaged over
        the devices."""
        tot = defaultdict(float)
        for d in self.devices:
            for s, e, n in self.ops[d]:
                tot[op_label(n)] += (e - s) * 1e-9 / len(self.ops)
        return sorted(([n, v] for n, v in tot.items()), key=lambda x: -x[1])[:k]

    def idle_gaps(self, device=None):
        """Device idle stretches of the window, each with the host span that
        overlaps it most (``none`` when none does): [(seconds, label)]. The
        benchmark's spans do not overlap one another."""
        d = self.devices[0] if device is None else device
        spans = sorted(self.spans)
        starts = [s for s, _, _ in spans]
        out = []
        for gs, ge in gaps([(s, e) for s, e, _ in self.ops.get(d, [])], self.lo, self.hi):
            best, label = 0.0, "none"
            i = bisect.bisect_left(starts, ge) - 1
            while i >= 0 and spans[i][1] > gs:
                s, e, name = spans[i]
                ov = min(e, ge) - max(s, gs)
                if ov > best:
                    best, label = ov, name
                i -= 1
            out.append(((ge - gs) * 1e-9, label))
        return out

    def breakdown(self, k=10):
        longest = sorted(self.idle_gaps(), key=lambda x: -x[0])[:k]
        return {"device_ops": self.top_ops(k),
                "idle_gaps": [[label, s] for s, label in longest]}


def op_label(name) -> str:
    """An op's instruction name without its instance numbers. A TPU trace
    names an op by its HLO text, ``%fusion.12 = f32[...] fusion(...), ...``:
    the label is ``fusion``."""
    return re.sub(r"(\.\d+|\.clone)+$", "", name.split(" = ", 1)[0].lstrip("%"))

