"""Finds everything a cell needs by name, from ``BENCHMARK.json`` and the
files beside this package, so a new cell, configuration or metric is new
files and new entries, never an edit:

- ``configs/<config>.json``: the configuration as it is run (the path
  ``BENCHMARK.json`` gives), with its plain reference ``configs/<config>.py``;
- ``traffic/<traffic>.json``: the traffic mix;
- ``limits/<cell>.json``: the limit of each number the correctness check
  compares;
- ``metrics/<metric>.py``: one reader per metric, ``read(run)`` -> number or
  None. A metric named ``<metric>.<suffix>`` is the same quantity reported
  under a name of its own in some cells (one per end-to-end metric it
  moves) and reads with ``metrics/<metric>.py``;
- ``kernels/<kernel>.py``: the bytes and operations a kernel must move;
- ``peaks.json``: the chips' published peaks, keyed by ``device_kind``.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]  # benchmarks/chip
ROOT = HERE.parents[1]  # the checkout


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(f"chipbench_{path.parent.name}_{path.stem}",
                                                  path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_json(path: Path):
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    chips: int
    config: dict  # the configuration file, as run
    model: object  # its plain reference module
    traffic: dict
    limits: dict
    end_to_end: list  # BENCHMARK.json entries of the metrics this cell reports
    per_layer: list


def _reports(metric: dict, cell: str, e2e_names=None) -> bool:
    """An entry with ``workloads`` is reported in those cells; an end-to-end
    metric without it in every cell, a per-layer one in every cell that
    reports the metric it moves."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return e2e_names is None or metric["moves"] in e2e_names


def load_cell(name: str, *, root: Path = ROOT, here: Path = HERE) -> Cell:
    bench = read_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_file = root / configs[w["config"]]["file"]
    e2e = [m for m in bench["end_to_end"] if _reports(m, name)]
    e2e_names = {m["name"] for m in e2e}
    return Cell(
        name=name,
        chips=w["chips"],
        config=read_json(cfg_file),
        model=load_module(cfg_file.with_suffix(".py")),
        traffic=read_json(here / "traffic" / f"{w['traffic']}.json"),
        limits=read_json(here / "limits" / f"{name}.json"),
        end_to_end=e2e,
        per_layer=[m for m in bench["per_layer"] if _reports(m, name, e2e_names)],
    )


def reader(metric: str, *, here: Path = HERE):
    return load_module(here / "metrics" / f"{metric.split('.')[0]}.py").read


def kernel_counts(kernel: str, *, here: Path = HERE):
    return load_module(here / "kernels" / f"{kernel}.py")


def peaks(device_kind: str, *, here: Path = HERE) -> dict:
    table = read_json(here / "peaks.json")
    if device_kind not in table["chips"]:
        raise KeyError(f"no peaks for device_kind {device_kind!r} in peaks.json")
    return table["chips"][device_kind]
