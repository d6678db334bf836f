"""The benchmark is driven by data: every cell, configuration and metric
that BENCHMARK.json names is found by name, a new one is found from new
files alone, and a run without a TPU exits non-zero with no result."""
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))

from chipbench import cells  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
TRAFFIC_KEYS = {"why", "peers", "lambdas", "batch_per_peer", "batches_per_peer",
                "accum_steps", "exchange", "ef", "checked_rounds"}
CHECKS = {"loss_gap", "grad_norm_gap", "change_norm_gap", "grad_dist_gap"}


def test_benchmark_json_keys_and_names():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmarks/chip"]
    assert BENCH["command"][1] == "benchmarks/chip/run.py"
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in BENCH[k]]
    assert all(NAME.match(n) for n in names)
    for k in ("configs", "workloads"):
        assert len({x["name"] for x in BENCH[k]}) == len(BENCH[k])
    metrics = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(set(metrics)) == len(metrics)
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert e2e["setup_s"]["bound"] == 0.25
    assert all(0.01 <= m["bound"] <= 0.25 for m in e2e.values())
    assert all(m["moves"] in e2e for m in BENCH["per_layer"])
    for m in BENCH["per_layer"]:  # a per-layer metric's cells report what it moves
        assert set(m.get("workloads", [])) <= set(e2e[m["moves"]].get("workloads", m["workloads"]))


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_every_workload_parses_and_names_a_config(workload):
    cell = cells.load_cell(workload)
    w = next(x for x in BENCH["workloads"] if x["name"] == workload)
    assert w["config"] in {c["name"] for c in BENCH["configs"]}
    assert cell.config["name"] == w["config"]
    assert TRAFFIC_KEYS <= set(cell.traffic)
    assert cell.traffic["peers"] * cell.traffic["lambdas"] == w["chips"]
    assert cell.limits and set(cell.limits) <= CHECKS
    assert callable(cell.model.init) and callable(cell.model.forward)
    reported = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in reported and any(n.startswith("samples_per_s") for n in reported)
    assert cell.per_layer
    for m in cell.end_to_end + cell.per_layer:
        assert callable(cells.reader(m["name"]))


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file_states_what_it_reduced(config):
    cfg = json.loads((ROOT / config["file"]).read_text())
    assert cfg["name"] == config["name"] and cfg["source"] == config["source"]
    for key in config["reduced"]:
        assert cfg[key] != cfg["published"][key]
    assert (ROOT / config["file"]).with_suffix(".py").exists()


def test_a_new_workload_and_metric_are_found_from_new_files(tmp_path):
    here = tmp_path / "benchmarks" / "chip"
    shutil.copytree(HERE, here, ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append({"name": "vgg11-new", "config": "vgg11", "traffic": "new",
                               "chips": 1, "why": "a cell added by files alone"})
    e2e = next(m for m in bench["end_to_end"] if m["name"] == "samples_per_s.m1")
    e2e["workloads"].append("vgg11-new")
    bench["per_layer"].append({"name": "new_metric", "unit": "ms", "better": "lower",
                               "source": "host_clock", "layer": "device",
                               "moves": "samples_per_s.m1", "workloads": ["vgg11-new"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    traffic = json.loads((HERE / "traffic" / "qsgd127ef-b64m1.json").read_text())
    (here / "traffic" / "new.json").write_text(json.dumps({**traffic, "batch_per_peer": 32}))
    (here / "limits" / "vgg11-new.json").write_text(
        (HERE / "limits" / "vgg11-qsgd127ef-b64m1.json").read_text())
    (here / "metrics" / "new_metric.py").write_text("def read(run):\n    return 1.5\n")
    cell = cells.load_cell("vgg11-new", root=tmp_path, here=here)
    assert cell.traffic["batch_per_peer"] == 32
    assert [m["name"] for m in cell.per_layer][-1] == "new_metric"
    assert {m["name"] for m in cell.end_to_end} == {"samples_per_s.m1", "setup_s"}
    assert cells.reader("new_metric", here=here)(None) == 1.5
    assert "new_metric" not in {m["name"] for m in cells.load_cell(
        "vgg11-qsgd127ef-b64m1", root=tmp_path, here=here).per_layer}


def _run(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", "vgg11-qsgd127ef-b64m1",
         "--seed", "3000000001", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(out):
    return not any(line.lstrip().startswith("{") for line in out.splitlines())


def test_a_run_on_the_cpu_exits_nonzero_with_no_result():
    p = _run(ROOT)
    assert p.returncode != 0
    assert _no_result(p.stdout)
    assert "needs a TPU" in p.stderr


def test_a_run_without_the_program_exits_nonzero_with_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert _no_result(p.stdout)
