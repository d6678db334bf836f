"""The control of every cell's correctness check comes out not correct: the
plain reference with its products' operands in fp8 (the precision below
the program's one bfloat16 pass), put in the program's place, fails at
least one of the cell's limits. At a size a CPU test can hold: 8 images a
batch, 2 batches a round where the cell accumulates several. On the chip,
at the cells' own sizes, ``calibrate.py`` reads the same control (PERF.md
gives its readings)."""
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

from chipbench import cells, data, harness, reference  # noqa: E402

WORKLOADS = [w["name"] for w in cells.read_json(cells.ROOT / "BENCHMARK.json")["workloads"]]


def small(name):
    cell = cells.load_cell(name)
    cell.traffic.update(batch_per_peer=8, batches_per_peer=4,
                        accum_steps=min(cell.traffic["accum_steps"], 2))
    return cell


@pytest.mark.parametrize("workload", WORKLOADS)
def test_the_control_is_not_correct(workload):
    cell = small(workload)
    keys = data.seed_keys(2**31 + 17)
    rounds = cell.traffic["checked_rounds"]
    ref = harness.reference_readings(cell, keys, rounds)
    ctl = harness.reference_readings(cell, keys, rounds, ops=reference.CONTROL)
    numbers = reference.compare(reference.as_program(ctl), ref)
    assert any(numbers[k] > limit for k, limit in cell.limits.items()), numbers
