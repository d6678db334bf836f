"""A whole run of a cell, past the look for a chip, at a size a CPU test can
hold, with the timed path broken underneath: ``correct`` must come out
false for each fault a training cell can have, and true when nothing is
broken. Every cell has one peer, so no exchange crosses chips to be left
out."""
import sys
import time
from pathlib import Path

import jax
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

from chipbench import cells, harness  # noqa: E402
from chipbench import system as system_mod  # noqa: E402


def halve(batch):
    """Each peer's first half of its rows (one peer here)."""
    return jax.tree.map(lambda x: x[: x.shape[0] // 2], batch)


def plant(monkeypatch, fault):
    compile_ = system_mod.System.compile

    def broken(self, batch):
        if fault == "state_unchanged":
            step = compile_(self, batch)
            return lambda s, b: (s, step(s, b)[1])
        if fault == "half_batch":
            step = compile_(self, halve(batch))
            return lambda s, b: step(s, halve(b))
        return compile_(self, batch)

    monkeypatch.setattr(system_mod.System, "compile", broken)


@pytest.mark.parametrize("fault", [None, "state_unchanged", "half_batch"])
@pytest.mark.parametrize("workload", ["vgg11-qsgd127ef-b64m1", "vgg11-allgather-b64m235"])
def test_a_broken_step_is_not_correct(monkeypatch, workload, fault):
    cell = cells.load_cell(workload)
    cell.traffic.update(batch_per_peer=4, batches_per_peer=4,
                        accum_steps=min(cell.traffic["accum_steps"], 2))
    plant(monkeypatch, fault)
    out = harness.run(cell, 4_000_000_123, 0.2, False, t0=time.perf_counter(),
                      require_tpu=False)
    assert out["correct"] is (fault is None), out["checks"]
    assert list(out)[-1] == "checks"
