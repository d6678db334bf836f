"""The cell ``mobilenet-v3-small-allgather-b64m235`` on the CPU: a whole run
with the timed path broken underneath (``correct`` false for each fault,
true when nothing is broken), its configuration's forward MACs counted by
hand, and the ``depthwise_ms`` reader on the step compiled for the CPU."""
import re
import sys
import time
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

from chipbench import cells, harness, scopes  # noqa: E402
from chipbench.trace import Trace  # noqa: E402
from test_chipbench_faults import plant  # noqa: E402

CELL = "mobilenet-v3-small-allgather-b64m235"


def small(name):
    cell = cells.load_cell(name)
    cell.traffic.update(batch_per_peer=4, batches_per_peer=4,
                        accum_steps=min(cell.traffic["accum_steps"], 2))
    return cell


@pytest.mark.parametrize("fault", [None, "state_unchanged", "half_batch"])
def test_a_broken_step_is_not_correct(monkeypatch, fault):
    cell = small(CELL)
    plant(monkeypatch, fault)
    out = harness.run(cell, 4_000_000_123, 0.2, False, t0=time.perf_counter(),
                      require_tpu=False)
    assert out["correct"] is (fault is None), out["checks"]


def test_layer_macs_by_hand():
    cell = cells.load_cell(CELL)
    macs = cell.model.layer_macs(cell.config)
    # 32 px, stem at stride 1: maps 32 -> 16 (block 1) -> 8 (2) -> 4 (4) -> 2 (9)
    assert macs["stem"] == 32 * 32 * 9 * 3 * 16 == 442_368
    assert "block1.expand" not in macs  # expansion 16 equals the input
    assert macs["block1.depthwise"] == 16 * 16 * 9 * 16 == 36_864
    assert macs["block5.se"] == 2 * 240 * 64
    assert macs["block11.depthwise"] == 2 * 2 * 25 * 576
    assert macs["head"] == 2 * 2 * 96 * 576
    assert macs["classifier"] == 576 * 1024 + 1024 * 10
    assert sum(v for k, v in macs.items() if k.endswith("depthwise")) == 609_024
    assert sum(macs.values()) == 5_502_720
    # torchvision's count at 224 px and 1,000 classes, stem at stride 2
    published = dict(cell.config, image_size=224, num_classes=1000, stem_stride=2)
    assert sum(cell.model.layer_macs(published).values()) == 56_510_400


def top_level_lines(hlo_text):
    """The instruction lines of the computations a trace shows ops of: the
    entry and the loops, not the fused computations or reducers."""
    called = set(re.findall(r"(?:calls|to_apply)=%([\w.\-]+)", hlo_text))
    out, keep = [], False
    for line in hlo_text.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            keep = line.removeprefix("ENTRY ").split()[0].lstrip("%") not in called
        elif keep and " = " in line:
            out.append(line.strip())
    return out


def fake_run(cell, lines, held=2, partial=0):
    """A run whose trace holds ``held`` whole rounds and then ``partial``
    rounds the profiler kept every other op of, 1 us an op, back to back:
    in each round the step's ``while`` spans every other line once."""
    loop = [x for x in lines if scopes.opcode(x) == "while"][:1]
    body = [x for x in lines if scopes.opcode(x) != "while"]
    ops, t = [], 0.0
    for r in range(held + partial):
        inner = body if r < held else body[::2]
        ops += [(t, t + 1e3 * len(inner), x) for x in loop]
        ops += [(t + 1e3 * i, t + 1e3 * (i + 1), x) for i, x in enumerate(inner)]
        t += 1e3 * len(inner)
    trace = Trace(lo=0.0, hi=t, ops={0: ops})
    n = held + partial
    return harness.Run(cell=cell, device_kind="cpu", chips=1, setup_s=0.0,
                       window_s=trace.window_s, rounds=n, samples=0, dispatch_s=0.0,
                       dispatches=n, leaf_sizes=[], trace=trace)


@pytest.fixture(scope="module")
def hlo():
    return {name: scopes.step_hlo(small(name))
            for name in (CELL, "vgg11-allgather-b64m235")}


def test_depthwise_ms_reads_forward_and_backward(monkeypatch, hlo):
    read = cells.reader("depthwise_ms.m235")
    names = cells.load_module(HERE / "metrics" / "depthwise_ms.py").scoped_instructions(hlo[CELL])
    lines = top_level_lines(hlo[CELL])
    scoped = [x for x in lines if scopes.instr_name(x) in names]
    assert any("/jvp(cnn.depthwise)" in x for x in scoped)
    assert any("transpose(jvp(cnn.depthwise))" in x for x in scoped)
    assert len(scoped) < len(lines) / 2  # the rest of the step is not read
    assert any(scopes.opcode(x) == "while" for x in lines)  # the scan's loop
    cell = small(CELL)
    monkeypatch.setattr(scopes, "step_hlo", lambda c: hlo[c.name])
    per_round = len(scoped) * 1e-3
    assert read(fake_run(cell, lines)) == pytest.approx(per_round)
    # rounds the trace holds in part are left out, not averaged in
    assert read(fake_run(cell, lines, held=2, partial=3)) == pytest.approx(per_round)
    assert read(fake_run(cell, lines[:0])) is None  # an empty trace
    no_loop = [x for x in lines if scopes.opcode(x) != "while"]
    assert read(fake_run(cell, no_loop)) is None  # no round to read


def test_depthwise_ms_is_none_on_a_step_without_the_scope(monkeypatch, hlo):
    read = cells.reader("depthwise_ms.m235")
    cell = small("vgg11-allgather-b64m235")
    monkeypatch.setattr(scopes, "step_hlo", lambda c: hlo[c.name])
    assert read(fake_run(cell, top_level_lines(hlo[cell.name]))) is None
