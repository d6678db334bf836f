"""Device time by layer (``chipbench/scopes.py``): the program's named scopes
reach the compiled step's HLO, the HLO map and the trace reduce as read by
hand, and the four-peer cell's exchange is checked across peers.

The four-peer cases run once, in a subprocess on four CPU devices (the
device-count flag must not reach this process), at the size the fault
tests use: 4 images a batch. There the codec runs its jnp path: the Pallas
kernels, interpreted on a CPU, take about 50 s a round over four peers, and
what is checked here, the exchange across peers, is the same on both paths.
The chip runs the kernels."""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))

from chipbench import cells, scopes, trace  # noqa: E402

FOUR = "vgg11-qsgd127ef-b64m1-4x1"
SMALL = dict(batch_per_peer=4, batches_per_peer=4)


def small(name, **kw):
    cell = cells.load_cell(name)
    cell.traffic.update(SMALL, accum_steps=min(cell.traffic["accum_steps"], 2))
    cell.traffic.update(kw)
    return cell


# --- HLO text -------------------------------------------------------------

HLO = textwrap.dedent("""\
    HloModule jit_step

    %body.1 (p.1: (s32[], f32[8])) -> (s32[], f32[8]) {
      %p.1 = (s32[], f32[8]) parameter(0)
      %gte.1 = f32[8]{0} get-tuple-element(%p.1), index=1
      %mul.1 = f32[8]{0} multiply(%gte.1, %gte.1), metadata={op_name="jit(step)/p2p.fanout/while/body/mul"}
      ROOT %t.1 = (s32[], f32[8]) tuple(%c.0, %mul.1)
    }

    ENTRY %main (a: f32[8]) -> f32[8] {
      %a = f32[8]{0} parameter(0)
      %while.3 = (s32[], f32[8]) while(%t.0), condition=%cond.1, body=%body.1, metadata={op_name="jit(step)/p2p.fanout/while"}
      %copy-start.2 = (f32[8]{0:S(1)}, f32[8]{0}, u32[]{:S(2)}) copy-start(%a)
      %copy-done.2 = f32[8]{0:S(1)} copy-done(%copy-start.2)
      %fusion.7 = f32[8]{0} fusion(%copy-done.2, %gte.9), kind=kLoop, calls=%fc.1, metadata={op_name="jit(step)/shard_map/p2p.encode/jit(qsgd_quantize)/mul"}
      %all-gather-start.4 = (f32[8]{0}, f32[32]{0}) all-gather-start(%fusion.7), dimensions={0}, metadata={op_name="jit(step)/p2p.gather/all_gather"}
      %all-gather-done.4 = f32[32]{0} all-gather-done(%all-gather-start.4)
      %bitcast.5 = f32[4,8]{1,0} bitcast(%all-gather-done.4)
      %loose.1 = f32[] constant(0)
      ROOT %sub.9 = f32[8]{0} subtract(%a, %a), metadata={op_name="jit(step)/p2p.optimizer/sub"}
    }
    """)


def test_instruction_names_and_opcodes_are_read_from_hlo_text():
    line = ("%copy-start.2 = (f32[8]{0:T(128)S(1)}, f32[8]{0:T(128)}, u32[]{:S(2)}) "
            "copy-start(f32[8]{0:T(128)} %a)")
    assert scopes.instr_name(line) == "copy-start.2"
    assert scopes.instr_name("  ROOT %tuple.5 = (f32[]) tuple(%x)") == "tuple.5"
    assert scopes.opcode(line) == "copy-start"
    assert scopes.opcode("%fusion.7 = f32[8]{0:T(8,128)} fusion(%a), kind=kLoop") == "fusion"
    assert scopes.innermost("jit(step)/p2p.exchange/p2p.encode/jit(q)/mul") == "p2p.encode"
    assert scopes.innermost("jit(step)/shard_map/mul") is None


@pytest.mark.parametrize("text,want", [
    ("%all-gather.5 = f32[4,8]{1,0} all-gather(f32[1,8]{1,0} %x), dimensions={0}", True),
    ("%all-gather-start.4 = (f32[8]{0}, f32[32]{0}) all-gather-start(%fusion.7)", True),
    ("%all-gather-done.4 = f32[32]{0:T(128)} all-gather-done((f32[8], f32[32]) %s)", True),
    ("%all-reduce.1 = f32[] all-reduce(f32[] %l), to_apply=%add", True),
    ("%all-reduce-start.2 = f32[] all-reduce-start(f32[] %l), to_apply=%add", True),
    ("%reduce-scatter.3 = f32[2]{0} reduce-scatter(f32[8]{0} %g), dimensions={0}", True),
    ("%collective-permute-done.6 = f32[8]{0} collective-permute-done(%cp)", True),
    ("%all-to-all.2 = f32[8]{0} all-to-all(f32[8]{0} %g), dimensions={0}", True),
    ("%copy-start.2 = (f32[8]{0:S(1)}, f32[8]{0}, u32[]{:S(2)}) copy-start(%a)", False),
    ("%all_gather_fusion.3 = f32[32]{0} fusion(%a), kind=kLoop, calls=%fc", False),
    ("%qsgd_dequant_reduce.34 = f32[32,512]{1,0} custom-call(%c, %p), "
     "custom_call_target=\"tpu_custom_call\"", False),
])
def test_collective_ops_are_picked_by_opcode(text, want):
    assert scopes.is_collective(text) is want


def test_an_instruction_without_a_scope_takes_its_users_then_its_operands():
    m = scopes.hlo_scopes(HLO)
    assert m["while.3"] == m["mul.1"] == "p2p.fanout"
    assert m["fusion.7"] == "p2p.encode"
    # copy-start -> copy-done -> fusion.7: the users' scope
    assert m["copy-start.2"] == m["copy-done.2"] == "p2p.encode"
    # all-gather-done and the bitcast after it have no user: the operand's
    assert m["all-gather-done.4"] == m["bitcast.5"] == "p2p.gather"
    assert m["sub.9"] == "p2p.optimizer" and m["loose.1"] == "unscoped"


# --- the trace reduced by scope -------------------------------------------

def ev(plane, name, start, dur):
    line = "XLA Ops" if plane.startswith("/device") else "python"
    return {"plane": plane, "line": line, "name": name, "start": float(start),
            "dur": float(dur)}


TPU0, TPU1, HOST = "/device:TPU:0", "/device:TPU:1", "/host:CPU"
HAND = [
    ev(HOST, "bench.window", 0, 1000),
    # device 0: a while [100, 600) whose body ops lie inside it, one of them a
    # copy-done whose own scope is another's
    ev(TPU0, "%while.3 = (s32[], f32[8]) while(%t.0), body=%body.1", 100, 500),
    ev(TPU0, "%mul.1 = f32[8]{0} multiply(%gte.1, %gte.1)", 120, 100),
    ev(TPU0, "%copy-done.2 = f32[8]{0:S(1)} copy-done(%copy-start.2)", 300, 50),
    ev(TPU0, "%fusion.7 = f32[8]{0} fusion(%copy-done.2)", 650, 100),
    ev(TPU0, "%all-gather-start.4 = (f32[8], f32[32]) all-gather-start(%fusion.7)", 760, 40),
    ev(TPU0, "%sub.9 = f32[8]{0} subtract(%a, %a)", 800, 100),
    ev(TPU0, "%loose.1 = f32[] constant(0)", 950, 10),
    # device 1: the same loop, shorter
    ev(TPU1, "%while.3 = (s32[], f32[8]) while(%t.0), body=%body.1", 100, 300),
    ev(TPU1, "%mul.1 = f32[8]{0} multiply(%gte.1, %gte.1)", 150, 100),
    ev(TPU1, "%sub.9 = f32[8]{0} subtract(%a, %a)", 500, 100),
]


def test_scope_seconds_count_a_loop_and_its_body_once():
    t = trace.Trace.from_events(HAND)
    secs = scopes.scope_seconds(t, scopes.hlo_scopes(HLO))
    # fanout: the while alone, 500 and 300 ns (its body and the copy-done
    # inside it are not added); encode 100 on device 0; gather 40; optimizer
    # 100 on each; unscoped 10 on device 0; averaged over the two devices
    assert secs == pytest.approx({"p2p.fanout": 400e-9, "p2p.encode": 50e-9,
                                  "p2p.gather": 20e-9, "p2p.optimizer": 100e-9,
                                  "unscoped": 5e-9})
    assert sum(secs.values()) == pytest.approx(t.busy_s())
    assert t.op_s(scopes.is_collective) == pytest.approx(20e-9)


def test_a_program_without_scopes_reads_nothing(monkeypatch):
    t = trace.Trace.from_events(HAND)
    monkeypatch.setattr(scopes, "step_hlo", lambda cell: HLO.replace("p2p.", "x."))
    run = type("Run", (), {"trace": t, "rounds": 2, "cell": small(FOUR)})()
    for metric in ("fanout_ms", "codec_ms", "ef_ms", "optimizer_ms"):
        assert cells.reader(metric)(run) is None
    assert cells.reader("collective_ms")(run) == pytest.approx(40e-9 / 2 / 2 * 1e3)


def test_the_scope_metrics_read_device_ms_per_round(monkeypatch):
    t = trace.Trace.from_events(HAND)
    monkeypatch.setattr(scopes, "step_hlo", lambda cell: HLO)
    run = type("Run", (), {"trace": t, "rounds": 2, "cell": small(FOUR)})()
    got = {m: cells.reader(m)(run) for m in ("fanout_ms", "codec_ms", "ef_ms", "optimizer_ms")}
    assert got == pytest.approx({"fanout_ms": 400e-9 / 2 * 1e3, "codec_ms": 50e-9 / 2 * 1e3,
                                 "ef_ms": 0.0, "optimizer_ms": 100e-9 / 2 * 1e3})


def test_a_recorded_tpu_trace_reduces_by_scope():
    """10 ms of a traced window of the qsgd cell on a TPU v5e, about two
    rounds, saved with the map of its instructions (``record_trace.py``)."""
    import gzip

    with gzip.open(HERE / "fixtures" / "vgg11-qsgd127ef-b64m1.scopes.json.gz", "rt") as f:
        fix = json.load(f)
    t = trace.Trace.from_events(fix["events"])
    hlo_map = fix["hlo_map"]
    assert t.devices == [0] and t.window_s == pytest.approx(0.01)
    assert all(scopes.instr_name(n) in hlo_map for _, _, n in t.ops[0])
    secs = scopes.scope_seconds(t, hlo_map)
    busy = t.busy_s()
    assert 0.006 < busy < 0.008
    assert sum(secs.values()) == pytest.approx(busy, rel=0.02)
    assert secs.get(scopes.UNSCOPED, 0.0) < 0.05 * busy
    assert set(secs) <= set(scopes.SCOPES) | {scopes.UNSCOPED}
    # the codec holds its kernels; fc2's update is the optimizer's
    qsgd = cells.load_module(HERE / "metrics" / "qsgd_kernel_ms.py").is_qsgd
    assert secs["p2p.encode"] + secs["p2p.decode"] > t.op_s(qsgd) > 0.001
    assert {hlo_map[scopes.instr_name(n)] for _, _, n in t.ops[0] if qsgd(n)} == {
        "p2p.encode", "p2p.decode"}
    assert {hlo_map[scopes.instr_name(n)] for _, _, n in t.ops[0]
            if trace.op_label(n) == "multiply_subtract_fusion"} == {"p2p.optimizer"}
    # one peer: nothing crosses a chip
    assert t.op_s(scopes.is_collective) == 0


# --- the compiled step ------------------------------------------------------

def _body_names(text, loop):
    """Instruction names of the body computation of the while ``loop``."""
    line = next(x for x in text.splitlines() if x.strip().startswith(f"%{loop} = "))
    body = line.split("body=%", 1)[1].split(",")[0].split()[0]
    out, inside = [], False
    for x in text.splitlines():
        if x.startswith(f"%{body} "):
            inside = True
        elif inside and x.startswith("}"):
            break
        elif inside and " = " in x:
            out.append(scopes.instr_name(x))
    return out


def test_the_accumulation_loop_and_its_body_map_to_fanout():
    cell = small("vgg11-allgather-b64m235", accum_steps=4)
    text = scopes.step_hlo(cell)
    m = scopes.hlo_scopes(text)
    loops = [scopes.instr_name(x) for x in text.splitlines()
             if scopes.opcode(x) == "while" and "p2p.fanout" in x]
    assert loops
    for loop in loops:
        body = _body_names(text, loop)
        assert m[loop] == "p2p.fanout" and body
        assert {m[n] for n in body} == {"p2p.fanout"}


FOUR_PEERS = textwrap.dedent("""
    import json, sys, time
    sys.path.insert(0, sys.argv[1])
    import jax, jax.numpy as jnp
    from jax import lax
    from chipbench import cells, harness, scopes
    from chipbench import system as system_mod
    from repro.core import exchange

    class Own:
        # lax whose gathers return the peer's own payload from every peer
        def __getattr__(self, name):
            return getattr(lax, name)

        @staticmethod
        def all_gather(x, axis, **kw):
            return jnp.broadcast_to(x[None], (lax.axis_size(axis),) + x.shape)

    compile_ = system_mod.System.compile

    def own_gradient(self, batch):
        exchange.lax = Own()
        try:
            return compile_(self, batch)
        finally:
            exchange.lax = lax

    cell = cells.load_cell(sys.argv[2])
    cell.traffic.update(json.loads(sys.argv[3]))
    text = scopes.step_hlo(cell)
    out = {"scopes": sorted(set(scopes.hlo_scopes(text).values())),
           "op_names_with_two_scopes": sum(
               1 for line in text.splitlines() if "op_name=" in line and
               len([c for c in line.split('op_name="', 1)[1].split('"', 1)[0].split("/")
                    if c in scopes.SCOPES]) > 1),
           "collective_ops": sum(map(scopes.is_collective, text.splitlines()))}
    for fault in (None, "exchange_left_out"):
        if fault:
            system_mod.System.compile = own_gradient
        r = harness.run(cell, 4_000_000_123, 0.2, False, t0=time.perf_counter(),
                        require_tpu=False)
        out[str(fault)] = {"correct": r["correct"], "checks": r["checks"]}
    print("RESULT " + json.dumps(out))
    """)


@pytest.fixture(scope="module")
def four_peers():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(ROOT / "src"))
    p = subprocess.run([sys.executable, "-c", FOUR_PEERS, str(HERE), FOUR,
                        json.dumps({**SMALL, "accum_steps": 1,
                                    "qsgd": {"levels": 127, "bucket": 512, "impl": "jnp"}})],
                       env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    line = next(x for x in p.stdout.splitlines() if x.startswith("RESULT "))
    return json.loads(line[len("RESULT "):])


def test_every_layer_of_the_four_peer_step_has_its_scope(four_peers):
    assert set(scopes.SCOPES) <= set(four_peers["scopes"])
    assert four_peers["op_names_with_two_scopes"] == 0
    assert four_peers["collective_ops"] > 0


@pytest.mark.parametrize("fault", [None, "exchange_left_out"])
def test_a_four_peer_step_without_its_exchange_is_not_correct(four_peers, fault):
    assert four_peers[str(fault)]["correct"] is (fault is None), four_peers[str(fault)]
