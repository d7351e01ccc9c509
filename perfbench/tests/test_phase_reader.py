"""``readers/phase_ms_per_step.py`` on a hand-made trace and phase map:
the train step's device time by phase, each op's exclusive time, only
the step module's ops, nothing where the map covers too little."""

import importlib

import pytest

from perfbench import harness, tracered as R
from perfbench.readers import phase_ms_per_step as P

METRICS = harness.metric_files()
PHASE_METRICS = sorted(n for n, m in METRICS.items() if m["reader"] == "phase_ms_per_step")
MAP = {
    "jit_local_step": {
        "fusion.1": "fwd", "attn.2": "bwd", "fusion.3": "optimizer", "fusion.4": "telemetry",
        "while.5": "unscoped", "fusion.6": "bwd", "all-reduce.7": "sync",
    },
}


def op(name, lo, hi):
    line = f"%{name} = f32[8]{{0}} fusion(f32[8] %x)"
    return (line, R.op_key(line), lo, hi)


def trace(devices=1, extra=()):
    """Two steps of 1.0 s; in each: fwd 0.3, a while op holding a bwd
    fusion (0.2 of its 0.5), the flash backward 0.1, optimizer 0.05,
    telemetry 0.02, a collective 0.03; between the steps an op of another
    module."""
    ops, programs = {}, {}
    for d in range(devices):
        ops[d], programs[d] = [], []
        for k in range(2):
            t = 10.0 * k
            programs[d].append(("jit_local_step(123)", t, t + 1.0))
            ops[d] += [
                op("fusion.1", t, t + 0.3),
                op("while.5", t + 0.3, t + 0.8),
                op("fusion.6", t + 0.4, t + 0.6),
                op("attn.2", t + 0.8, t + 0.9),
                op("fusion.3", t + 0.9, t + 0.95),
                op("fusion.4", t + 0.95, t + 0.97),
                op("all-reduce.7", t + 0.97, t + 1.0),
            ] + [op(name, t + lo, t + hi) for name, lo, hi in extra]
            programs[d].append(("jit_other(9)", t + 5.0, t + 6.0))
            ops[d].append(op("fusion.1", t + 5.0, t + 5.5))
    host = [("lm", 0.0, 1.0), ("lm", 10.0, 11.0)]
    return R.Trace(ops, host, programs)


def read(phase, t, monkeypatch, phases=MAP, program=r"^jit_local_step\("):
    monkeypatch.setattr(P, "program_phases", lambda: phases)
    ctx = {"run": {}, "trace": t}
    return P.read(ctx, {"args": {"phase": phase, "program": program, "step_span": "lm"}})


@pytest.mark.parametrize("phase,ms", [
    ("fwd", 300.0), ("bwd", 300.0), ("unscoped", 300.0), ("optimizer", 50.0),
    ("telemetry", 20.0), ("sync", 30.0), ("augment", 0.0),
])
def test_each_phase_is_its_ops_exclusive_time_per_step(phase, ms, monkeypatch):
    assert read(phase, trace(), monkeypatch) == pytest.approx(ms)


def test_phases_sum_to_the_module_busy_time_and_ignore_other_modules(monkeypatch):
    monkeypatch.setattr(P, "program_phases", lambda: MAP)
    by, chips = P.phase_seconds(trace(), r"^jit_local_step\(", MAP)
    assert chips == 1 and P.UNMAPPED not in by
    # 2 steps x 1.0 s; the other module's op (0.5 s a step) is not counted.
    assert sum(by.values()) == pytest.approx(2.0)
    assert by["fwd"] == pytest.approx(0.6)


def test_nested_ops_count_exclusively():
    # A parent, a child inside it, a grandchild inside that, and an op
    # that starts inside the parent and outlasts it: each instant once.
    ops = [(0.0, 10.0), (2.0, 6.0), (3.0, 4.0), (8.0, 12.0)]
    own = P.exclusive(ops)
    assert own == pytest.approx([10.0 - 4.0 - 2.0, 4.0 - 1.0, 1.0, 4.0])
    assert sum(own) == pytest.approx(R.union_length(ops))
    # A child that starts with its parent still takes its own time.
    assert P.exclusive([(0.0, 5.0), (0.0, 1.0)]) == pytest.approx([4.0, 1.0])


def test_chips_are_averaged_and_steps_divided(monkeypatch):
    assert read("fwd", trace(devices=4), monkeypatch) == pytest.approx(300.0)


def test_no_reading_under_full_coverage(monkeypatch):
    # An op the map lacks holds 0.02 of each 1.0 s step: 98% covered.
    t = trace(extra=[("fusion.99", 0.98, 1.0)])
    assert read("fwd", t, monkeypatch) is None
    # 0.5% uncovered still reads.
    t = trace(extra=[("fusion.99", 0.995, 1.0)])
    assert read("fwd", t, monkeypatch) == pytest.approx(300.0)


def test_no_reading_without_the_program_s_map_or_the_module(monkeypatch):
    assert read("fwd", trace(), monkeypatch, phases=None) is None
    assert read("fwd", trace(), monkeypatch, phases={}) is None
    assert read("fwd", trace(), monkeypatch, phases={"jit_other": {}}) is None
    assert read("fwd", trace(), monkeypatch, program=r"^jit_local_train_step\(") is None


def test_the_instruction_is_the_head_of_the_op_s_hlo_line():
    assert P.instruction("%iota_compare_fusion.1 = pred[1024]{0} fusion(), kind=kLoop") == "iota_compare_fusion.1"
    assert P.instruction("fusion.3") == "fusion.3"


@pytest.mark.parametrize("name", PHASE_METRICS)
def test_each_phase_metric_reads_its_cell_s_step_module(name, monkeypatch):
    m = METRICS[name]
    module = "jit_local_step" if name.endswith(".lm") else "jit_local_train_step"
    phases = {module: MAP["jit_local_step"]}
    t = trace()
    for d in t.device_programs:
        t.device_programs[d] = [(n.replace("jit_local_step", module), lo, hi) for n, lo, hi in t.device_programs[d]]
    monkeypatch.setattr(P, "program_phases", lambda: phases)
    host = "lm" if name.endswith(".lm") else "train"
    t.host_spans = [(host, lo, hi) for _, lo, hi in t.host_spans]
    v = importlib.import_module(f"perfbench.readers.{m['reader']}").read({"run": {}, "trace": t}, m)
    assert isinstance(v, float) and v >= 0.0
    assert m["args"]["phase"] in ("augment", "fwd", "bwd", "optimizer", "telemetry", "unscoped")
