"""Every per-layer metric's reader: a number from what it is given to
read, nothing (never 0) where there is nothing to read."""

import importlib
import json
from pathlib import Path

import pytest

from perfbench import harness, peaks, tracered as R, work

ROOT = Path(__file__).resolve().parents[2]
GPT2 = json.loads((ROOT / "perfbench/configs/gpt2-small.json").read_text())
RESNET = json.loads((ROOT / "perfbench/configs/resnet18-cifar.json").read_text())
METRICS = harness.metric_files()


def trace(with_kernels=True):
    ops = [("%fusion.1 = f32[8]{0} fusion(f32[8] %x)", "fusion_fusion_f32_8_", 0.0, 0.9)]
    if with_kernels:
        ops += [
            ("%attn.1 = bf16[192,1024,64]{2,1,0} custom-call(bf16[192,1024,64] %q)", "attn_custom-call_bf16_192_1024_64_", 1.0, 1.1),
            ("%all-reduce.1 = f32[8]{0} all-reduce(f32[8] %g)", "all-reduce_all-reduce_f32_8_", 1.1, 1.2),
        ]
    host = [(n, 0.0, 0.5) for n in ("lm", "train", "perfbench/engine_step", "graftscope/input_fetch")] if with_kernels else []
    programs = {0: [("jit_step(1)", 0.0, 0.5), ("jit_prefill(2)", 0.5, 0.9)]} if with_kernels else {}
    return R.Trace({0: ops}, host, programs)


def ctx(config, with_kernels=True):
    run = {
        "end_to_end": {"train_samples_per_s_per_chip": 34000.0, "train_tokens_per_s_per_chip": 118000.0},
        "window_s": 30.0, "batch": 16, "seq_len": 1024, "steps_traced": 1,
        "compile_s": 4.0, "compiles_in_window": 0,
        "counts": {"slot_occupancy": 1.0, "mean_live_tokens": 60000.0, "mean_live_tokens_traced": 60000.0,
                   "prompt_tokens_in_window": 60000, "tokens_in_window": 15000, "attention_flops_in_window": 1e12},
        "spans": {"step_gaps_s": [0.138, 0.139, 0.2], "ttft_ms": [100.0, 300.0], "itl_ms": [100.0, 250.0]},
    }
    return {"run": run, "trace": trace(with_kernels), "config": config, "traffic": {}, "cell": {},
            "peaks": peaks.peaks_for("TPU v5 lite")}


@pytest.mark.parametrize("name", sorted(METRICS))
def test_reader_reads_a_number(name):
    m = METRICS[name]
    config = RESNET if name.endswith(".cifar") else GPT2
    v = importlib.import_module(f"perfbench.readers.{m['reader']}").read(ctx(config), m)
    assert isinstance(v, float) and v == v and v >= 0.0
    if m["unit"] == "%":
        assert v <= 100.0


@pytest.mark.parametrize("name", sorted(n for n, m in METRICS.items() if m["source"] == "device_trace"
                                        and m["reader"] not in ("idle_share", "idle_ms_per_step", "busy_ms_per_step")))
def test_reader_with_nothing_to_read_returns_nothing(name):
    m = METRICS[name]
    bare = ctx(GPT2, with_kernels=False)
    bare["run"]["steps_traced"] = 0
    assert importlib.import_module(f"perfbench.readers.{m['reader']}").read(bare, m) is None


def test_mfu_is_the_rate_times_the_counted_work_over_the_peak():
    m = METRICS["mfu.lm"]
    v = importlib.import_module("perfbench.readers.mfu").read(ctx(GPT2), m)
    assert v == pytest.approx(100 * 118000.0 * work.transformer_train_flops_per_token(GPT2, 1024) / 197e12)
    assert 45.0 < v < 50.0


def test_collective_reader_total_and_exposed_part():
    # No metric file names this reader until the four-chip cell is added
    # (perfbench/tests/cells.py); the entry it will get is spelled out here.
    read = importlib.import_module("perfbench.readers.collective_ms_per_step").read
    entry = {"args": {"step_span": "train"}}
    total = read(ctx(RESNET), entry)
    exposed = read(ctx(RESNET), {"args": {"step_span": "train", "exposed": True}})
    assert total == pytest.approx(100.0) and 0.0 <= exposed <= total
    bare = ctx(RESNET, with_kernels=False)
    assert read(bare, entry) is None
