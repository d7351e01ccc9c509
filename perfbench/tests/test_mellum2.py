"""The ``mellum`` family's part of the benchmark (PR 32): the cell's
rehearsal through driver, reference and comparison, with the control and
every planted fault read above the limit the program passes; the FLOP
and byte counts against hand counts; each new metric file read from a
hand-made trace of this model; the configuration file against the
catalog entry it was copied from, and its arithmetic."""

import importlib
import json
from pathlib import Path

import numpy as np
import pytest

from perfbench import harness, peaks, tracered as R, work, work_sparse_moe as wsm, work_window_moe as wwm

ROOT = Path(__file__).resolve().parents[2]
CELL = "mellum2-serve-mixed-closed-1chip"
MELLUM = json.loads((ROOT / "perfbench/configs/mellum2-12b-a2.5b.json").read_text())
TRAFFIC = harness.load_json(ROOT / "perfbench/traffic/serve-mixed-closed.json")
METRICS = harness.metric_files()
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
FAULTS = ("control_fp8", "fault_window_as_full", "fault_window_short", "fault_window_long",
          "fault_rope_default", "fault_drop_expert")


# ---- the rehearsal: program passes, control and faults do not -------------

def test_rehearsal_is_correct_and_every_wrong_reading_is_over_the_limit(rehearse):
    rc, line, _ = rehearse(CELL, probe=True)
    assert rc == 0 and line["correct"] is True, line["check"]
    limits = {k: n["limit"] for k, n in line["check"].items() if n["limit"] is not None}
    assert set(limits) == {"served_logit_gap", "served_logit_gap_mean", "requests_failed"}
    for probe in FAULTS:
        assert line["check"][f"{probe}.served_logit_gap_mean"]["value"] > limits["served_logit_gap_mean"], probe
    assert line["check"]["fault_token_altered.served_logit_gap"]["value"] > limits["served_logit_gap"]
    counts = line["rehearsal"]["counts"]
    assert counts["slot_occupancy"] > 0.5 and 0.0 < counts["kv_live_share"] < 1.0
    w = counts["window"]
    assert w["prefill_chunks"] >= w["admissions"] > 0 and w["window_pages_freed"] > 0
    # contexts pass the window of 8, so a sliding layer reads fewer keys
    # than a full one: 6 sliding layers, 2 full
    assert 0 < w["window_tokens_read"] < 3 * w["full_tokens_read"]
    assert counts["pool_pages"] == {"full": 49, "window": 3 * 4 + 1}
    # the one of the longest prompt is among the checked
    assert line["check"]["longest_prompt_checked"]["value"] > 3 * 8


def test_the_cell_s_traffic_is_the_issue_s():
    from perfbench.drivers.serve_engine_sparse_moe import sized_pool

    a_prompts, a_answers = sized_pool(TRAFFIC, 3200000001)
    b_prompts, b_answers = sized_pool(TRAFFIC, 3200000002)
    assert [len(p) for p in a_prompts] == [len(p) for p in b_prompts] and a_answers == b_answers
    assert not np.array_equal(a_prompts[0], b_prompts[0])
    first, second = sorted(map(len, a_prompts[:32])), sorted(map(len, a_prompts[32:64]))
    assert first == second and len(set(first)) == 32
    assert 256 <= first[0] < 300 and 8192 < first[-5] and first[-1] <= 16384
    assert all(128 <= a <= 512 for a in a_answers)
    assert all(len(p) + a <= TRAFFIC["max_total_len"] == 16896 for p, a in zip(a_prompts, a_answers))
    assert max(int(p.max()) for p in a_prompts[:8]) < TRAFFIC["token_id_below"] == MELLUM["vocab_size"]
    # the full group holds every slot at its cap; the window group is the engine's own
    assert TRAFFIC["num_pages"] == 32 * 1056 + 1 and TRAFFIC["max_pages_per_slot"] * 16 == 16896
    assert (TRAFFIC["clients"], TRAFFIC["num_slots"], TRAFFIC["prefill_chunk"]) == (32, 32, 512)
    assert (TRAFFIC["warm_requests"], TRAFFIC["check_requests"], TRAFFIC["trace_seconds"]) == (32, 4, 4)


# ---- counts -----------------------------------------------------------------

def test_active_parameters_by_hand():
    attn = 2304 * 4096 * 2 + 2304 * 512 * 2          # q, out; k, v
    experts = 8 * 3 * 2304 * 896
    assert attn == 21_233_664
    assert wwm.active_matmul_params_per_layer(MELLUM) == attn + 2304 * 64 + experts == 70_926_336
    assert wwm.matmul_params_per_layer(MELLUM) == attn + 2304 * 64 + 64 * 3 * 2304 * 896 == 417_742_848
    assert wwm.active_matmul_params(MELLUM) == 8 * 70_926_336 + 2304 * 98_304
    eq = wwm.dense_equivalent(MELLUM)
    assert eq == {"n_embd": 2304, "n_inner": 10784, "n_layer": 8}
    # the accepted count, over the GPT-2-style keys, is the active count
    assert work.transformer_matmul_params({**MELLUM, **eq}) == wwm.active_matmul_params(MELLUM)


def test_keys_attended_and_bytes_by_hand():
    d = wwm.dims(MELLUM)
    assert (d["window_layers"], d["full_layers"], d["window"]) == (6, 2, 1024)
    cfg = {**MELLUM, "sliding_window": 4}
    assert wwm.prefill_keys(10, cfg) == (55.0, float(sum(min(t + 1, 4) for t in range(10))))
    assert wwm.prefill_keys(3, cfg) == (6.0, 6.0)
    # a key: K and V rows of 4 heads x 128 in bf16, 4 x 128 x 32 FLOPs
    assert wwm.attention_bytes(1, MELLUM) == 2 * 4 * 128 * 2 == 2048
    assert wwm.attention_flops(1, MELLUM) == 4 * 128 * 32
    # one prompt of 10 and the decode steps' own counts, window 4
    want = 4 * 128 * 32 * (100 + 2 * 55 + 50 + 6 * 34)
    assert wwm.attention_flops_in_window(100, 50, [10], cfg) == want
    # two groups against one: 2 layers hold the context, 6 hold a window
    assert wwm.kv_live_share(1000, 100, MELLUM) == pytest.approx((2 * 1000 + 6 * 100) / 8000)
    # the experts' counts the accepted reader takes (work_sparse_moe) at this model's sizes
    both = {**MELLUM, "sa_config": wwm.NO_INDEXER}
    assert wsm.moe_flops(1, both) == 6 * 2304 * 896 and wsm.moe_bytes(1, both) == 3 * 2304 * 896 * 2


# ---- readers ------------------------------------------------------------------

def trace():
    ops = [
        ("%attn_window.1 = bf16[32,4,8,128] custom-call()", "attn_window_custom-call_bf16_32_4_8_128_", 1.00, 1.01),
        ("%attn_full.1 = bf16[32,4,8,128] custom-call()", "attn_full_custom-call_bf16_32_4_8_128_", 1.01, 1.03),
        ("%moe.1 = bf16[256,896] custom-call()", "moe_custom-call_bf16_256_896_", 1.03, 1.07),
        ("%moe.2 = bf16[4096,896] custom-call()", "moe_custom-call_bf16_4096_896_", 0.20, 0.30),  # in a chunk
    ]
    host = [
        ("serve/step", 0.0, 1.2), ("serve/admit", 0.0, 0.9), ("serve/prefill", 0.1, 0.9),
        ("serve/window_free", 0.1, 0.101), ("serve/prefill_chunk", 0.101, 0.5),
        ("serve/window_free", 0.5, 0.502), ("serve/prefill_chunk", 0.502, 0.9),
        ("serve/decode_prep", 0.9, 1.0), ("serve/window_free", 0.9, 0.903),
        ("serve/decode", 1.0, 1.1), ("perfbench/engine_step", 0.0, 1.2),
    ]
    return R.Trace({0: ops}, host, {0: [("jit_prefill_chunk(1)", 0.1, 0.45), ("jit_step(2)", 1.0, 1.08)]})


def ctx(traced=True):
    counts = {
        "slot_occupancy": 1.0, "prompt_tokens_in_window": 200_000, "tokens_in_window": 15_000,
        "attention_flops_in_window": 3e13, "kv_live_share": 0.36,
        "traced": {"decode_steps": 1, "full_tokens_read": 2 * 32 * 7000, "window_tokens_read": 6 * 32 * 1024,
                   "experts_hit": 8 * 63, "token_expert_pairs": 8 * 8 * 32} if traced else None,
    }
    run = {"window_s": 30.0, "counts": counts, "spans": {"itl_ms": [30.0, 1200.0]}, "compile_s": 70.0,
           "compiles_in_window": 0}
    config = {**MELLUM, **wwm.dense_equivalent(MELLUM), "sa_config": wwm.NO_INDEXER}
    return {"run": run, "trace": trace(), "config": config, "traffic": {}, "cell": {},
            "peaks": peaks.peaks_for("TPU v5 lite")}


def read(name, c, **args):
    m = METRICS[name]
    m = {**m, "args": {**m["args"], **args}}
    return importlib.import_module(f"perfbench.readers.{m['reader']}").read(c, m)


NEW = sorted(n for n, m in METRICS.items() if m.get("workloads") == [CELL])


def test_the_new_metrics_are_the_issue_s_ten():
    assert NEW == sorted([
        "serve_window_attn_ms_per_step", "window_attn_roofline", "serve_full_attn_ms_per_step",
        "full_attn_roofline", "serve_moe_ms_per_step.mixed", "moe_gmm_roofline.mixed",
        "serve_prefill_chunk_ms_p50.mixed", "serve_chunks_per_admit_p50.mixed",
        "serve_window_free_ms_per_step", "serve_kv_live_share",
    ])
    assert all(METRICS[n]["moves"] == "serve_tokens_per_s" for n in NEW)
    manifest = harness.load_manifest()
    assert CELL in next(m for m in manifest["end_to_end"] if m["name"] == "serve_tokens_per_s")["workloads"]
    entries = {m["name"]: m for m in manifest["per_layer"]}
    assert all({k: METRICS[n][k] for k in entries[n]} == entries[n] for n in NEW)
    # the cell's own two are the last entries of their lists
    assert manifest["workloads"][-1]["name"] == CELL and manifest["configs"][-1]["name"] == "mellum2-12b-a2.5b"


def test_each_kernel_is_read_by_its_name_inside_the_decode_span():
    c = ctx()
    assert read("serve_window_attn_ms_per_step", c) == pytest.approx(10.0)
    assert read("serve_full_attn_ms_per_step", c) == pytest.approx(20.0)
    assert read("serve_moe_ms_per_step.mixed", c) == pytest.approx(40.0)  # the chunk's call is not counted
    need = wwm.attention_bytes(6 * 32 * 1024, MELLUM) / 819e9
    assert need > wwm.attention_flops(6 * 32 * 1024, MELLUM) / 197e12  # bandwidth-bound
    assert read("window_attn_roofline", c) == pytest.approx(100 * need / 0.01)
    assert read("full_attn_roofline", c) == pytest.approx(100 * wwm.attention_bytes(2 * 32 * 7000, MELLUM) / 819e9 / 0.02)
    assert read("moe_gmm_roofline.mixed", c) == pytest.approx(100 * 3 * 2304 * 896 * 2 * 8 * 63 / 819e9 / 0.04)
    assert all(0 < read(n, c) < 100 for n in NEW if n.endswith("_roofline") or "_roofline." in n)


def test_span_metrics_and_the_share():
    assert read("serve_prefill_chunk_ms_p50.mixed", ctx()) == pytest.approx(398.5)
    assert read("serve_chunks_per_admit_p50.mixed", ctx()) == 2.0
    assert read("serve_window_free_ms_per_step", ctx()) == pytest.approx(6.0)  # 1 + 2 + 3 ms in one step
    assert read("serve_kv_live_share", ctx()) == pytest.approx(36.0)


def test_the_whole_step_share_counts_active_parameters():
    """``mfu.serve`` (accepted, GPT-2-style keys) over this cell's counts."""
    got = importlib.import_module("perfbench.readers.mfu").read(ctx(), METRICS["mfu.serve"])
    flops = 2 * wwm.active_matmul_params(MELLUM) * 215_000 + 3e13
    assert got == pytest.approx(100 * flops / 30.0 / 197e12) and 0.0 < got < 100.0


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_spans_or_counters_leaves_the_metric_out(name):
    """The parent of PR 32 has no such counters, spans or kernel names
    (nor the cell); the readers return nothing and do not raise."""
    bare = ctx(traced=False)
    bare["run"]["counts"].pop("kv_live_share")
    bare["trace"] = R.Trace({0: [("%x = f32[8] fusion()", "fusion_fusion_f32_8_", 0.0, 0.1)]}, [], {})
    assert read(name, bare) is None
    if name.endswith("_roofline") or "_roofline." in name:
        assert read(name, ctx(traced=False)) is None


# ---- the configuration file ---------------------------------------------------

def test_configuration_is_the_catalog_s_but_for_depth():
    if not CATALOG.is_file():
        pytest.skip("no catalog beside the model-configs guide here")
    entry = next(e for e in map(json.loads, CATALOG.read_text().splitlines())
                 if e["name"] == "Mellum2-12B-A2.5B-Instruct")
    assert MELLUM["source"] == entry["source_url"]
    differ = sorted(k for k, v in entry["config"].items() if MELLUM.get(k) != v)
    assert differ == ["num_hidden_layers"] == MELLUM["reduced"]
    assert MELLUM["num_hidden_layers"] == 8 and MELLUM["published"]["num_hidden_layers"] == 28
    assert MELLUM["layer_types"][:8] == (["sliding_attention"] * 3 + ["full_attention"]) * 2
    assert {"qk_norm", "sliding_window", "rope", "router", "weights"} <= set(MELLUM["assumed"])
    assert len(MELLUM["departures"]) >= 4


def test_the_deployment_s_arithmetic():
    dep = MELLUM["deployment"]
    assert dep["params_per_layer"] == wwm.matmul_params_per_layer(MELLUM) == 417_742_848
    assert dep["params_embedding_and_head"] == 2 * 98_304 * 2304
    assert dep["weights_bytes"] == 2 * (8 * dep["params_per_layer"] + dep["params_embedding_and_head"])
    row = 4 * 128 * 2  # a token's K (or V) row of one layer, bf16
    full, window = dep["full_group"], dep["window_group"]
    assert full["pages"] == TRAFFIC["num_pages"] == 33_793 and full["pool_bytes"] == 33_793 * 16 * row
    assert full["pools"] == 2 * full["layers"] == 4 and full["bytes"] == 4 * full["pool_bytes"]
    p_w = -(-(1024 + 512 - 1) // 16) + 1
    assert window["pages_per_slot"] == p_w == 97 and window["pages"] == 32 * p_w + 1 == 3105
    assert window["pools"] == 2 * window["layers"] == 12 and window["bytes"] == 12 * 3105 * 16 * row
    assert dep["total_bytes"] == dep["weights_bytes"] + full["bytes"] + window["bytes"]
    assert 10.4e9 < dep["total_bytes"] < 10.5e9 and dep["one_group_for_all_layers_bytes"] == 16 * full["pool_bytes"]
    # one group for all eight layers would not fit beside the weights
    assert dep["weights_bytes"] + dep["one_group_for_all_layers_bytes"] > 16e9
