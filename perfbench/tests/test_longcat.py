"""The ``longcat_flash`` family's part of the benchmark (PR 34): the
cell's rehearsal through driver, reference and comparison, with the
control and every planted fault read above the limit the program passes;
the FLOP and byte counts against hand counts; each new metric file read
from a hand-made trace of this model; the configuration file against the
catalog entry it was copied from, and its arithmetic."""

import importlib
import json
from pathlib import Path

import numpy as np
import pytest

from perfbench import harness, peaks, tracered as R, work, work_latent_moe as wlm, work_sparse_moe as wsm

ROOT = Path(__file__).resolve().parents[2]
CELL = "longcat-serve-chat-closed-1chip"
LONGCAT = json.loads((ROOT / "perfbench/configs/longcat-flash-chat.json").read_text())
TRAFFIC = harness.load_json(ROOT / "perfbench/traffic/serve-chat-closed.json")
METRICS = harness.metric_files()
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
FAULTS = ("control_fp8", "fault_no_zero_experts", "fault_bias_in_weights", "fault_no_kv_scale",
          "fault_rope_half_on_q", "fault_moe_before_second_attention", "fault_drop_expert")


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
    w = counts["window"]
    assert counts["slot_occupancy"] > 0.5 and w["prefill_chunks"] >= w["admissions"] > 0
    # 4 layers x top-3 a decoded token; 4 of the router's 12 outputs are zero-compute, 2 are held
    pairs = w["held_expert_pairs"] + w["zero_expert_pairs"] + w["absent_expert_pairs"]
    assert pairs == 4 * 3 * round(w["occupancy_steps"] * 3)
    assert 0.15 < counts["zero_expert_share"] < 0.55 and 0.0 < counts["held_expert_hit_share"] <= 1.0
    assert 0.0 < counts["held_experts_per_token"] < 3.0 and w["token_expert_pairs"] == w["held_expert_pairs"]
    assert w["latent_tokens_read"] > 8 * w["decode_steps"]  # 8 sublayers, contexts past one row
    # the one of the longest prompt is among the checked
    assert line["check"]["longest_prompt_checked"]["value"] > 60


def test_the_cell_s_traffic_is_the_issue_s():
    from perfbench.drivers.serve_engine_sparse_moe import sized_pool

    a_prompts, a_answers = sized_pool(TRAFFIC, 3400000001)
    b_prompts, b_answers = sized_pool(TRAFFIC, 3400000002)
    assert [len(p) for p in a_prompts] == [len(p) for p in b_prompts] and a_answers == b_answers
    assert not np.array_equal(a_prompts[0], b_prompts[0])
    assert len(a_prompts) == TRAFFIC["pool_requests"] == 512
    first, second = sorted(map(len, a_prompts[:32])), sorted(map(len, a_prompts[32:64]))
    assert first == second and len(set(first)) == 32
    assert 256 <= first[0] < 300 and 2048 < first[-5] and first[-1] <= 4096
    assert all(128 <= a <= 512 for a in a_answers)
    assert all(len(p) + a <= TRAFFIC["max_total_len"] == 4608 for p, a in zip(a_prompts, a_answers))
    assert max(int(p.max()) for p in a_prompts[:8]) < TRAFFIC["token_id_below"] == LONGCAT["vocab_size"] == 16384
    assert TRAFFIC["num_pages"] == 64 * 288 + 1 and TRAFFIC["max_pages_per_slot"] * 16 == 4608
    assert (TRAFFIC["clients"], TRAFFIC["num_slots"], TRAFFIC["prefill_chunk"]) == (64, 64, 512)
    assert (TRAFFIC["warm_requests"], TRAFFIC["check_requests"], TRAFFIC["trace_seconds"]) == (64, 4, 4)
    assert TRAFFIC["lengths_seed"] == 0 and TRAFFIC["temperature"] == 0.0


# ---- counts -----------------------------------------------------------------

def test_parameters_by_hand():
    attn = 6144 * 1536 + 1536 * 64 * 192 + 6144 * 576 + 512 * 64 * 256 + 64 * 128 * 6144
    assert attn == wlm.attention_params(LONGCAT) == 90_570_752
    outside = 2 * attn + 2 * 3 * 6144 * 12288 + 6144 * 768
    assert outside == wlm.params_outside_experts_per_layer(LONGCAT) == 638_844_928
    assert wlm.expert_params(LONGCAT) == 3 * 6144 * 2048 == 37_748_736
    assert wlm.held_experts_per_token_expected(LONGCAT) == 12 * 16 / 768 == 0.25
    active = 4 * (outside + 0.25 * 37_748_736) + 6144 * 16384
    assert wlm.active_matmul_params(LONGCAT) == active
    assert wlm.active_matmul_params(LONGCAT, 0.5) == active + 4 * 0.25 * 37_748_736
    eq = wlm.dense_equivalent(LONGCAT, 0.3)
    assert eq["n_embd"] == 6144 and eq["n_layer"] == 4
    # the accepted count, over the GPT-2-style keys, is the count of what a token multiplies here
    assert work.transformer_matmul_params({**LONGCAT, **eq}) == pytest.approx(wlm.active_matmul_params(LONGCAT, 0.3))


def test_rows_flops_and_bytes_by_hand():
    assert wlm.latent_row_bytes(LONGCAT) == 1152 and wlm.kv_row_bytes_unabsorbed(LONGCAT) == 40_960
    assert wlm.latent_attention_bytes(10, LONGCAT) == 11_520
    # a row, 64 heads: scores over 576 lanes, values over 512
    assert wlm.absorbed_attention_flops(1, LONGCAT) == 2 * 64 * (576 + 512) == 139_264
    assert wlm.built_attention_flops(1, LONGCAT) == 2 * 64 * (192 + 128) == 40_960
    # a window: the decode steps' rows and one prompt of 10 over 8 sublayers
    want = 139_264 * 1000 + 40_960 * 8 * 55
    assert wlm.attention_flops_in_window(1000, [10], LONGCAT) == want
    # the walk is bound by its bytes on the v5e
    assert 1152 / 819e9 > 139_264 / 197e12
    # the experts' counts the accepted reader takes (work_sparse_moe) at this model's sizes
    both = {**LONGCAT, **wlm.as_sparse_moe_config(LONGCAT)}
    assert wsm.moe_flops(1, both) == 6 * 6144 * 2048 and wsm.moe_bytes(1, both) == 3 * 6144 * 2048 * 2


# ---- readers ------------------------------------------------------------------

def trace():
    ops = [
        ("%attn_latent.1 = bf16[64,64,512] custom-call()", "attn_latent_custom-call_bf16_64_64_512_", 1.00, 1.002),
        ("%attn_latent.2 = bf16[64,64,512] custom-call()", "attn_latent_custom-call_bf16_64_64_512_", 1.01, 1.012),
        ("%moe.1 = f32[192,2048] custom-call()", "moe_custom-call_f32_192_2048_", 1.03, 1.04),
        ("%moe.2 = f32[1536,2048] custom-call()", "moe_custom-call_f32_1536_2048_", 0.20, 0.30),  # in a chunk
    ]
    host = [
        ("serve/step", 0.0, 1.2), ("serve/admit", 0.0, 0.9), ("serve/prefill", 0.1, 0.9),
        ("serve/prefill_chunk", 0.101, 0.102), ("serve/prefill_chunk", 0.502, 0.505),
        ("serve/prefill_chunk", 0.6, 0.602),
        ("serve/admit_fetch", 0.85, 0.9),
        ("serve/decode_prep", 0.9, 1.0), ("serve/decode", 1.0, 1.1), ("perfbench/engine_step", 0.0, 1.2),
    ]
    return R.Trace({0: ops}, host, {0: [("jit_prefill_chunk(1)", 0.1, 0.14), ("jit_step(2)", 1.0, 1.02)]})


def ctx(traced=True):
    counts = {
        "slot_occupancy": 1.0, "prompt_tokens_in_window": 200_000, "tokens_in_window": 45_000,
        "attention_flops_in_window": 3e13, "zero_expert_share": 0.33, "held_expert_hit_share": 0.62,
        "traced": {"decode_steps": 1, "latent_tokens_read": 8 * 64 * 1500, "experts_hit": 40,
                   "token_expert_pairs": 64, "held_expert_pairs": 64} if traced else None,
    }
    run = {"window_s": 30.0, "counts": counts, "spans": {"itl_ms": [30.0, 1200.0]}, "compile_s": 70.0,
           "compiles_in_window": 0}
    config = {**LONGCAT, **wlm.as_sparse_moe_config(LONGCAT), **wlm.dense_equivalent(LONGCAT, 0.25)}
    return {"run": run, "trace": trace(), "config": config, "traffic": {}, "cell": {},
            "peaks": peaks.peaks_for("TPU v5 lite")}


def read(name, c, **args):
    m = METRICS[name]
    m = {**m, "args": {**m["args"], **args}}
    return importlib.import_module(f"perfbench.readers.{m['reader']}").read(c, m)


NEW = sorted(n for n, m in METRICS.items() if m.get("workloads") == [CELL])


def test_the_new_metrics_are_the_issue_s_nine_and_the_admission_s_seven():
    assert NEW == sorted([
        "serve_latent_attn_ms_per_step", "latent_attn_roofline", "serve_moe_ms_per_step.chat",
        "moe_gmm_roofline.chat", "serve_prefill_chunk_ms_p50.chat", "serve_chunks_per_admit_p50.chat",
        "serve_prefill_program_ms_p50.chat", "serve_zero_expert_share", "serve_held_expert_hit_share",
        # the packed admission's and the idle split's, by the accepted readers and spans
        "serve_admit_fetch_ms_p50.chat", "serve_device_ms_per_admit.chat", "serve_admit_wall_share.chat",
        "serve_decode_prep_ms_p50.chat", "serve_idle_in_admit_ms_per_step.chat",
        "serve_idle_in_decode_ms_per_step.chat", "serve_idle_between_steps_ms_per_step.chat",
    ])
    # each of the seven is its accepted file but for the name and the cell
    for n in NEW:
        base = n.removesuffix(".chat")
        if n.startswith(("serve_admit", "serve_device", "serve_decode_prep", "serve_idle")):
            same = {k: v for k, v in METRICS[base].items() if k not in ("name", "workloads")}
            assert {k: v for k, v in METRICS[n].items() if k not in ("name", "workloads")} == same
    assert all(METRICS[n]["moves"] == "serve_tokens_per_s" for n in NEW)
    manifest = harness.load_manifest()
    assert CELL in next(m for m in manifest["end_to_end"] if m["name"] == "serve_tokens_per_s")["workloads"]
    entries = {m["name"]: m for m in manifest["per_layer"]}
    assert all({k: METRICS[n][k] for k in entries[n]} == entries[n] for n in NEW)
    # the cell and its configuration are there, on one chip (not "last": a later PR appends)
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("longcat-flash-chat", "serve-chat-closed", 1)
    config = next(c for c in manifest["configs"] if c["name"] == "longcat-flash-chat")
    assert config["reduced"] == LONGCAT["reduced"] and config["source"] == LONGCAT["source"]


def test_each_kernel_is_read_by_its_name_inside_the_decode_span():
    c = ctx()
    assert read("serve_latent_attn_ms_per_step", c) == pytest.approx(4.0)
    assert read("serve_moe_ms_per_step.chat", c) == pytest.approx(10.0)  # the chunk's call is not counted
    rows = 8 * 64 * 1500
    need = 1152 * rows / 819e9
    assert need > 139_264 * rows / 197e12  # bandwidth-bound, the unpadded row read once
    assert read("latent_attn_roofline", c) == pytest.approx(100 * need / 0.004)
    assert read("moe_gmm_roofline.chat", c) == pytest.approx(100 * 3 * 6144 * 2048 * 2 * 40 / 819e9 / 0.01)
    assert all(0 < read(n, c) < 100 for n in NEW if n.endswith("_roofline") or "_roofline." in n)


def test_span_metrics_and_the_shares():
    assert read("serve_prefill_chunk_ms_p50.chat", ctx()) == pytest.approx(2.0)
    assert read("serve_chunks_per_admit_p50.chat", ctx()) == 3.0
    assert read("serve_prefill_program_ms_p50.chat", ctx()) == pytest.approx(40.0)
    assert read("serve_zero_expert_share", ctx()) == pytest.approx(33.0)
    assert read("serve_held_expert_hit_share", ctx()) == pytest.approx(62.0)


def test_the_admission_s_and_the_idle_split_s_metrics_read_this_trace():
    """The seven the gpt2 cell reads of PR 33's packed admission and of
    where the device idles, here by the same readers over the same spans:
    one step of 1.2 s whose admission is 0.9 s and holds the chunk's one
    device call of 0.1 s."""
    c = ctx()
    assert read("serve_admit_fetch_ms_p50.chat", c) == pytest.approx(50.0)
    assert read("serve_decode_prep_ms_p50.chat", c) == pytest.approx(100.0)
    assert read("serve_admit_wall_share.chat", c) == pytest.approx(75.0)
    assert read("serve_device_ms_per_admit.chat", c) == pytest.approx(100.0)
    # idle is a gap BETWEEN device operations: 0.3 (the chunk's call ends) to 1.0 (the step's first)
    assert read("serve_idle_in_admit_ms_per_step.chat", c) == pytest.approx(600.0)
    # all of decode_prep, and the two gaps between the decode span's three calls
    assert read("serve_idle_in_decode_ms_per_step.chat", c) == pytest.approx(100.0 + 8.0 + 18.0)
    assert read("serve_idle_between_steps_ms_per_step.chat", c) == pytest.approx(0.0)


def test_the_whole_step_share_counts_what_a_token_multiplies_here():
    """``mfu.serve`` (accepted, GPT-2-style keys) over this cell's counts."""
    got = importlib.import_module("perfbench.readers.mfu").read(ctx(), METRICS["mfu.serve"])
    flops = 2 * wlm.active_matmul_params(LONGCAT, 0.25) * 245_000 + 3e13
    assert got == pytest.approx(100 * flops / 30.0 / 197e12) and 0.0 < got < 100.0


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_spans_or_counters_leaves_the_metric_out(name):
    """The parent of PR 34 has no such counters or kernel names (nor the
    cell); the readers return nothing and do not raise."""
    bare = ctx(traced=False)
    bare["run"]["counts"].pop("zero_expert_share")
    bare["run"]["counts"].pop("held_expert_hit_share")
    bare["trace"] = R.Trace({0: [("%x = f32[8] fusion()", "fusion_fusion_f32_8_", 0.0, 0.1)]}, [], {})
    assert read(name, bare) is None
    if name.endswith("_roofline") or "_roofline." in name:
        assert read(name, ctx(traced=False)) is None


# ---- the configuration file ---------------------------------------------------

def test_configuration_is_the_catalog_s_but_for_the_share():
    if not CATALOG.is_file():
        pytest.skip("no catalog beside the model-configs guide here")
    entry = next(e for e in map(json.loads, CATALOG.read_text().splitlines())
                 if e["name"] == "LongCat-Flash-Chat")
    assert LONGCAT["source"] == entry["source_url"]
    differ = sorted(k for k, v in entry["config"].items() if LONGCAT.get(k) != v)
    assert differ == sorted(LONGCAT["reduced"]) == ["n_routed_experts", "num_layers", "vocab_size"]
    assert {k: entry["config"][k] for k in differ} == LONGCAT["published"]
    assert (LONGCAT["num_layers"], LONGCAT["n_routed_experts"], LONGCAT["vocab_size"]) == (4, 16, 16384)
    # every published width, the router's outputs and experts a token
    assert [LONGCAT[k] for k in ("hidden_size", "num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
                                 "v_head_dim", "q_lora_rank", "kv_lora_rank", "ffn_hidden_size",
                                 "expert_ffn_hidden_size", "moe_topk", "zero_expert_num")] == [
        6144, 64, 128, 64, 128, 1536, 512, 12288, 2048, 12, 256]
    assert LONGCAT["published"]["n_routed_experts"] + LONGCAT["zero_expert_num"] == 768
    assert {"model_type", "router_bias", "hidden_act", "rope", "router", "weights"} <= set(LONGCAT["assumed"])
    assert len(LONGCAT["departures"]) >= 3
    # the floors of the guide's section 4: four layers, eight experts, an eighth of the vocabulary
    assert LONGCAT["num_layers"] >= 4 and LONGCAT["n_routed_experts"] >= 8
    assert LONGCAT["vocab_size"] * 8 >= LONGCAT["published"]["vocab_size"]


def test_the_deployment_s_arithmetic():
    dep = LONGCAT["deployment"]
    assert dep["chips_per_layer"] == 32 and dep["held_experts"] == list(range(16))
    assert dep["chips_per_layer"] * len(dep["held_experts"]) == LONGCAT["published"]["n_routed_experts"]
    assert dep["vocab_slices"] * LONGCAT["vocab_size"] == LONGCAT["published"]["vocab_size"]
    assert dep["params_attention"] == wlm.attention_params(LONGCAT)
    assert dep["params_per_layer_outside_experts"] == wlm.params_outside_experts_per_layer(LONGCAT) == 638_844_928
    assert dep["params_per_layer_here"] == 638_844_928 + 16 * wlm.expert_params(LONGCAT) == 1_242_824_704
    assert dep["params_embedding_and_head"] == 2 * 16384 * 6144
    assert dep["weights_bytes"] == 2 * (4 * dep["params_per_layer_here"] + dep["params_embedding_and_head"])
    assert dep["latent_row_bytes"] == wlm.latent_row_bytes(LONGCAT) == 1152 and dep["latent_row_bytes_padded"] == 1280
    assert dep["kv_row_bytes_unabsorbed"] == wlm.kv_row_bytes_unabsorbed(LONGCAT) == 40_960
    assert dep["pages"] == TRAFFIC["num_pages"] == 18_433 and dep["pages_per_slot"] == TRAFFIC["max_pages_per_slot"]
    assert dep["pool_bytes"] == 18_433 * 16 * 1280 and dep["pools"] == 2 * LONGCAT["num_layers"] == 8
    assert dep["pools_bytes"] == 8 * dep["pool_bytes"] and dep["total_bytes"] == dep["weights_bytes"] + dep["pools_bytes"]
    assert 13.3e9 < dep["total_bytes"] < 13.4e9
    # kept as keys and values a head the same bytes would hold two of these requests
    assert dep["pools_bytes"] // (8 * dep["kv_row_bytes_unabsorbed"]) < 2.1 * 4608
