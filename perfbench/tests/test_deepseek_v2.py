"""The ``deepseek_v2`` family's part of the benchmark: the cell's
rehearsal through driver, reference and comparison, with the control and
every planted fault read above the limit the program passes; the FLOP
and byte counts against hand counts; each new metric read from a
hand-made trace of this model; the configuration file against the
published ``config.json`` it was copied from, and its arithmetic; the
traffic's page geometry."""

import importlib
import json
from pathlib import Path

import numpy as np
import pytest

from perfbench import harness, peaks, tracered as R, work, work_deepseek_v2 as wd2, work_sparse_moe as wsm

ROOT = Path(__file__).resolve().parents[2]
CELL = "dsv2-serve-doc-closed-1chip"
DSV2 = json.loads((ROOT / "perfbench/configs/deepseek-v2.json").read_text())
TRAFFIC = harness.load_json(ROOT / "perfbench/traffic/serve-doc-closed.json")
METRICS = harness.metric_files()
FAULTS = ("control_fp8", "fault_no_groups", "fault_yarn_on_cos_sin", "fault_no_shared_experts", "fault_drop_expert")


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
    # 2 MoE layers x top-3 a decoded token; no zero-compute experts; 4 of 16 held
    pairs = w["held_expert_pairs"] + w["zero_expert_pairs"] + w["absent_expert_pairs"]
    assert pairs == 2 * 3 * round(w["occupancy_steps"] * 3) and w["zero_expert_pairs"] == 0
    tokens = 2 * round(w["occupancy_steps"] * 3)  # (token, MoE layer) pairs
    assert counts["held_group_share"] == pytest.approx(w["held_group_tokens"] / tokens)
    assert 0.1 < counts["held_group_share"] < 1.0 and 0.0 < counts["held_expert_hit_share"] <= 1.0
    assert 0.0 < counts["held_experts_per_token"] < 3.0 and w["token_expert_pairs"] == w["held_expert_pairs"]
    assert w["latent_tokens_read"] > 3 * w["decode_steps"]  # 3 layers, contexts past one row
    assert line["check"]["longest_prompt_checked"]["value"] > 60


def test_the_cell_s_traffic():
    from perfbench.drivers.serve_engine_sparse_moe import sized_pool

    a_prompts, a_answers = sized_pool(TRAFFIC, 3800000001)
    b_prompts, b_answers = sized_pool(TRAFFIC, 3800000002)
    assert [len(p) for p in a_prompts] == [len(p) for p in b_prompts] and a_answers == b_answers
    assert not np.array_equal(a_prompts[0], b_prompts[0])
    assert len(a_prompts) == TRAFFIC["pool_requests"] == 512
    first, second = sorted(map(len, a_prompts[:32])), sorted(map(len, a_prompts[32:64]))
    assert first == second and len(set(first)) == 32
    assert 4096 <= first[0] < 4600 and 12000 < first[-3] and first[-1] <= 16384
    assert all(256 <= a <= 1024 for a in a_answers)
    assert all(len(p) + a <= TRAFFIC["max_total_len"] == 17408 for p, a in zip(a_prompts, a_answers))
    assert max(int(p.max()) for p in a_prompts[:8]) < TRAFFIC["token_id_below"] == DSV2["vocab_size"] == 12800
    assert (TRAFFIC["clients"], TRAFFIC["num_slots"], TRAFFIC["prefill_chunk"]) == (48, 48, 512)
    assert (TRAFFIC["check_requests"], TRAFFIC["trace_seconds"], TRAFFIC["length_classes"]) == (4, 4, 32)
    assert TRAFFIC["lengths_seed"] == 0 and TRAFFIC["temperature"] == 0.0


def test_the_page_geometry():
    """Pages of 16: 1,088 a slot hold 17,408 positions; 48 slots and the
    trash page make 52,225; five pools of 640-lane bfloat16 rows."""
    assert TRAFFIC["page_size"] == 16 and TRAFFIC["max_pages_per_slot"] * 16 == TRAFFIC["max_total_len"]
    assert TRAFFIC["num_pages"] == 48 * 1088 + 1 == 52_225
    assert TRAFFIC["num_pages"] * 16 * 640 * 2 == DSV2["deployment"]["pool_bytes"] == 1_069_568_000


# ---- counts -----------------------------------------------------------------

def test_parameters_by_hand():
    attn = 5120 * 1536 + 1536 * 128 * 192 + 5120 * 576 + 512 * 128 * 256 + 128 * 128 * 5120
    assert attn == wd2.attention_params(DSV2) == 149_225_472
    assert wd2.expert_params(DSV2) == 3 * 5120 * 1536 == 23_592_960
    outside = attn + 2 * 23_592_960 + 5120 * 160
    assert outside == wd2.moe_layer_params_outside_routed(DSV2) == 197_230_592
    assert wd2.dense_layer_params(DSV2) == attn + 3 * 5120 * 12288 == 337_969_152
    assert wd2.held_experts_per_token_expected(DSV2) == 6 * 20 / 160 == 0.75
    assert wd2.held_group_share_expected(DSV2) == 3 / 8
    active = 337_969_152 + 4 * (outside + 0.75 * 23_592_960) + 5120 * 12800
    assert wd2.active_matmul_params(DSV2) == active
    assert wd2.active_matmul_params(DSV2, 1.0) == active + 4 * 0.25 * 23_592_960
    eq = wd2.dense_equivalent(DSV2, 0.6)
    assert eq["n_embd"] == 5120 and eq["n_layer"] == 5
    # the accepted count, over the GPT-2-style keys, is the count of what a token multiplies here
    assert work.transformer_matmul_params({**DSV2, **eq}) == pytest.approx(wd2.active_matmul_params(DSV2, 0.6))


def test_rows_flops_and_bytes_by_hand():
    assert wd2.latent_row_bytes(DSV2) == 1152 and wd2.kv_row_bytes_unabsorbed(DSV2) == 81_920
    assert wd2.latent_attention_bytes(10, DSV2) == 11_520
    # a row, 128 heads: scores over 576 lanes, values over 512
    assert wd2.absorbed_attention_flops(1, DSV2) == 2 * 128 * (576 + 512) == 278_528
    assert wd2.built_attention_flops(1, DSV2) == 2 * 128 * (192 + 128) == 81_920
    want = 278_528 * 1000 + 81_920 * 5 * 55  # the decode steps' rows and one prompt of 10 over 5 layers
    assert wd2.attention_flops_in_window(1000, [10], DSV2) == want
    # the walk sits at the v5e's ridge: 241.8 FLOP/B against 197e12 / 819e9 = 240.5
    assert 278_528 / 1152 == pytest.approx(241.78, abs=0.01) and 278_528 / 1152 > 197e12 / 819e9
    both = {**DSV2, **wd2.as_sparse_moe_config(DSV2)}
    assert wsm.moe_flops(1, both) == 6 * 5120 * 1536 and wsm.moe_bytes(1, both) == 3 * 5120 * 1536 * 2


# ---- readers ------------------------------------------------------------------

def trace():
    ops = [
        ("%attn_latent.1 = bf16[48,128,512] custom-call()", "attn_latent_custom-call_bf16_48_128_512_", 1.00, 1.002),
        ("%attn_latent.2 = bf16[48,128,512] custom-call()", "attn_latent_custom-call_bf16_48_128_512_", 1.01, 1.012),
        ("%moe.1 = bf16[160,1536] custom-call()", "moe_custom-call_bf16_160_1536_", 1.03, 1.04),
        ("%moe.2 = bf16[1536,1536] custom-call()", "moe_custom-call_bf16_1536_1536_", 0.20, 0.30),  # in a chunk
    ]
    host = [
        ("serve/step", 0.0, 1.2), ("serve/admit", 0.0, 0.9), ("serve/prefill", 0.1, 0.9),
        ("serve/prefill_chunk", 0.101, 0.102), ("serve/prefill_chunk", 0.502, 0.505),
        ("serve/prefill_chunk", 0.6, 0.602), ("serve/admit_fetch", 0.85, 0.9),
        ("serve/decode_prep", 0.9, 1.0), ("serve/decode", 1.0, 1.1), ("perfbench/engine_step", 0.0, 1.2),
    ]
    return R.Trace({0: ops}, host, {0: [("jit_prefill_chunk(1)", 0.1, 0.14), ("jit_step(2)", 1.0, 1.02)]})


def ctx(traced=True):
    counts = {
        "slot_occupancy": 1.0, "prompt_tokens_in_window": 200_000, "tokens_in_window": 15_000,
        "attention_flops_in_window": 3e13, "held_group_share": 0.41, "held_expert_hit_share": 0.55,
        "traced": {"decode_steps": 1, "latent_tokens_read": 5 * 48 * 9000, "experts_hit": 60,
                   "token_expert_pairs": 150, "held_expert_pairs": 150} if traced else None,
    }
    run = {"window_s": 30.0, "counts": counts, "spans": {"itl_ms": [30.0, 1200.0]}, "compile_s": 70.0,
           "compiles_in_window": 0}
    config = {**DSV2, **wd2.as_sparse_moe_config(DSV2), **wd2.dense_equivalent(DSV2, 0.75)}
    return {"run": run, "trace": trace(), "config": config, "traffic": {}, "cell": {},
            "peaks": peaks.peaks_for("TPU v5 lite")}


def read(name, c):
    m = METRICS[name]
    return importlib.import_module(f"perfbench.readers.{m['reader']}").read(c, m)


NEW = sorted(n for n, m in METRICS.items() if m.get("workloads") == [CELL])


def test_the_new_metrics_are_these_eight():
    assert NEW == sorted([
        "serve_latent_attn_ms_per_step.doc", "latent_attn_roofline.doc", "serve_moe_ms_per_step.doc",
        "moe_gmm_roofline.doc", "serve_prefill_program_ms_p50.doc", "serve_chunks_per_admit_p50.doc",
        "serve_held_expert_hit_share.doc", "serve_held_group_share",
    ])
    # each of the six that have an accepted twin is that file but for the name and the cell
    for n in NEW:
        base = n.removesuffix(".doc")
        twin = base + ".chat" if base + ".chat" in METRICS else base
        if n.endswith(".doc") and n != "latent_attn_roofline.doc":
            same = {k: v for k, v in METRICS[twin].items() if k not in ("name", "workloads")}
            assert {k: v for k, v in METRICS[n].items() if k not in ("name", "workloads")} == same, n
    assert all(METRICS[n]["moves"] == "serve_tokens_per_s" for n in NEW)
    manifest = harness.load_manifest()
    assert CELL in next(m for m in manifest["end_to_end"] if m["name"] == "serve_tokens_per_s")["workloads"]
    entries = {m["name"]: m for m in manifest["per_layer"]}
    assert all({k: METRICS[n][k] for k in entries[n]} == entries[n] for n in NEW)
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("deepseek-v2", "serve-doc-closed", 1)
    config = next(c for c in manifest["configs"] if c["name"] == "deepseek-v2")
    assert config["reduced"] == DSV2["reduced"] and config["source"] == DSV2["source"]


def test_each_kernel_is_read_by_its_name_inside_the_decode_span():
    c = ctx()
    assert read("serve_latent_attn_ms_per_step.doc", c) == pytest.approx(4.0)
    assert read("serve_moe_ms_per_step.doc", c) == pytest.approx(10.0)  # the chunk's call is not counted
    rows = 5 * 48 * 9000
    flops, nbytes = 278_528 * rows / 197e12, 1152 * rows / 819e9
    assert flops > nbytes  # at 128 heads the compute bound is the nearer, by a hair
    assert read("latent_attn_roofline.doc", c) == pytest.approx(100 * flops / 0.004)
    assert read("moe_gmm_roofline.doc", c) == pytest.approx(100 * 3 * 5120 * 1536 * 2 * 60 / 819e9 / 0.01)
    assert all(0 < read(n, c) < 100 for n in NEW if "_roofline" in n)


def test_span_metrics_and_the_shares():
    assert read("serve_chunks_per_admit_p50.doc", ctx()) == 3.0
    assert read("serve_prefill_program_ms_p50.doc", ctx()) == pytest.approx(40.0)
    assert read("serve_held_group_share", ctx()) == pytest.approx(41.0)
    assert read("serve_held_expert_hit_share.doc", ctx()) == pytest.approx(55.0)


def test_the_whole_step_share_counts_what_a_token_multiplies_here():
    """``mfu.serve`` (accepted, GPT-2-style keys) over this cell's counts."""
    got = importlib.import_module("perfbench.readers.mfu").read(ctx(), METRICS["mfu.serve"])
    flops = 2 * wd2.active_matmul_params(DSV2, 0.75) * 215_000 + 3e13
    assert got == pytest.approx(100 * flops / 30.0 / 197e12) and 0.0 < got < 100.0


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_spans_or_counters_leaves_the_metric_out(name):
    """A program without this family's counters and kernel names (nor
    the cell): the readers return nothing and do not raise."""
    bare = ctx(traced=False)
    bare["run"]["counts"].pop("held_group_share")
    bare["run"]["counts"].pop("held_expert_hit_share")
    bare["trace"] = R.Trace({0: [("%x = f32[8] fusion()", "fusion_fusion_f32_8_", 0.0, 0.1)]}, [], {})
    assert read(name, bare) is None
    if "_roofline" in name:
        assert read(name, ctx(traced=False)) is None


# ---- the configuration file ---------------------------------------------------

# deepseek-ai/DeepSeek-V2's config.json, the keys the family's code reads
PUBLISHED_CONFIG = {
    "attention_bias": False,
    "first_k_dense_replace": 1,
    "hidden_act": "silu",
    "hidden_size": 5120,
    "intermediate_size": 12288,
    "kv_lora_rank": 512,
    "max_position_embeddings": 163840,
    "model_type": "deepseek_v2",
    "moe_intermediate_size": 1536,
    "moe_layer_freq": 1,
    "n_group": 8,
    "n_routed_experts": 160,
    "n_shared_experts": 2,
    "norm_topk_prob": False,
    "num_attention_heads": 128,
    "num_experts_per_tok": 6,
    "num_hidden_layers": 60,
    "num_key_value_heads": 128,
    "q_lora_rank": 1536,
    "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707, "mscale_all_dim": 0.707, "original_max_position_embeddings": 4096, "type": "yarn"},
    "rope_theta": 10000,
    "routed_scaling_factor": 16,
    "scoring_func": "softmax",
    "seq_aux": True,
    "tie_word_embeddings": False,
    "topk_group": 3,
    "topk_method": "group_limited_greedy",
    "v_head_dim": 128,
    "vocab_size": 102400,
}


def test_configuration_is_the_published_one_but_for_the_share():
    assert DSV2["source"] == "https://huggingface.co/deepseek-ai/DeepSeek-V2/blob/main/config.json"
    differ = sorted(k for k, v in PUBLISHED_CONFIG.items() if DSV2.get(k) != v)
    assert differ == sorted(DSV2["reduced"]) == ["n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert {k: PUBLISHED_CONFIG[k] for k in differ} == DSV2["published"]


def test_configuration_keeps_every_width_and_the_router():
    assert (DSV2["num_hidden_layers"], DSV2["n_routed_experts"], DSV2["vocab_size"]) == (5, 20, 12800)
    assert DSV2["published"] == {"num_hidden_layers": 60, "n_routed_experts": 160, "vocab_size": 102400}
    assert [DSV2[k] for k in ("hidden_size", "num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
                              "v_head_dim", "q_lora_rank", "kv_lora_rank", "intermediate_size",
                              "moe_intermediate_size", "num_experts_per_tok", "n_group", "topk_group",
                              "n_shared_experts", "routed_scaling_factor", "first_k_dense_replace")] == [
        5120, 128, 128, 64, 128, 1536, 512, 12288, 1536, 6, 8, 3, 2, 16, 1]
    assert DSV2["rope_scaling"] == {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707,
                                    "mscale_all_dim": 0.707, "original_max_position_embeddings": 4096,
                                    "type": "yarn"}
    assert DSV2["topk_method"] == "group_limited_greedy" and DSV2["norm_topk_prob"] is False
    assert {"router_bias", "hidden_act", "rope", "yarn", "router", "weights"} <= set(DSV2["assumed"])
    assert len(DSV2["departures"]) >= 3
    # the guide's floors: four routed layers past the dense one, eight experts, an eighth of the vocabulary
    assert DSV2["num_hidden_layers"] - DSV2["first_k_dense_replace"] >= 4 and DSV2["n_routed_experts"] >= 8
    assert DSV2["vocab_size"] * 8 >= DSV2["published"]["vocab_size"]


def test_the_deployment_s_arithmetic():
    dep = DSV2["deployment"]
    assert dep["chips_per_layer"] == 8 and dep["held_experts"] == list(range(20)) and dep["held_group"] == 0
    assert dep["chips_per_layer"] * len(dep["held_experts"]) == DSV2["published"]["n_routed_experts"]
    assert dep["chips_per_layer"] == DSV2["n_group"]  # one routing group a chip
    assert dep["vocab_slices"] * DSV2["vocab_size"] == DSV2["published"]["vocab_size"]
    assert dep["params_attention"] == wd2.attention_params(DSV2) == 149_225_472
    assert dep["params_per_expert"] == wd2.expert_params(DSV2) and dep["params_shared_experts"] == 47_185_920
    assert dep["params_router"] == 819_200
    assert dep["params_moe_layer_outside_routed"] == wd2.moe_layer_params_outside_routed(DSV2) == 197_230_592
    assert dep["params_moe_layer_here"] == 197_230_592 + 20 * 23_592_960 == 669_089_792
    assert dep["params_dense_layer"] == wd2.dense_layer_params(DSV2) == 337_969_152
    assert dep["params_embedding_and_head"] == 2 * 12800 * 5120 == 131_072_000
    assert dep["params_here"] == 4 * 669_089_792 + 337_969_152 + 131_072_000 == 3_145_400_320
    assert dep["weights_bytes"] == 2 * dep["params_here"] == 6_290_800_640
    assert dep["latent_row_bytes"] == 1152 and dep["latent_row_bytes_padded"] == 1280
    assert dep["kv_row_bytes_unabsorbed"] == wd2.kv_row_bytes_unabsorbed(DSV2) == 81_920
    assert dep["pages"] == TRAFFIC["num_pages"] and dep["pages_per_slot"] == TRAFFIC["max_pages_per_slot"]
    assert dep["pools"] == DSV2["num_hidden_layers"] == 5 and dep["pools_bytes"] == 5 * dep["pool_bytes"]
    assert dep["total_bytes"] == dep["weights_bytes"] + dep["pools_bytes"] == 11_638_640_640
    assert dep["total_bytes"] > 0.25 * 16e9 and dep["total_bytes"] < 0.75 * 16e9
