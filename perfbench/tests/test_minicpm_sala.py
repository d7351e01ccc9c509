"""The ``minicpm_sala`` family's part of the benchmark: the cell's
rehearsal through driver, reference and comparison, with the control and
every planted fault read above the limit the program passes; the FLOP
and byte counts against hand counts; each new metric read from a
hand-made trace of this model, and left out where a program has none of
it; the configuration file against the catalog's ``config`` it was
copied from, and its arithmetic; the traffic's page geometry."""

import importlib
import json
from pathlib import Path

import numpy as np
import pytest

from perfbench import harness, peaks, tracered as R, work, work_minicpm_sala as wms

ROOT = Path(__file__).resolve().parents[2]
CELL = "sala-serve-longctx-closed-1chip"
SALA = json.loads((ROOT / "perfbench/configs/minicpm-sala.json").read_text())
TRAFFIC = harness.load_json(ROOT / "perfbench/traffic/serve-longctx-closed.json")
METRICS = harness.metric_files()
FAULTS = (
    "control_fp8", "fault_recent_blocks", "fault_no_decay", "fault_bf16_state", "fault_no_window",
    "fault_no_output_gate", "fault_no_mup",
)


# ---- the rehearsal: program passes, control and faults do not -------------

def test_rehearsal_is_correct_and_every_wrong_reading_is_over_the_limit(rehearse):
    """Every fault, and the float8 control, reads over the rehearsal's
    limits on the logits the engine sampled from. The served tokens'
    gaps are read for each too; at this size (a few in-flight answers of
    a handful of tokens from a 64-wide model) most faults flip none of
    them, and the logits hold what the tokens cannot."""
    rc, line, _ = rehearse(CELL, probe=True)
    assert rc == 0 and line["correct"] is True, line["check"]
    limits = {k: n["limit"] for k, n in line["check"].items() if n["limit"] is not None}
    assert set(limits) == {
        "served_logit_gap", "served_logit_gap_mean", "decode_logit_dev", "decode_logit_dev_mean", "requests_failed",
    }
    for probe in FAULTS:
        assert line["check"][f"{probe}.served_logit_gap_mean"]["value"] >= 0.0, probe
        for name in ("decode_logit_dev", "decode_logit_dev_mean"):
            assert line["check"][f"{probe}.{name}"]["value"] > limits[name], (probe, name)
    assert line["check"]["fault_token_altered.served_logit_gap"]["value"] > limits["served_logit_gap"]
    counts = line["rehearsal"]["counts"]
    w = counts["window"]
    assert counts["slot_occupancy"] > 0.5 and w["prefill_chunks"] >= w["admissions"] > 0
    # two lightning layers a decoded token; two block-sparse layers of two groups
    tokens = round(w["occupancy_steps"] * 3)
    assert w["lightning_state_updates"] == 2 * tokens
    assert 0 < w["sparse_selected_tokens"] < w["sparse_live_tokens"] and w["sparse_scored_kernels"] > 0
    assert counts["sparse_selected_share"] == pytest.approx(w["sparse_selected_tokens"] / w["sparse_live_tokens"])
    assert line["check"]["longest_prompt_checked"]["value"] > 60


def test_the_cell_s_traffic():
    from perfbench.drivers.serve_engine_sparse_moe import sized_pool

    a_prompts, a_answers = sized_pool(TRAFFIC, 4200000001)
    b_prompts, b_answers = sized_pool(TRAFFIC, 4200000002)
    assert [len(p) for p in a_prompts] == [len(p) for p in b_prompts] and a_answers == b_answers
    assert not np.array_equal(a_prompts[0], b_prompts[0])
    assert len(a_prompts) == TRAFFIC["pool_requests"] == 512
    first, second = sorted(map(len, a_prompts[:32])), sorted(map(len, a_prompts[32:64]))
    assert first == second and len(set(first)) == 32
    # every prompt past dense_len, so every decode step and every chunk past 8192 selects
    assert 16384 <= first[0] < 17500 and 60000 < first[-1] <= 65536
    assert first[0] > SALA["sparse_config"]["dense_len"]
    assert all(1024 <= a <= 4096 for a in a_answers)
    assert all(len(p) + a <= TRAFFIC["max_total_len"] == 69632 for p, a in zip(a_prompts, a_answers))
    assert max(int(p.max()) for p in a_prompts[:8]) < TRAFFIC["token_id_below"] == SALA["vocab_size"] == 73448
    assert (TRAFFIC["clients"], TRAFFIC["num_slots"], TRAFFIC["prefill_chunk"]) == (32, 32, 512)
    assert (TRAFFIC["check_requests"], TRAFFIC["trace_seconds"], TRAFFIC["length_classes"]) == (2, 4, 32)
    assert TRAFFIC["lengths_seed"] == 0 and TRAFFIC["temperature"] == 0.0


def test_the_page_geometry():
    """Pages of 16: 4,352 a slot hold 69,632 positions; 32 slots and the
    trash page make 139,265; a compressed key a page (the kernel stride
    is the page size)."""
    assert TRAFFIC["page_size"] == 16 == SALA["sparse_config"]["kernel_stride"]
    assert TRAFFIC["max_pages_per_slot"] * 16 == TRAFFIC["max_total_len"]
    assert TRAFFIC["num_pages"] == 32 * 4352 + 1 == 139_265
    assert SALA["sparse_config"]["block_size"] % 16 == 0


# ---- counts -----------------------------------------------------------------

def test_parameters_by_hand():
    lightning = 5 * 4096 * 4096 + 3 * 4096 * 16384
    sparse = 3 * 4096 * 4096 + 2 * 4096 * 256 + 3 * 4096 * 16384
    assert wms.lightning_layer_params(SALA) == lightning == 285_212_672
    assert wms.sparse_layer_params(SALA) == sparse == 253_755_392
    assert wms.active_matmul_params(SALA) == 6 * lightning + 2 * sparse + 4096 * 73448
    eq = wms.dense_equivalent(SALA)
    assert eq["n_embd"] == 4096 and eq["n_layer"] == 8
    # the accepted count, over the GPT-2-style keys, is the count of what a token multiplies
    assert work.transformer_matmul_params({**SALA, **eq}) == pytest.approx(wms.active_matmul_params(SALA))


def test_flops_and_bytes_by_hand():
    # a (slot, layer) state update: 32 heads of a 128 x 128 float32 state, read and written
    assert wms.lightning_decode_bytes(1, SALA) == 2 * 4 * 32 * 128 * 128 == 4_194_304
    assert wms.lightning_decode_flops(1, SALA) == 4 * 32 * 128 * 128
    # a chunk of 512 rows: 512 * 513 / 2 causal pairs, and the state once
    pairs = 512 * 513 / 2
    assert wms.lightning_chunk_flops(512, pairs, SALA) == 4 * 32 * 128 * pairs + 4 * 32 * 128 * 128 * 512
    assert wms.lightning_chunk_bytes(512, 1, SALA) == 10 * 32 * 128 * 512 + 8 * 32 * 128 * 128
    # a (compressed key, group) scored by 16 heads; a (position, group) attended by 16 heads
    assert wms.sparse_select_flops(1, SALA) == 2 * 16 * 128 and wms.sparse_select_bytes(1, SALA) == 256
    assert wms.sparse_attn_flops(1, SALA) == 4 * 16 * 128 and wms.sparse_attn_bytes(1, SALA) == 512
    # the decode walk: 32 FLOP a byte, far below the v5e's ridge (240)
    assert wms.sparse_attn_flops(1, SALA) / wms.sparse_attn_bytes(1, SALA) == 16.0
    # past dense_len a query attends topk blocks' worth; below, every position
    assert wms.attended_positions(100, SALA) == 101 and wms.attended_positions(20000, SALA) == 4096
    n = 9000
    sizes = [512] * 17 + [296]
    pairs = sum(m * (m + 1) / 2 for m in sizes)
    attended = 8192 * 8193 / 2 + 808 * 4096
    want = 6 * wms.lightning_chunk_flops(n, pairs, SALA) + 2 * 4 * 32 * 128 * attended
    assert wms.prompt_attention_flops(n, 512, SALA) == pytest.approx(want)
    window = {"lightning_state_updates": 10, "sparse_selected_tokens": 20, "sparse_scored_kernels": 30}
    assert wms.attention_flops_in_window(window, [n], 512, SALA) == pytest.approx(
        want + wms.lightning_decode_flops(10, SALA) + wms.sparse_attn_flops(20, SALA)
        + wms.sparse_select_flops(30, SALA))


# ---- readers ------------------------------------------------------------------

def trace():
    ops = [
        ("%attn_lightning.1 = f32[32,32,8,128] custom-call()", "attn_lightning_custom-call_f32_32_32_8_128_", 1.000, 1.001),
        ("%attn_lightning.2 = f32[32,32,8,128] custom-call()", "attn_lightning_custom-call_f32_32_32_8_128_", 1.002, 1.003),
        ("%attn_sparse.1 = bf16[32,2,16,128] custom-call()", "attn_sparse_custom-call_bf16_32_2_16_128_", 1.004, 1.0045),
        ("%attn_lightning_chunk.1 = f32[512,4096] custom-call()", "attn_lightning_chunk_custom-call_f32_512_4096_", 0.20, 0.201),
        # XLA ops of the selection (decode and chunk) and of the chunk's
        # attention: named by their instruction, put in scope by the map
        ("%sort.3 = f32[32,1,2,1088] sort()", "sort_sort_f32_32_1_2_1088_", 1.0050, 1.0052),
        ("%fusion.13 = bf16[139264,256] fusion()", "fusion_fusion_bf16_139264_256_", 1.0053, 1.0056),
        ("%while.2 = f32[512,2,16,1] while()", "while_while_f32_512_2_16_1_", 0.104, 0.109),
        ("%fusion.13 = bf16[512,32,128] fusion()", "fusion_fusion_bf16_512_32_128_", 0.105, 0.108),
        ("%sort.3 = f32[1,512,2,1088] sort()", "sort_sort_f32_1_512_2_1088_", 0.110, 0.111),
        ("%fusion.2 = bf16[32,4096] fusion()", "fusion_fusion_bf16_32_4096_", 1.0060, 1.0070),
    ]
    host = [
        ("serve/step", 0.0, 1.2), ("serve/admit", 0.0, 0.9), ("serve/prefill", 0.1, 0.9),
        ("serve/prefill_chunk", 0.101, 0.102), ("serve/admit_fetch", 0.85, 0.9),
        ("serve/decode_prep", 0.9, 1.0), ("serve/decode", 1.0, 1.1), ("perfbench/engine_step", 0.0, 1.2),
    ]
    return R.Trace({0: ops}, host, {0: [("jit_prefill_chunk(1)", 0.1, 0.14), ("jit_step(2)", 1.0, 1.02)]})


def ctx(traced=True):
    counts = {
        "slot_occupancy": 1.0, "prompt_tokens_in_window": 400_000, "tokens_in_window": 20_000,
        "attention_flops_in_window": 5e13, "sparse_selected_share": 0.11,
        "traced": {"decode_steps": 1, "lightning_state_updates": 6 * 32, "sparse_selected_tokens": 2 * 2 * 32 * 4096,
                   "sparse_scored_kernels": 2 * 2 * 32 * 2300, "chunk_rows": 512, "chunk_pairs": 512 * 513 / 2,
                   "chunks": 1} if traced else None,
        "scopes": {
            "jit_step": {"sort.3": "attn_sparse_select", "fusion.13": "attn_sparse_select",
                         "attn_lightning.1": "attn_lightning"},
            "jit_prefill_chunk": {"sort.3": "attn_sparse_select", "fusion.13": "attn_sparse_chunk",
                                  "while.2": "attn_sparse_chunk"},
        } if traced else {},
    }
    run = {"window_s": 30.0, "counts": counts, "spans": {"itl_ms": [30.0, 1200.0]}, "compile_s": 70.0,
           "compiles_in_window": 0}
    config = {**SALA, **wms.dense_equivalent(SALA)}
    return {"run": run, "trace": trace(), "config": config, "traffic": {}, "cell": {},
            "peaks": peaks.peaks_for("TPU v5 lite")}


def read(name, c):
    m = METRICS[name]
    return importlib.import_module(f"perfbench.readers.{m['reader']}").read(c, m)


NEW = sorted(n for n, m in METRICS.items() if m.get("workloads") == [CELL])


SEVEN = [
    "lightning_decode_roofline.longctx", "lightning_chunk_roofline.longctx",
    "sparse_select_roofline.longctx", "sparse_attn_roofline.longctx",
    "serve_lightning_ms_per_step.longctx", "serve_sparse_attn_ms_per_step.longctx",
    "serve_sparse_selected_share.longctx",
]
# the chunked cells' engine metrics, and the selection's and the chunk's
# attention by named scope
CHUNKED = [
    "serve_prefill_program_ms_p50.longctx", "serve_chunks_per_admit_p50.longctx",
    "serve_admit_fetch_ms_p50.longctx", "serve_device_ms_per_admit.longctx",
    "serve_idle_in_admit_ms_per_step.longctx", "serve_sparse_select_ms_per_step.longctx",
    "serve_sparse_chunk_select_ms_per_chunk.longctx", "serve_sparse_chunk_attn_ms_per_chunk.longctx",
]


def test_the_new_metrics_are_these_seven():
    """The kernels' seven, beside the chunked cells' metrics the cell
    reads under its own names."""
    assert NEW == sorted(SEVEN + CHUNKED)
    for name in CHUNKED[:5]:
        twin = next(METRICS[n] for n in METRICS if n.rsplit(".", 1)[0] == name.rsplit(".", 1)[0] and n != name
                    and METRICS[n].get("workloads") != [CELL] and "." in n)
        assert (METRICS[name]["reader"], METRICS[name]["args"]) == (twin["reader"], twin["args"]), name
    assert all(METRICS[n]["moves"] == "serve_tokens_per_s" for n in NEW)
    manifest = harness.load_manifest()
    assert CELL in next(m for m in manifest["end_to_end"] if m["name"] == "serve_tokens_per_s")["workloads"]
    entries = {m["name"]: m for m in manifest["per_layer"]}
    assert all({k: METRICS[n][k] for k in entries[n]} == entries[n] for n in NEW)
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("minicpm-sala", "serve-longctx-closed", 1)
    config = next(c for c in manifest["configs"] if c["name"] == "minicpm-sala")
    assert config["reduced"] == SALA["reduced"] and config["source"] == SALA["source"]


def test_each_kernel_is_read_by_its_name():
    c = ctx()
    assert read("serve_lightning_ms_per_step.longctx", c) == pytest.approx(2.0)
    assert read("serve_sparse_attn_ms_per_step.longctx", c) == pytest.approx(0.5)
    state = 2 * 4 * 32 * 128 * 128 * 6 * 32
    assert read("lightning_decode_roofline.longctx", c) == pytest.approx(100 * state / 819e9 / 0.002)
    chunk_flops = 6 * wms.lightning_chunk_flops(512, 512 * 513 / 2, SALA)
    chunk_bytes = 6 * wms.lightning_chunk_bytes(512, 1, SALA)
    # one chunk of 512 a layer: q, k, v, o and the state's bytes outweigh its pairs' products
    assert chunk_bytes / 819e9 > chunk_flops / 197e12
    assert read("lightning_chunk_roofline.longctx", c) == pytest.approx(100 * chunk_bytes / 819e9 / 0.001)
    sel = 2 * 2 * 32 * 4096 * 512
    assert read("sparse_attn_roofline.longctx", c) == pytest.approx(100 * sel / 819e9 / 0.0005)
    assert read("serve_sparse_selected_share.longctx", c) == pytest.approx(11.0)
    # by named scope: the decode step's selection (a sort and the
    # compressed keys' gather, not the unscoped fusion.2), the chunk's
    # selection and attention (a loop of 5 ms around a body of 3: each
    # instant once), over one run of each program
    assert read("serve_sparse_select_ms_per_step.longctx", c) == pytest.approx(0.5)
    assert read("serve_sparse_chunk_select_ms_per_chunk.longctx", c) == pytest.approx(1.0)
    assert read("serve_sparse_chunk_attn_ms_per_chunk.longctx", c) == pytest.approx(5.0)
    scored = 2 * 2 * 32 * 2300
    assert read("sparse_select_roofline.longctx", c) == pytest.approx(
        100 * max(2 * 16 * 128 * scored / 197e12, 256 * scored / 819e9) / 0.0005)
    assert all(0 < read(n, c) < 100 for n in NEW if "_roofline" in n and read(n, c) is not None)


def test_the_whole_step_share_counts_what_a_token_multiplies():
    """``mfu.serve`` (accepted, GPT-2-style keys) over this cell's counts."""
    got = importlib.import_module("perfbench.readers.mfu").read(ctx(), METRICS["mfu.serve"])
    flops = 2 * wms.active_matmul_params(SALA) * 420_000 + 5e13
    assert got == pytest.approx(100 * flops / 30.0 / 197e12) and 0.0 < got < 100.0


@pytest.mark.parametrize("name", sorted(n for n in NEW if n not in CHUNKED[:5]))
def test_a_program_without_the_kernels_or_counters_leaves_the_metric_out(name):
    """A program without this family's counters and kernel names (the
    parent's): the readers return nothing and do not raise."""
    bare = ctx(traced=False)
    bare["run"]["counts"].pop("sparse_selected_share")
    bare["trace"] = R.Trace({0: [("%x = f32[8] fusion()", "fusion_fusion_f32_8_", 0.0, 0.1)]}, [], {})
    assert read(name, bare) is None
    if "_roofline" in name:
        assert read(name, ctx(traced=False)) is None


# ---- the configuration file ---------------------------------------------------

# openbmb/MiniCPM-SALA's config.json, the keys the family's code reads
PUBLISHED_CONFIG = {
    "attention_bias": False,
    "attn_use_rope": False,
    "head_dim": 128,
    "hidden_act": "silu",
    "hidden_size": 4096,
    "intermediate_size": 16384,
    "lightning_head_dim": 128,
    "lightning_nh": 32,
    "lightning_nkv": 32,
    "lightning_scale": "1/sqrt(d)",
    "lightning_use_rope": True,
    "max_position_embeddings": 524288,
    "model_type": "minicpm_sala",
    "mixer_types": (
        ["minicpm4"] + ["lightning-attn"] * 8 + ["minicpm4"] + ["lightning-attn"] * 6
        + ["minicpm4"] * 2 + ["lightning-attn"] * 4 + ["minicpm4"] + ["lightning-attn"] * 6
        + ["minicpm4"] * 3
    ),
    "num_attention_heads": 32,
    "num_hidden_layers": 32,
    "num_key_value_heads": 2,
    "qk_norm": True,
    "rand_init": False,
    "rms_norm_eps": 1e-06,
    "vocab_size": 73448,
    "rope_theta": 10000,
    "scale_emb": 12,
    "scale_depth": 1.4,
    "mup_denominator": 32,
    "dim_model_base": 256,
    "tie_word_embeddings": False,
    "use_output_gate": True,
    "use_output_norm": True,
    "attn_use_output_gate": True,
}


def test_configuration_is_the_published_one_but_for_the_depth():
    assert SALA["source"] == "https://huggingface.co/openbmb/MiniCPM-SALA/blob/main/config.json"
    differ = sorted(k for k, v in PUBLISHED_CONFIG.items() if SALA.get(k) != v)
    assert differ == sorted(SALA["reduced"]) == ["mixer_types", "num_hidden_layers"]
    assert SALA["published"] == {k: PUBLISHED_CONFIG[k] for k in differ}


def test_configuration_keeps_every_width_and_whole_periods():
    assert SALA["num_hidden_layers"] == 8 and SALA["published"]["num_hidden_layers"] == 32
    kept = SALA["kept_layers"]
    assert kept == [0, 1, 2, 3, 9, 10, 11, 12]
    assert [SALA["published"]["mixer_types"][i] for i in kept] == SALA["mixer_types"]
    # two whole periods of [minicpm4, lightning x 3], the published ratio 8:24
    assert SALA["mixer_types"] == ["minicpm4", "lightning-attn", "lightning-attn", "lightning-attn"] * 2
    assert SALA["published"]["mixer_types"].count("minicpm4") * 3 == SALA["published"]["mixer_types"].count(
        "lightning-attn")
    assert [SALA[k] for k in ("hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim",
                              "intermediate_size", "lightning_nh", "lightning_nkv", "lightning_head_dim",
                              "vocab_size")] == [4096, 32, 2, 128, 16384, 32, 32, 128, 73448]
    assert SALA["sparse_config"] == {"kernel_size": 32, "kernel_stride": 16, "init_blocks": 1, "block_size": 64,
                                     "window_size": 2048, "topk": 64, "dense_len": 8192}
    assert {"sparse_config", "lightning_decay", "lightning_layer", "sparse_layer", "mup", "weights"} <= set(
        SALA["assumed"])
    assert len(SALA["departures"]) >= 3 and "pipeline" in SALA["deployment"]["text"]


def test_the_deployment_s_arithmetic():
    dep = SALA["deployment"]
    assert dep["params_lightning_layer"] == wms.lightning_layer_params(SALA)
    assert dep["params_sparse_layer"] == wms.sparse_layer_params(SALA)
    assert dep["params_embedding_and_head"] == 2 * 73448 * 4096 == 601_686_016
    assert dep["params_here"] == 6 * 285_212_672 + 2 * 253_755_392 + 601_686_016 == 2_820_472_832
    assert dep["weights_bytes"] == 2 * dep["params_here"]
    assert dep["pages"] == TRAFFIC["num_pages"] and dep["pages_per_slot"] == TRAFFIC["max_pages_per_slot"]
    # K and V of two block-sparse layers, 2 KV heads of 128 in bfloat16 a row
    assert dep["kv_pool_bytes"] == 2 * 2 * 139_265 * 16 * 256 * 2
    assert dep["compressed_key_bytes"] == 2 * 139_265 * 256 * 2
    assert dep["state_bytes"] == 6 * 32 * 32 * 128 * 128 * 4 == 402_653_184
    total = dep["weights_bytes"] + dep["kv_pool_bytes"] + dep["compressed_key_bytes"] + dep["state_bytes"]
    assert dep["total_bytes"] == total and 0.25 * 16e9 < total < 0.75 * 16e9
