"""The ``keye`` family's part of the benchmark (PR 27): the cell's
rehearsal through driver, reference and comparison, with the control and
every planted fault read above the limit the program passes; the FLOP
and byte counts against hand counts; the new readers on a hand-made
trace; the configuration file against the catalog entry it was copied
from."""

import importlib
import json
from pathlib import Path

import numpy as np
import pytest

from perfbench import harness, peaks, tracered as R, work, work_sparse_moe as wsm

ROOT = Path(__file__).resolve().parents[2]
CELL = "keye30b-serve-long-closed-1chip"
KEYE = json.loads((ROOT / "perfbench/configs/keye-vl2-30b-a3b.json").read_text())
METRICS = harness.metric_files()
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")


# ---- the rehearsal: program passes, control and faults do not -------------

def test_rehearsal_is_correct_and_every_wrong_reading_is_over_the_limit(rehearse):
    rc, line, _ = rehearse(CELL, probe=True)
    assert rc == 0 and line["correct"] is True, line["check"]
    limits = {k: n["limit"] for k, n in line["check"].items() if n["limit"] is not None}
    assert set(limits) == {"served_logit_gap", "served_logit_gap_mean", "requests_failed"}
    # a fault that touches every token moves the mean past its limit;
    # one altered token moves only the widest gap
    for probe in ("control_fp8", "fault_window", "fault_topk_half", "fault_drop_expert"):
        assert line["check"][f"{probe}.served_logit_gap_mean"]["value"] > limits["served_logit_gap_mean"], probe
    assert line["check"]["fault_token_altered.served_logit_gap"]["value"] > limits["served_logit_gap"]
    counts = line["rehearsal"]["counts"]
    assert counts["slot_occupancy"] > 0.5 and 0.0 < counts["selected_share"] < 1.0
    assert counts["window"]["prefill_chunks"] >= counts["window"]["admissions"] > 0


def test_the_seed_changes_the_inputs_and_not_the_schedule_of_sizes():
    from perfbench.drivers.serve_engine_sparse_moe import sized_pool

    tr = harness.load_json(ROOT / "perfbench/traffic/serve-long-closed.json")
    a_prompts, a_answers = sized_pool(tr, 3700000001)
    b_prompts, b_answers = sized_pool(tr, 3700000002)
    assert [len(p) for p in a_prompts] == [len(p) for p in b_prompts] and a_answers == b_answers
    assert not np.array_equal(a_prompts[0], b_prompts[0])
    # round after round of all 32 classes, within the table's ranges
    first, second = sorted(map(len, a_prompts[:32])), sorted(map(len, a_prompts[32:64]))
    assert first == second and len(set(first)) == 32
    assert 4096 <= first[0] and first[-1] <= 16384
    assert all(128 <= a <= 384 for a in a_answers)
    assert all(len(p) + a <= tr["max_total_len"] for p, a in zip(a_prompts, a_answers))
    assert max(int(p.max()) for p in a_prompts[:8]) < tr["token_id_below"]


def test_reference_faults_change_the_logits():
    from perfbench import weights_keye as WK
    from perfbench.reference import keye

    cfg = {**KEYE, **harness.load_json(ROOT / "perfbench/traffic/serve-long-closed.json")["rehearse"]["config"]}
    flat = WK.make_weights(cfg, 3, "float32")
    tokens = np.random.default_rng(0).integers(0, 250, 72).astype(np.int32)
    base, sel = keye.forward(flat, tokens, cfg, return_selection=True)
    assert all(s.sum(-1).max() == cfg["sa_config"]["topk"] for s in sel)
    for fault in ("window", "topk_half", "drop_expert"):
        other = keye.forward(flat, tokens, cfg, fault=fault)
        # the first topk / 2 positions select everything under every fault
        assert float(np.abs(np.asarray(other - base))[-1].max()) > 1e-3, fault
    window = keye.forward(flat, tokens, cfg, fault="window", return_selection=True)[1][0]
    t = len(tokens) - 1
    assert list(np.nonzero(window[t])[0]) == list(range(t - 15, t + 1))


# ---- counts -----------------------------------------------------------------

def test_active_parameters_by_hand():
    attn = 2048 * 4096 * 2 + 2048 * 512 * 2          # q, out; k, v
    indexer = 2048 * (16 * 64 + 64 + 16)             # qI, kI, w
    experts = 8 * 3 * 2048 * 768
    assert wsm.active_matmul_params_per_layer(KEYE) == attn + indexer + 2048 * 128 + experts == 59_146_240
    assert wsm.active_matmul_params(KEYE) == 6 * 59_146_240 + 2048 * 151_936
    eq = wsm.dense_equivalent(KEYE)
    assert eq == {"n_embd": 2048, "n_inner": 10344, "n_layer": 6}
    # the accepted count, over the GPT-2-style keys, is the active count
    assert work.transformer_matmul_params({**KEYE, **eq}) == wsm.active_matmul_params(KEYE)


def test_selection_counts_and_bytes_by_hand():
    cfg = {**KEYE, "sa_config": {**KEYE["sa_config"], "topk": 4}}
    want_sel = sum(min(t + 1, 4) for t in range(10))
    assert wsm.prefill_selection(10, cfg) == (want_sel, 55.0)
    assert wsm.prefill_selection(3, cfg) == (6.0, 6.0)
    # a selected token: K and V rows of 4 heads x 128 in bf16, 4 x 128 x 32 FLOPs
    assert wsm.attention_bytes(1, KEYE) == 2 * 4 * 128 * 2 == 2048
    assert wsm.attention_flops(1, KEYE) == 4 * 128 * 32
    assert wsm.indexer_bytes(1, KEYE) == 128 and wsm.indexer_flops(1, KEYE) == 2 * 16 * 64
    assert wsm.moe_flops(1, KEYE) == 6 * 2048 * 768
    assert wsm.moe_bytes(1, KEYE) == 3 * 2048 * 768 * 2


# ---- readers ------------------------------------------------------------------

def trace():
    gather = "gather_fusion_bf16_16_2048_512_"
    ops = [
        ("%g.1 = bf16[16,2048,512] fusion()", gather, 1.00, 1.02),       # inside serve/decode
        ("%g.2 = bf16[16,2048,512] fusion()", gather, 0.20, 0.30),       # inside a prefill chunk
        ("%m.1 = bf16[128,768] custom-call()", "gmm_custom-call_bf16_128_768_", 1.02, 1.05),
    ]
    host = [
        ("serve/step", 0.0, 1.2), ("serve/admit", 0.0, 0.9), ("serve/prefill", 0.1, 0.9),
        ("serve/prefill_chunk", 0.1, 0.5), ("serve/prefill_chunk", 0.5, 0.9),
        ("serve/decode", 1.0, 1.1), ("perfbench/engine_step", 0.0, 1.2),
    ]
    return R.Trace({0: ops}, host, {0: [("jit_prefill_chunk(1)", 0.1, 0.45), ("jit_step(2)", 1.0, 1.08)]})


def ctx(traced=True):
    counts = {
        "slot_occupancy": 1.0, "prompt_tokens_in_window": 200_000, "tokens_in_window": 5_000,
        "attention_flops_in_window": 3e13, "selected_share": 0.2,
        "traced": {"decode_steps": 1, "selected_tokens": 6 * 16 * 2048, "scored_tokens": 6 * 150_000,
                   "experts_hit": 6 * 80, "token_expert_pairs": 6 * 8 * 16} if traced else None,
    }
    run = {"window_s": 30.0, "counts": counts, "spans": {"itl_ms": [30.0, 1200.0]}, "compile_s": 70.0,
           "compiles_in_window": 0}
    return {"run": run, "trace": trace(), "config": {**KEYE, **wsm.dense_equivalent(KEYE)}, "traffic": {},
            "cell": {}, "peaks": peaks.peaks_for("TPU v5 lite")}


def read(name, c, **args):
    m = METRICS[name]
    m = {**m, "args": {**m["args"], **args}}
    return importlib.import_module(f"perfbench.readers.{m['reader']}").read(c, m)


NEW = sorted(n for n, m in METRICS.items() if m.get("workloads") == [CELL])


def test_the_new_metrics_are_the_issue_s_ten():
    assert NEW == sorted([
        "mfu.serve_moe", "serve_prefill_chunk_ms_p50", "serve_chunks_per_admit_p50",
        "serve_sparse_attn_ms_per_step", "sparse_attn_roofline", "serve_indexer_ms_per_step",
        "indexer_roofline", "serve_moe_ms_per_step", "moe_gmm_roofline", "serve_selected_share",
    ])
    assert all(METRICS[n]["moves"] == "serve_tokens_per_s" for n in NEW)


def test_ops_are_counted_inside_the_decode_span_only():
    # 0.02 s of the gather ran under serve/decode, 0.10 s under a chunk
    v = read("serve_sparse_attn_ms_per_step", ctx(), pattern="^gather_fusion")
    assert v == pytest.approx(20.0)
    share = read("sparse_attn_roofline", ctx(), pattern="^gather_fusion")
    need = wsm.attention_bytes(6 * 16 * 2048, KEYE) / 819e9
    assert need > wsm.attention_flops(6 * 16 * 2048, KEYE) / 197e12  # bandwidth-bound
    assert share == pytest.approx(100 * need / 0.02)
    moe = read("moe_gmm_roofline", ctx(), pattern="^gmm_custom-call")
    assert moe == pytest.approx(100 * wsm.moe_bytes(6 * 80, KEYE) / 819e9 / 0.03)
    idx = read("indexer_roofline", ctx(), pattern="^gather_fusion")
    assert idx == pytest.approx(100 * wsm.indexer_bytes(6 * 150_000, KEYE) / 819e9 / 0.02)


def test_span_metrics_and_the_share():
    assert read("serve_prefill_chunk_ms_p50", ctx()) == pytest.approx(400.0)
    assert read("serve_chunks_per_admit_p50", ctx()) == 2.0
    assert read("serve_selected_share", ctx()) == pytest.approx(20.0)


def test_the_two_whole_step_shares_agree():
    """``mfu.serve`` (accepted, GPT-2-style keys) and ``mfu.serve_moe``
    (the family's own count) read the same share here."""
    c = ctx()
    ours = read("mfu.serve_moe", c)
    accepted = importlib.import_module("perfbench.readers.mfu").read(c, METRICS["mfu.serve"])
    flops = 2 * wsm.active_matmul_params(KEYE) * 205_000 + 3e13
    assert ours == pytest.approx(100 * flops / 30.0 / 197e12) == pytest.approx(accepted)
    assert 0.0 < ours < 100.0


@pytest.mark.parametrize("name", [n for n in NEW if n.endswith("_roofline")])
def test_a_program_without_the_counters_leaves_the_rooflines_out(name):
    """The parent of PR 27 has no such counters (nor the cell); the
    readers return nothing and do not raise."""
    assert read(name, ctx(traced=False), pattern=".") is None
    bare = ctx()
    bare["trace"] = R.Trace({0: [("%x = f32[8] fusion()", "fusion_fusion_f32_8_", 0.0, 0.1)]}, [], {})
    for n in NEW:
        if METRICS[n]["source"] == "device_trace" or "chunk" in n:
            assert read(n, bare) is None


# ---- the configuration file ---------------------------------------------------

def test_configuration_is_the_catalog_s_but_for_depth():
    if not CATALOG.is_file():
        pytest.skip("no catalog beside the model-configs guide here")
    entry = next(e for e in map(json.loads, CATALOG.read_text().splitlines())
                 if e["name"] == "Keye-VL-2.0-30B-A3B")
    assert KEYE["source"] == entry["source_url"]
    differ = sorted(k for k, v in entry["config"].items() if KEYE.get(k) != v)
    assert differ == ["num_hidden_layers"] == KEYE["reduced"]
    assert KEYE["num_hidden_layers"] == 6 and KEYE["published"]["num_hidden_layers"] == 48
    assert {"qk_norm", "indexer", "indexer_rope", "indexer_k_norm", "chunk_sizes"} <= set(KEYE["assumed"])
