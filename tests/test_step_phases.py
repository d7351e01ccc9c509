"""The train steps' phase map (utils/profiling.py): every instruction of a
compiled step named by its ``graftscope/*`` scope, built only where a fit
loop opens a capture, and written beside it as ``step_phases.json``."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import TINY_DP4_CFG

from cs744_pytorch_distributed_tutorial_tpu.config import TrainConfig
from cs744_pytorch_distributed_tutorial_tpu.obs.system import CompileCounter
from cs744_pytorch_distributed_tutorial_tpu.parallel.mesh import shard_global_batch, replicated
from cs744_pytorch_distributed_tutorial_tpu.train import LMConfig, LMTrainer, Trainer
from cs744_pytorch_distributed_tutorial_tpu.utils import profiling
from cs744_pytorch_distributed_tutorial_tpu.utils.profiling import PHASES, phase_map, phase_of

TINY_LM = dict(vocab_size=64, num_layers=1, num_heads=2, d_model=32, d_ff=64,
               max_seq_len=32, seq_len=16, global_batch_size=2, data_parallel=1)


def entry_instructions(text):
    """Names of the entry computation's instructions."""
    body = text.split("\nENTRY ", 1)[1].split("\n}", 1)[0]
    return [m.group(1) for m in map(profiling._INSTRUCTION.match, body.splitlines()[1:]) if m]


def cifar_step_text(mesh):
    tr = Trainer(TrainConfig(**TINY_DP4_CFG), mesh=mesh)
    state = tr.init()
    x, y = shard_global_batch(mesh, np.zeros((32, 32, 32, 3), np.uint8), np.zeros((32,), np.int32))
    key = jax.device_put(jax.random.key(0), replicated(mesh))
    return tr.train_step.lower(state, x, y, key).compile().as_text()


def lm_step_text():
    tr = LMTrainer(LMConfig(**TINY_LM, attention_impl="flash"))
    params, opt = tr.init()
    x, y = tr.shard_batch(np.zeros((2, 17), np.int32))
    return tr.jitted_train_step.lower(params, opt, x, y, jnp.int32(0)).compile().as_text()


@pytest.fixture(scope="module")
def cifar_text(mesh4):
    return cifar_step_text(mesh4)


@pytest.fixture(scope="module")
def lm_text():
    return lm_step_text()


@pytest.mark.parametrize("op_name,opcode,phase", [
    ("jit(s)/graftscope/input_augment/graftscope/fwd_bwd/add", "fusion", "augment"),
    ("jit(s)/graftscope/fwd_bwd/transpose(jvp(fwd))/psum", "all-reduce", "sync"),
    ("jit(s)/graftscope/sync/dp_pmean/graftscope/optimizer/mul", "fusion", "sync"),
    ("", "all-gather-start", "sync"),
    ("jit(s)/graftscope/optimizer_zero1/mul", "fusion", "optimizer"),
    ("jit(s)/graftscope/optimizer/graftscope/telemetry/sqrt", "fusion", "optimizer"),
    ("jit(s)/graftscope/telemetry/sqrt", "fusion", "telemetry"),
    ("jit(s)/graftscope/fwd_bwd/transpose(jvp(fwd))/dot_general", "convolution", "bwd"),
    ("jit(s)/graftscope/fwd_bwd/transpose(graftscope/fwd_bwd)/jvp()/pallas_call", "custom-call", "bwd"),
    ("jit(s)/graftscope/fwd_bwd/jvp(fwd)/dot_general", "convolution", "fwd"),
    ("jit(s)/transpose(jvp(fwd))/mul", "fusion", "unscoped"),
    ("", "copy", "unscoped"),
])
def test_the_first_matching_rule_names_the_phase(op_name, opcode, phase):
    assert phase_of(op_name, opcode) == phase


def test_phase_map_reads_what_runs_as_an_op():
    text = """HloModule jit_step, is_scheduled=true

%fused (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %m = f32[8]{0} multiply(%p, %p), metadata={op_name="jit(step)/graftscope/optimizer/mul"}
}

%add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %s = f32[] add(%a, %b), metadata={op_name="jit(step)/graftscope/fwd_bwd/add"}
}

%fused_mo (p.1: f32[8]) -> (f32[], f32[8]) {
  %p.1 = f32[8]{0} parameter(0)
  %u = f32[8]{0} multiply(%p.1, %p.1), metadata={op_name="jit(step)/graftscope/optimizer/mul"}
  %n = f32[] reduce(%u, %z), dimensions={0}, to_apply=%add, metadata={op_name="jit(step)/graftscope/telemetry/reduce_sum"}
  ROOT %mo = (f32[], f32[8]{0}) tuple(%n, %u)
}

%fused_dw (a.1: f32[4,8], w.1: f32[8,8]) -> (f32[], f32[8,8]) {
  %a.1 = f32[4,8]{1,0} parameter(0)
  %w.1 = f32[8,8]{1,0} parameter(1)
  %dw = f32[8,8]{1,0} convolution(%a.1, %a.1), dim_labels=fb_bo->fo, metadata={op_name="jit(step)/graftscope/fwd_bwd/transpose(jvp(fwd))/dot_general"}
  %w.2 = f32[8,8]{1,0} add(%w.1, %dw), metadata={op_name="jit(step)/graftscope/optimizer/add"}
  %n.2 = f32[] reduce(%w.2, %z), dimensions={0,1}, to_apply=%add, metadata={op_name="jit(step)/graftscope/telemetry/reduce_sum"}
  ROOT %mo.2 = (f32[], f32[8,8]{1,0}) tuple(%n.2, %w.2)
}

%wrapped (q: f32[8]) -> f32[8] {
  %q = f32[8]{0} parameter(0)
  ROOT %ar = f32[8]{0} all-reduce(%q), replica_groups={}, to_apply=%add
}

ENTRY %main.3 (x: f32[8]) -> (f32[8], f32[]) {
  %x = f32[8]{0} parameter(0)
  %fusion.1 = f32[8]{0} fusion(%x), kind=kLoop, calls=%fused
  %fusion.2 = (f32[], f32[8]{0}) fusion(%x), kind=kLoop, calls=%fused_mo, metadata={op_name="jit(step)/graftscope/telemetry/reduce_sum"}
  %fusion.3 = (f32[], f32[8,8]{1,0}) fusion(%x, %x), kind=kOutput, calls=%fused_dw, metadata={op_name="jit(step)/graftscope/telemetry/reduce_sum"}
  %r = f32[] reduce(%x, %c), dimensions={0}, to_apply=%add, metadata={op_name="jit(step)/graftscope/telemetry/reduce_sum"}
  %start = ((f32[8]{0}), f32[8]{0}) async-start(%fusion.1), calls=%wrapped
  %done = f32[8]{0} async-done(%start), calls=%wrapped
  ROOT %t = (f32[8]{0}, f32[]) tuple(%done, %r)
}
"""
    module, phases = phase_map(text)
    assert module == "jit_step"
    # A fusion that records no op_name takes its fused root's, a
    # multi-output one its largest output's (XLA names this one after the
    # norm fused into the update), one that holds a matmul the matmul's
    # (a weight gradient with the update and the norm fused in); the
    # bodies of fusions and reductions are not ops of their own; an async
    # wrapper is what it wraps.
    assert phases == {
        "x": "unscoped", "fusion.1": "optimizer", "fusion.2": "optimizer", "fusion.3": "bwd", "r": "telemetry",
        "start": "sync", "done": "sync", "t": "unscoped", "q": "unscoped", "ar": "sync",
    }


def check_map(text, expected):
    module, phases = phase_map(text)
    named = entry_instructions(text)
    assert named and set(named) <= set(phases)
    assert set(phases.values()) <= set(PHASES)
    assert expected <= set(phases.values())
    return module, phases


def test_phase_map_takes_another_classifier():
    """A serving program's named scopes through the same rules: a fusion
    takes its largest matmul's label, else its root's."""
    text = """HloModule jit_step, is_scheduled=true

%fused (p: f32[8,8], q: f32[8]) -> f32[8] {
  %p = f32[8,8]{1,0} parameter(0)
  %q = f32[8]{0} parameter(1)
  %d = f32[8]{0} dot(%p, %q), metadata={op_name="jit(step)/TransformerLM/attn/attn_sparse_select/dot_general"}
  ROOT %a = f32[8]{0} add(%d, %q), metadata={op_name="jit(step)/TransformerLM/attn/add"}
}

%fused.1 (r: f32[8]) -> f32[8] {
  %r = f32[8]{0} parameter(0)
  ROOT %e = f32[8]{0} exponential(%r), metadata={op_name="jit(step)/TransformerLM/attn/attn_sparse/exp"}
}

ENTRY %main (w: f32[8,8], x: f32[8]) -> f32[8] {
  %w = f32[8,8]{1,0} parameter(0)
  %x = f32[8]{0} parameter(1)
  %fusion = f32[8]{0} fusion(%w, %x), kind=kOutput, calls=%fused
  %fusion.1 = f32[8]{0} fusion(%fusion), kind=kLoop, calls=%fused.1
  ROOT %sort = f32[8]{0} sort(%fusion.1), metadata={op_name="jit(step)/TransformerLM/mlp/sort"}
}
"""
    scopes = ("attn_sparse_select", "attn_sparse")

    def scope_of(op_name, opcode):
        return next((s for s in scopes if f"/{s}/" in op_name), "")

    module, got = profiling.phase_map(text, scope_of, scopes + ("",))
    assert module == "jit_step"
    assert {k: got[k] for k in ("fusion", "fusion.1", "sort")} == {
        "fusion": "attn_sparse_select", "fusion.1": "attn_sparse", "sort": "",
    }


def test_cifar_step_phase_map(cifar_text):
    module, phases = check_map(cifar_text, {"augment", "fwd", "bwd", "optimizer", "telemetry"})
    assert module == "jit_local_train_step"


def test_lm_step_phase_map_puts_the_flash_backward_in_bwd(lm_text):
    module, phases = check_map(lm_text, {"fwd", "bwd", "optimizer", "telemetry"})
    assert module == "jit_local_step"
    # On the CPU the kernels run interpreted: the forward's loop carries
    # the scope, the custom_vjp backward's carries it under transpose(...).
    kernel = {"fwd": 0, "bwd": 0}
    for line in lm_text.splitlines():
        m = profiling._INSTRUCTION.match(line)
        if m and m.group(1) in phases and "/attn/while" in line:
            phase = phases[m.group(1)]
            assert phase == ("bwd" if "transpose(graftscope/fwd_bwd)" in line else "fwd")
            kernel[phase] += 1
    assert kernel["fwd"] and kernel["bwd"]


def test_no_capture_builds_no_map_and_compiles_nothing(mesh4, tmp_path):
    """A fit loop with no ``profile_dir`` makes no map; with one, the map
    is built once, before the capture opens, and written beside it."""
    tr = Trainer(TrainConfig(**TINY_DP4_CFG), mesh=mesh4)
    state, _ = tr.fit(epochs=1)
    before = {k: dict(v) for k, v in profiling.step_phases().items()}
    counter = CompileCounter()
    state, _ = tr.fit(state=state, epochs=2)
    assert counter.count == 0
    assert profiling.step_phases() == before

    trace_dir = str(tmp_path / "trace")
    tr.cfg.profile_dir, tr.cfg.profile_start_step, tr.cfg.profile_num_steps = trace_dir, 9, 2
    tr.fit(state=state, epochs=3)
    # The map's ahead-of-time compile of the step the loop already ran is
    # answered by JAX's in-memory cache: no backend compile either.
    assert counter.count == 0
    with open(os.path.join(trace_dir, "step_phases.json")) as f:
        written = json.load(f)
    assert list(written) == ["jit_local_train_step"]
    assert written["jit_local_train_step"] == profiling.step_phases()["jit_local_train_step"]
    assert any(files for _, _, files in os.walk(os.path.join(trace_dir, "plugins")))


def test_lm_fit_writes_the_map_beside_its_capture(tmp_path):
    from cs744_pytorch_distributed_tutorial_tpu.data import synthetic_tokens

    trace_dir = str(tmp_path / "lm_trace")
    tr = LMTrainer(LMConfig(**TINY_LM, profile_dir=trace_dir, profile_start_step=1, profile_num_steps=1))
    _, _, losses = tr.fit(synthetic_tokens(8, 16, 64, seed=0), steps=3)
    assert len(losses) == 3
    with open(os.path.join(trace_dir, "step_phases.json")) as f:
        written = json.load(f)
    assert set(written["jit_local_step"].values()) >= {"fwd", "bwd", "optimizer", "telemetry"}
