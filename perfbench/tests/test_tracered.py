"""The reduction from a profiler trace to numbers, on synthetic intervals
and on a small recorded trace: the first two steps of the LM cell's
traced run on the v5e (PR 24's hunt), cut to the device's op and program
lines and the host spans of 20 us or more, gzipped."""

import gzip
from pathlib import Path

import pytest

from perfbench import tracered as R

DATA = Path(__file__).resolve().parent / "data"


def test_union_merge_gaps_subtract():
    iv = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (3.2, 3.4)]
    assert R.union_length(iv) == pytest.approx(3.0)
    assert R.merge(iv) == [(0.0, 2.0), (3.0, 4.0)]
    assert R.gaps_of(R.merge(iv)) == [(2.0, 3.0)]
    # collectives [1,3] and [5,6]; compute covers [0,2] and [5.5,7]
    assert R.subtract([(1.0, 3.0), (5.0, 6.0)], [(0.0, 2.0), (5.5, 7.0)]) == pytest.approx(1.5)
    assert R.subtract([(1.0, 2.0)], []) == pytest.approx(1.0)


@pytest.mark.parametrize("name,key", [
    ("%attn.71 = (bf16[192,1024,64]{2,1,0:T(8,128)(2,1)}, bf16[192,1024,64]{2,1,0}) custom-call(bf16[192,1024,64]{2,1,0} %x)",
     "attn_custom-call_bf16_192_1024_64_"),
    ("%multiply_reduce_fusion.1 = (f32[]{:T(128)}, f32[768,50304]{1,0:T(8,128)}) fusion(f32[] %a), kind=kLoop",
     "multiply_reduce_fusion_fusion_f32_768_50304_"),
    ("%copy.3 = bf16[8193,16,12,64]{3,2,1,0:T(8,128)(2,1)} copy(bf16[8193,16,12,64]{3,2,0,1} %p)",
     "copy_copy_bf16_8193_16_12_64_"),
    ("%fusion.9 = f32[16,1024]{1,0} fusion(bf16[192,1024,64]{2,1,0} %attn.71)", "fusion_fusion_f32_16_1024_"),
    ("jit_step(123)", "jit_step(123)"),
])
def test_op_key(name, key):
    assert R.op_key(name) == key


def synthetic():
    ops = {
        0: [("%a.1 = f32[8]{0} fusion(f32[8] %x)", "a_fusion_f32_8_", 0.0, 1.0),
            ("%all-reduce.2 = f32[8]{0} all-reduce(f32[8] %a.1)", "all-reduce_all-reduce_f32_8_", 0.5, 2.0),
            ("%attn.3 = bf16[4,8]{1,0} custom-call(bf16[4,8] %q)", "attn_custom-call_bf16_4_8_", 3.0, 4.0)],
        1: [("%a.1 = f32[8]{0} fusion(f32[8] %x)", "a_fusion_f32_8_", 0.0, 2.0),
            ("%attn.3 = bf16[4,8]{1,0} custom-call(bf16[4,8] %q)", "attn_custom-call_bf16_4_8_", 3.0, 4.0)],
    }
    host = [("$whole", 0.0, 10.0), ("fit", 0.0, 5.0), ("np.asarray(jax.Array)", 2.1, 2.9), ("step", 3.0, 4.0)]
    programs = {0: [("jit_step(1)", 0.0, 2.0), ("jit_step(1)", 3.0, 4.0), ("jit_prefill(2)", 4.0, 4.5)]}
    return R.Trace(ops, host, programs)


def test_busy_idle_and_kernel_sums_average_over_chips():
    t = synthetic()
    assert t.window_s == pytest.approx(4.0)
    assert t.busy_s == pytest.approx(3.0)
    assert t.idle_share == pytest.approx(0.25)
    assert t.seconds("^attn_custom-call") == pytest.approx(1.0)
    # A consumer of the kernel names it among its operands and is not it.
    consumer = R.Trace({0: [("%f.1 = f32[8]{0} fusion(bf16[4,8] %attn.3)", R.op_key("%f.1 = f32[8]{0} fusion(bf16[4,8] %attn.3)"), 0.0, 1.0)]}, [])
    assert consumer.seconds("attn") == 0.0


def test_gap_is_attributed_to_the_innermost_host_span():
    gaps = synthetic().attribute_gaps()
    assert gaps[0][0] == "np.asarray_jax.Array_" and gaps[0][1] == pytest.approx(1.0)


def test_collectives_and_their_exposed_part():
    total, exposed = synthetic().collective_seconds()
    # chip 0: all-reduce 1.5 s, of which 1.0 s after the compute ended; chip 1: none
    assert total == pytest.approx(0.75) and exposed == pytest.approx(0.5)


def test_program_durations():
    t = synthetic()
    assert t.program_durations("jit_step") == pytest.approx([2.0, 1.0])
    assert t.program_durations("nothing") == []


def test_a_trace_without_device_operations_is_refused():
    with pytest.raises(RuntimeError):
        R.Trace({}, [("fit", 0.0, 1.0)])


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "lm-two-steps.xplane.pb"
    path.write_bytes(gzip.open(DATA / "lm-two-steps.xplane.pb.gz").read())
    return R.Trace.from_file(path, n_devices=1)


def test_recorded_trace_busy_idle(recorded):
    # Two steps of 132.7 ms each on the device, 6.8 ms apart (the window
    # runs from the first op to the last, so it holds one such gap).
    assert recorded.program_durations("jit_local_step") == pytest.approx([0.13273657, 0.13273275], abs=1e-6)
    assert recorded.window_s == pytest.approx(0.272045, abs=1e-5)
    assert recorded.busy_s == pytest.approx(0.265220, abs=1e-5)
    assert 100 * recorded.idle_share == pytest.approx(2.509, abs=0.01)


def test_recorded_trace_kernels_and_gaps(recorded):
    # 12 layers x (forward + two backward kernels) x 2 steps, 39.7 ms a step
    flash = list(recorded.ops("^attn_custom-call"))
    assert len(flash) == 72
    assert recorded.seconds("^attn_custom-call") / 2 == pytest.approx(0.0396895, abs=1e-5)
    b = recorded.breakdown()
    assert b["device_ops"][0][0] == "attn_custom-call_bf16_192_1024_64_"
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) <= 10
    # The device waits while the host fetches the loss (fit()'s per-step fetch).
    assert b["idle_gaps"][0][0] == "np.asarray_jax.Array_"
    assert recorded.collective_seconds() == (0.0, 0.0)
    assert len(recorded.host_span_durations("lm")) == 2
