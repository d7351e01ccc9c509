"""FLOP and byte functions against hand counts."""

import pytest

from perfbench import work
from perfbench.peaks import UnknownDevice, peaks_for

GPT2S = {"n_embd": 768, "n_inner": 3072, "vocab_size": 50304, "n_layer": 12}


def test_resnet18_forward_by_hand():
    # stem 3->64 at 32x32; stage 1: four 64->64 convs at 32x32; stage 2:
    # 64->128 s2 + three 128->128 at 16x16 + 1x1 projection; and so on.
    mac = 32 * 32 * 9 * 3 * 64
    mac += 4 * 32 * 32 * 9 * 64 * 64
    mac += 16 * 16 * 9 * 64 * 128 + 3 * 16 * 16 * 9 * 128 * 128 + 16 * 16 * 64 * 128
    mac += 8 * 8 * 9 * 128 * 256 + 3 * 8 * 8 * 9 * 256 * 256 + 8 * 8 * 128 * 256
    mac += 4 * 4 * 9 * 256 * 512 + 3 * 4 * 4 * 9 * 512 * 512 + 4 * 4 * 256 * 512
    mac += 512 * 10
    assert work.resnet18_cifar_forward_flops_per_sample() == 2 * mac
    assert work.resnet18_cifar_train_flops_per_sample() == 6 * mac
    assert work.resnet18_cifar_train_flops_per_sample() / 1e9 == pytest.approx(3.3325, abs=1e-3)


@pytest.mark.parametrize("causal,expect", [(True, 0.798031872e9), (False, 0.854654976e9)])
def test_gpt2_small_per_token(causal, expect):
    # blocks 12 * (4*768^2 + 2*768*3072) = 84,934,656; head 768*50304 = 38,633,472
    assert work.transformer_matmul_params(GPT2S) == 84_934_656 + 38_633_472
    assert work.transformer_train_flops_per_token(GPT2S, 1024, causal) == pytest.approx(expect)


def test_attention_share_is_what_6n_leaves_out():
    full = work.transformer_train_flops_per_token(GPT2S, 1024)
    six_n = 6 * work.transformer_matmul_params(GPT2S)
    assert (full - six_n) / six_n == pytest.approx(0.0764, abs=1e-3)


def test_flash_step_work_matches_per_token_attention():
    per_tok = work.transformer_train_flops_per_token(GPT2S, 1024) - 6 * work.transformer_matmul_params(GPT2S)
    assert work.flash_train_flops_per_step(16, 1024, GPT2S) == pytest.approx(per_tok * 16 * 1024)
    assert work.flash_train_bytes_per_step(16, 1024, GPT2S) == 12 * 12 * 16 * 1024 * 768 * 2


def test_paged_decode_work():
    assert work.paged_decode_attn_bytes(1000, GPT2S) == 2 * 12 * 1000 * 768 * 2
    assert work.paged_decode_attn_flops(1000, GPT2S) == 4 * 12 * 1000 * 768


def test_roofline_takes_the_larger_bound():
    p = peaks_for("TPU v5 lite")
    assert work.roofline_seconds(197e12, 1.0, p) == pytest.approx(1.0)
    assert work.roofline_seconds(1.0, 819e9, p) == pytest.approx(1.0)


def test_unknown_device_is_an_error():
    with pytest.raises(UnknownDevice):
        peaks_for("TPU v9 imaginary")
