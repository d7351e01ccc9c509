"""Operations and bytes the algorithms need, computed from shapes.

These count what the mathematics requires, not what the current kernels
do (recomputation, padding and masked-out work are not counted), so a
later PR that replaces a kernel is read against the same work.
"""

from __future__ import annotations

from typing import Mapping


def _conv_flops(h: int, w: int, k: int, cin: int, cout: int) -> int:
    """Forward FLOPs of one conv at output resolution h x w (2 per MAC)."""
    return 2 * h * w * k * k * cin * cout


def resnet18_cifar_forward_flops_per_sample(
    stages=(2, 2, 2, 2), widths=(64, 128, 256, 512), image=32, classes=10
) -> int:
    """ResNet-18 with the CIFAR 3x3 stem: convs, 1x1 projections, head."""
    total = _conv_flops(image, image, 3, 3, widths[0])  # stem
    res, cin = image, widths[0]
    for s, (blocks, cout) in enumerate(zip(stages, widths)):
        for b in range(blocks):
            stride = 2 if (s > 0 and b == 0) else 1
            res_out = res // stride
            total += _conv_flops(res_out, res_out, 3, cin, cout)
            total += _conv_flops(res_out, res_out, 3, cout, cout)
            if stride != 1 or cin != cout:
                total += _conv_flops(res_out, res_out, 1, cin, cout)
            res, cin = res_out, cout
    return total + 2 * widths[-1] * classes


def resnet18_cifar_train_flops_per_sample(**kw) -> int:
    """Forward plus backward (input and weight gradients): 3x forward."""
    return 3 * resnet18_cifar_forward_flops_per_sample(**kw)


def transformer_matmul_params(cfg: Mapping[str, int]) -> int:
    """Parameters that sit in matmuls: the blocks' q,k,v,out and MLP
    kernels, and the vocabulary head. The embedding lookup is a gather."""
    d, f, v, n = cfg["n_embd"], cfg["n_inner"], cfg["vocab_size"], cfg["n_layer"]
    return n * (4 * d * d + 2 * d * f) + d * v


def transformer_train_flops_per_token(
    cfg: Mapping[str, int], seq_len: int, causal_half: bool = True
) -> float:
    """6 FLOPs per matmul parameter per token, plus attention scores and
    values: forward 2*2*T*d per token per layer (QK^T and PV), halved
    under a causal mask, times 3 for forward plus backward."""
    d, n = cfg["n_embd"], cfg["n_layer"]
    attn_fwd = 4 * seq_len * d * (0.5 if causal_half else 1.0)
    return 6.0 * transformer_matmul_params(cfg) + 3.0 * n * attn_fwd


def flash_train_flops_per_step(
    batch: int, seq_len: int, cfg: Mapping[str, int], causal_half: bool = True
) -> float:
    """Attention's own FLOPs in one training step, all layers: forward
    QK^T and PV (4*T*T*d per sequence), backward twice that."""
    d, n = cfg["n_embd"], cfg["n_layer"]
    fwd = 4.0 * seq_len * seq_len * d * (0.5 if causal_half else 1.0)
    return 3.0 * n * batch * fwd


def flash_train_bytes_per_step(
    batch: int, seq_len: int, cfg: Mapping[str, int], itemsize: int = 2
) -> float:
    """Least HBM traffic of attention in one step, all layers: forward
    reads q,k,v and writes o; backward reads q,k,v,o,do and writes
    dq,dk,dv. Twelve [B,T,d] tensors per layer."""
    d, n = cfg["n_embd"], cfg["n_layer"]
    return 12.0 * n * batch * seq_len * d * itemsize


def paged_decode_attn_bytes(live_tokens: int, cfg: Mapping[str, int], itemsize: int = 2) -> float:
    """K and V rows of the live tokens, read once, all layers."""
    return 2.0 * cfg["n_layer"] * live_tokens * cfg["n_embd"] * itemsize


def paged_decode_attn_flops(live_tokens: int, cfg: Mapping[str, int]) -> float:
    """q.K^T and p.V over the live tokens, all layers: 4*d per token."""
    return 4.0 * cfg["n_layer"] * live_tokens * cfg["n_embd"]


def roofline_seconds(flops: float, nbytes: float, peaks: Mapping[str, float]) -> float:
    """Least time the chip could take: the larger of compute and memory."""
    return max(flops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
