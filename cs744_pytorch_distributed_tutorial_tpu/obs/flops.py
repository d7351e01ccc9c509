"""Analytic FLOPs models and MFU accounting.

Training telemetry's ONE definition of model FLOPs and peak throughput
(the benchmark counts its own work, ``perfbench/work.py``).

Conventions (the standard MFU accounting):
- FLOPs = 2 * MACs.
- Training = 3x forward (backward is dgrad + wgrad, each ~1x forward);
  for transformers this is the familiar 6*N*D rule — 3x on 2*N*D.
- Bandwidth-bound ops (BN, activations, pooling, data augmentation)
  are excluded.
- MFU = achieved model FLOPs/sec divided by *peak dense* FLOPs of the
  chip — not "hardware FLOPs" including recompute, so MFU is
  comparable across implementations.
"""

from __future__ import annotations

__all__ = [
    "V5E_PEAK_FLOPS",
    "peak_flops_per_chip",
    "peak_hbm_bytes_per_sec",
    "DEFAULT_RIDGE_FLOPS_PER_BYTE",
    "roofline_classify",
    "resnet18_cifar_train_flops_per_sample",
    "transformer_train_flops_per_token",
    "mfu",
]

# TPU v5e (v5 lite) peak dense bf16 throughput, per chip.
V5E_PEAK_FLOPS = 197e12

# Peak dense bf16 FLOPs/sec per chip by jax device_kind substring.
# Only kinds we can vouch for; unknown kinds (and CPU) map to None so
# an MFU figure is never fabricated against a made-up peak.
_PEAKS: tuple[tuple[str, float], ...] = (
    ("v5 lite", V5E_PEAK_FLOPS),
    ("v5e", V5E_PEAK_FLOPS),
    ("v5p", 459e12),
    ("v4", 275e12),
    ("v6 lite", 918e12),
    ("v6e", 918e12),
)


# Peak HBM bandwidth (bytes/sec) per chip, same matching discipline.
# Pairs with _PEAKS to give each chip's roofline ridge point
# (peak_flops / peak_hbm_bw) for roofline_classify.
_HBM_PEAKS: tuple[tuple[str, float], ...] = (
    ("v5 lite", 819e9),
    ("v5e", 819e9),
    ("v5p", 2765e9),
    ("v4", 1228e9),
    ("v6 lite", 1638e9),
    ("v6e", 1638e9),
)


def peak_flops_per_chip(device_kind: str) -> float | None:
    """Peak dense bf16 FLOPs/sec for a jax ``device_kind`` string, or
    None when the kind is unknown (CPU, GPU, future TPUs) — callers
    must then report MFU as null rather than guess."""
    kind = device_kind.lower()
    for sub, peak in _PEAKS:
        if sub in kind:
            return peak
    return None


def peak_hbm_bytes_per_sec(device_kind: str) -> float | None:
    """Peak HBM bandwidth (bytes/sec) for a jax ``device_kind``, or
    None when unknown — roofline classifiers then fall back to a
    documented default ridge instead of a fabricated one."""
    kind = device_kind.lower()
    for sub, peak in _HBM_PEAKS:
        if sub in kind:
            return peak
    return None


# Ridge point (flops/byte) used by roofline_classify when the device
# kind has no known peak pair: v5e's 197e12 / 819e9 ~= 240.
DEFAULT_RIDGE_FLOPS_PER_BYTE = 240.0


def roofline_classify(
    flops: float | None,
    bytes_accessed: float | None,
    device_kind: str | None,
) -> str:
    """'compute' | 'memory' | 'unknown': arithmetic intensity against the
    chip's ridge point (peak_flops / peak_hbm_bw) when both peaks are
    known, else the documented v5e default ridge."""
    if not flops or not bytes_accessed:
        return "unknown"
    peak_f = peak_flops_per_chip(device_kind or "")
    peak_b = peak_hbm_bytes_per_sec(device_kind or "")
    ridge = (
        peak_f / peak_b if (peak_f and peak_b) else DEFAULT_RIDGE_FLOPS_PER_BYTE
    )
    return "compute" if flops / bytes_accessed >= ridge else "memory"


def resnet18_cifar_train_flops_per_sample() -> float:
    """Analytic model FLOPs of one ResNet-18/CIFAR training step, per
    sample. Counts convs, the stage-entry 1x1 projections, and the FC
    head (``models/resnet.py`` cifar_stem architecture: 3x3 stem at
    32x32, stages (2,2,2,2) at 64/128/256/512 ch, strides 1/2/2/2)."""

    def conv(hw: int, cin: int, cout: int, k: int = 3) -> float:
        return 2.0 * hw * hw * cin * cout * k * k  # per output position

    f = conv(32, 3, 64)  # stem
    cin = 64
    for cout, hw in ((64, 32), (128, 16), (256, 8), (512, 4)):
        f += conv(hw, cin, cout) + conv(hw, cout, cout)  # block 0
        if cin != cout:  # stage-entry projection shortcut
            f += conv(hw, cin, cout, k=1)
        f += 2 * conv(hw, cout, cout)  # block 1
        cin = cout
    f += 2.0 * 512 * 10  # FC head
    return 3.0 * f


def transformer_train_flops_per_token(n_params: int | float) -> float:
    """The 6*N rule: ~6 FLOPs per parameter per trained token (2N
    forward, 4N backward). Attention-score FLOPs are excluded, as in
    the PaLM/Chinchilla MFU convention for seq_len << d_model regimes;
    for this repo's short-sequence LMs the correction is <2%."""
    return 6.0 * float(n_params)


def mfu(
    achieved_flops_per_sec_per_chip: float, device_kind: str
) -> float | None:
    """Model FLOPs utilization in [0, 1], or None off known TPUs."""
    peak = peak_flops_per_chip(device_kind)
    if peak is None:
        return None
    return achieved_flops_per_sec_per_chip / peak
