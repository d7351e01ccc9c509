"""Round-4 probe: does scan_layers tear down the GPT-2 batch-8 wall?

Round 3's measured negative (bench_lm_gpt2.py docstring): b16 flat,
b32 did not compile on the development setup of the time — with 12
UNROLLED blocks. VERDICT r3 #1: the unrolled program size is the prime suspect;
scan_layers (one block body + a loop) is the tear-down attempt. This
probe measures flash/remat-off at b8 (scan-vs-unroll overhead check),
then walks b16/b32/b64 with scan_layers=True, remat off while memory
admits and remat=dots as the fallback.

Each config runs in THIS process sequentially; compile failures are
caught and recorded per config.
"""

from __future__ import annotations

import json
import sys
import os

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench_lm_gpt2 import bench_config  # noqa: E402


def run(label, **kw):
    try:
        row = bench_config(**kw)
        row["probe"] = label
        print(json.dumps(row), flush=True)
    except Exception as e:
        print(json.dumps({
            "probe": label, "error": f"{type(e).__name__}: {str(e)[:160]}",
            **{k: str(v) for k, v in kw.items()},
        }), flush=True)


def main() -> None:
    # Overhead check at the round-3 headline point.
    run("scan-b8-nomat", attention_impl="flash", fused_xent=False,
        batch=8, remat=False, scan_layers=True)
    # The wall itself.
    run("scan-b16-nomat", attention_impl="flash", fused_xent=False,
        batch=16, remat=False, scan_layers=True)
    run("scan-b32-nomat", attention_impl="flash", fused_xent=False,
        batch=32, remat=False, scan_layers=True)
    run("scan-b32-dots", attention_impl="flash", fused_xent=False,
        batch=32, remat=True, scan_layers=True)
    run("scan-b64-dots", attention_impl="flash", fused_xent=False,
        batch=64, remat=True, scan_layers=True)


if __name__ == "__main__":
    main()
