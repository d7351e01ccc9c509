"""Decode throughput on the real chip: KV-cache generation.

Autoregressive decoding is bound by HBM bandwidth (weights + KV cache
re-read every step) and, for small models, by per-op latency on the
step's serial dependency chain. Measures generated tokens/sec for the
jitted sampling loop (infer/generate.py) across:

- MHA vs GQA vs MQA KV-head counts (the cache-bandwidth lever);
- weight-only int8 (ops/quant.py) at two scopes, on a toy 4L/512d model
  AND a GPT-2-small-scale model (the regime split below).

Timing methodology: a fence per generation puts a host round-trip in
every sample. Instead each measurement dispatches CALLS generations
back-to-back (they pipeline on device — each depends only on params)
and fences ONCE; best-of-3 rounds, variants interleaved so drift hits
all of them equally.

Measured 2026-07-31 (one TPU v5e chip, greedy, best-of-rounds):

kv sweep (toy 4L/512d): MHA 69.5k / GQA-2 116.2k / MQA 150.3k tok/s
toy 4L/512d/kv2, vocab 32k (weights ~54 MB bf16):
  bf16       35.1 ms/gen  116.7k tok/s
  int8 head  37.1 ms/gen  110.5k tok/s (0.95x)
  int8 all   38.8 ms/gen  105.5k tok/s (0.90x)
GPT-2-small 12L/768d/kv4, vocab 50304 (weights ~325 MB bf16):
  bf16      106.5 ms/gen  19.2k tok/s
  int8 head  91.2 ms/gen  22.5k tok/s (1.17x, reproduced 1.167x/1.168x/1.135x)
  int8 all  104.7 ms/gen  19.6k tok/s (1.02x)
long context (toy model, prompt 4096, ~142 MB bf16 cache; the wall
number carries the constant prefill + dispatch, so the decode LOOP's
device time from the trace is the honest metric):
  bf16 cache       decode loop 232 us/step
  int8 cache       decode loop 184 us/step (1.26x)
  int8 cache+head  decode loop 162 us/step (1.43x)

The regime split the numbers pin: at toy scale the decode step is
op-latency-bound (~137 us/step against ~66 us of weight reads — the
reads hide under the serial chain), so int8 only adds Pallas-call
overhead. At GPT-2 scale the step is bandwidth-bound and quantizing the
wide lm_head matmul alone wins 1.17x, while quantizing the 72 small
per-layer projections gives the win back in per-call dispatch cost —
hence ``QUANT_HEAD_ONLY`` is the decode default
(``LMTrainer.quantized_decode_model``).

Run: python benchmarks/bench_generate.py
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from cs744_pytorch_distributed_tutorial_tpu.infer import make_generator
from cs744_pytorch_distributed_tutorial_tpu.models import TransformerLM
from cs744_pytorch_distributed_tutorial_tpu.ops.quant import (
    QUANT_HEAD_ONLY,
    QUANT_MODULES,
    quantize_lm_params,
)

BATCH = 16
PROMPT = 128
CALLS = 8  # generations per timing batch (one fence at the end)
ROUNDS = 3


def batch_time(gen, params, prompt, calls=CALLS) -> float:
    outs = [gen(params, prompt, jax.random.key(2)) for _ in range(2)]
    float(outs[-1][0, 0])  # steady-state warm
    t0 = time.perf_counter()
    outs = [gen(params, prompt, jax.random.key(2)) for _ in range(calls)]
    float(outs[-1][0, 0])  # ONE fence: device work pipelines, RTT amortizes
    return (time.perf_counter() - t0) / calls


def run_block(title: str, model: TransformerLM, new_tokens: int) -> None:
    print(title)
    prompt = jax.random.randint(
        jax.random.key(0), (BATCH, PROMPT), 0, model.vocab_size
    )
    params = model.init(jax.random.key(1), jnp.zeros((1, 8), jnp.int32))[
        "params"
    ]
    variants: dict[str, tuple] = {
        "bf16": (
            make_generator(model, max_new_tokens=new_tokens, temperature=0.0),
            params,
        ),
        "int8 head": (
            make_generator(
                model.clone(quant_dense=True, quant_modules=QUANT_HEAD_ONLY),
                max_new_tokens=new_tokens,
                temperature=0.0,
            ),
            quantize_lm_params(params, QUANT_HEAD_ONLY),
        ),
        "int8 all": (
            make_generator(
                model.clone(
                    quant_dense=True,
                    quant_modules=tuple(sorted(QUANT_MODULES)),
                ),
                max_new_tokens=new_tokens,
                temperature=0.0,
            ),
            quantize_lm_params(params, tuple(sorted(QUANT_MODULES))),
        ),
    }
    for gen, p in variants.values():  # compile
        out = gen(p, prompt, jax.random.key(2))
        float(out[0, 0])
    best = {k: float("inf") for k in variants}
    for _ in range(ROUNDS):  # interleave so drift hits all variants
        for name, (gen, p) in variants.items():
            best[name] = min(best[name], batch_time(gen, p, prompt))
    base = best["bf16"]
    for name, dt in best.items():
        print(
            f"  {name:10s} {dt * 1e3:7.1f} ms/gen  "
            f"{BATCH * new_tokens / dt:9.0f} tok/s  ({base / dt:.3f}x vs bf16)"
        )


def kv_block() -> None:
    """MHA vs GQA vs MQA on the toy model — the KV-cache bandwidth lever
    (the grouped decode_attention reads the cache at kv width)."""
    print("kv-head sweep (4L/512d toy, bf16)")
    for kv in (8, 2, 1):
        model = TransformerLM(
            vocab_size=32768,
            num_layers=4,
            num_heads=8,
            num_kv_heads=kv,
            d_model=512,
            d_ff=2048,
            max_seq_len=PROMPT + 256,
            dtype=jnp.bfloat16,
            attention_impl="dense",
            use_rope=True,
        )
        prompt = jax.random.randint(
            jax.random.key(0), (BATCH, PROMPT), 0, 32768
        )
        params = model.init(jax.random.key(1), jnp.zeros((1, 8), jnp.int32))[
            "params"
        ]
        gen = make_generator(model, max_new_tokens=256, temperature=0.0)
        out = gen(params, prompt, jax.random.key(2))
        float(out[0, 0])
        dt = min(batch_time(gen, params, prompt) for _ in range(ROUNDS))
        print(
            f"  kv_heads={kv}  {dt * 1e3:7.1f} ms/gen  "
            f"{BATCH * 256 / dt:9.0f} tok/s"
        )


def long_context_block() -> None:
    """Int8 KV cache at long context: with a 4096-token prompt the cache
    (~142 MB bf16/step at this config), not the weights (~54 MB), is most
    of what a decode step reads — the regime quant_kv_cache targets. The
    cache mutates every step so XLA cannot hoist its dequant (contrast
    the weight path, which needed the Pallas kernel for exactly that
    reason); pure-XLA int8 reads are the win. Prefill runs the flash
    kernel (dense would materialize [B, H, 4096, 4096] scores)."""
    print("int8 KV cache at long context (4L/512d/kv2, prompt 4096)")
    lc_prompt_len, new = 4096, 128
    model = TransformerLM(
        vocab_size=32768,
        num_layers=4,
        num_heads=8,
        num_kv_heads=2,
        d_model=512,
        d_ff=2048,
        max_seq_len=lc_prompt_len + new,
        dtype=jnp.bfloat16,
        attention_impl="flash",
        use_rope=True,
    )
    prompt = jax.random.randint(
        jax.random.key(0), (BATCH, lc_prompt_len), 0, 32768
    )
    params = model.init(jax.random.key(1), jnp.zeros((1, 8), jnp.int32))[
        "params"
    ]
    variants = {
        "bf16 cache": (
            make_generator(model, max_new_tokens=new, temperature=0.0),
            params,
        ),
        "int8 cache": (
            make_generator(
                model.clone(quant_kv_cache=True),
                max_new_tokens=new,
                temperature=0.0,
            ),
            params,
        ),
        "int8 cache+head": (
            make_generator(
                model.clone(
                    quant_kv_cache=True,
                    quant_dense=True,
                    quant_modules=QUANT_HEAD_ONLY,
                ),
                max_new_tokens=new,
                temperature=0.0,
            ),
            quantize_lm_params(params, QUANT_HEAD_ONLY),
        ),
    }
    from cs744_pytorch_distributed_tutorial_tpu.utils.profiling import (
        device_op_breakdown,
    )

    # Wall-clock per generation is dominated by the CONSTANT 4096-token
    # prefill (~37 ms device) plus dispatch, which masks the decode-loop
    # delta — so report the decode loop's own device time (the single
    # `while` op in the trace) alongside the wall number.
    loop_ms = {}
    best = {k: float("inf") for k in variants}
    for name, (gen, p) in variants.items():
        out = gen(p, prompt, jax.random.key(2))
        float(out[0, 0])
        _, ops = device_op_breakdown(
            gen, p, prompt, jax.random.key(2), iters=2, top=40
        )
        loop_ms[name] = sum(ms for ms, n in ops if n.startswith("while"))
    for _ in range(ROUNDS):
        for name, (gen, p) in variants.items():
            best[name] = min(best[name], batch_time(gen, p, prompt, calls=4))
    base_loop = loop_ms["bf16 cache"]
    for name, dt in best.items():
        print(
            f"  {name:16s} wall {dt * 1e3:7.1f} ms/gen   decode-loop "
            f"{loop_ms[name]:6.1f} ms ({loop_ms[name] / new * 1e3:5.0f} us/"
            f"step, {base_loop / loop_ms[name]:.3f}x vs bf16)"
        )


def main() -> None:
    kv_block()
    run_block(
        "int8 ablation: toy 4L/512d/kv2 (op-latency-bound regime)",
        TransformerLM(
            vocab_size=32768,
            num_layers=4,
            num_heads=8,
            num_kv_heads=2,
            d_model=512,
            d_ff=2048,
            max_seq_len=PROMPT + 256,
            dtype=jnp.bfloat16,
            attention_impl="dense",
            use_rope=True,
        ),
        new_tokens=256,
    )
    run_block(
        "int8 ablation: GPT-2-small 12L/768d/kv4 (bandwidth-bound regime)",
        TransformerLM(
            vocab_size=50304,
            num_layers=12,
            num_heads=12,
            num_kv_heads=4,
            d_model=768,
            d_ff=3072,
            max_seq_len=PROMPT + 128,
            dtype=jnp.bfloat16,
            attention_impl="dense",
            use_rope=True,
        ),
        new_tokens=128,
    )
    long_context_block()


if __name__ == "__main__":
    main()
