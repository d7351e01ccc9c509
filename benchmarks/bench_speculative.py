"""Speculative decoding on the real chip: trained byte-LM draft+target.

Measures what `infer/speculative.py` buys in the regime bench_generate
pinned as OP-LATENCY-bound: batch-1 greedy decoding, where the serial
per-token chain (not bandwidth or FLOPs) sets wall-clock. A 4-layer
target and a 1-layer draft train briefly on this repo's own README as a
byte corpus (enough for real draft/target agreement — random drafts
accept ~nothing and measure only overhead), then tokens/sec and the
realized acceptance are measured for plain greedy vs speculative at
several k.

Timing: whole generations are single dispatches (the entire
draft-propose/verify loop is one jitted while_loop), batched CALLS-deep
with one fence — same RTT-amortization as bench_generate.

Run: python benchmarks/bench_speculative.py

Measured 2026-07-31 (one TPU v5e chip, trained byte-LMs, device time
from the trace; both models reach ~0 train loss and teacher-forced
draft/target agreement 1.00 on the generated text):
  plain greedy      12.6 ms/gen   20.3k tok/s
  speculative k=2    5.5 ms/gen   47.0k tok/s  (2.31x)  acceptance ~1.0
  speculative k=4    4.9 ms/gen   52.5k tok/s  (2.58x)  acceptance 1.00
  speculative k=8    4.6 ms/gen   55.7k tok/s  (2.74x)  acceptance 0.98
Target forwards drop 256 -> 29 at k=8 (8.8x); the draft's own serial
steps bound the remaining time. An earlier version of the decoder
measured only ~0.83 acceptance on this same agreement-1.00 pair — the
draft cache row at pos+k was never written (found in review, fixed,
and the strict self-draft stats test now pins it). Earlier wall-clock
attempts measured 0.4-0.9x "slowdowns" that were host round-trip time,
not device time; the trace is ground truth. A random
(untrained-agreement) draft costs ~3x plain in device time at k=8 —
speculation must be earned by a draft that actually agrees.

EARNED-ACCEPTANCE regime, round 4 (VERDICT r3 #3a) — undertrained
drafts picked by a step sweep to land in the 0.5-0.9 agreement band:
  agreement 0.81 (330-step draft):
    k=2 1.87x (acc 0.72)   k=4 1.81x (acc 0.63)   k=8 1.40x (acc 0.44)
  agreement 0.52 (260-step draft):
    k=2 1.44x (acc 0.43)   k=4 1.05x (acc 0.26)   k=8 0.64x (acc 0.13)
  agreement 0.24 (120-step draft):
    k=2 0.99x              k=4 0.68x              k=8 0.41x
The shape is the textbook speculative curve: real speedup needs
agreement >~0.5, moderate-acceptance pairs want SMALL k (k=2 dominates
at 0.5; k=8 only pays at >~0.7), and a weak draft is a net LOSS. Also
measured: the band only exists on in-distribution prompts — on an
off-distribution prompt the target's own continuation is chaotic and
even a near-converged draft scores ~0.2 agreement (agreement-vs-steps:
150->0.27, 200->0.35, 260->0.52, 330->0.81, 420->0.99).

SAMPLING mode, round 4 (VERDICT r3 #3b) — rejection-sampling
speculative at temperature 0.8 vs plain sampling (distribution
exactness pinned separately by the chi-square test):
  plain sampling   12.9 ms/gen  19.9k tok/s
  k=4               6.1 ms/gen  41.9k tok/s  (2.11x)  acceptance 1.00
  k=8               5.6 ms/gen  45.4k tok/s  (2.29x)  acceptance 0.98
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from cs744_pytorch_distributed_tutorial_tpu.data import byte_corpus
from cs744_pytorch_distributed_tutorial_tpu.infer import (
    make_generator,
    make_speculative_generator,
)
from cs744_pytorch_distributed_tutorial_tpu.train import LMConfig, LMTrainer

SEQ = 512
MAX_SEQ = 1024
PROMPT = 128
NEW = 256
STEPS = 800
CALLS = 6
ROUNDS = 3


def train(num_layers: int, d_model: int, d_ff: int, tokens,
          steps: int = STEPS):
    cfg = LMConfig(
        vocab_size=256,
        num_layers=num_layers,
        num_heads=4,
        d_model=d_model,
        d_ff=d_ff,
        max_seq_len=MAX_SEQ,
        seq_len=SEQ,
        attention_impl="dense",
        compute_dtype="bfloat16",
        use_rope=True,
        global_batch_size=8,
        learning_rate=1e-3,
        lr_schedule="warmup_cosine",
        warmup_steps=min(50, steps // 4),
        total_steps=steps,
        optimizer="adamw",
    )
    tr = LMTrainer(cfg)
    params, _, losses = tr.fit(tokens, steps)
    return tr, jax.device_get(params), losses[-1]


def timed(gen, *args) -> float:
    """DEVICE time per generation from the profiler trace: a generation
    is a few milliseconds, so host round-trips would dominate a wall
    clock (see utils/profiling.py)."""
    from cs744_pytorch_distributed_tutorial_tpu.utils.profiling import (
        device_op_breakdown,
    )

    out = gen(*args)
    float(jax.tree.leaves(out)[0].ravel()[0])
    total, _ = device_op_breakdown(gen, *args, iters=3, top=1)
    return total / 1e3


def agreement(draft, tp, dp, plain, prompt) -> float:
    """Teacher-forced agreement of the draft with the target's own
    greedy continuation (via the closed-over ``plain`` generator on
    ``tp``) — the diagnostic upper bound on acceptance."""
    t_out = plain(tp, prompt, jax.random.key(0))
    seq = jnp.concatenate([prompt, t_out.astype(jnp.int32)], axis=1)
    d_logits = draft.apply({"params": dp}, seq)
    d_pred = jnp.argmax(d_logits[:, PROMPT - 1 : -1], axis=-1)
    return float((d_pred == t_out).mean())


def sweep(label, target, draft, tp, dp, base, prompt) -> None:
    for k in (2, 4, 8):
        spec = make_speculative_generator(
            target, draft, max_new_tokens=NEW, k=k, return_stats=True
        )
        dt = min(timed(spec, tp, dp, prompt) for _ in range(ROUNDS))
        _, calls = spec(tp, dp, prompt)
        calls = int(calls)
        accept = (NEW / max(calls, 1) - 1) / k
        print(
            f"{label} k={k}       {dt * 1e3:7.1f} ms/gen  "
            f"{NEW / dt:8.0f} tok/s  ({base / dt:.2f}x)  "
            f"[{calls} target calls, acceptance {accept:.2f}]"
        )


def main() -> None:
    corpus = byte_corpus("README.md", SEQ, max_seqs=512, seed=0)
    target_tr, tp, tl = train(4, 256, 1024, corpus)
    draft_tr, dp, dl = train(1, 256, 1024, corpus)
    print(f"trained: target 4L/256d loss {tl:.3f}, draft 1L/256d loss {dl:.3f}")

    prompt = jnp.asarray(corpus[:1, :PROMPT], jnp.int32)
    target = target_tr.decode_model()
    draft = draft_tr.decode_model()

    plain = make_generator(target, max_new_tokens=NEW, temperature=0.0)
    key = jax.random.key(0)
    base = min(timed(plain, tp, prompt, key) for _ in range(ROUNDS))
    agree = agreement(draft, tp, dp, plain, prompt)
    print(f"teacher-forced draft/target agreement: {agree:.2f}")
    print(
        f"plain greedy          {base * 1e3:7.1f} ms/gen  "
        f"{NEW / base:8.0f} tok/s"
    )
    sweep("speculative", target, draft, tp, dp, base, prompt)

    # ---- earned-acceptance regime (VERDICT r3 #3a) ----------------------
    # UNDERTRAINED shallow drafts against the converged target, picked
    # (by a step sweep) to land teacher-forced agreement in the 0.5-0.9
    # band a real draft/target pair lives at: 260 steps -> ~0.5, 330 ->
    # ~0.8 on this corpus. A byte-LM transitions through the band
    # quickly (agreement vs steps: 150->0.27, 200->0.35, 260->0.52,
    # 330->0.81, 420->0.99), and on OFF-distribution prompts the band
    # does not exist at all — the target's own continuation is chaotic
    # there and even a near-converged draft measures ~0.2 agreement
    # (measured; the tail-prompt rows of an earlier revision).
    for label, steps, dm, dff in (
        ("draft-330step", 330, 256, 1024),
        ("draft-260step", 260, 256, 1024),
        ("draft-120step", 120, 256, 1024),
    ):
        u_tr, up, ul = train(1, dm, dff, corpus, steps=steps)
        u_draft = u_tr.decode_model()
        agree_u = agreement(u_draft, tp, up, plain, prompt)
        print(
            f"{label} (1L/{dm}d, loss {ul:.2f}): "
            f"teacher-forced agreement {agree_u:.2f}"
        )
        sweep(f"  {label}", target, u_draft, tp, up, base, prompt)

    # ---- sampling mode (VERDICT r3 #3b) ---------------------------------
    # Rejection-sampling speculative vs plain sampling at the same
    # temperature: the latency story must survive temperature > 0 (the
    # distribution-exactness itself is pinned by the chi-square test).
    temp = 0.8
    plain_s = make_generator(target, max_new_tokens=NEW, temperature=temp)
    base_s = min(timed(plain_s, tp, prompt, key) for _ in range(ROUNDS))
    print(
        f"plain sampling t={temp}  {base_s * 1e3:7.1f} ms/gen  "
        f"{NEW / base_s:8.0f} tok/s"
    )
    for k in (4, 8):
        spec_s = make_speculative_generator(
            target, draft, max_new_tokens=NEW, k=k, temperature=temp,
            return_stats=True,
        )
        dt = min(
            timed(spec_s, tp, dp, prompt, key) for _ in range(ROUNDS)
        )
        _, calls = spec_s(tp, dp, prompt, key)
        calls = int(calls)
        accept = (NEW / max(calls, 1) - 1) / k
        print(
            f"sampling-spec k={k}    {dt * 1e3:7.1f} ms/gen  "
            f"{NEW / dt:8.0f} tok/s  ({base_s / dt:.2f}x)  "
            f"[{calls} target calls, acceptance {accept:.2f}]"
        )


if __name__ == "__main__":
    main()
