"""Speculative decoding: draft-propose, target-verify, exact greedy.

No counterpart exists in the reference (it never runs inference beyond
a float eval loop, ``master/part1/part1.py:47-62``). Motivation from
this repo's own measurements (``docs/kernels.md``, decode rows): small-
model decode is OP-LATENCY-bound — the serial one-token-at-a-time chain,
not bandwidth or FLOPs, sets the wall-clock. Speculative decoding
converts up to ``k`` serial target steps into ONE chunked verification
pass: a cheap draft model proposes ``k`` greedy tokens, the target
scores all of them in a single ``mode="decode"`` chunk (the
``decode_attention`` T>1 path), and the longest agreeing prefix plus
the target's own next token are emitted.

Greedy-exactness: every emitted token is the target's OWN argmax at its
position (draft tokens are only emitted where they EQUAL the target's
argmax at that position in the verification chunk), so the output
matches plain greedy decoding of the target alone — for ANY draft,
including a random one. One honest caveat: the chunked verification
program and the per-token program compute the same math with different
XLA reduction orders, so a near-tie argmax can in principle flip
between them (this is inherent to all speculative implementations; the
parity tests pin agreement empirically).
The draft controls speed only: acceptance rate r gives ~(1 + r*k)
emitted tokens per target dispatch.

Cache bookkeeping: both models write K/V at the positions they feed;
rejected-token cache rows become stale but every position is rewritten
before it is next attended (the following iteration re-feeds from the
first disagreement), and per-row masking in ``decode_attention`` hides
rows beyond each query's own position. Batch is fixed at 1: speculative
decoding is a LATENCY optimization, and per-row acceptance counts would
need per-row cache offsets (scatter writes) that buy nothing for the
latency use case.

The whole generation — draft scans, verification chunks, acceptance
logic — is ONE jitted ``lax.while_loop`` program: zero host round-trips
per token.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from cs744_pytorch_distributed_tutorial_tpu.infer.generate import (
    check_decode_model,
)


def make_speculative_generator(
    target_model: Any,
    draft_model: Any,
    *,
    max_new_tokens: int,
    k: int = 4,
    temperature: float = 0.0,
    eos_id: int | None = None,
    pad_id: int = 0,
    return_stats: bool = False,
):
    """Build a jitted speculative decoder.

    ``temperature == 0.0`` (default): greedy draft-propose /
    target-verify — ``generate(target_params, draft_params, prompt)``,
    output bit-identical to ``make_generator(target_model,
    temperature=0.0)`` on the same params/prompt (pinned in tests).

    ``temperature > 0.0``: REJECTION-SAMPLING speculative decoding
    (Leviathan et al. / Chen et al.) — ``generate(target_params,
    draft_params, prompt, key)``. Each draft token ``x_i ~ q_i``
    (draft softmax at the shared temperature) is accepted with
    probability ``min(1, p_i(x_i) / q_i(x_i))``; the first rejection
    emits from the residual ``norm(max(p_i - q_i, 0))`` and closes the
    window; a fully accepted window emits a bonus token from
    ``p_k``. The emitted sequence is distributed EXACTLY as sampling
    from the target alone at that temperature, for ANY draft — pinned
    by a chi-square distribution test on a tiny vocab
    (tests/test_speculative.py). Temperature only (no top-k/top-p):
    truncation re-normalizes the target distribution, which would
    break the exactness identity the accept ratio is built on.

    ``target_model``/``draft_model`` are decode-configured
    ``TransformerLM``s (``seq_axis=None``; e.g. ``trainer.decode_model()``)
    sharing the vocabulary; ``k`` is the number of draft proposals per
    verification chunk. ``eos_id`` masks everything after
    the first EOS to ``pad_id`` (the loop itself always runs to
    ``max_new_tokens`` — static shapes). ``return_stats=True`` returns
    ``(tokens, target_calls)`` — the number of verification chunks run;
    the realized acceptance rate is
    ``(max_new_tokens/target_calls - 1) / k``.
    """
    check_decode_model(target_model, "speculative decoding")
    check_decode_model(draft_model, "speculative decoding (draft)")
    if target_model.vocab_size != draft_model.vocab_size:
        raise ValueError(
            f"target vocab {target_model.vocab_size} != draft vocab "
            f"{draft_model.vocab_size}"
        )
    if k < 1:
        raise ValueError(f"k must be >= 1, got {k}")
    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
    if temperature < 0.0:
        raise ValueError(f"temperature must be >= 0, got {temperature}")
    if temperature > 0.0:
        return _make_sampling_speculative(
            target_model, draft_model,
            max_new_tokens=max_new_tokens, k=k, temperature=temperature,
            eos_id=eos_id, pad_id=pad_id, return_stats=return_stats,
        )

    def generate(target_params, draft_params, prompt: jax.Array) -> jax.Array:
        b, t0 = prompt.shape
        if b != 1:
            raise ValueError(
                f"speculative decoding is batch-1 (a latency optimization; "
                f"per-row acceptance would need scatter cache writes), got "
                f"batch {b}"
            )
        # The verification chunk reaches position pos-1+k+1; the last
        # full chunk starts at most at t0 + max_new_tokens - 1.
        need = t0 + max_new_tokens + k
        for name, model in (("target", target_model), ("draft", draft_model)):
            if need > model.max_seq_len:
                raise ValueError(
                    f"prompt ({t0}) + max_new_tokens ({max_new_tokens}) + "
                    f"k ({k}) exceeds {name} max_seq_len ({model.max_seq_len})"
                )

        t_logits, t_vars = target_model.apply(
            {"params": target_params}, prompt, mode="prefill", mutable=["cache"]
        )
        d_logits, d_vars = draft_model.apply(
            {"params": draft_params}, prompt, mode="prefill", mutable=["cache"]
        )
        del d_logits  # the draft's prefill only fills its cache
        first_tok = jnp.argmax(t_logits[:, -1], axis=-1)  # [1]

        # Output buffer padded by k+1 so each iteration can write its
        # full candidate window; only `n` counts as emitted.
        out0 = jnp.full((max_new_tokens + k + 1,), pad_id, jnp.int32)
        out0 = out0.at[0].set(first_tok[0].astype(jnp.int32))

        def draft_propose(d_cache, last_tok, pos):
            """Greedy-scan k draft tokens; feeds last_tok at pos first."""

            def body(carry, _):
                cache, tok = carry
                logits, mutated = draft_model.apply(
                    {"params": draft_params, "cache": cache},
                    tok[None, None].astype(jnp.int32),
                    mode="decode",
                    decode_pos=pos + _,
                    mutable=["cache"],
                )
                nxt = jnp.argmax(logits[0, 0], axis=-1).astype(jnp.int32)
                return (mutated["cache"], nxt), nxt

            (cache, last), toks = lax.scan(
                body, (d_cache, last_tok), jnp.arange(k)
            )
            # Also write the FINAL proposal's K/V (row pos+k): it was
            # produced but never fed, and after a full acceptance the
            # next iteration resumes past it — the row would otherwise
            # stay zeros and be attended forever, silently degrading
            # every later draft prediction. One extra draft forward per
            # chunk; its logits are discarded.
            _, mutated = draft_model.apply(
                {"params": draft_params, "cache": cache},
                last[None, None].astype(jnp.int32),
                mode="decode",
                decode_pos=pos + k,
                mutable=["cache"],
            )
            return mutated["cache"], toks  # toks [k]

        def cond(carry):
            n = carry[0]
            return n < max_new_tokens

        def body(carry):
            n, out, last_tok, t_cache, d_cache, iters = carry
            pos = t0 + n - 1  # global position of last_tok
            d_cache, drafts = draft_propose(d_cache, last_tok, pos)
            # Verification chunk: [last_tok, d_0..d_{k-1}] at positions
            # pos..pos+k; logits row i predicts the token AT pos+i+1.
            chunk = jnp.concatenate([last_tok[None], drafts])[None, :]
            v_logits, mutated = target_model.apply(
                {"params": target_params, "cache": t_cache},
                chunk.astype(jnp.int32),
                mode="decode",
                decode_pos=pos,
                mutable=["cache"],
            )
            t_cache = mutated["cache"]
            greedy = jnp.argmax(v_logits[0], axis=-1).astype(jnp.int32)  # [k+1]
            # Longest agreeing prefix: m = #leading i with drafts[i] ==
            # greedy[i]; emit drafts[:m] then greedy[m] — all of them the
            # target's own argmax at their position.
            agree = jnp.cumprod((drafts == greedy[:k]).astype(jnp.int32))
            m = jnp.sum(agree)
            accepted = jnp.where(jnp.arange(k) < m, drafts, pad_id)
            window = jnp.concatenate(
                [accepted, jnp.zeros((1,), jnp.int32)]
            )
            window = window.at[m].set(greedy[m])
            out = lax.dynamic_update_slice(out, window, (n,))
            new_last = greedy[m]
            return (n + m + 1, out, new_last, t_cache, d_cache, iters + 1)

        n, out, _, _, _, iters = lax.while_loop(
            cond,
            body,
            (
                jnp.asarray(1, jnp.int32),
                out0,
                first_tok[0].astype(jnp.int32),
                t_vars["cache"],
                d_vars["cache"],
                jnp.asarray(0, jnp.int32),
            ),
        )
        tokens = out[:max_new_tokens]
        if eos_id is not None:
            seen = jnp.cumsum((tokens == eos_id).astype(jnp.int32))
            after_eos = (seen - (tokens == eos_id).astype(jnp.int32)) > 0
            tokens = jnp.where(after_eos, pad_id, tokens)
        if return_stats:
            return tokens[None, :], iters
        return tokens[None, :]

    return jax.jit(generate)


def _make_sampling_speculative(
    target_model: Any,
    draft_model: Any,
    *,
    max_new_tokens: int,
    k: int,
    temperature: float,
    eos_id: int | None,
    pad_id: int,
    return_stats: bool,
):
    """Rejection-sampling speculative decoding (see
    ``make_speculative_generator``'s temperature>0 contract). Same
    loop/cache structure as the greedy variant; what changes is the
    acceptance rule (probabilistic, against the p/q ratio) and that a
    rejection emits from the RESIDUAL distribution rather than the
    target argmax — the construction that makes the output distribution
    exactly the target's."""
    vocab = target_model.vocab_size
    inv_t = 1.0 / temperature

    def generate(
        target_params, draft_params, prompt: jax.Array, key: jax.Array
    ) -> jax.Array:
        b, t0 = prompt.shape
        if b != 1:
            raise ValueError(
                "speculative decoding is batch-1 (a latency optimization; "
                f"per-row acceptance would need scatter cache writes), got "
                f"batch {b}"
            )
        need = t0 + max_new_tokens + k
        for name, model in (("target", target_model), ("draft", draft_model)):
            if need > model.max_seq_len:
                raise ValueError(
                    f"prompt ({t0}) + max_new_tokens ({max_new_tokens}) + "
                    f"k ({k}) exceeds {name} max_seq_len ({model.max_seq_len})"
                )

        t_logits, t_vars = target_model.apply(
            {"params": target_params}, prompt, mode="prefill", mutable=["cache"]
        )
        _, d_vars = draft_model.apply(
            {"params": draft_params}, prompt, mode="prefill", mutable=["cache"]
        )
        key, k0 = jax.random.split(key)
        first_tok = jax.random.categorical(
            k0, t_logits[0, -1].astype(jnp.float32) * inv_t
        ).astype(jnp.int32)

        out0 = jnp.full((max_new_tokens + k + 1,), pad_id, jnp.int32)
        out0 = out0.at[0].set(first_tok)

        def draft_propose(d_cache, last_tok, pos, key):
            """Sample k draft tokens ~ q (draft softmax at temperature);
            returns the refreshed cache, the tokens, and the FULL q
            distributions [k, V] (the accept ratio and the residual both
            need them)."""

            def body(carry, inputs):
                cache, tok = carry
                i, ki = inputs
                logits, mutated = draft_model.apply(
                    {"params": draft_params, "cache": cache},
                    tok[None, None].astype(jnp.int32),
                    mode="decode",
                    decode_pos=pos + i,
                    mutable=["cache"],
                )
                q = jax.nn.softmax(
                    logits[0, 0].astype(jnp.float32) * inv_t
                )
                nxt = jax.random.categorical(
                    ki, logits[0, 0].astype(jnp.float32) * inv_t
                ).astype(jnp.int32)
                return (mutated["cache"], nxt), (nxt, q)

            keys = jax.random.split(key, k)
            (cache, last), (toks, qs) = lax.scan(
                body, (d_cache, last_tok), (jnp.arange(k), keys)
            )
            # Final proposal's K/V row (same bookkeeping as greedy).
            _, mutated = draft_model.apply(
                {"params": draft_params, "cache": cache},
                last[None, None].astype(jnp.int32),
                mode="decode",
                decode_pos=pos + k,
                mutable=["cache"],
            )
            return mutated["cache"], toks, qs  # [k], [k, V]

        def cond(carry):
            return carry[0] < max_new_tokens

        def body(carry):
            n, out, last_tok, t_cache, d_cache, iters, key = carry
            pos = t0 + n - 1
            key, kd, ka, kr = jax.random.split(key, 4)
            d_cache, drafts, qs = draft_propose(d_cache, last_tok, pos, kd)
            chunk = jnp.concatenate([last_tok[None], drafts])[None, :]
            v_logits, mutated = target_model.apply(
                {"params": target_params, "cache": t_cache},
                chunk.astype(jnp.int32),
                mode="decode",
                decode_pos=pos,
                mutable=["cache"],
            )
            t_cache = mutated["cache"]
            ps = jax.nn.softmax(
                v_logits[0].astype(jnp.float32) * inv_t, axis=-1
            )  # [k+1, V]

            # Accept draft i iff u_i < p_i(x_i) / q_i(x_i); the emitted
            # prefix is the longest ACCEPTED run (cumprod).
            p_tok = jnp.take_along_axis(
                ps[:k], drafts[:, None], axis=-1
            )[:, 0]
            q_tok = jnp.take_along_axis(qs, drafts[:, None], axis=-1)[:, 0]
            u = jax.random.uniform(ka, (k,))
            accept = u < jnp.minimum(1.0, p_tok / jnp.maximum(q_tok, 1e-20))
            m = jnp.sum(jnp.cumprod(accept.astype(jnp.int32)))

            # Closing token: residual norm(max(p_m - q_m, 0)) on a
            # rejection; the bonus row p_k on full acceptance (its
            # "residual vs a zero q" IS p_k, so one padded gather serves
            # both cases).
            qs_pad = jnp.concatenate(
                [qs, jnp.zeros((1, vocab), jnp.float32)]
            )
            resid = jnp.maximum(ps[m] - qs_pad[m], 0.0)
            # An all-accepted-to-numerical-zero residual cannot happen
            # mathematically (sum(max(p-q,0)) = 0 iff p == q, where
            # rejection has probability 0); the epsilon guards the
            # division for float paranoia only.
            resid = resid / jnp.maximum(resid.sum(), 1e-20)
            closing = jax.random.categorical(
                kr, jnp.log(jnp.maximum(resid, 1e-30))
            ).astype(jnp.int32)

            accepted = jnp.where(jnp.arange(k) < m, drafts, pad_id)
            window = jnp.concatenate([accepted, jnp.zeros((1,), jnp.int32)])
            window = window.at[m].set(closing)
            out = lax.dynamic_update_slice(out, window, (n,))
            return (n + m + 1, out, closing, t_cache, d_cache, iters + 1, key)

        n, out, _, _, _, iters, _ = lax.while_loop(
            cond,
            body,
            (
                jnp.asarray(1, jnp.int32),
                out0,
                first_tok,
                t_vars["cache"],
                d_vars["cache"],
                jnp.asarray(0, jnp.int32),
                key,
            ),
        )
        tokens = out[:max_new_tokens]
        if eos_id is not None:
            seen = jnp.cumsum((tokens == eos_id).astype(jnp.int32))
            after_eos = (seen - (tokens == eos_id).astype(jnp.int32)) > 0
            tokens = jnp.where(after_eos, pad_id, tokens)
        if return_stats:
            return tokens[None, :], iters
        return tokens[None, :]

    return jax.jit(generate)
