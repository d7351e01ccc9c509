"""Mixture-of-Experts FFN with expert parallelism via all-to-all.

No counterpart exists in the reference (data parallelism over one dense
VGG-11 is its whole scope, SURVEY §2.3) — this is the expert-parallel
capability that completes the framework's dp/tp/pp/sp/ep strategy set.

Design, TPU-first:

- **Static shapes everywhere.** Token->expert routing is data-dependent,
  which XLA cannot tile; the standard TPU answer is the capacity-slot
  formulation (Switch Transformer / GShard): each expert has a fixed
  number of slots ``C`` and dropped tokens ride the residual. Token
  MOVEMENT into/out of the slots has two implementations
  (``dispatch_impl``): the GShard one-hot einsums, and the round-5
  scatter-add/gather default — measured on a v5e, scatter at one
  global group beats the einsum path's best grouped setting while
  keeping the ungrouped near-zero drop rate (einsum at the same drop
  rate is 2.9x slower; docs/kernels.md).
- **Expert parallelism is one ``lax.all_to_all`` pair.** With experts
  sharded over a mesh axis (here: the ``data`` axis — the standard
  "EP over DP" layout), each device dispatches its local tokens into
  per-expert slot blocks, one tiled all-to-all re-shards
  experts->tokens so every device holds ALL slot blocks for ITS experts,
  the expert FFNs run as one batched einsum over the local expert dim,
  and the inverse all-to-all routes results home. Autodiff through
  ``all_to_all`` transposes to the reverse all-to-all, so cross-device
  gradient routing needs no hand-written backward.
- **Overflow drops to the residual.** Tokens beyond an expert's capacity
  get zero combine weight; the surrounding Block's residual connection
  carries them through unchanged (standard Switch semantics).

The router computes in float32 (softmax numerics), experts in the model
compute dtype (bfloat16 on TPU -> MXU).
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import numpy as np
import jax
import jax.numpy as jnp
from jax import lax


class MoEFFN(nn.Module):
    """Switch/GShard-style top-k routed FFN, optionally expert-parallel.

    Called on ``x [B, T_local, D]``; returns the combined expert outputs
    (zeros for dropped tokens — add to the residual stream). Sows the
    load-balancing auxiliary loss into the ``"losses"`` collection as
    ``moe_aux``.

    With ``expert_axis`` set, the module must be traced inside
    ``shard_map`` with that mesh axis in scope; each device then declares
    only its ``num_experts // expert_axis_size`` local experts' parameters
    (the trainer's partition specs shard the global ``[E, ...]`` arrays
    over the axis). With ``expert_axis=None`` the same code computes all
    experts locally — which also makes host-side ``init`` produce the
    global parameter shapes.
    """

    num_experts: int
    d_ff: int
    top_k: int = 2
    capacity_factor: float = 1.25
    dtype: Any = jnp.float32
    expert_axis: str | None = None
    expert_axis_size: int = 1
    # Token grouping (GShard sec. 3.2 — round 4): routing/capacity and
    # the dispatch/combine one-hot contractions are computed per group
    # of N/G tokens instead of over all N at once. The dispatch einsum
    # costs O(N * E * C * D) with C ~ k*N_group*cf/E, so G groups cut it
    # G-fold — at N=16k tokens/device the ungrouped formulation measured
    # 4.8x slower than a FLOPs-matched dense FFN
    # (docs/kernels.md). Capacity (and hence drop decisions)
    # becomes per-group — num_groups is part of the routing semantics,
    # not just a performance knob. 0 = auto: target ~1024 tokens/group.
    num_groups: int = 1
    # Token movement implementation (round 5, VERDICT r4 #6 — the
    # 1.41x residual routed-vs-dense tax lived in the dispatch/combine
    # one-hot einsums). "einsum" and "scatter" share routing, priority,
    # capacity and drop semantics (the same cumsum-derived slot
    # positions drive both); only how tokens reach their slots differs:
    # - "einsum": dense [G,N,E,C] dispatch/combine one-hot contractions
    #   (MXU work, O(N*E*C*D) per group — the GShard formulation);
    # - "scatter": scatter-add tokens into [G,E,C,D] slot buffers and
    #   gather+weight the outputs back (O(N*K*D) per group — the
    #   sort-free equivalent of sort-based/ragged dispatch; AD
    #   transposes scatter<->gather, so gradients route for free).
    # - "dropless": NO capacity — megablocks-style semantics. Tokens
    #   argsort by expert into contiguous ragged groups (static shapes,
    #   dynamic counts) and the expert FFN runs as two grouped matmuls
    #   (``ops/gmm.py``: lax.ragged_dot or the Pallas gmm kernel, per
    #   ``gmm_impl``). Every routed token computes — ``moe_drop`` is
    #   identically 0; non-default ``capacity_factor``/``num_groups``
    #   are REJECTED (capacity semantics do not exist here).
    #   Does NOT compose with ``expert_axis``: EP's all_to_all
    #   needs static per-destination counts, which is exactly what
    #   capacity slots buy — dropless + EP would reintroduce them.
    dispatch_impl: str = "scatter"
    # Grouped-matmul backend for dispatch_impl="dropless": "pallas"
    # (the megablox-style kernels with the bias/gelu epilogues FUSED —
    # measured 1.13x over ragged_dot in-model on a v5e; XLA cannot
    # fuse elementwise chains into a custom call, the epilogue
    # restores what ragged_dot gets from fusion and then wins),
    # "ragged" (XLA's lax.ragged_dot), or "auto" (default): pallas on
    # TPU, ragged where kernels would run in interpret mode (CPU
    # tests — interpreted kernels are orders slower).
    gmm_impl: str = "auto"
    gmm_block_m: int = 256
    gmm_block_n: int = 512
    # None = interpret Pallas kernels off-TPU (ops/_backend.py).
    gmm_interpret: Any = None
    # Gated experts: out = w_out(silu(w_gate x) * w_in x), the SwiGLU
    # form of the llama-family dense MLP, a third [E, D, d_ff] matrix an
    # expert. On the dropless path only (what serves them: no capacity,
    # so no token is dropped).
    gated: bool = False
    # Expert biases b_in / b_out; False declares neither.
    use_bias: bool = True
    # A chip's SHARE of the experts (expert parallelism without its
    # exchange): the ids, of the ``num_experts`` routed ones, whose
    # matrices this layer holds. The router keeps its full width and its
    # ``top_k``; the layer computes the held experts' terms for the
    # tokens that chose them and leaves out what the absent experts
    # would add (the shares of all chips sum to the whole layer,
    # tests/test_latent_moe.py). None = every expert is held. Dropless
    # path only; under ``expert_axis`` (which shares the experts out
    # itself) it raises.
    held_experts: tuple | None = None
    # Zero-compute experts: this many more router outputs, behind the
    # routed ones, whose "expert" is the identity: a token that chooses
    # one gets ``w * x``, with no weights and no matmul. They run where
    # the token lives, so every share computes them.
    zero_experts: int = 0
    # top-k weights divided by their sum (today's behaviour) or left as
    # the softmax gave them, then times ``routed_scale``.
    renormalize: bool = True
    routed_scale: float = 1.0
    # A bias on the CHOICE only (a ``choice_bias`` parameter, one a
    # router output): experts are the top-k of ``scores + bias``, their
    # weights are ``scores`` without it.
    choice_bias: bool = False
    # Group-limited choice (DeepSeek-V2's ``group_limited_greedy``): the
    # routed experts form ``n_group`` contiguous groups, a group scores
    # its best expert's score, a token keeps its ``topk_group`` best
    # groups and takes its ``top_k`` experts among theirs; the weights
    # are the experts' own scores. 1 = one group, plain top-k.
    n_group: int = 1
    topk_group: int = 1
    # Shared experts: a SwiGLU of this width (``n_shared_experts`` times
    # the experts' width, as one) that every token goes through, its
    # output added to the routed sum; every share computes it, where the
    # token lives. 0 = none. Dropless gated path only.
    shared_d_ff: int = 0

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        b, t, d = x.shape
        # the router's width: routed experts, then zero-compute ones
        e = self.num_experts + self.zero_experts
        k = self.top_k
        shared_out = self.held_experts is not None or self.zero_experts > 0
        if k < 1 or k > e:
            raise ValueError(f"top_k {k} must be in [1, {e}]")
        if self.dispatch_impl not in ("einsum", "scatter", "dropless"):
            raise ValueError(
                f"unknown dispatch_impl {self.dispatch_impl!r}; "
                "choose 'einsum', 'scatter' or 'dropless'"
            )
        dropless = self.dispatch_impl == "dropless"
        ep = self.expert_axis is not None and self.expert_axis_size > 1
        if dropless and ep:
            raise ValueError(
                "dispatch_impl='dropless' does not compose with "
                "expert_axis: EP's all_to_all needs static per-"
                "destination counts (capacity slots); use 'scatter' or "
                "'einsum' for expert-parallel layouts"
            )
        if dropless and (self.capacity_factor != 1.25 or self.num_groups != 1):
            # Same reject-don't-drop rule as the expert_axis case: a
            # non-default capacity/grouping request on the capacity-free
            # path would silently train different routing semantics than
            # asked (dropless has no capacity and exactly one group).
            raise ValueError(
                "dispatch_impl='dropless' ignores capacity_factor and "
                f"num_groups (got capacity_factor={self.capacity_factor}, "
                f"num_groups={self.num_groups}); leave them at the "
                "defaults (1.25, 1) or use 'scatter'/'einsum' for "
                "capacity-based routing"
            )
        if shared_out and not dropless:
            raise ValueError(
                "held_experts / zero_experts run on dispatch_impl="
                f"'dropless' only, got {self.dispatch_impl!r}: a capacity "
                "slot belongs to an expert that is computed here"
            )
        if self.held_experts is not None and self.expert_axis is not None:
            raise ValueError(
                "held_experts is one chip's share without the exchange; "
                "expert_axis shares the experts out itself. Set one"
            )
        if self.held_experts is not None and not (
            self.held_experts
            and len(set(self.held_experts)) == len(self.held_experts)
            and all(0 <= i < self.num_experts for i in self.held_experts)
        ):
            raise ValueError(
                f"held_experts {self.held_experts} must be distinct ids of "
                f"the {self.num_experts} routed experts, at least one"
            )
        if self.gated and not dropless:
            raise ValueError(
                "gated experts run on dispatch_impl='dropless' only, got "
                f"{self.dispatch_impl!r}"
            )
        if self.shared_d_ff and not (dropless and self.gated):
            raise ValueError(
                "shared experts are a SwiGLU beside gated experts on "
                f"dispatch_impl='dropless' (got {self.dispatch_impl!r}, "
                f"gated={self.gated})"
            )
        if self.n_group != 1 or self.topk_group != 1:
            per = self.num_experts // max(self.n_group, 1)
            if (
                self.n_group < 1 or self.num_experts % self.n_group
                or not 1 <= self.topk_group <= self.n_group
                or k > self.topk_group * per
            ):
                raise ValueError(
                    f"group-limited choice needs n_group ({self.n_group}) "
                    f"to divide the {self.num_experts} routed experts and "
                    f"1 <= topk_group ({self.topk_group}) <= n_group, with "
                    f"top_k ({k}) no more than the kept groups' experts"
                )
            if self.zero_experts or self.choice_bias:
                raise ValueError(
                    "group-limited choice is built over routed experts "
                    "alone, by their scores: no zero-compute experts and "
                    "no choice_bias"
                )
        if e % (self.expert_axis_size if ep else 1):
            raise ValueError(
                f"num_experts {e} not divisible by expert axis "
                f"{self.expert_axis_size}"
            )
        e_local = e // self.expert_axis_size if ep else e
        if shared_out:  # the matrices held here
            e_local = (
                self.num_experts if self.held_experts is None
                else len(self.held_experts)
            )
        n_total = b * t
        g = self.num_groups
        if g < 0:
            raise ValueError(f"num_groups must be >= 0, got {g}")
        if dropless:
            g = 1  # grouping exists to bound capacity; dropless has none
        elif g == 0:  # auto: ~1024 tokens per group
            g = max(1, n_total // 1024)
        # Effective groups: the largest divisor of N at most the request
        # — a decode/prefill call (N as small as 1) must not trip over a
        # training-time group count, and a non-divisor request degrades
        # predictably instead of erroring (capacity semantics follow the
        # EFFECTIVE count; training shapes are chosen divisible).
        g = min(g, n_total)
        while n_total % g:
            g -= 1
        n = n_total // g  # tokens per group
        # Fixed slots per expert PER GROUP; ceil so tiny test batches
        # still route at least one token per expert.
        capacity = max(1, int(-(-(k * n * self.capacity_factor) // e)))

        tokens = x.reshape(g, n, d)

        # ---- router (float32 end-to-end) --------------------------------
        logits = nn.Dense(
            e, use_bias=False, dtype=jnp.float32, param_dtype=jnp.float32,
            # the top-k choice is discrete: no bf16 pass over the logits
            # that make it (a [D, E] matmul; the cost is nothing)
            precision=lax.Precision.HIGHEST,
            name="router",
        )(tokens.astype(jnp.float32))
        gates = jax.nn.softmax(logits, axis=-1)  # [G, N, E]
        kept_groups = None
        if self.n_group > 1:
            # a group's score is its best expert's; the experts of the
            # groups a token does not keep score 0 and are not chosen
            per = e // self.n_group
            _, top_groups = lax.top_k(
                gates.reshape(g, n, self.n_group, per).max(-1), self.topk_group
            )
            kept_groups = jnp.sum(
                jax.nn.one_hot(top_groups, self.n_group, dtype=jnp.int32), -2
            ) > 0  # [G, N, n_group]
            topk_gate, topk_idx = lax.top_k(
                jnp.where(jnp.repeat(kept_groups, per, axis=-1), gates, 0.0), k
            )
        elif self.choice_bias:
            bias = self.param(
                "choice_bias", nn.initializers.zeros_init(), (e,), jnp.float32
            )
            _, topk_idx = lax.top_k(gates + bias, k)
            topk_gate = jnp.take_along_axis(gates, topk_idx, axis=-1)
        else:
            topk_gate, topk_idx = lax.top_k(gates, k)  # [G, N, K]
        if k > 1 and self.renormalize:
            topk_gate = topk_gate / jnp.maximum(
                topk_gate.sum(-1, keepdims=True), 1e-9
            )
        if self.routed_scale != 1.0:
            topk_gate = topk_gate * self.routed_scale

        # Load-balancing aux loss (Switch eq. 4): experts should see equal
        # token fractions f_e and equal mean router mass P_e. Computed
        # over ALL tokens (group-invariant — grouping changes capacity,
        # not the router's objective).
        top1 = jax.nn.one_hot(topk_idx[..., 0], e, dtype=jnp.float32)
        aux = e * jnp.sum(
            top1.reshape(-1, e).mean(0) * gates.reshape(-1, e).mean(0)
        )
        self.sow("losses", "moe_aux", aux)

        # Telemetry: normalized entropy of the per-expert token-load
        # fractions (1.0 = balanced, 0.0 = collapse). Sown into
        # "metrics" — NOT "losses", which moe_aux_loss() sums blindly.
        from cs744_pytorch_distributed_tutorial_tpu.obs.metrics import (
            expert_load_entropy,
        )

        self.sow(
            "metrics",
            "moe_load_entropy",
            expert_load_entropy(top1.reshape(-1, e).mean(0)),
        )

        # ---- expert parameters (shared by every dispatch path) ----------
        init = nn.initializers.lecun_normal()
        w_in = self.param("w_in", init, (e_local, d, self.d_ff))
        w_out = self.param("w_out", init, (e_local, self.d_ff, d))
        if self.gated:
            w_gate = self.param("w_gate", init, (e_local, d, self.d_ff))
        if self.use_bias:
            b_in = self.param(
                "b_in", nn.initializers.zeros_init(), (e_local, self.d_ff)
            )
            b_out = self.param(
                "b_out", nn.initializers.zeros_init(), (e_local, d)
            )
        else:
            b_in = jnp.zeros((e_local, self.d_ff), jnp.float32)
            b_out = jnp.zeros((e_local, d), jnp.float32)

        if dropless:
            # ---- dropless: sort by expert, ragged grouped matmuls -------
            # Every routed (token, k) pair computes — no capacity, no
            # drops. argsort is stable, so within an expert tokens keep
            # batch order (irrelevant to math, deterministic for tests).
            from cs744_pytorch_distributed_tutorial_tpu.ops._backend import (
                default_interpret,
            )
            from cs744_pytorch_distributed_tutorial_tpu.ops.gmm import (
                fit_block_n,
                grouped_matmul,
            )

            interpret = (
                default_interpret()
                if self.gmm_interpret is None
                else bool(self.gmm_interpret)
            )
            gmm_impl = self.gmm_impl
            if gmm_impl == "auto":
                gmm_impl = "ragged" if interpret else "pallas"
            p_tot = n_total * k

            def expert_ffn(xs, group_sizes, sorted_e, p_rows, live=None):
                """The experts' FFN over pair rows sorted by expert: rows
                of group ``g`` times expert ``g``'s matrices. ``p_rows``
                is as many rows as a row tile need hold (all the pairs,
                or what a group is expected to have). ``live [rows, 1]``
                says that the groups fill only the rows it marks (a
                chip's share): the kernel then visits no tile past them
                and leaves their output unwritten."""
                # A row tile no taller than the pairs there are (a decode
                # step routes a few dozen); the column tile is the
                # kernel's to fit (ops/gmm.py).
                block_m = min(
                    self.gmm_block_m, max(32, 1 << (p_rows - 1).bit_length())
                )

                def block_n(rhs):
                    return fit_block_n(
                        *rhs.shape[-2:], block_m, self.gmm_block_n,
                        jnp.dtype(self.dtype).itemsize,
                    )

                if gmm_impl == "pallas" and self.use_bias and not self.gated:
                    # Fused-epilogue kernels: the per-group bias (and gelu)
                    # ride inside the gmm — XLA cannot fuse elementwise
                    # chains into a Pallas custom call, so the unfused
                    # kernel pays an extra [P, d_ff] HBM round-trip the
                    # ragged_dot path does not (ops/gmm.py).
                    from cs744_pytorch_distributed_tutorial_tpu.ops.gmm import (
                        grouped_matmul_fused,
                    )

                    fused = lambda lhs, rhs, b, act: grouped_matmul_fused(
                        lhs,
                        rhs,
                        b,
                        group_sizes,
                        activation=act,
                        block_m=block_m,
                        block_n=block_n(rhs),
                        interpret=interpret,
                    )
                    h = fused(xs, w_in.astype(self.dtype), b_in, "gelu")
                    out = fused(
                        h.astype(self.dtype), w_out.astype(self.dtype),
                        b_out, "none",
                    )
                else:
                    gmm = lambda lhs, rhs: grouped_matmul(
                        lhs,
                        rhs,
                        group_sizes,
                        impl=gmm_impl,
                        block_m=block_m,
                        block_n=block_n(rhs),
                        interpret=interpret,
                        live_only=live is not None,
                    )
                    h = gmm(xs, w_in.astype(self.dtype))
                    if self.use_bias:
                        h = h + b_in[sorted_e].astype(h.dtype)
                    if self.gated:
                        # unfused: the gate's product is an XLA elementwise
                        # over two [P, d_ff] kernel outputs
                        h = nn.silu(gmm(xs, w_gate.astype(self.dtype))) * h
                    else:
                        h = nn.gelu(h)
                    if live is not None:
                        # unwritten rows are no operand of the next matmul
                        h = jnp.where(live, h, 0)
                    out = gmm(h.astype(self.dtype), w_out.astype(self.dtype))
                    if self.use_bias:
                        out = out + b_out[sorted_e].astype(out.dtype)
                return out

            if shared_out:
                y = self._shared_out(
                    tokens.reshape(n_total, d), topk_idx.reshape(n_total, k),
                    topk_gate.reshape(n_total, k), expert_ffn,
                    None if kept_groups is None
                    else kept_groups.reshape(n_total, self.n_group),
                ).reshape(b, t, d)
                return self._with_shared_experts(y, x)
            expert_flat = topk_idx.reshape(p_tot)
            order = jnp.argsort(expert_flat, stable=True)
            sorted_e = expert_flat[order]
            group_sizes = jnp.bincount(expert_flat, length=e)
            tok_ids = order // k  # pair -> owning token row
            xs = tokens.reshape(n_total, d)[tok_ids].astype(self.dtype)
            # The serving engine's counters read the routing (a no-op
            # unless "serve_stats" is asked for).
            if not self.is_initializing():
                self.sow(
                    "serve_stats", "expert_idx", topk_idx.reshape(n_total, k)
                )
            out = expert_ffn(xs, group_sizes, sorted_e, p_tot)
            self.sow("metrics", "moe_drop", jnp.float32(0.0))
            gate_flat = topk_gate.reshape(p_tot)[order].astype(out.dtype)
            y = (
                jnp.zeros((n_total, d), out.dtype)
                .at[tok_ids]
                .add(out * gate_flat[:, None])
            )
            return self._with_shared_experts(
                y.reshape(b, t, d).astype(self.dtype), x
            )

        # ---- capacity-slot assignment (static shapes, per group) --------
        # Priority: rank-0 choices of every token beat rank-1 choices
        # (k-major cumsum order), so top-1 routes are the last to drop.
        onehot = jax.nn.one_hot(topk_idx, e, dtype=jnp.float32)  # [G, N, K, E]
        flat = onehot.transpose(0, 2, 1, 3).reshape(g, k * n, e)
        pos = (jnp.cumsum(flat, axis=1) - 1.0).reshape(g, k, n, e)
        pos_k = (pos.transpose(0, 2, 1, 3) * onehot).sum(-1)  # [G, N, K]
        keep = (pos_k < capacity).astype(jnp.float32)
        # Observability (VERDICT r3 #6): fraction of top-k routes that
        # overflowed capacity and fell to the residual. Sown into the
        # separate "metrics" collection — "losses" feeds the objective
        # (moe_aux_loss sums ALL its leaves), a monitoring value must
        # not. Callers that pass mutable=["metrics"] receive it; others
        # (the pipeline stage fn) silently drop it, by flax's contract.
        self.sow("metrics", "moe_drop", 1.0 - keep.mean())
        scatter = self.dispatch_impl == "scatter"
        if scatter:
            # ---- scatter tokens into expert slot blocks -----------------
            # Each kept (token, k) pair owns exactly one slot (the
            # cumsum positions are unique per expert), so the
            # scatter-add never accumulates and is order-independent;
            # dropped pairs write to the out-of-bounds slot C and are
            # discarded by mode="drop".
            g_ar = jnp.arange(g)[:, None, None]
            pos_i = pos_k.astype(jnp.int32)
            slot_pos = jnp.where(keep > 0, pos_i, capacity)
            buf = jnp.zeros((g, e, capacity, d), self.dtype)
            buf = buf.at[g_ar, topk_idx, slot_pos].add(
                jnp.broadcast_to(
                    tokens.astype(self.dtype)[:, :, None, :], (g, n, k, d)
                ),
                mode="drop",
            )
            expert_in = buf.transpose(1, 0, 2, 3).reshape(
                e, g * capacity, d
            )  # [E, G*C, D]
        else:
            routed = onehot * keep[..., None]  # [G, N, K, E]
            slot = jax.nn.one_hot(
                pos_k.astype(jnp.int32), capacity, dtype=jnp.float32
            )  # [G, N, K, C]
            dispatch = jnp.einsum("gnke,gnkc->gnec", routed, slot)
            combine = jnp.einsum(
                "gnk,gnke,gnkc->gnec", topk_gate, routed, slot
            )

            # ---- gather tokens into expert slot blocks (MXU einsum) -----
            expert_in = jnp.einsum(
                "gnec,gnd->egcd",
                dispatch.astype(self.dtype),
                tokens.astype(self.dtype),
            ).reshape(e, g * capacity, d)  # [E, G*C, D]

        if ep:
            # Re-shard experts -> tokens: every device ends up with the
            # slot blocks of ITS e_local experts from ALL axis peers.
            expert_in = lax.all_to_all(
                expert_in, self.expert_axis, split_axis=0, concat_axis=1,
                tiled=True,
            )  # [E_local, S*G*C, D]

        # ---- batched expert FFN -----------------------------------------
        h = jnp.einsum(
            "ecd,edf->ecf", expert_in, w_in.astype(self.dtype)
        ) + b_in[:, None, :].astype(self.dtype)
        h = nn.gelu(h)
        out = jnp.einsum(
            "ecf,efd->ecd", h, w_out.astype(self.dtype)
        ) + b_out[:, None, :].astype(self.dtype)

        if ep:
            out = lax.all_to_all(
                out, self.expert_axis, split_axis=1, concat_axis=0, tiled=True
            )  # back to [E, G*C, D], slots owned by this device's tokens

        # ---- scatter back + weight by gate ------------------------------
        out = out.reshape(e, g, capacity, d)
        if scatter:
            # Gather each (token, k) pair's slot output and weight by
            # its (kept) gate — O(N*K*D); the gather's AD transpose is
            # the scatter-add that routes d out.
            out_g = out.transpose(1, 0, 2, 3)  # [G, E, C, D]
            g_ar = jnp.arange(g)[:, None, None]
            picked = out_g[
                g_ar, topk_idx, jnp.clip(pos_i, 0, capacity - 1)
            ]  # [G, N, K, D]
            w = (topk_gate * keep).astype(self.dtype)
            y = (picked * w[..., None]).sum(axis=2)
        else:
            y = jnp.einsum(
                "gnec,egcd->gnd", combine.astype(self.dtype), out
            )
        return y.reshape(b, t, d)


    def _with_shared_experts(self, y, x):
        """``y`` plus the shared experts' SwiGLU of ``x`` (``shared_d_ff``
        wide); ``y`` itself where there are none."""
        if not self.shared_d_ff:
            return y
        dense = lambda f, name: nn.Dense(
            f, use_bias=False, dtype=self.dtype, name=name
        )
        h = nn.silu(dense(self.shared_d_ff, "shared_gate")(x)) * dense(
            self.shared_d_ff, "shared_in"
        )(x)
        return y + dense(x.shape[-1], "shared_out")(h).astype(y.dtype)

    def _shared_out(self, x, idx, gate, expert_ffn, kept_groups=None):
        """The dropless layer of a chip that holds a SHARE of the routed
        experts, and of a router with zero-compute experts: ``x [N, D]``,
        each token's ``idx`` / ``gate`` ``[N, K]`` over the router's whole
        width -> ``[N, D]``: the held experts' terms for the tokens that
        chose them, plus ``w * x`` for every zero-compute expert chosen;
        the terms of routed experts held elsewhere are left out.
        ``kept_groups [N, n_group]`` (group-limited choice) is what the
        counter of tokens whose kept groups hold a held expert reads.

        Pairs sort by where their expert is, the held ones first (by
        local index), and the grouped matmuls visit only the row tiles
        the held pairs fill (``live_only``, ops/gmm.py): a chunk's other
        rows cost no matmul and an expert no token chose streams no
        weights. What XLA does around the kernels (the rows' gather, the
        gate's product, the scatter back) runs over ``p_cap`` rows: four
        times the pairs an even router sends to the held experts, the
        rule of the row tile's height. No pair is ever dropped: a second
        branch of the same program, over all the pair rows, serves the
        step in which more were drawn."""
        n, k = idx.shape
        routed = self.num_experts
        held = (
            tuple(range(routed)) if self.held_experts is None
            else tuple(self.held_experts)
        )
        # router output -> index among the held matrices; -1 = not here
        local_of = np.full((routed + self.zero_experts,), -1, np.int32)
        local_of[list(held)] = np.arange(len(held))
        local = jnp.asarray(local_of)[idx]  # [N, K]
        is_zero = idx >= routed
        is_held = local >= 0
        if not self.is_initializing():
            # what the serving engine's counters read (a no-op unless
            # "serve_stats" is asked for): the held experts' hits, and
            # each token's pairs by where their expert is
            self.sow("serve_stats", "expert_idx", local)
            self.sow("serve_stats", "held_expert_pairs", is_held.sum(-1))
            self.sow("serve_stats", "zero_expert_pairs", is_zero.sum(-1))
            self.sow(
                "serve_stats", "absent_expert_pairs",
                (~is_held & ~is_zero).sum(-1),
            )
            if kept_groups is not None:
                # tokens whose kept groups include one that holds a held
                # expert (3 of 8 groups kept: 3/8 under an even router)
                held_group = np.zeros((self.n_group,), bool)
                held_group[[i // (routed // self.n_group) for i in held]] = True
                self.sow(
                    "serve_stats", "held_group_tokens",
                    jnp.any(kept_groups & held_group, axis=-1).astype(jnp.int32),
                )
        p_tot = n * k
        flat = jnp.where(is_held, local, len(held)).reshape(p_tot)
        order = jnp.argsort(flat, stable=True)
        group_sizes = jnp.bincount(flat, length=len(held) + 1)[: len(held)]
        n_held = group_sizes.sum()
        gate_flat = gate.reshape(p_tot)
        # a row tile as tall as four times what an expert expects of an
        # even router, and as many rows as that for each held expert
        rows_a_group = max(1, 4 * p_tot // (routed + self.zero_experts))

        def held_terms(rows):
            take = order[:rows]
            tok_ids = take // k
            # rows past the held pairs: the kernel writes none of them
            live = (jnp.arange(rows) < n_held)[:, None]
            # inside ``lax.cond`` a call is named after its branch: the
            # scope gives the grouped matmuls the module's name back
            with jax.named_scope("moe"):
                out = expert_ffn(
                    x[tok_ids].astype(self.dtype), group_sizes,
                    jnp.minimum(flat[take], len(held) - 1), rows_a_group,
                    live,
                )
            out = jnp.where(live, out * gate_flat[take][:, None].astype(out.dtype), 0)
            return jnp.zeros((n, x.shape[-1]), out.dtype).at[tok_ids].add(out)

        p_cap = min(p_tot, max(32, -(-rows_a_group * len(held) // 32) * 32))
        if p_cap < p_tot:
            y = lax.cond(
                n_held <= p_cap, lambda: held_terms(p_cap),
                lambda: held_terms(p_tot),
            )
        else:
            y = held_terms(p_tot)
        self.sow("metrics", "moe_drop", jnp.float32(0.0))
        if self.zero_experts:
            w_zero = jnp.sum(jnp.where(is_zero, gate, 0.0), axis=-1)
            y = y + w_zero[:, None].astype(y.dtype) * x.astype(y.dtype)
        return y.astype(self.dtype)


def moe_aux_loss(mutated_variables) -> jnp.ndarray:
    """Sum every sown ``moe_aux`` value (one per MoE layer) from the
    ``"losses"`` collection returned by ``apply(..., mutable=["losses"])``."""
    losses = mutated_variables.get("losses", {})
    leaves = jax.tree_util.tree_leaves(losses)
    if not leaves:
        return jnp.float32(0.0)
    return sum(leaves)
