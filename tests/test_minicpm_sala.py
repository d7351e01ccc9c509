"""MiniCPM-SALA (``model_type: minicpm_sala``) through the normal path:
the whole model against the plain reference (perfbench/reference/
minicpm_sala.py); the lightning layer's three forms (the whole sequence,
the chunk, the one-token recurrence; the Pallas kernels interpreted)
against its definition, the fastest-decaying head at a chunk of 512
among them; the block selection against a brute force written here,
with ties and the ``dense_len`` boundary; the engine's served tokens by
chunks and decode (gather and the kernels), a reused slot and a
preempted request against a fresh engine, the counters; the config builder
and its refusals; each planted fault of the reference; and a model without
the new kinds building what it always built.

Tolerances: float32 on the CPU, the two sides differing in the order of
their sums only, so 1e-4 on logits of unit size (the tiny model's
logits are N(0, 1)), 1e-5 relative on the lightning forms against a
float64 definition; a bfloat16 state or a decay left out moves them by
more (``test_a_bf16_state_or_no_decay_fails_the_forms_tolerance``, and
the faults' own test).
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cs744_pytorch_distributed_tutorial_tpu.models import (
    TransformerLM,
    minicpm_sala_model_config,
    model_config_from_hf,
)
from cs744_pytorch_distributed_tutorial_tpu.models.hf_interop import lightning_rates
from cs744_pytorch_distributed_tutorial_tpu.ops import block_sparse as B
from cs744_pytorch_distributed_tutorial_tpu.ops import lightning as L
from cs744_pytorch_distributed_tutorial_tpu.serve import Request, ServeConfig, ServingEngine
from perfbench.reference import minicpm_sala as R
from perfbench.work_minicpm_sala import as_published

from minicpm_sala_tiny import build, model_kwargs, tiny_config

ROOT = Path(__file__).resolve().parents[1]
PUBLISHED = json.loads((ROOT / "perfbench/configs/minicpm-sala.json").read_text())


@pytest.fixture(scope="module")
def tiny():
    cfg = tiny_config()
    return (cfg, *build(cfg))


# ---- the model ----------------------------------------------------------------------

@pytest.mark.parametrize("length", [1, 17, 70, 150])
def test_the_whole_model_s_logits_against_the_reference_s(tiny, length):
    """Two block-sparse layers (selecting past position 48) and two
    lightning layers, the muP scalars, at contexts past dense_len."""
    cfg, model, params, flat = tiny
    toks = np.asarray(jax.random.randint(jax.random.key(length), (length,), 0, 256))
    got = model.apply({"params": params}, toks[None])[0]
    want = R.forward(flat, toks, cfg)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4
    assert float(jnp.mean(jnp.abs(want))) > 0.3


def test_the_blocks_hold_each_kind_s_parameters(tiny):
    _, model, params, _ = tiny
    assert model.layer_types == (
        "block_sparse_attention", "lightning_attention", "block_sparse_attention", "lightning_attention")
    common = ["attn_out", "gate", "k", "k_norm", "q", "q_norm", "v"]
    assert sorted(params["block_0"]["attn"]) == common
    assert sorted(params["block_1"]["attn"]) == sorted(common + ["o_norm"])
    assert params["block_0"]["attn"]["k"]["kernel"].shape == (64, 2 * 32)  # 2 KV heads
    assert params["block_1"]["attn"]["k"]["kernel"].shape == (64, 4 * 32)  # every head its own
    assert params["block_1"]["attn"]["o_norm"]["scale"].shape == (128,)  # over every head
    assert sorted(params["block_2"]) == ["attn", "ln_attn", "ln_ffn", "mlp_gate", "mlp_in", "mlp_out"]


@pytest.mark.parametrize("fault", R.FAULTS)
def test_every_planted_fault_moves_the_reference_s_logits(tiny, fault):
    """Each fault moves a logit past the tolerance the model is held to
    (1e-4) by far: the decay left out, the state rounded to bfloat16,
    the output gates left out, the last positions in the selection's
    place, the window not forced, the muP scalars left out."""
    cfg, _, _, flat = tiny
    toks = np.asarray(jax.random.randint(jax.random.key(1), (150,), 0, 256))
    moved = float(jnp.max(jnp.abs(R.forward(flat, toks, cfg, fault=fault) - R.forward(flat, toks, cfg))))
    assert moved > 1e-3, fault


# ---- the lightning layer's forms --------------------------------------------------

T, H, D = 1100, 4, 32
# the published layer 1's fastest and slowest heads, and two between
RATES = np.asarray(lightning_rates(32, 1, 32), np.float32)[[0, 7, 20, 31]]


def _qkv(seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], (T, H, D)) / np.sqrt(D)
    return q, jax.random.normal(ks[1], (T, H, D)), jax.random.normal(ks[2], (T, H, D))


def _definition(q, k, v, rates):
    """``o_t = sum_{s <= t} lam^(t - s) (q_t . k_s) v_s`` in float64."""
    q, k, v = (np.asarray(x, np.float64) for x in (q, k, v))
    t = np.arange(q.shape[0])
    lag = (t[:, None] - t[None, :]).astype(np.float64)
    w = np.where(lag >= 0, np.exp(-np.asarray(rates, np.float64)[:, None, None] * np.maximum(lag, 0)), 0.0)
    return np.einsum("hts,ths,shd->thd", w, np.einsum("thd,shd->ths", q, k), v)


def _close(got, want, rel=1e-5):
    want = np.asarray(want)
    err = np.max(np.abs(np.asarray(got, np.float64) - want))
    return err <= rel * np.max(np.abs(want)), err


def _chunks_then_decode(q, k, v, rates, kernel, chunk=512, decode_from=1024):
    """Chunks of ``chunk`` to ``decode_from``, then one token at a time,
    a slot's state row carried in a table of two slots."""
    rates = jnp.asarray(rates)
    state = jnp.zeros((2, H, D, D), jnp.float32)
    outs = []
    for off in range(0, decode_from, chunk):
        n = min(chunk, decode_from - off)
        pad = lambda x: jnp.pad(x[off:off + n], ((0, chunk - n), (0, 0), (0, 0)))  # noqa: E731
        if kernel:
            o, state = L.lightning_chunk(pad(q), pad(k), pad(v), state, rates, 1, off, n, interpret=True)
        else:
            prev = jnp.where(off == 0, 0.0, state[1])
            o, new = L.chunk_reference(pad(q), pad(k), pad(v), prev, rates, n)
            state = state.at[1].set(new)
        outs.append(o[:n])
    step = jax.jit(
        (lambda *a: L.lightning_decode(*a, interpret=True)) if kernel else L.decode_reference
    )
    live = jnp.asarray([0, 1])
    for t in range(decode_from, q.shape[0]):
        two = lambda x: jnp.stack([jnp.zeros_like(x[t]), x[t]])  # noqa: E731
        o, state = step(two(q), two(k), two(v), state, rates, live)
        outs.append(o[1:])
    return jnp.concatenate(outs)


@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "pallas"])
def test_the_lightning_forms_agree_with_the_definition(kernel):
    """The whole-sequence form (blocks of 256), chunks of 512 then the
    one-token recurrence, all against the definition in float64. The
    fastest head decays by exp(-0.81) a position: its mask over a chunk
    of 512 reaches exp(-414), which a ratio of powers would overflow."""
    q, k, v = _qkv()
    want = _definition(q, k, v, RATES)
    ok, err = _close(L.full_forward(q, k, v, jnp.asarray(RATES)), want)
    assert ok, err
    ok, err = _close(_chunks_then_decode(q, k, v, RATES, kernel), want)
    assert ok, err
    assert RATES[0] > 0.8 and np.exp(-RATES[0] * 511) == 0.0


def test_a_bf16_state_or_no_decay_fails_the_forms_tolerance():
    """What the tolerance above catches: the state rounded to bfloat16
    after every token, or the decay left out of the recurrence."""
    q, k, v = _qkv(1)
    want = _definition(q, k, v, RATES)
    s = jnp.zeros((H, D, D))
    lam = jnp.exp(-jnp.asarray(RATES))[:, None, None]
    rounded = []
    for t in range(T):
        s = (lam * s + k[t][:, :, None] * v[t][:, None, :]).astype(jnp.bfloat16).astype(jnp.float32)
        rounded.append(jnp.einsum("hi,hij->hj", q[t], s))
    assert not _close(jnp.stack(rounded), want)[0]
    assert not _close(L.full_forward(q, k, v, jnp.zeros(H)), want)[0]


def test_the_slot_s_row_starts_from_zero_at_position_zero():
    """A chunk at offset 0 reads its row as zero whatever it holds, and
    writes only that row."""
    q, k, v = (x[:16] for x in _qkv(2))
    stale = jax.random.normal(jax.random.key(9), (3, H, D, D))
    rates = jnp.asarray(RATES)
    o, new = L.lightning_chunk(q, k, v, stale, rates, 2, 0, 16, interpret=True)
    want_o, want_s = L.chunk_reference(q, k, v, jnp.zeros((H, D, D)), rates, 16)
    np.testing.assert_allclose(o, want_o, atol=1e-5)
    np.testing.assert_allclose(new[2], want_s, atol=1e-5)
    np.testing.assert_array_equal(new[:2], stale[:2])


# ---- the selection ----------------------------------------------------------------

SP = model_kwargs(tiny_config())["block_sparse"]  # the config builder's reading of SPARSE


def _brute_selection(q, ckeys, t, sp):
    """Block ids a query at ``t`` attends, a KV group each, in plain
    Python loops over every kernel and block."""
    h, d = q.shape
    g = ckeys.shape[1]
    out = []
    for gi in range(g):
        heads = range(gi * h // g, (gi + 1) * h // g)
        kernels = [i for i in range(ckeys.shape[0]) if i * sp.kernel_stride + sp.kernel_size - 1 <= t]
        rel = np.zeros(ckeys.shape[0])
        for hi in heads:
            if not kernels:
                break
            logits = np.array([q[hi] @ ckeys[i, gi] / np.sqrt(d) for i in kernels], np.float64)
            p = np.exp(logits - logits.max())
            rel[kernels] += p / p.sum()
        blocks = [b for b in range(-(-ckeys.shape[0] * sp.kernel_stride // sp.block_size))
                  if b * sp.block_size <= t]
        if t < sp.dense_len:
            out.append(sorted(blocks))
            continue
        score = {}
        for b in blocks:
            touching = [i for i in range(ckeys.shape[0])
                        if i * sp.kernel_stride <= (b + 1) * sp.block_size - 1
                        and i * sp.kernel_stride + sp.kernel_size - 1 >= b * sp.block_size]
            score[b] = max(rel[i] for i in touching)
        forced = {b for b in blocks if b < sp.init_blocks
                  or b * sp.block_size + sp.block_size - 1 >= t - sp.window + 1}
        rest = sorted((b for b in blocks if b not in forced), key=lambda b: (-score[b], b))
        out.append(sorted(forced | set(rest[: sp.topk - len(forced)])))
    return out


@pytest.mark.parametrize("tie", [False, True], ids=["random", "ties"])
def test_the_selection_against_brute_force(tie):
    """Queries at every position around dense_len (47 dense, 48 the
    first that selects) and past it; with every compressed key the same,
    every score ties and the lower blocks win."""
    rng = np.random.default_rng(3)
    p, g, h, d = 40, 2, 4, 32
    ckeys = rng.normal(size=(p, g, d)).astype(np.float32)
    if tie:
        ckeys[:] = ckeys[:1]
    ts = np.asarray([0, 5, 30, 46, 47, 48, 49, 60, 100, 150])
    q = rng.normal(size=(len(ts), h, d)).astype(np.float32) * 2
    ids, count, scored = B.select(
        jnp.asarray(q)[None], jnp.asarray(ckeys)[None], jnp.asarray(ts)[None], SP, d ** -0.5
    )
    nblk = -(-p * SP.kernel_stride // SP.block_size)
    for j, t in enumerate(ts):
        want = _brute_selection(q[j], ckeys, int(t), SP)
        for gi in range(g):
            got = [int(b) for b in ids[0, j, gi] if b < nblk]
            assert got == want[gi], (t, gi)
            assert int(count[0, j, gi]) == len(want[gi])
            if t >= SP.dense_len:
                assert len(got) == SP.topk
        n_whole = max(0, (t - SP.kernel_size + 1) // SP.kernel_stride + 1)
        assert int(scored[0, j]) == (n_whole if t >= SP.dense_len else 0)


def test_the_reference_selects_as_the_brute_force():
    """The plain reference's selection (a running sum, a scatter of
    kernel scores into blocks, a stable sort) against the loops above."""
    rng = np.random.default_rng(4)
    t_len, g, h, d = 160, 2, 4, 32
    k = jnp.asarray(rng.normal(size=(t_len, g, d)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(t_len, h, d)), jnp.float32) * 2
    s = R.sizes(tiny_config())
    s["seq"] = t_len
    kc = R._kernel_means(k, s)
    pos = jnp.arange(t_len)
    chosen = np.asarray(R._chosen(q, kc, pos, s, None))
    for t in (40, 47, 48, 77, 159):
        want = _brute_selection(np.asarray(q[t]), np.asarray(kc), t, SP)
        for gi in range(g):
            assert list(np.nonzero(chosen[t, gi])[0]) == want[gi], (t, gi)


# (offset, real length, planted) of a chunk of 32 over a slot of 160 pages
# of 4 (five key blocks of 128 positions): at position 0, below dense_len,
# past it, a short last chunk, and a selection planted so that the first
# tile chose nothing in key block 2 and no tile anything in key block 1
WALKS = {
    "offset_0": (0, 32, False), "dense": (8, 32, False), "sparse": (400, 32, False),
    "short_last": (448, 13, False), "planted_gaps": (420, 32, True),
}


@pytest.mark.parametrize("case", WALKS)
def test_the_chunk_walk_matches_the_xla_form(case, monkeypatch):
    """``block_sparse_chunk_attention`` (interpreted) against
    ``chunk_attention`` over the same selection, in tiles of 8 queries
    and key blocks of 128 positions. The kernel's pools are NaN wherever
    it must not read: the trash page 0, every page past the chunk's last
    real position and every page off the slot's row; its padding rows
    come back 0."""
    offset, length, planted = WALKS[case]
    c, h, g, d, ps, cap, tile = 32, 4, 2, 32, 4, 160, 8
    monkeypatch.setattr(B, "_CHUNK_ROWS", tile * h // g)
    per = B._CHUNK_KEY_BLOCKS
    rng = np.random.default_rng(7)
    num_pages = cap + 9
    table = 1 + rng.permutation(num_pages - 1)[:cap]
    kp, vp = (rng.normal(size=(num_pages, ps, g * d)).astype(np.float32) for _ in range(2))
    q = jnp.asarray(rng.normal(size=(c, h, d)), jnp.float32)
    pos = offset + np.arange(c)
    ckeys = jnp.asarray(rng.normal(size=(1, cap, g, d)), jnp.float32)
    ids, _, _ = B.select(q[None], ckeys, jnp.asarray(pos)[None], SP, d ** -0.5)
    nblk = cap * ps // SP.block_size
    allowed = np.array(B.allowed_blocks(ids[0], nblk))
    if planted:
        allowed[:tile, :, 2 * per:3 * per] = False
        allowed[:, :, per:2 * per] = False
    want = B.chunk_attention(q, jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(table),
                             jnp.asarray(pos), jnp.asarray(allowed), SP, d ** -0.5)
    last_page = (offset + length - 1) // ps
    unread = [0] + [int(table[i]) for i in range(last_page + 1, cap)]
    unread += sorted(set(range(num_pages)) - set(table.tolist()))
    kp, vp = kp.copy(), vp.copy()  # the XLA form may still read the arrays it was given
    kp[unread], vp[unread] = np.nan, np.nan
    got = np.asarray(B.block_sparse_chunk_attention(
        q, jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(table), jnp.int32(offset),
        jnp.int32(length), jnp.asarray(allowed), SP, d ** -0.5, interpret=True,
    ))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got[:length], np.asarray(want)[:length], atol=2e-6, rtol=1e-5)
    assert (got[length:] == 0).all()


# ---- served by chunks and decode ---------------------------------------------------

LENGTHS = ((70, 12), (23, 9), (130, 20), (9, 5), (64, 8))
SERVE = dict(num_slots=3, page_size=4, num_pages=200, max_pages_per_slot=48, prefill_chunk=16)


def _serve(model, params, lengths=LENGTHS, seed=0, **cfg):
    engine = ServingEngine(model, params, ServeConfig(**{**SERVE, **cfg}))
    rng = np.random.default_rng(seed)
    reqs = [
        engine.submit(Request(prompt=rng.integers(0, 256, n).astype(np.int32), max_new_tokens=m))
        for n, m in lengths
    ]
    engine.run()
    return engine, reqs


def _served_gap(flat, cfg, req):
    """The widest gap, over the answer, between the reference's best
    logit and the served token's."""
    seq = np.concatenate([req.prompt, np.asarray(req.generated, np.int32)])
    lo, hi = req.orig_prompt_len - 1, len(seq) - 1
    ref = R.forward(flat, seq, cfg, at=np.arange(lo, hi))
    served = jnp.asarray(seq[lo + 1: hi + 1])
    return float(jnp.max(jnp.max(ref, -1) - jnp.take_along_axis(ref, served[:, None], -1)[:, 0]))


@pytest.fixture(scope="module")
def served(tiny):
    _, model, params, _ = tiny
    return _serve(model, params, paged_attention_impl="gather")


def test_chunks_then_decode_serve_the_reference_s_tokens(tiny, served):
    cfg, _, _, flat = tiny
    engine, reqs = served
    assert all(r.status == "completed" and len(r.generated) == m for r, (_, m) in zip(reqs, LENGTHS))
    for r in reqs:
        assert _served_gap(flat, cfg, r) < 1e-4
    assert engine.stats()["prefill_chunks"] == sum(-(-n // 16) for n, _ in LENGTHS)


def test_the_kernels_serve_the_same_tokens(tiny, served):
    """The lightning chunk and decode kernels and the block-sparse walks,
    decode and chunk (interpret mode), serve the gather path's tokens."""
    cfg, _, params, flat = tiny
    model, _, _ = build(cfg, flash_interpret=True)
    engine, reqs = _serve(model, params, paged_attention_impl="kernel")
    assert [list(r.generated) for r in reqs] == [list(r.generated) for r in served[1]]
    assert _served_gap(flat, cfg, reqs[2]) < 1e-4


def _plant(monkeypatch, fault):
    """A fault in the served state path (the "gather" path's chunk and
    decode forms): the state row stored in bfloat16, or no decay."""
    for name in ("chunk_reference", "decode_reference"):
        form = getattr(L, name)
        if fault == "bf16_state":
            def planted(*a, _form=form):
                o, state = _form(*a)
                return o, state.astype(jnp.bfloat16).astype(jnp.float32)
        else:
            def planted(q, k, v, state, rate, *rest, _form=form):
                return _form(q, k, v, state, rate * 0.0, *rest)
        monkeypatch.setattr(L, name, planted)


@pytest.mark.parametrize("fault", [None, "bf16_state", "no_decay"])
def test_the_kept_logits_are_the_reference_s(tiny, fault, monkeypatch):
    """The logits a decode step samples from (``keep_logits``), over
    slots whose state rows and pages chunks and earlier steps wrote, are
    the reference's at the same positions (float32 at this size, to
    1e-4); a state row stored in bfloat16, or left undecayed, moves them
    past that."""
    cfg, model, params, flat = tiny
    if fault:
        _plant(monkeypatch, fault)
    engine = ServingEngine(model, params, ServeConfig(**SERVE, paged_attention_impl="gather"))
    engine.keep_logits()
    rng = np.random.default_rng(4)
    reqs = [
        engine.submit(Request(prompt=rng.integers(0, 256, n).astype(np.int32), max_new_tokens=m))
        for n, m in LENGTHS
    ]
    kept = {r.req_id: [] for r in reqs}
    while engine.busy:
        before = {r.req_id: r.output_tokens for r in reqs}
        engine.step()
        for r in reqs:
            row = engine.last_logit_rows.get(r.req_id)
            if row is not None and r.output_tokens == before[r.req_id] + 1:
                kept[r.req_id].append((len(r.generated) - 1, np.asarray(engine.last_logits[row])))
    # every token but the admission's two (its chunk's and its first step's)
    assert sum(map(len, kept.values())) == sum(m - 2 for _, m in LENGTHS)
    dev = 0.0
    for r in reqs:
        seq = np.concatenate([r.prompt, np.asarray(r.generated, np.int32)])
        ref = np.asarray(R.forward(flat, seq, cfg, at=np.arange(r.orig_prompt_len - 1, len(seq) - 1)))
        dev = max([dev] + [float(np.abs(logits - ref[m]).max()) for m, logits in kept[r.req_id]])
    assert (dev < 1e-4) == (fault is None), dev


def test_the_state_rows_and_the_pools(served):
    engine, _ = served
    leaves = {
        "/".join(p.key for p in path): leaf.shape
        for path, leaf in jax.tree_util.tree_leaves_with_path(engine._pages)
    }
    assert leaves == {
        "block_0/attn/key_pages": (200, 4, 64), "block_0/attn/value_pages": (200, 4, 64),
        "block_0/attn/compressed_key_pages": (200, 64),
        "block_1/attn/lightning_state": (3, 4, 32, 32),
        "block_2/attn/key_pages": (200, 4, 64), "block_2/attn/value_pages": (200, 4, 64),
        "block_2/attn/compressed_key_pages": (200, 64),
        "block_3/attn/lightning_state": (3, 4, 32, 32),
    }
    assert engine._pages["block_1"]["attn"]["lightning_state"].dtype == jnp.float32
    assert engine.pool.check_invariants() and engine.pool.allocated_pages == 0


def test_a_reused_slot_serves_what_a_fresh_engine_serves(tiny):
    """One slot, three requests in turn: each starts on the state row its
    predecessor left, and serves what it serves alone."""
    _, model, params, _ = tiny
    lengths = ((90, 6), (40, 7), (75, 5))
    _, together = _serve(model, params, lengths, num_slots=1, paged_attention_impl="gather")
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 256, n).astype(np.int32) for n, _ in lengths]
    for (n, m), prompt, got in zip(lengths, prompts, together):
        alone = ServingEngine(model, params, ServeConfig(**{**SERVE, "num_slots": 1}))
        req = alone.submit(Request(prompt=prompt, max_new_tokens=m))
        alone.run()
        assert list(got.generated) == list(req.generated)


def test_a_preempted_request_serves_what_a_fresh_engine_serves(tiny):
    """A pool too small for two long requests: the younger is preempted
    (its pages and its state row given up) and recomputed from position
    0, and serves what a fresh engine serves it."""
    cfg, model, params, flat = tiny
    lengths = ((100, 40), (100, 40))
    engine, reqs = _serve(
        model, params, lengths, num_slots=2, num_pages=61, paged_attention_impl="gather"
    )
    assert engine.stats()["preemptions"] >= 1 and any(r.preemptions for r in reqs)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 256, n).astype(np.int32) for n, _ in lengths]
    for (n, m), prompt, got in zip(lengths, prompts, reqs):
        alone = ServingEngine(model, params, ServeConfig(**{**SERVE, "num_slots": 1}))
        req = alone.submit(Request(prompt=prompt, max_new_tokens=m))
        alone.run()
        assert list(got.prompt[got.orig_prompt_len:]) + list(got.generated) == list(req.generated)
    assert _served_gap(flat, cfg, reqs[1]) < 1e-4


def test_the_counters(tiny):
    """Behind the step's tokens, no new transfer: (slot, layer) state
    updates, and over the block-sparse layers and KV groups the
    positions live, the positions attended (all of them below dense_len,
    at most topk blocks past it) and the compressed keys scored (every
    whole kernel, past dense_len only)."""
    _, model, params, _ = tiny
    engine = ServingEngine(model, params, ServeConfig(**SERVE, paged_attention_impl="gather"))
    prompts = (21, 60, 100)
    rng = np.random.default_rng(5)
    for n in prompts:
        engine.submit(Request(prompt=rng.integers(0, 256, n).astype(np.int32), max_new_tokens=4))
    engine.run()
    stats = engine.stats()
    assert engine._counter_names == (
        "selected_tokens", "scored_tokens", "experts_hit", "expert_ratio_milli",
        "lightning_state_updates", "sparse_selected_tokens", "sparse_live_tokens", "sparse_scored_kernels",
    )
    depths = [n + i for n in prompts for i in range(3)]  # three decode steps a request
    g, layers = 2, 2
    assert stats["lightning_state_updates"] == layers * len(depths)
    assert stats["sparse_live_tokens"] == layers * g * sum(t + 1 for t in depths)
    whole = [(t - SP.kernel_size + 1) // SP.kernel_stride + 1 for t in depths if t >= SP.dense_len]
    assert stats["sparse_scored_kernels"] == layers * g * sum(whole)
    dense = layers * g * sum(t + 1 for t in depths if t < SP.dense_len)
    sparse_live = layers * g * sum(t + 1 for t in depths if t >= SP.dense_len)
    cap = layers * g * SP.topk * SP.block_size * sum(t >= SP.dense_len for t in depths)
    assert dense < stats["sparse_selected_tokens"] <= dense + min(cap, sparse_live)
    assert stats["sparse_selected_tokens"] < stats["sparse_live_tokens"]


# ---- the config builder ----------------------------------------------------------------------

def test_the_builder_reads_the_published_keys():
    published, kept = as_published(PUBLISHED)
    assert kept == (0, 1, 2, 3, 9, 10, 11, 12) and published["num_hidden_layers"] == 32
    kw = model_config_from_hf(published, layer_ids=kept)
    assert kw == minicpm_sala_model_config(published, layer_ids=kept)
    assert (kw["num_layers"], kw["num_heads"], kw["num_kv_heads"], kw["head_dim"]) == (8, 32, 2, 128)
    assert (kw["d_model"], kw["d_ff"], kw["vocab_size"], kw["max_seq_len"]) == (4096, 16384, 73448, 524288)
    assert kw["layer_types"] == (("block_sparse_attention",) + ("lightning_attention",) * 3) * 2
    assert kw["block_sparse"] == B.BlockSparse(32, 16, 64, 2048, 64, 1, 8192)
    assert kw["embed_scale"] == 12.0 and kw["logit_scale"] == 256 / 4096
    assert kw["residual_scale"] == pytest.approx(1.4 / np.sqrt(32))
    assert kw["norm_eps"] == 1e-6 and kw["rope_base"] == 10000.0 and kw["tie_embeddings"] is False
    # a lightning layer's heads decay by its published index: layer 10 of 32
    rates = kw["lightning_rates"][5]
    assert rates[0] == pytest.approx(2 ** -0.25 * (1 - 10 / 31 + 1e-5))
    assert rates[31] == pytest.approx(2 ** -8 * (1 - 10 / 31 + 1e-5))
    assert kw["lightning_rates"][0] is None and kw["lightning_rates"][4] is None
    # the file as it stands (8 layers, no cut) builds too, each layer its own index of 8
    whole = minicpm_sala_model_config(PUBLISHED)
    assert whole["num_layers"] == 8 and whole["lightning_rates"][1][0] == pytest.approx(
        2 ** -0.25 * (1 - 1 / 7 + 1e-5))


@pytest.mark.parametrize("change, reason", [
    (dict(attn_use_rope=True), "attn_use_rope"),
    (dict(lightning_use_rope=False), "lightning_use_rope"),
    (dict(qk_norm=False), "qk_norm"),
    (dict(use_output_gate=False), "use_output_gate"),
    (dict(use_output_norm=False), "use_output_norm"),
    (dict(attn_use_output_gate=False), "attn_use_output_gate"),
    (dict(lightning_nkv=8), "lightning heads"),
    (dict(lightning_scale="1/d"), "lightning_scale"),
    (dict(mixer_types=["mamba"] * 32), "mixer_types"),
    (dict(hidden_act="gelu"), "hidden_act"),
    (dict(attention_bias=True), "attention_bias"),
    (dict(tie_word_embeddings=True), "tied embeddings"),
    (dict(num_attention_heads=24, lightning_nh=24, lightning_nkv=24), "power of two"),
])
def test_the_builder_refuses_what_is_not_built(change, reason):
    with pytest.raises(ValueError, match=reason):
        minicpm_sala_model_config({**as_published(PUBLISHED)[0], **change})


@pytest.mark.parametrize("overrides, reason", [
    (dict(quant_kv_cache=True), "pools, state and kernels are float"),
    (dict(tensor_axis="model", tensor_axis_size=2), "one device"),
    (dict(scan_layers=True), "built unrolled"),
    (dict(num_experts=4), "dense SwiGLU"),
    (dict(lightning_rates=None), "lightning_rates"),
    (dict(block_sparse=None), "block_sparse"),
    (dict(norm="layernorm"), "norm='rmsnorm'"),
    (dict(layer_types=("block_sparse_attention", "full_attention") * 2), "layer_types"),
])
def test_each_unbuilt_combination_raises_with_its_reason(overrides, reason):
    model = TransformerLM(**{**model_kwargs(tiny_config()), **overrides})
    with pytest.raises(ValueError, match=reason):
        model.init(jax.random.key(0), jnp.zeros((1, 4), jnp.int32))


def test_the_new_options_belong_to_the_new_layers():
    with pytest.raises(ValueError, match="belong to"):
        TransformerLM(vocab_size=16, num_layers=1, num_heads=2, d_model=8, d_ff=8,
                      residual_scale=0.5).init(jax.random.key(0), jnp.zeros((1, 4), jnp.int32))


def test_the_engine_refuses_the_one_shot_prefill(tiny):
    _, model, params, _ = tiny
    with pytest.raises(ValueError, match="served by chunks"):
        ServingEngine(model, params, ServeConfig(num_slots=2, page_size=4, num_pages=9, max_pages_per_slot=4))


def test_a_model_without_the_new_kinds_builds_what_it_built():
    """In a fresh process: an engine over a model without lightning or
    block-sparse layers imports none of their modules, declares no state
    row, and its chunk program's packed argument has no slot."""
    code = (
        "import sys, jax, jax.numpy as jnp\n"
        "from cs744_pytorch_distributed_tutorial_tpu.models import TransformerLM\n"
        "from cs744_pytorch_distributed_tutorial_tpu.serve import ServeConfig, ServingEngine\n"
        "m = TransformerLM(vocab_size=64, num_layers=2, num_heads=2, d_model=32, d_ff=64,"
        " max_seq_len=64, attention_impl='dense', use_rope=True)\n"
        "p = m.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))['params']\n"
        "e = ServingEngine(m, p, ServeConfig(num_slots=2, page_size=4, num_pages=9,"
        " max_pages_per_slot=4, prefill_chunk=8))\n"
        "names = {k.key for path, _ in jax.tree_util.tree_leaves_with_path(e._pages) for k in path[-1:]}\n"
        "mods = [n for n in sys.modules if n.split('.')[-1] in ('lightning', 'block_sparse', 'hybrid')]\n"
        "print(sorted(names), mods, e._slot_state, e._chunk_scalars(), e._program_arg_len(8, 4))\n"
    )
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(ROOT)}
    out = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True, text=True,
                         timeout=300, check=True).stdout.strip().splitlines()[-1]
    assert out == "['key_pages', 'value_pages'] [] False 4 16"
