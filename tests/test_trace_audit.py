"""graftcheck trace-audit tests.

Three layers:

1. **TA003 sweep** — every ``--sync`` strategy (CIFAR) and every LM data
   -parallel mode is traced on the 8-virtual-device CPU harness and its
   collective schedule + bytes-on-wire are checked against the contract
   model in :mod:`parallel.sync` and the telemetry accounting in
   :func:`parallel.sync.sync_wire_bytes`.
2. **Seeded regressions** — hand-built step functions with an injected
   f32 upcast, a dropped donation, a giant trace constant, and a dead
   matmul must each be flagged by exactly the intended rule.
3. **Contract tests** — registry, suppressions, CLI exit codes, and the
   clean-repo gate (auditing the real registered entrypoints finds
   nothing).

Tracing uses ``jax.make_jaxpr`` only, so the sweep is cheap; only the
donation tests compile (tiny shapes).
"""

from __future__ import annotations

import json
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cs744_pytorch_distributed_tutorial_tpu.analysis.trace import (
    TracedStep,
    get_entrypoints,
    load_builtin_entrypoints,
    register_entrypoint,
)
from cs744_pytorch_distributed_tutorial_tpu.analysis.trace.audits import (
    TRACE_RULES,
    audit_entry,
    run_audits,
)
from cs744_pytorch_distributed_tutorial_tpu.analysis.trace.cli import (
    main as trace_cli_main,
)
from cs744_pytorch_distributed_tutorial_tpu.analysis.trace import jaxpr_utils
from cs744_pytorch_distributed_tutorial_tpu.analysis.trace.registry import (
    _REGISTRY,
)

ALL_RULES = set(TRACE_RULES)
TRACE_ONLY = ALL_RULES - {"TA002"}  # TA002 lowers+compiles; the rest trace


@pytest.fixture(autouse=True)
def _registry_guard():
    """Tests register throwaway entrypoints; restore the registry after."""
    before = dict(_REGISTRY)
    yield
    _REGISTRY.clear()
    _REGISTRY.update(before)


def entry_for(step: TracedStep, name: str):
    register_entrypoint(name, lambda: step)
    return get_entrypoints([name])[0]


def audit(step: TracedStep, rules=TRACE_ONLY, name: str = "fixture"):
    findings, _info = audit_entry(entry_for(step, name), set(rules))
    return findings


# ============================================ the collective-name table
def _replicated_weight_grad(x):
    """Gradient of a replicated weight: the all-reduce nobody writes
    (``sync="auto"``). Under ``check_vma`` the AD transpose of the
    weight's ``pvary`` binds it; without, nothing does."""
    w = jnp.ones((4,), x.dtype)
    return x + jax.grad(lambda w: (x * w).sum())(w)


_RING4 = [(i, (i + 1) % 4) for i in range(4)]

#: lax call -> (two-line shard_map body, canonical class); every body
#: maps a [4, 4] shard to a [4, 4] shard so one spec serves all
COLLECTIVE_BODIES = {
    "psum": (lambda x: jax.lax.psum(x, "data"), "psum"),
    "pmean": (lambda x: jax.lax.pmean(x, "data"), "psum"),
    "pmax": (lambda x: jax.lax.pmax(x, "data"), "pmax"),
    "pmin": (lambda x: jax.lax.pmin(x, "data"), "pmin"),
    "all_gather": (
        lambda x: jax.lax.all_gather(x, "data", tiled=True)[:4],
        "all_gather",
    ),
    "psum_scatter": (
        lambda x: jnp.tile(
            jax.lax.psum_scatter(x, "data", tiled=True), (4, 1)
        ),
        "reduce_scatter",
    ),
    "ppermute": (lambda x: jax.lax.ppermute(x, "data", _RING4), "ppermute"),
    "all_to_all": (
        lambda x: jax.lax.all_to_all(x, "data", 0, 0, tiled=True),
        "all_to_all",
    ),
    "grad_of_replicated": (_replicated_weight_grad, "psum"),
    # no public name in jax 0.9.0, but a primitive that moves bytes
    "all_gather_invariant": (
        lambda x: jax._src.lax.parallel.all_gather_invariant(
            x, "data", tiled=True
        )[:4],
        "all_gather",
    ),
}


@pytest.mark.parametrize("check_vma", [True, False])
@pytest.mark.parametrize("call", list(COLLECTIVE_BODIES))
def test_collective_table_knows_what_jax_binds(call, check_vma, mesh4):
    """Whatever primitive this jax binds for each ``lax`` collective,
    with the replication checker on and off, ``COLLECTIVE_CLASS`` folds
    it to the right class. A rename upstream (0.9.0 binds
    ``psum_invariant`` for ``lax.psum`` under ``check_vma``) fails here
    by name, not as audits that silently see no collectives."""
    from jax.sharding import PartitionSpec as P

    body, cls = COLLECTIVE_BODIES[call]
    fn = jax.shard_map(
        body, mesh=mesh4, in_specs=P("data"), out_specs=P("data"),
        check_vma=check_vma,
    )
    closed = jax.make_jaxpr(fn)(jnp.ones((16, 4), jnp.float32))
    found = jaxpr_utils.collect_collectives(closed, dict(mesh4.shape))
    if call == "grad_of_replicated" and not check_vma:
        expected = []  # local gradients: the manual strategies' start
    else:
        expected = [cls]
    bound = sorted(
        {e.primitive.name for e, _ in jaxpr_utils.iter_eqns(closed.jaxpr)}
    )
    assert [c.cls for c in found] == expected, f"{call} binds {bound}"
    assert all(c.group_size == 4 and not c.trivial for c in found)


# =================================================== TA003 schedule sweep
CIFAR_SYNCS = [
    "allreduce",
    "ring",
    "int8_allreduce",
    "zero1",
    "fsdp",
    "gather_scatter",
    "p2p_star",
    "auto",
]


@pytest.mark.parametrize("sync", CIFAR_SYNCS)
def test_ta003_cifar_schedule_matches_contract(sync, devices):
    from cs744_pytorch_distributed_tutorial_tpu.train.engine import (
        make_trace_entry,
    )

    step = make_trace_entry(sync=sync)
    closed = jax.make_jaxpr(step.fn)(*step.args)
    colls = jaxpr_utils.collect_collectives(closed, step.axis_sizes)
    counts = jaxpr_utils.schedule_counts(colls)
    assert step.expected_schedule is not None
    expected = {k: v for k, v in step.expected_schedule.items() if v}
    assert counts == expected, f"{sync}: {counts} != {expected}"

    wire = jaxpr_utils.total_wire_bytes(colls)
    assert step.expected_wire_bytes is not None
    tol = max(0.01 * step.expected_wire_bytes, 512.0)
    assert abs(wire - step.expected_wire_bytes) <= tol, (
        f"{sync}: jaxpr wire {wire} vs accounting "
        f"{step.expected_wire_bytes}"
    )


OVERLAP_CONFIGS = [
    ("allreduce", "bucket", {}),
    ("ring", "bucket", {}),
    ("int8_allreduce", "bucket+int8", {}),
    ("zero1", "bucket", {}),
    ("fsdp", "bucket", {}),
    ("zero1", "bucket+int8", {"grad_compress": "int8"}),
]


@pytest.mark.parametrize("sync,overlap,extra", OVERLAP_CONFIGS)
def test_ta003_overlapped_schedule_matches_contract(
    sync, overlap, extra, devices
):
    """The overlapped bucket schedule (--sync-overlap) keeps TA003's
    contract byte-exact: the same collective classes and wire bytes as
    the fused bucketed wire, just placed per reverse-order bucket
    (sync_units/sync_wire_bytes count the reverse layout when
    overlap=True). Covers the sharded schedules too: zero1/fsdp run the
    per-bucket psum_scatter -> chunk apply -> all_gather chain, and
    zero1+int8 swaps each scatter for the quantized allreduce
    (2 all_to_alls + 2 all_gathers per unit, plus the delta gather)."""
    from cs744_pytorch_distributed_tutorial_tpu.train.engine import (
        make_trace_entry,
    )

    step = make_trace_entry(sync=sync, sync_overlap=overlap, **extra)
    closed = jax.make_jaxpr(step.fn)(*step.args)
    colls = jaxpr_utils.collect_collectives(closed, step.axis_sizes)
    counts = jaxpr_utils.schedule_counts(colls)
    assert step.expected_schedule is not None
    expected = {k: v for k, v in step.expected_schedule.items() if v}
    assert counts == expected, f"{sync}+{overlap}: {counts} != {expected}"

    wire = jaxpr_utils.total_wire_bytes(colls)
    tol = max(0.01 * step.expected_wire_bytes, 512.0)
    assert abs(wire - step.expected_wire_bytes) <= tol, (
        f"{sync}+{overlap}: jaxpr wire {wire} vs accounting "
        f"{step.expected_wire_bytes}"
    )
    if overlap == "bucket":
        # Float wires: overlap changes WHERE the collectives sit, not
        # how many bytes move — fused and overlapped accounting agree
        # exactly. (int8 exempt: reverse bucketing regroups the
        # quantization chunks, shifting per-bucket padding slightly.)
        fused = make_trace_entry(sync=sync)
        assert step.expected_wire_bytes == fused.expected_wire_bytes


LM_OVERLAP_MODES = {
    "dp-sgd": (dict(optimizer="sgd"), "bucket"),
    "zero1": (dict(zero1=True), "bucket"),
    "fsdp": (dict(fsdp=True), "bucket"),
    "zero1-int8": (dict(zero1=True, grad_compress="int8"), "bucket+int8"),
}


@pytest.mark.parametrize("mode", sorted(LM_OVERLAP_MODES))
def test_ta003_lm_overlapped_schedule(mode, devices):
    """LM overlap sweep: pure-DP SGD plus the sharded schedules (which
    admit any registry optimizer — these trace the default AdamW)."""
    from cs744_pytorch_distributed_tutorial_tpu.train.lm import (
        make_lm_trace_entry,
    )

    kw, overlap = LM_OVERLAP_MODES[mode]
    step = make_lm_trace_entry(sync_overlap=overlap, **kw)
    closed = jax.make_jaxpr(step.fn)(*step.args)
    colls = jaxpr_utils.collect_collectives(closed, step.axis_sizes)
    counts = jaxpr_utils.schedule_counts(colls)
    expected = {k: v for k, v in step.expected_schedule.items() if v}
    assert counts == expected, f"lm-{mode}: {counts} != {expected}"
    wire = jaxpr_utils.total_wire_bytes(colls)
    tol = max(0.01 * step.expected_wire_bytes, 512.0)
    assert abs(wire - step.expected_wire_bytes) <= tol, (
        f"lm-{mode}: jaxpr wire {wire} vs accounting "
        f"{step.expected_wire_bytes}"
    )


def test_ta003_int8_wire_beats_f32(devices):
    from cs744_pytorch_distributed_tutorial_tpu.train.engine import (
        make_trace_entry,
    )

    def jaxpr_wire(sync):
        step = make_trace_entry(sync=sync)
        closed = jax.make_jaxpr(step.fn)(*step.args)
        return jaxpr_utils.total_wire_bytes(
            jaxpr_utils.collect_collectives(closed, step.axis_sizes)
        )

    f32 = jaxpr_wire("allreduce")
    int8 = jaxpr_wire("int8_allreduce")
    assert 0 < int8 < f32, (int8, f32)


LM_MODES = {
    "allreduce": {},
    "int8": {"grad_compress": "int8"},
    "zero1": {"zero1": True},
    "fsdp": {"fsdp": True},
}


@pytest.mark.parametrize("mode", sorted(LM_MODES))
def test_ta003_lm_schedule_matches_contract(mode, devices):
    from cs744_pytorch_distributed_tutorial_tpu.train.lm import (
        make_lm_trace_entry,
    )

    step = make_lm_trace_entry(**LM_MODES[mode])
    closed = jax.make_jaxpr(step.fn)(*step.args)
    colls = jaxpr_utils.collect_collectives(closed, step.axis_sizes)
    counts = jaxpr_utils.schedule_counts(colls)
    assert step.expected_schedule is not None
    expected = {k: v for k, v in step.expected_schedule.items() if v}
    assert counts == expected, f"{mode}: {counts} != {expected}"

    wire = jaxpr_utils.total_wire_bytes(colls)
    tol = max(0.01 * step.expected_wire_bytes, 512.0)
    assert abs(wire - step.expected_wire_bytes) <= tol, (
        f"{mode}: jaxpr wire {wire} vs accounting "
        f"{step.expected_wire_bytes}"
    )


def test_ta003_flags_schedule_mismatch(mesh4):
    """A step whose contract promises ring but runs allreduce is caught."""

    def psum_step(x):
        return jax.shard_map(
            lambda v: jax.lax.psum(v, "data"),
            mesh=mesh4,
            in_specs=jax.sharding.PartitionSpec("data"),
            out_specs=jax.sharding.PartitionSpec(),
        )(x)

    step = TracedStep(
        name="mismatch",
        fn=psum_step,
        args=(jnp.zeros((4, 128), jnp.float32),),
        axis_sizes={"data": 4},
        expected_schedule={"ppermute": 6},
        check_donation=False,
    )
    findings = audit(step, rules={"TA003"})
    assert [f.rule for f in findings] == ["TA003"]
    assert "ppermute" in findings[0].message


# ================================================== seeded TA001 upcast
def _bf16_block_with_f32_leak(leak: bool):
    w1 = jnp.ones((16, 16), jnp.bfloat16)
    w2 = jnp.ones((16, 16), jnp.bfloat16)

    def step(x):
        h = jnp.dot(x, w1)  # bf16 x bf16 -> bf16: fine
        if leak:
            # The forgotten-cast bug TA001 hunts: one block promotes to
            # f32 and the matmul silently runs at 4 bytes/element.
            h = jnp.dot(h.astype(jnp.float32), w2.astype(jnp.float32))
        else:
            h = jnp.dot(h, w2)
        return h.astype(jnp.float32).sum()

    return step, (jnp.ones((8, 16), jnp.bfloat16),)


def test_ta001_flags_injected_f32_upcast():
    fn, args = _bf16_block_with_f32_leak(leak=True)
    step = TracedStep(
        name="leak",
        fn=fn,
        args=args,
        axis_sizes={},
        compute_dtype="bfloat16",
        check_donation=False,
    )
    findings = audit(step)
    assert [f.rule for f in findings] == ["TA001"]
    assert "f32 dot_general" in findings[0].message


def test_ta001_clean_bf16_block():
    fn, args = _bf16_block_with_f32_leak(leak=False)
    step = TracedStep(
        name="clean",
        fn=fn,
        args=args,
        axis_sizes={},
        compute_dtype="bfloat16",
        check_donation=False,
    )
    assert audit(step) == []


def test_ta001_allowlists_loss_and_optimizer_frames():
    """f32 math inside loss/norm/optimizer code is the sanctioned
    mixed-precision pattern, not a leak."""
    w = jnp.ones((16, 16), jnp.bfloat16)

    def cross_entropy_loss(h):
        # f32 matmul, but the frame name matches the allowlist.
        return jnp.dot(h.astype(jnp.float32), jnp.eye(16)).sum()

    def step(x):
        return cross_entropy_loss(jnp.dot(x, w))

    step_t = TracedStep(
        name="allow",
        fn=step,
        args=(jnp.ones((8, 16), jnp.bfloat16),),
        axis_sizes={},
        compute_dtype="bfloat16",
        check_donation=False,
    )
    assert audit(step_t) == []


# ================================================ seeded TA002 donation
def test_ta002_flags_dropped_donation():
    """Donating a buffer the output cannot alias (shape mismatch) is a
    dropped donation — HBM holds both copies."""

    def fn(x):
        return x.sum()  # scalar out: the (8,8) donated input can't alias

    step = TracedStep(
        name="dropped",
        fn=jax.jit(fn, donate_argnums=0),
        args=(jnp.ones((8, 8), jnp.float32),),
        axis_sizes={},
    )
    findings = audit(step, rules={"TA002"})
    assert [f.rule for f in findings] == ["TA002"]
    assert "donated" in findings[0].message


def test_ta002_clean_honoured_donation():
    def fn(x):
        return x + 1.0

    step = TracedStep(
        name="honoured",
        fn=jax.jit(fn, donate_argnums=0),
        args=(jnp.ones((8, 8), jnp.float32),),
        axis_sizes={},
    )
    assert audit(step, rules={"TA002"}) == []


# =========================================== seeded TA004 trace constant
def test_ta004_flags_large_closure_constant():
    big = jnp.asarray(np.ones((512, 1024), np.float32))  # 2 MiB

    def fn(x):
        return (x @ big).sum()

    step = TracedStep(
        name="const",
        fn=fn,
        args=(jnp.ones((4, 512), jnp.float32),),
        axis_sizes={},
        check_donation=False,
    )
    findings = audit(step)
    assert [f.rule for f in findings] == ["TA004"]
    assert "2.0 MiB" in findings[0].message


def test_ta004_small_literals_are_fine():
    scale = jnp.float32(2.0)

    def fn(x):
        return (x * scale).sum()

    step = TracedStep(
        name="small",
        fn=fn,
        args=(jnp.ones((4, 4), jnp.float32),),
        axis_sizes={},
        check_donation=False,
    )
    assert audit(step) == []


# ============================================== seeded TA005 dead matmul
def test_ta005_flags_dead_matmul():
    def fn(x, w):
        dead = x @ w  # computed, never used
        del dead
        return x.sum()

    step = TracedStep(
        name="dead",
        fn=fn,
        args=(
            jnp.ones((32, 32), jnp.float32),
            jnp.ones((32, 32), jnp.float32),
        ),
        axis_sizes={},
        check_donation=False,
    )
    findings = audit(step)
    assert [f.rule for f in findings] == ["TA005"]
    assert "dot_general" in findings[0].message


def test_ta005_live_matmul_is_fine():
    def fn(x, w):
        return (x @ w).sum()

    step = TracedStep(
        name="live",
        fn=fn,
        args=(
            jnp.ones((32, 32), jnp.float32),
            jnp.ones((32, 32), jnp.float32),
        ),
        axis_sizes={},
        check_donation=False,
    )
    assert audit(step) == []


# ====================================================== registry contract
def test_registry_records_registration_site():
    def factory():
        raise AssertionError("not built by --list-entrypoints")

    register_entrypoint("site-probe", factory, tags=("test",))
    (entry,) = get_entrypoints(["site-probe"])
    assert entry.path.endswith("test_trace_audit.py")
    assert entry.line > 0
    assert entry.tags == ("test",)


def test_registry_unknown_name_lists_known():
    register_entrypoint("known-one", lambda: None)
    with pytest.raises(KeyError) as exc:
        get_entrypoints(["nope"])
    assert "known-one" in exc.value.args[0]


def test_builtin_entrypoints_load():
    load_builtin_entrypoints()
    names = {e.name for e in get_entrypoints()}
    assert {"cifar", "cifar-int8", "cifar-overlap", "cifar-overlap-zero1",
            "lm", "lm-overlap", "lm-overlap-fsdp",
            "lm-serve", "lm-serve-paged"} <= names


def test_clean_repo_audits_green(devices):
    """The acceptance gate: every registered entrypoint audits clean."""
    load_builtin_entrypoints()
    entries = get_entrypoints(
        ["cifar", "cifar-int8", "cifar-overlap", "cifar-overlap-zero1",
         "lm", "lm-overlap", "lm-overlap-fsdp"]
    )
    findings, _suppressed, summaries, _sources, errors = run_audits(
        entries, ALL_RULES
    )
    assert errors == []
    assert findings == []
    assert len(summaries) == 7
    for s in summaries:
        assert s["donation"]["donated"] == s["donation"]["aliased"]


def test_serve_entrypoints_audit_clean(devices):
    """Both serving decode steps — gather reference AND the Pallas
    paged-attention kernel — audit clean over the engine's REAL jitted
    step: TA003 finds no unexpected collectives, TA005 no dead matmuls
    (the kernel path leaves no dead dense-gather ops behind), and the
    page-pool donation contract stays fully aliased (4/4) with the
    kernel in the graph."""
    load_builtin_entrypoints()
    entries = get_entrypoints(["lm-serve", "lm-serve-paged"])
    findings, _suppressed, summaries, _sources, errors = run_audits(
        entries, ALL_RULES
    )
    assert errors == []
    assert findings == []
    assert len(summaries) == 2
    for s in summaries:
        assert s["donation"]["donated"] == 4
        assert s["donation"]["aliased"] == 4


# ========================================================== suppressions
def test_ta_suppression_pragma_at_registration_site(tmp_path):
    """``# graftlint: disable=TA001`` on the register_entrypoint line
    silences that rule for that entrypoint, exactly like GL pragmas."""
    mod = tmp_path / "seeded_entry.py"
    mod.write_text(
        textwrap.dedent(
            """
            import jax.numpy as jnp
            from cs744_pytorch_distributed_tutorial_tpu.analysis.trace import (
                TracedStep,
                register_entrypoint,
            )

            w = jnp.ones((16, 16), jnp.bfloat16)

            def _fn(x):
                h = jnp.dot(x, w)
                return jnp.dot(
                    h.astype(jnp.float32), jnp.eye(16, dtype=jnp.float32)
                ).sum()

            def _factory():
                return TracedStep(
                    name="seeded",
                    fn=_fn,
                    args=(jnp.ones((8, 16), jnp.bfloat16),),
                    axis_sizes={},
                    compute_dtype="bfloat16",
                    check_donation=False,
                )

            register_entrypoint("seeded-suppressed", _factory)  # graftlint: disable=TA001
            register_entrypoint("seeded-loud", _factory)
            """
        )
    )
    code = compile(mod.read_text(), str(mod), "exec")
    exec(code, {"__name__": "seeded_entry", "__file__": str(mod)})

    entries = get_entrypoints(["seeded-suppressed", "seeded-loud"])
    findings, suppressed, _summaries, _sources, errors = run_audits(
        entries, {"TA001"}
    )
    assert errors == []
    assert suppressed == 1
    assert len(findings) == 1
    assert "[seeded-loud]" in findings[0].message


# ================================================================== CLI
def test_cli_list_rules(capsys):
    assert trace_cli_main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rid in TRACE_RULES:
        assert rid in out


def test_cli_list_entrypoints(capsys):
    assert trace_cli_main(["--list-entrypoints"]) == 0
    out = capsys.readouterr().out
    assert "cifar" in out and "lm" in out


def test_cli_unknown_rule_is_usage_error(capsys):
    assert trace_cli_main(["--select", "TA999"]) == 2


def test_cli_unknown_entry_is_usage_error(capsys):
    assert trace_cli_main(["no-such-entry"]) == 2


def test_cli_json_report_roundtrip(tmp_path, capsys, monkeypatch):
    monkeypatch.chdir(tmp_path)  # keep any baseline writes out of the repo
    report = tmp_path / "audit_report.json"
    rc = trace_cli_main(
        [
            "cifar",
            "--select",
            "TA003,TA004,TA005",
            "--format",
            "json",
            "--report",
            str(report),
        ]
    )
    assert rc == 0
    stdout_payload = json.loads(capsys.readouterr().out)
    disk_payload = json.loads(report.read_text())
    assert stdout_payload == disk_payload
    assert disk_payload["exit_code"] == 0
    assert disk_payload["errors"] == []
    (summary,) = disk_payload["entries"]
    assert summary["entry"] == "cifar"
    assert summary["schedule"] == {"psum": 1}


def test_cli_dispatch_from_analysis_main(capsys):
    """``python -m ...analysis trace`` routes to graftcheck."""
    from cs744_pytorch_distributed_tutorial_tpu.analysis.cli import (
        main as analysis_main,
    )

    assert analysis_main(["trace", "--list-rules"]) == 0
    assert "TA001" in capsys.readouterr().out


def _upcast_step() -> TracedStep:
    """Trace-only step with a seeded bf16->f32 matmul upcast (TA001)."""
    w = jnp.ones((16, 16), jnp.bfloat16)

    def _fn(x):
        h = jnp.dot(x, w)
        return jnp.dot(
            h.astype(jnp.float32), jnp.eye(16, dtype=jnp.float32)
        ).sum()

    return TracedStep(
        name="seeded",
        fn=_fn,
        args=(jnp.ones((8, 16), jnp.bfloat16),),
        axis_sizes={},
        compute_dtype="bfloat16",
        check_donation=False,
    )


def test_cli_write_baseline_roundtrip(tmp_path, capsys):
    """--write-baseline records current findings; a rerun against that
    baseline passes; --no-baseline surfaces them again."""
    step = _upcast_step()
    register_entrypoint("seeded-baseline", lambda: step)
    bl = tmp_path / "graftcheck_baseline.json"
    sel = ["seeded-baseline", "--select", "TA001", "--baseline", str(bl)]

    assert trace_cli_main(sel + ["--no-baseline"]) == 1  # finding is live
    capsys.readouterr()

    assert trace_cli_main(sel + ["--write-baseline"]) == 0
    assert "wrote 1 baseline entr" in capsys.readouterr().out
    assert json.loads(bl.read_text())["entries"]

    assert trace_cli_main(sel) == 0  # baselined now
    assert "1 baselined" in capsys.readouterr().out

    assert trace_cli_main(sel + ["--no-baseline"]) == 1  # still reportable


def test_checked_in_baseline_is_valid_and_empty():
    """The repo ships an EMPTY accepted-findings file: the default
    ``--baseline`` path must load and suppress nothing."""
    import pathlib

    from cs744_pytorch_distributed_tutorial_tpu.analysis import Baseline

    p = pathlib.Path(__file__).resolve().parent.parent / "graftcheck_baseline.json"
    data = json.loads(p.read_text())
    assert data == {"version": 1, "entries": []}
    assert Baseline.load(p) is not None


# ================================================ TA006 branch divergence
def _cond_entry(mesh4, sync_branch, skip_branch):
    def step(x):
        def body(v):
            return jax.lax.cond(v[0, 0] > 0, sync_branch, skip_branch, v)

        return jax.shard_map(
            body,
            mesh=mesh4,
            in_specs=jax.sharding.PartitionSpec("data"),
            out_specs=jax.sharding.PartitionSpec("data"),
        )(x)

    return TracedStep(
        name="cond-fixture",
        fn=step,
        args=(jnp.zeros((4, 128), jnp.float32),),
        axis_sizes={"data": 4},
        check_donation=False,
    )


def test_ta006_flags_divergent_cond(mesh4):
    """A cond that psums in one branch only desynchronizes the ranks."""
    step = _cond_entry(
        mesh4,
        lambda u: u + jax.lax.psum(u, "data"),
        lambda u: u * 2.0,
    )
    findings = audit(step)
    assert [f.rule for f in findings] == ["TA006"]
    assert "psum" in findings[0].message


def test_ta006_matched_branches_are_fine(mesh4):
    """Both branches lowering the same collective schedule is legal —
    every rank runs exactly one psum whichever way the predicate goes."""
    step = _cond_entry(
        mesh4,
        lambda u: u + jax.lax.psum(u, "data"),
        lambda u: u - jax.lax.psum(u, "data"),
    )
    assert audit(step) == []


def test_ta006_counts_scalar_collectives(mesh4):
    """Unlike TA003's schedule contract, TA006 must NOT drop
    scalar-payload collectives: a 4-byte psum in one branch still hangs
    the branch that skips it."""
    step = _cond_entry(
        mesh4,
        lambda u: u + jax.lax.psum(u.sum(), "data"),
        lambda u: u * 2.0,
    )
    findings = audit(step, rules={"TA006"})
    assert [f.rule for f in findings] == ["TA006"]


def test_ta006_flags_divergent_switch(mesh4):
    """lax.switch lowers to the same cond primitive; a divergent branch
    list is caught the same way."""

    def step(x):
        def body(v):
            idx = (v[0, 0] > 0).astype(jnp.int32) + (v[0, 1] > 0).astype(
                jnp.int32
            )
            return jax.lax.switch(
                idx,
                [
                    lambda u: u * 2.0,
                    lambda u: u + jax.lax.psum(u, "data"),
                    lambda u: u + jax.lax.psum(u, "data"),
                ],
                v,
            )

        return jax.shard_map(
            body,
            mesh=mesh4,
            in_specs=jax.sharding.PartitionSpec("data"),
            out_specs=jax.sharding.PartitionSpec("data"),
        )(x)

    step = TracedStep(
        name="switch-fixture",
        fn=step,
        args=(jnp.zeros((4, 128), jnp.float32),),
        axis_sizes={"data": 4},
        check_donation=False,
    )
    findings = audit(step, rules={"TA006"})
    assert [f.rule for f in findings] == ["TA006"]
    assert "3 branch" in findings[0].message or "branches" in findings[0].message
