"""graftmem memory-audit tests (TA007-TA010).

Three layers, mirroring test_trace_audit.py:

1. **Seeded fixtures** — a replicated-but-declared-sharded param, a
   partitioner-inserted reshard, a dropped donation, and budget
   regressions must each be flagged by exactly the intended rule under
   the FULL graftmem rule set.
2. **Contract tests** — budget file IO (missing file = empty budget,
   merge-on-write), suppression pragmas at the registration site, and
   the CLI exit-code/JSON/report surface including the budget-gate
   lifecycle (missing entry -> write -> pass -> regression).
3. **Clean-repo gate** — every registered entrypoint audits green
   against the checked-in ``benchmarks/memory_budget.json``.

Every fixture compiles (graftmem reads ``memory_analysis()``), so the
shapes are tiny; the clean-repo gate compiles the real entries exactly
as the trace-audit donation gate already does.
"""

from __future__ import annotations

import json
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from cs744_pytorch_distributed_tutorial_tpu.analysis.trace import (
    TracedStep,
    get_entrypoints,
    load_builtin_entrypoints,
    register_entrypoint,
)
from cs744_pytorch_distributed_tutorial_tpu.analysis.trace.memory import (
    MEMORY_RULES,
    audit_memory_entry,
    hlo_collective_counts,
    load_budget,
    main as memory_cli_main,
    measure_entry,
    run_memory_audits,
    write_budget,
)
from cs744_pytorch_distributed_tutorial_tpu.analysis.trace.registry import (
    _REGISTRY,
)

ALL_RULES = set(MEMORY_RULES)

REPO = Path(__file__).resolve().parent.parent


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


def audit(step: TracedStep, rules=None, budget=None, name: str = "mem-fixture"):
    return audit_memory_entry(
        entry_for(step, name), set(rules) if rules is not None else None, budget
    )


# ------------------------------------------------------------- fixtures
def _replication_step(mesh4, shard_w: bool) -> TracedStep:
    """Elementwise step on a 4-device mesh: ``w`` is DECLARED sharded via
    sharded_param_paths but placed replicated (the TA008 seed) or
    properly sharded (the clean twin). Elementwise only, so neither the
    jaxpr nor the HLO contains collectives — TA009 stays silent."""
    sh_data = NamedSharding(mesh4, P("data"))
    sh_rep = NamedSharding(mesh4, P())
    w = jax.device_put(
        jnp.ones((64, 64), jnp.float32), sh_data if shard_w else sh_rep
    )
    x = jax.device_put(jnp.ones((8, 64), jnp.float32), sh_data)
    return TracedStep(
        name="mem-fixture",
        fn=jax.jit(lambda w, x: (w * 2.0, x + 1.0)),
        args=(w, x),
        axis_sizes={"data": 4},
        sync="zero1",
        check_donation=False,
        sharded_param_paths=("[0]",),
    )


def _reshard_step(mesh4, clean: bool) -> TracedStep:
    """Data-sharded input forced to a replicated output: the SPMD
    partitioner must insert an all-gather that no jaxpr eqn asked for
    (the TA009 seed). The clean twin keeps in/out specs aligned."""
    sh_in = NamedSharding(mesh4, P("data"))
    sh_out = sh_in if clean else NamedSharding(mesh4, P())
    x = jax.device_put(jnp.ones((8, 64), jnp.float32), sh_in)
    return TracedStep(
        name="mem-fixture",
        fn=jax.jit(lambda x: x * 2.0, in_shardings=sh_in, out_shardings=sh_out),
        args=(x,),
        axis_sizes={"data": 4},
        check_donation=False,
    )


def _donation_step(dropped: bool) -> TracedStep:
    """Donated 32x32 buffer (4096B). ``dropped=True`` uses it but returns
    nothing shape-compatible, so XLA drops the donation (the TA010 seed);
    the clean twin returns an aliasable same-shape output."""
    if dropped:
        fn = jax.jit(lambda buf, x: (buf.sum(), x * 2.0), donate_argnums=(0,))
        args = (jnp.ones((32, 32), jnp.float32), jnp.ones((8,), jnp.float32))
    else:
        fn = jax.jit(lambda buf: buf + 1.0, donate_argnums=(0,))
        args = (jnp.ones((32, 32), jnp.float32),)
    return TracedStep(
        name="mem-fixture", fn=fn, args=args, axis_sizes={}
    )


def _budget_for(ledger: dict, **overrides) -> dict:
    entry = {
        k: ledger[k]
        for k in (
            "devices",
            "argument_bytes",
            "output_bytes",
            "temp_bytes",
            "alias_bytes",
            "total_bytes",
            "dropped_donation_bytes",
        )
    }
    entry.update(overrides.pop("entry_overrides", {}))
    budget = {
        "version": 1,
        "tolerance": 0.05,
        "floor_bytes": 0,
        "entries": {ledger["entry"]: entry},
    }
    budget.update(overrides)
    return budget


# ================================================================ TA008
def test_ta008_replicated_declared_sharded_param(mesh4):
    findings, ledger = audit(_replication_step(mesh4, shard_w=False))
    assert {f.rule for f in findings} == {"TA008"}
    (f,) = findings
    assert "REPLICATED" in f.message and "[0]" in f.message
    assert "zero1" in f.message
    assert ledger["replicated_leaves"] == 1


def test_ta008_sharded_param_is_clean(mesh4):
    findings, ledger = audit(_replication_step(mesh4, shard_w=True))
    assert findings == []
    assert ledger["replicated_leaves"] == 0


def test_ta008_undeclared_replication_is_silent(mesh4):
    """Replication is only a finding when the engine PROMISED sharding:
    without sharded_param_paths the same replicated placement is fine
    (that's what plain data-parallel params look like)."""
    import dataclasses

    step = dataclasses.replace(
        _replication_step(mesh4, shard_w=False), sharded_param_paths=()
    )
    findings, _ledger = audit(step)
    assert findings == []


def test_ta008_small_leaves_exempt(mesh4):
    """Leaves under the min-bytes threshold (scalars, biases, norm
    scales) are never flagged — replicating them is the right call."""
    sh_data = NamedSharding(mesh4, P("data"))
    w = jax.device_put(jnp.ones((4, 4), jnp.float32), NamedSharding(mesh4, P()))
    x = jax.device_put(jnp.ones((8, 64), jnp.float32), sh_data)
    step = TracedStep(
        name="mem-fixture",
        fn=jax.jit(lambda w, x: (w * 2.0, x + 1.0)),
        args=(w, x),
        axis_sizes={"data": 4},
        sync="zero1",
        check_donation=False,
        sharded_param_paths=("[0]",),
    )
    findings, _ledger = audit(step)
    assert findings == []


# ================================================================ TA009
def test_ta009_partitioner_inserted_reshard(mesh4):
    findings, ledger = audit(_reshard_step(mesh4, clean=False))
    assert {f.rule for f in findings} == {"TA009"}
    (f,) = findings
    assert "all-gather" in f.message
    assert ledger["hlo_collectives"].get("all-gather", 0) >= 1


def test_ta009_aligned_specs_clean(mesh4):
    findings, ledger = audit(_reshard_step(mesh4, clean=True))
    assert findings == []
    assert ledger["hlo_collectives"] == {}


def test_hlo_collective_counts_parses_plain_and_start_forms():
    hlo = textwrap.dedent(
        """
        %ag = f32[8,64]{1,0} all-gather(f32[2,64]{1,0} %p0), replica_groups={}
        %ars = (f32[4]{0}, f32[4]{0}) all-reduce-start(f32[4]{0} %p1)
        %ard = f32[4]{0} all-reduce-done((f32[4]{0}, f32[4]{0}) %ars)
        """
    )
    counts = hlo_collective_counts(hlo)
    assert counts == {"all-gather": 1, "all-reduce": 1}


# ================================================================ TA010
def test_ta010_dropped_donation_priced():
    findings, ledger = audit(_donation_step(dropped=True))
    assert {f.rule for f in findings} == {"TA010"}
    (f,) = findings
    assert "4096B" in f.message and "dropped donation" in f.message
    assert ledger["dropped_donation_bytes"] == 4096


def test_ta010_aliased_donation_clean():
    findings, ledger = audit(_donation_step(dropped=False))
    assert findings == []
    assert ledger["dropped_donation_bytes"] == 0
    assert ledger["aliased_leaves"] == 1
    assert ledger["alias_saved_bytes"] == 4096


def test_ta010_respects_check_donation_flag():
    import dataclasses

    step = dataclasses.replace(_donation_step(dropped=True), check_donation=False)
    findings, _ledger = audit(step)
    assert findings == []


# ================================================================ TA007
def test_ta007_within_band_and_inflated_budget_pass():
    step = _donation_step(dropped=False)
    _f, ledger = audit(step, rules=set())
    # exact budget passes...
    findings, _l = audit(step, budget=_budget_for(ledger))
    assert findings == []
    # ...and so does an INFLATED one (memory went down, not up)
    roomy = _budget_for(
        ledger, entry_overrides={"total_bytes": ledger["total_bytes"] * 10}
    )
    findings, _l = audit(step, budget=roomy)
    assert findings == []


def test_ta007_regression_past_tolerance_fires():
    step = _donation_step(dropped=False)
    _f, ledger = audit(step, rules=set())
    tight = _budget_for(
        ledger,
        tolerance=0.0,
        entry_overrides={"total_bytes": ledger["total_bytes"] - 1},
    )
    findings, _l = audit(step, budget=tight)
    assert {f.rule for f in findings} == {"TA007"}
    (f,) = findings
    assert "exceeds the budget" in f.message and "--write-budget" in f.message


def test_ta007_missing_entry_fires():
    step = _donation_step(dropped=False)
    budget = {"version": 1, "tolerance": 0.05, "floor_bytes": 0, "entries": {}}
    findings, _l = audit(step, budget=budget)
    assert {f.rule for f in findings} == {"TA007"}
    assert "no HBM budget entry" in findings[0].message
    assert "--write-budget" in findings[0].message


def test_ta007_device_count_mismatch_fires():
    step = _donation_step(dropped=False)
    _f, ledger = audit(step, rules=set())
    stale = _budget_for(ledger, entry_overrides={"devices": 4})
    findings, _l = audit(step, budget=stale)
    assert {f.rule for f in findings} == {"TA007"}
    assert "not comparable" in findings[0].message


def test_ta007_skipped_without_budget():
    """budget=None (fixture runs, --no-budget) must not fire
    missing-entry findings."""
    findings, _l = audit(_donation_step(dropped=False), budget=None)
    assert findings == []


# ============================================================ budget IO
def test_load_budget_missing_file_is_empty(tmp_path):
    budget = load_budget(tmp_path / "nope.json")
    assert budget["entries"] == {}
    assert budget["tolerance"] == 0.05


def test_load_budget_malformed_raises(tmp_path):
    p = tmp_path / "bad.json"
    p.write_text(json.dumps([1, 2, 3]))
    with pytest.raises(ValueError):
        load_budget(p)


def test_write_budget_merges_existing_entries(tmp_path):
    p = tmp_path / "budget.json"
    p.write_text(
        json.dumps(
            {
                "version": 1,
                "tolerance": 0.1,
                "floor_bytes": 123,
                "entries": {"other": {"devices": 2, "total_bytes": 7}},
            }
        )
    )
    step = _donation_step(dropped=False)
    ledger = measure_entry(entry_for(step, "mem-fixture"), step)
    n = write_budget(p, [ledger])
    assert n == 2
    data = json.loads(p.read_text())
    assert sorted(data["entries"]) == ["mem-fixture", "other"]
    assert data["tolerance"] == 0.1  # preserved, not reset
    assert data["entries"]["mem-fixture"]["total_bytes"] == ledger["total_bytes"]


# ========================================================== suppressions
def test_memory_suppression_pragma_at_registration_site(tmp_path):
    """``# graftlint: disable=TA010`` on the register_entrypoint line
    silences the memory rule for that entrypoint, like GL/TA pragmas."""
    mod = tmp_path / "seeded_mem_entry.py"
    mod.write_text(
        textwrap.dedent(
            """
            import jax
            import jax.numpy as jnp
            from cs744_pytorch_distributed_tutorial_tpu.analysis.trace import (
                TracedStep,
                register_entrypoint,
            )

            def _fn(buf, x):
                return buf.sum(), x * 2.0

            def _factory():
                return TracedStep(
                    name="seeded",
                    fn=jax.jit(_fn, donate_argnums=(0,)),
                    args=(
                        jnp.ones((32, 32), jnp.float32),
                        jnp.ones((8,), jnp.float32),
                    ),
                    axis_sizes={},
                )

            register_entrypoint("mem-suppressed", _factory)  # graftlint: disable=TA010
            register_entrypoint("mem-loud", _factory)
            """
        )
    )
    code = compile(mod.read_text(), str(mod), "exec")
    exec(code, {"__name__": "seeded_mem_entry", "__file__": str(mod)})

    entries = get_entrypoints(["mem-suppressed", "mem-loud"])
    findings, suppressed, _ledgers, _sources, errors = run_memory_audits(
        entries, {"TA010"}
    )
    assert errors == []
    assert suppressed == 1
    assert len(findings) == 1
    assert "[mem-loud]" in findings[0].message


# ================================================================== CLI
def test_memory_cli_list_rules(capsys):
    assert memory_cli_main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rid in MEMORY_RULES:
        assert rid in out


def test_memory_cli_list_entrypoints(capsys):
    assert memory_cli_main(["--list-entrypoints"]) == 0
    out = capsys.readouterr().out
    assert "cifar" in out and "lm" in out


def test_memory_cli_unknown_rule_is_usage_error(capsys):
    assert memory_cli_main(["--select", "TA999"]) == 2
    assert memory_cli_main(["--select", "GL"]) == 2  # wrong family


def test_memory_cli_unknown_entry_is_usage_error(capsys):
    assert memory_cli_main(["no-such-entry"]) == 2


def test_memory_cli_dispatch_from_analysis_main(capsys):
    """``python -m ...analysis memory`` routes to graftmem."""
    from cs744_pytorch_distributed_tutorial_tpu.analysis.cli import (
        main as analysis_main,
    )

    assert analysis_main(["memory", "--list-rules"]) == 0
    assert "TA007" in capsys.readouterr().out


def test_memory_cli_bare_family_prefix_selects_all(tmp_path, capsys):
    """``--select TA`` expands to the whole graftmem family."""
    step = _donation_step(dropped=False)
    register_entrypoint("mem-cli-fixture", lambda: step)
    rc = memory_cli_main(
        ["mem-cli-fixture", "--no-budget", "--select", "TA"]
    )
    assert rc == 0


def test_memory_cli_json_report_roundtrip(tmp_path, capsys):
    step = _donation_step(dropped=False)
    register_entrypoint("mem-cli-fixture", lambda: step)
    report = tmp_path / "memory_report.json"
    rc = memory_cli_main(
        [
            "mem-cli-fixture",
            "--no-budget",
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
    (ledger,) = disk_payload["entries"]
    assert ledger["entry"] == "mem-cli-fixture"
    assert ledger["total_bytes"] > 0
    (record,) = disk_payload["records"]
    assert record["kind"] == "memory_ledger"
    assert record["total_bytes"] == ledger["total_bytes"]


def test_memory_cli_budget_gate_lifecycle(tmp_path, capsys):
    """The CI contract end to end: gate fails on a missing budget entry,
    --write-budget records it, the gated rerun passes, a seeded
    regression fails, and --no-budget disarms the gate."""
    step = _donation_step(dropped=False)
    register_entrypoint("mem-cli-fixture", lambda: step)
    budget = tmp_path / "budget.json"

    # 1. gate armed against an absent budget file -> missing entry
    rc = memory_cli_main(["mem-cli-fixture", "--budget", str(budget)])
    assert rc == 1
    assert "no HBM budget entry" in capsys.readouterr().out

    # 2. record the budget
    rc = memory_cli_main(
        ["mem-cli-fixture", "--budget", str(budget), "--write-budget"]
    )
    assert rc == 0 and budget.is_file()
    assert "wrote 1 budget entr" in capsys.readouterr().out

    # 3. gated rerun passes
    rc = memory_cli_main(["mem-cli-fixture", "--budget", str(budget)])
    assert rc == 0

    # 4. seeded regression: deflate the recorded total, zero the band
    data = json.loads(budget.read_text())
    data["tolerance"] = 0.0
    data["floor_bytes"] = 0
    data["entries"]["mem-cli-fixture"]["total_bytes"] -= 1
    budget.write_text(json.dumps(data))
    rc = memory_cli_main(["mem-cli-fixture", "--budget", str(budget)])
    assert rc == 1
    assert "exceeds the budget" in capsys.readouterr().out

    # 5. --no-budget disarms the gate
    rc = memory_cli_main(
        ["mem-cli-fixture", "--budget", str(budget), "--no-budget"]
    )
    assert rc == 0


def test_memory_cli_malformed_budget_is_usage_error(tmp_path, capsys):
    step = _donation_step(dropped=False)
    register_entrypoint("mem-cli-fixture", lambda: step)
    bad = tmp_path / "bad.json"
    bad.write_text("[]")
    rc = memory_cli_main(["mem-cli-fixture", "--budget", str(bad)])
    assert rc == 2


# ======================================================= clean-repo gate
def test_budget_gate_smoke_cifar(devices):
    """Tier-1 smoke: the flagship entry audits clean against the REAL
    checked-in budget file (catches budget-file drift cheaply; the full
    9-entry sweep below is slow-marked and CI's audit job runs it via
    the CLI with the gate armed)."""
    load_builtin_entrypoints()
    (entry,) = get_entrypoints(["cifar"])
    budget = load_budget(REPO / "benchmarks" / "memory_budget.json")
    findings, ledger = audit_memory_entry(entry, ALL_RULES, budget)
    assert findings == []
    assert ledger["devices"] == budget["entries"]["cifar"]["devices"]


@pytest.mark.slow
def test_clean_repo_memory_audits_green(devices):
    """The acceptance gate: every registered entrypoint audits clean
    against the checked-in budget file. Compiles all nine entries, so
    it rides outside tier-1; CI's audit job runs the same gate through
    ``analysis memory``."""
    load_builtin_entrypoints()
    entries = get_entrypoints(
        ["cifar", "cifar-int8", "cifar-overlap", "cifar-overlap-zero1",
         "lm", "lm-overlap", "lm-overlap-fsdp",
         "lm-serve", "lm-serve-paged"]
    )
    budget = load_budget(REPO / "benchmarks" / "memory_budget.json")
    assert len(budget["entries"]) == 9
    findings, _suppressed, ledgers, _sources, errors = run_memory_audits(
        entries, ALL_RULES, budget
    )
    assert errors == []
    assert findings == []
    assert len(ledgers) == 9
    for lg in ledgers:
        assert lg["total_bytes"] > 0
        assert lg["devices"] == budget["entries"][lg["entry"]]["devices"]
        assert lg["replicated_leaves"] == 0
        assert lg["dropped_donation_bytes"] == 0
