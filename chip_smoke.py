"""Proof that the system still starts on the chip: one short training run.

Drives the main path once, through the entry point a user calls —
``cs744_pytorch_distributed_tutorial_tpu.cli.main`` -> ``Trainer.fit()`` —
at the full width of the configuration the repo has always been scored
on: ResNet-18 (CIFAR stem), bf16 compute, ``--sync auto``, per-chip batch
4096 over every visible chip, synthetic data made from a seed, 12 steps
(so the batches-1..10 timing window closes), the eval pass and the
``--json`` summary. Then it checks what came out by the run's own
records (``manifest.json`` / ``metrics.jsonl`` under ``--metrics-dir``).

Contract (the driver runs this after every PR):
- exits non-zero, before building anything, unless
  ``jax.devices()[0].platform == "tpu"``;
- everything runs in this one process (a second process could not have
  the chip); no phase is wrapped in an ``except``;
- the last line of stdout is
  ``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``
  and is printed only if every check passed.

Run it on the chip: ``chiprun -- python3 chip_smoke.py`` (and
``--chips 4`` for the four-chip host).
"""

from __future__ import annotations

import contextlib
import importlib.metadata
import io
import json
import math
import shutil
import sys
import time
from pathlib import Path

STEPS = 12
PER_CHIP_BATCH = 4096
OUT_DIR = Path(__file__).resolve().parent / "chiprun_out" / "chip_smoke"


class _Tee:
    """Write-through to several text streams (stdout + a capture)."""

    def __init__(self, *streams):
        self._streams = streams

    def write(self, text: str) -> int:
        for s in self._streams:
            s.write(text)
        return len(text)

    def flush(self) -> None:
        for s in self._streams:
            s.flush()


def _read_jsonl(path: Path) -> list[dict]:
    with open(path, encoding="utf-8") as f:
        return [json.loads(line) for line in f if line.strip()]


def main() -> int:
    import jax
    import jaxlib

    devices = jax.devices()
    first = devices[0]
    if first.platform != "tpu":
        print(
            f"chip_smoke: needs a TPU, found platform {first.platform!r} "
            f"({first.device_kind!r} x{len(devices)}); nothing was built",
            file=sys.stderr,
        )
        return 1
    n = len(devices)
    versions = {
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "libtpu": importlib.metadata.version("libtpu"),
    }
    print(
        f"chip_smoke: platform={first.platform} "
        f"device_kind={first.device_kind!r} count={n} "
        + " ".join(f"{k}={v}" for k, v in versions.items())
    )

    from cs744_pytorch_distributed_tutorial_tpu import cli
    from cs744_pytorch_distributed_tutorial_tpu.native import native_available
    from cs744_pytorch_distributed_tutorial_tpu.obs.system import CompileCounter
    from cs744_pytorch_distributed_tutorial_tpu.utils.compile_cache import (
        configure_compile_cache,
    )

    cache_dir = configure_compile_cache()
    # This run's records only: the sink appends.
    if OUT_DIR.exists():
        shutil.rmtree(OUT_DIR)
    global_batch = PER_CHIP_BATCH * n
    argv = [
        "--model", "resnet18",
        "--compute-dtype", "bfloat16",
        "--sync", "auto",
        "--global-batch-size", str(global_batch),
        "--synthetic-data",
        "--synthetic-train-size", str(global_batch * STEPS),
        "--epochs", "1",
        "--log-every", "1",
        "--metrics-dir", str(OUT_DIR),
        "--json",
    ]
    print(f"chip_smoke: cli {' '.join(argv)}")
    compiles = CompileCounter()
    captured = io.StringIO()
    t0 = time.perf_counter()
    with contextlib.redirect_stdout(_Tee(sys.stdout, captured)):
        rc = cli.main(argv)
    wall_s = time.perf_counter() - t0

    failures: list[str] = []

    def check(ok: bool, what: str) -> None:
        if not ok:
            failures.append(what)

    check(rc == 0, f"cli.main returned {rc}")
    json_lines = [
        ln for ln in captured.getvalue().splitlines() if ln.startswith("{")
    ]
    check(bool(json_lines), "cli printed no --json summary")
    summary = json.loads(json_lines[-1]) if json_lines else {}

    manifest = json.loads((OUT_DIR / "manifest.json").read_text())
    records = _read_jsonl(OUT_DIR / "metrics.jsonl")
    steps = [r for r in records if r.get("kind") == "step"]
    evals = [
        r for r in records
        if r.get("kind") == "event" and r.get("event") == "eval"
    ]
    losses = [r.get("loss") for r in steps]

    check(manifest.get("backend") == "tpu",
          f"manifest backend is {manifest.get('backend')!r}, not 'tpu'")
    check(manifest.get("device_count") == n and manifest.get("mesh") == {"data": n},
          f"manifest devices/mesh {manifest.get('device_count')}/"
          f"{manifest.get('mesh')} != {n} visible devices")
    check([r.get("step") for r in steps] == list(range(STEPS)),
          f"step records {[r.get('step') for r in steps]} != 0..{STEPS - 1}")
    check(all(isinstance(v, float) and math.isfinite(v) for v in losses),
          f"non-finite loss in {losses}")
    if len(losses) >= 4 and all(isinstance(v, float) for v in losses):
        tail = sum(losses[-3:]) / 3
        check(tail < losses[0],
              f"no progress: mean of last three losses {tail:.4f} >= "
              f"first {losses[0]:.4f}")
    check(len(evals) == 1 and math.isfinite(evals[0].get("avg_loss", math.nan)),
          f"expected one finite eval event, got {evals}")
    check(summary.get("num_devices") == n,
          f"summary num_devices {summary.get('num_devices')} != {n}")
    check(summary.get("avg_batch_time_s") is not None,
          "summary avg_batch_time_s is null (timing window never closed)")

    peaks = {d.id: d.memory_stats()["peak_bytes_in_use"] for d in devices}
    check(all(v > 0 for v in peaks.values()),
          f"a visible device was never used: peak_bytes_in_use {peaks}")

    report = {
        "kind": "chip_smoke",
        "steps": len(steps),
        "global_batch": global_batch,
        "first_loss": losses[0] if losses else None,
        "last_loss": losses[-1] if losses else None,
        "eval": {k: evals[0].get(k) for k in ("avg_loss", "accuracy")} if evals else None,
        "compile_count": compiles.count,
        "compile_secs": round(compiles.seconds, 2),
        "compile_cache_dir": cache_dir,
        "batcher": "native" if native_available("batcher") else "numpy",
        "peak_bytes_in_use": peaks,
        "wall_s": round(wall_s, 1),
        **versions,
    }
    print(f"chip_smoke: {json.dumps(report)}")
    if failures:
        for what in failures:
            print(f"chip_smoke: FAILED: {what}", file=sys.stderr)
        return 1
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": first.platform,
            "kind": first.device_kind,
            "count": n,
        },
    }))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
