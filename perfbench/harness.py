"""One run of one cell: ``python3 -m perfbench --workload W --seed N
--seconds S --trace 0|1``.

The harness is driven by data. ``BENCHMARK.json`` names a cell's
configuration and traffic; the traffic file names its driver
(``perfbench/drivers/<driver>.py``); each per-layer metric is a file
under ``perfbench/metrics/`` that names its reader
(``perfbench/readers/<reader>.py``). A later PR adds files and entries
and edits nothing that is here.

A run: gate on the chip, point JAX's persistent cache at a fixed
directory inside the checkout, let the driver set up (weights from the
seed, warm-up, the first steps that ``correct`` will compare), measure
the window, read the peak memory, free the program's state, run the
plain reference and compare, then print the series summary and, last,
the result line.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Any

ROOT = Path(__file__).resolve().parent.parent
HERE = Path(__file__).resolve().parent
CACHE_DIR = ROOT / ".perfbench_cache" / "jax"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def load_json(path: Path) -> dict[str, Any]:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def load_manifest() -> dict[str, Any]:
    return load_json(ROOT / "BENCHMARK.json")


def find_cell(manifest: dict[str, Any], name: str) -> dict[str, Any]:
    for w in manifest["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(
        f"perfbench: no workload {name!r} in BENCHMARK.json "
        f"(has: {[w['name'] for w in manifest['workloads']]})"
    )


def config_of(manifest: dict[str, Any], cell: dict[str, Any]) -> dict[str, Any]:
    for c in manifest["configs"]:
        if c["name"] == cell["config"]:
            return load_json(ROOT / c["file"])
    raise SystemExit(f"perfbench: no config {cell['config']!r} in BENCHMARK.json")


def traffic_of(cell: dict[str, Any]) -> dict[str, Any]:
    return load_json(HERE / "traffic" / f"{cell['traffic']}.json")


def metric_files() -> dict[str, dict[str, Any]]:
    out = {}
    for p in sorted((HERE / "metrics").glob("*.json")):
        m = load_json(p)
        out[m["name"]] = m
    return out


def cells_of(metric: dict[str, Any], manifest: dict[str, Any]) -> list[str]:
    """The cells a per-layer metric is read in: those it lists, or, where
    it lists none, every cell that reports the end-to-end metric it moves
    (the cells later PRs add too)."""
    if "workloads" in metric:
        return metric["workloads"]
    moved = next(m for m in manifest["end_to_end"] if m["name"] == metric["moves"])
    return moved.get("workloads", [w["name"] for w in manifest["workloads"]])


def configure_jax_cache() -> str:
    """Every program goes to the persistent cache, the sub-second ones
    too; the directory is fixed (its path is part of the cache key)."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        CACHE_DIR.mkdir(parents=True, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return jax.config.jax_compilation_cache_dir


def quiet_profiler() -> None:
    """Started with JAX's defaults, as the program starts it, the profiler
    records every Python call. The reduction reads the device's lines and
    the host's TraceMe spans, not Python frames, so a traced run switches
    the Python tracer off. (It does not cure the ResNet cells' traced
    steps, which take twice the untraced time on the host side either
    way: PERF.md, section 5.)"""
    import jax

    start = jax.profiler.start_trace

    def start_trace(log_dir, *args, **kw):
        if not args and kw.get("profiler_options") is None:
            kw["profiler_options"] = jax.profiler.ProfileOptions()
            kw["profiler_options"].python_tracer_level = 0
        return start(log_dir, *args, **kw)

    jax.profiler.start_trace = start_trace


class Run:
    """What a driver is given, and where it leaves what it found."""

    def __init__(self, args, manifest, cell, config, traffic, t0):
        self.args = args
        self.manifest = manifest
        self.cell = cell
        self.config = config
        self.traffic = traffic
        self.seed = int(args.seed)
        self.seconds = float(args.seconds)
        self.trace = bool(args.trace)
        self.rehearse = bool(args.rehearse)
        self.chips = int(cell["chips"])
        self.t0 = t0
        self.tmp = Path(tempfile.mkdtemp(prefix="perfbench-"))
        self.trace_dir = self.tmp / "trace"
        self.memory_peak_bytes: int | None = None
        self.memory_peak_sources: dict[str, int] | None = None
        self.compiles = None  # the program's CompileCounter, set by main

    def log(self, msg: str) -> None:
        print(f"perfbench[{time.monotonic() - self.t0:7.2f}s] {msg}", file=sys.stderr, flush=True)

    def read_memory_peak(self, extra_bytes: int = 0) -> int:
        """Peak bytes on the fullest chip, read before the reference runs
        (a process's peak never falls again). ``extra_bytes`` is the
        driver's own account of its compiled step (arguments, outputs and
        temporaries by ``memory_analysis()``), which stands where the
        allocator's counter reads lower than what the step provably held."""
        import jax

        peak = 0
        for d in jax.local_devices():
            stats = d.memory_stats() or {}
            peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
        self.memory_peak_bytes = max(peak, int(extra_bytes))
        self.memory_peak_sources = {"allocator": peak, "compiled_step": int(extra_bytes)}
        return self.memory_peak_bytes

    def limits(self) -> dict[str, float]:
        """The cell's limits; a rehearsal, which runs another size on
        another machine, has its own in the traffic file."""
        from perfbench import check

        if self.rehearse:
            return dict(self.traffic["limits"])
        return check.load_limits(self.cell["name"])

    def cleanup(self) -> None:
        shutil.rmtree(self.tmp, ignore_errors=True)


def gate(run: Run):
    import jax

    devices = jax.devices()
    first = devices[0]
    if run.rehearse:
        return devices
    if first.platform != "tpu" or len(devices) < run.chips:
        raise NoChip(
            f"needs {run.chips} TPU chip(s), found platform {first.platform!r} "
            f"({first.device_kind!r} x{len(devices)}); nothing was measured"
        )
    from perfbench.peaks import peaks_for

    peaks_for(first.device_kind)
    return devices


def reduce_trace(run: Run):
    from perfbench import tracered

    path = tracered.find_xplane(run.trace_dir)
    if path is None:
        raise RuntimeError(f"--trace 1 left no xplane.pb under {run.trace_dir}")
    keep = os.environ.get("PERFBENCH_KEEP_TRACE")
    if keep:
        os.makedirs(keep, exist_ok=True)
        shutil.copy(path, os.path.join(keep, f"{run.cell['name']}.xplane.pb"))
    return tracered.Trace.from_file(path, n_devices=run.chips)


def per_layer(run: Run, result: dict[str, Any], trace) -> dict[str, Any]:
    from perfbench.peaks import peaks_for

    ctx = {
        "run": result,
        "trace": trace,
        "cell": run.cell,
        "config": result.get("config", run.config),
        "traffic": result.get("traffic", run.traffic),
        "peaks": peaks_for(result["device"]["kind"]) if not run.rehearse else None,
    }
    out = {}
    for name, m in metric_files().items():
        if run.cell["name"] not in cells_of(m, run.manifest):
            continue
        reader = importlib.import_module(f"perfbench.readers.{m['reader']}")
        value = reader.read(ctx, m)
        if value is not None:
            out[name] = {"value": float(value), "unit": m["unit"]}
    return out


def series_line(series: dict[str, Any]) -> str:
    return "perfbench series " + json.dumps(series, sort_keys=True)


def main(argv=None, t0: float | None = None) -> int:
    t0 = time.monotonic() if t0 is None else t0
    ap = argparse.ArgumentParser(prog="python3 -m perfbench")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument(
        "--rehearse", action="store_true",
        help="off the chip, at the traffic file's tiny sizes; prints no device metric",
    )
    args = ap.parse_args(argv)

    manifest = load_manifest()
    cell = find_cell(manifest, args.workload)
    config = config_of(manifest, cell)
    traffic = traffic_of(cell)
    if args.rehearse:
        over = dict(traffic.get("rehearse", {}))
        config = {**config, **over.pop("config", {})}
        traffic = {**traffic, **over}
    run = Run(args, manifest, cell, config, traffic, t0)
    try:
        try:
            devices = gate(run)
        except NoChip as e:
            print(f"perfbench: {e}", file=sys.stderr)
            return 3
        cache = configure_jax_cache()
        if run.trace:
            quiet_profiler()
        # The program's own counter of backend compiles (obs/system.py).
        from cs744_pytorch_distributed_tutorial_tpu.obs.system import CompileCounter

        run.compiles = CompileCounter()
        run.log(f"cell {cell['name']} seed {run.seed} seconds {run.seconds} trace {int(run.trace)} cache {cache}")
        driver = importlib.import_module(f"perfbench.drivers.{traffic['driver']}")
        result = driver.run(run)

        first = devices[0]
        device = {
            "platform": first.platform,
            "kind": first.device_kind,
            "count": run.chips if not run.rehearse else len(devices),
            "memory_peak_bytes": run.memory_peak_bytes,
        }
        result["device"] = device
        setup_s = result["window_start_mono"] - t0
        line: dict[str, Any] = {
            "correct": bool(result["check"]["correct"]),
            "attempted": int(result["attempted"]),
            "failed": int(result["failed"]),
        }
        if run.rehearse:
            # A CPU number is never written under a device metric's name.
            line["metrics"] = {}
            line["rehearsal"] = {"counts": result.get("counts", {}), "setup_s": setup_s}
        elif run.trace:
            trace = reduce_trace(run)
            device["busy_s"] = trace.busy_s
            device["window_s"] = trace.window_s
            line["metrics"] = per_layer(run, result, trace)
            line["breakdown"] = trace.breakdown()
        else:
            metrics = {"setup_s": {"value": setup_s, "unit": "s"}}
            units = {m["name"]: m["unit"] for m in manifest["end_to_end"]}
            for name, value in result["end_to_end"].items():
                metrics[name] = {"value": float(value), "unit": units[name]}
            line["metrics"] = metrics
        line["device"] = device
        line["memory_peak_sources"] = run.memory_peak_sources
        line["check"] = result["check"]["numbers"]

        print(series_line(result["series"]), flush=True)
        for name, pair in result["check"]["numbers"].items():
            print(f"perfbench check {name} value {pair['value']!r} limit {pair['limit']!r} "
                  f"{'ok' if pair['ok'] else 'FAILED'}", file=sys.stderr)
        sys.stderr.flush()
        print(json.dumps(line), flush=True)
        return 0
    finally:
        run.cleanup()
