"""Unified telemetry: on-device metrics, pluggable sinks, run
manifests, and HBM/MFU accounting.

Entry point for engines and CLIs is :class:`Telemetry`; everything
else (sinks, flops models, manifests, system monitors) is importable
from its submodule for tools that only need one piece.
"""

from .metrics import (
    Telemetry,
    expert_load_entropy,
    sown_scalar_mean,
    speculative_accept_rate,
    tree_l2_norm,
    tree_sq_norm,
)
from .fleet import (
    ClockAligner,
    FleetStamper,
    collective_skew,
    fleet_check,
    load_fleet_dir,
    merge_timeline,
    render_fleet_report,
    write_fleet_artifacts,
)
from .flight import FlightRecorder, HbmHighWater, StragglerMonitor
from .run_manifest import build_manifest, read_manifest, write_manifest
from .serve_trace import (
    ServeTracer,
    check_spans,
    profile_serve_programs,
    reconcile,
)
from .sinks import (
    CsvSink,
    JsonlSink,
    MetricSink,
    MultiSink,
    NullSink,
    RingSink,
    StreamSink,
    rank_zero,
    sanitize,
)
from .system import CompileCounter, SystemMonitor, hbm_stats
from . import flops

__all__ = [
    "Telemetry",
    "expert_load_entropy",
    "sown_scalar_mean",
    "speculative_accept_rate",
    "tree_l2_norm",
    "tree_sq_norm",
    "ClockAligner",
    "FleetStamper",
    "collective_skew",
    "fleet_check",
    "load_fleet_dir",
    "merge_timeline",
    "render_fleet_report",
    "write_fleet_artifacts",
    "FlightRecorder",
    "HbmHighWater",
    "StragglerMonitor",
    "build_manifest",
    "read_manifest",
    "write_manifest",
    "ServeTracer",
    "check_spans",
    "profile_serve_programs",
    "reconcile",
    "CsvSink",
    "JsonlSink",
    "MetricSink",
    "MultiSink",
    "NullSink",
    "RingSink",
    "StreamSink",
    "rank_zero",
    "sanitize",
    "CompileCounter",
    "SystemMonitor",
    "hbm_stats",
    "flops",
]
