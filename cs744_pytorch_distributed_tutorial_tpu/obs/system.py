"""System-level telemetry: HBM snapshots and compile-event counting.

``device.memory_stats()`` is the only portable window into HBM
pressure on TPU; it returns ``None`` on CPU (and some backends omit
individual keys), so every read here is guarded — a system record
with null memory fields is still a record of *when* we looked.

Compile counting hooks ``jax._src.monitoring``: the plain
``/jax/compilation_cache/...`` events fire once per cache *lookup*
(i.e. every jit call-site miss in the python cache), so we count the
duration event ``backend_compile_duration`` instead — it fires exactly
once per real XLA backend compile, which is the thing that silently
eats minutes when a shape leaks into a retrace loop.
"""

from __future__ import annotations

import threading
from typing import Any

__all__ = ["CompileCounter", "SystemMonitor", "hbm_stats"]

_COMPILE_EVENT_SUFFIX = "backend_compile_duration"

_lock = threading.Lock()
_compile_count = 0
_compile_secs = 0.0
_listener_installed = False


def _on_duration_event(name: str, secs: float, **_kw: Any) -> None:
    global _compile_count, _compile_secs
    if name.endswith(_COMPILE_EVENT_SUFFIX):
        with _lock:
            _compile_count += 1
            _compile_secs += float(secs)


def _ensure_listener() -> None:
    """Install the module-wide monitoring listener once. jax offers no
    unregister, so a single process-lifetime listener feeding a global
    counter is the leak-free shape; consumers snapshot deltas."""
    global _listener_installed
    with _lock:
        if _listener_installed:
            return
        _listener_installed = True
    # A private API: if a JAX upgrade moves it this raises, where a
    # swallowed failure would leave every compile count at zero and the
    # "no retrace after warm-up" checks vacuous.
    from jax._src import monitoring

    monitoring.register_event_duration_secs_listener(_on_duration_event)


def _compile_totals() -> tuple[int, float]:
    with _lock:
        return _compile_count, _compile_secs


class CompileCounter:
    """Counts *backend* compiles (and seconds spent in them) observed
    since this counter was constructed."""

    def __init__(self) -> None:
        _ensure_listener()
        self._base_count, self._base_secs = _compile_totals()

    @property
    def count(self) -> int:
        return _compile_totals()[0] - self._base_count

    @property
    def seconds(self) -> float:
        return _compile_totals()[1] - self._base_secs


def hbm_stats(device: Any) -> dict[str, int] | None:
    """``device.memory_stats()`` with every failure mode flattened to
    None (CPU returns None; some backends raise)."""
    try:
        stats = device.memory_stats()
    except Exception:
        return None
    if not stats:
        return None
    return {str(k): int(v) for k, v in stats.items() if isinstance(v, int)}


class SystemMonitor:
    """Produces flat "system" records: worst-case HBM across local
    devices plus the compile counters. One instance per run."""

    def __init__(self) -> None:
        self.compiles = CompileCounter()

    def snapshot(self) -> dict[str, Any]:
        import jax

        record: dict[str, Any] = {
            "compile_count": self.compiles.count,
            "compile_secs": round(self.compiles.seconds, 6),
        }
        try:
            devices = jax.local_devices()
        except RuntimeError:
            devices = []
        record["local_device_count"] = len(devices)
        if devices:
            record["device_kind"] = devices[0].device_kind
        bytes_in_use: int | None = None
        peak_bytes: int | None = None
        bytes_limit: int | None = None
        for d in devices:
            stats = hbm_stats(d)
            if not stats:
                continue
            if "bytes_in_use" in stats:
                bytes_in_use = max(bytes_in_use or 0, stats["bytes_in_use"])
            peak = stats.get("peak_bytes_in_use")
            if peak is not None:
                peak_bytes = max(peak_bytes or 0, peak)
            limit = stats.get("bytes_limit")
            if limit is not None:
                bytes_limit = max(bytes_limit or 0, limit)
        record["hbm_bytes_in_use"] = bytes_in_use
        record["hbm_peak_bytes_in_use"] = peak_bytes
        record["hbm_bytes_limit"] = bytes_limit
        return record
