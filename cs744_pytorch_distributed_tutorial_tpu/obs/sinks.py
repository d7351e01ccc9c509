"""Pluggable metric sinks.

One protocol — ``MetricSink.emit(record) / close()`` — and a small set
of concrete sinks behind it:

- :class:`JsonlSink`: newline-delimited JSON, the default on-disk
  format. Every record is flushed on write so a wedged or killed run
  still leaves a readable file (the watchdog depends on this).
- :class:`CsvSink`: spreadsheet-friendly; the header is frozen by the
  FIRST record emitted (later records with extra keys have those keys
  dropped, missing keys become empty cells) so the file stays
  rectangular no matter what mixture of record kinds flows through.
- :class:`RingSink`: bounded in-memory deque — the tail the watchdog
  flushes when a step wedges, and what tests assert against.
- :class:`MultiSink` / :class:`NullSink` / :class:`StreamSink`:
  fan-out, no-op, and write-to-stream (``serve_cli`` uses the stream
  sink to print its records to stdout through the same schema path
  as training telemetry).

``rank_zero(sink)`` wraps any sink so only process 0 writes on
multihost — every process computes the same replicated scalars, so
writing from all of them would only duplicate rows.

JSON does not allow ``NaN``/``Infinity`` literals; non-finite floats
are sanitized to ``None`` (JSON ``null``) at emission so a diverged
run produces a *parseable* record stream, not a corrupt one.
"""

from __future__ import annotations

import csv
import io
import json
import math
import threading
from collections import deque
from typing import Any, Iterable, Mapping, Protocol, runtime_checkable

__all__ = [
    "MetricSink",
    "JsonlSink",
    "CsvSink",
    "RingSink",
    "MultiSink",
    "NullSink",
    "StreamSink",
    "rank_zero",
    "sanitize",
]


@runtime_checkable
class MetricSink(Protocol):
    """Anything that accepts flat metric records (str -> scalar/str)."""

    def emit(self, record: Mapping[str, Any]) -> None: ...

    def close(self) -> None: ...


def sanitize(record: Mapping[str, Any]) -> dict[str, Any]:
    """Flatten a record to JSON-safe python scalars.

    Non-finite floats become ``None`` — strict JSON has no ``NaN``
    token, and a diverged loss must not corrupt the stream the
    post-mortem depends on. Numpy/JAX 0-d scalars are coerced via
    ``float()``/``int()`` by json itself; anything unknown falls back
    to ``str``.
    """
    out: dict[str, Any] = {}
    for k, v in record.items():
        if isinstance(v, float) and not math.isfinite(v):
            out[k] = None
        elif isinstance(v, (str, int, bool)) or v is None:
            out[k] = v
        elif isinstance(v, float):
            out[k] = v
        else:
            # Numpy scalars, 0-d arrays, dtypes, paths, ...
            try:
                f = float(v)
            except (TypeError, ValueError):
                out[k] = str(v)
            else:
                out[k] = f if math.isfinite(f) else None
    return out


class JsonlSink:
    """Append-mode newline-delimited JSON with per-record flush."""

    def __init__(self, path: str):
        self.path = path
        self._f = open(path, "a", encoding="utf-8")
        self._lock = threading.Lock()

    def emit(self, record: Mapping[str, Any]) -> None:
        line = json.dumps(sanitize(record), allow_nan=False)
        with self._lock:
            self._f.write(line + "\n")
            self._f.flush()

    def close(self) -> None:
        with self._lock:
            if not self._f.closed:
                self._f.close()


class CsvSink:
    """CSV with the header frozen at the first emitted record.

    Keys absent from a later record write as empty cells; keys the
    first record didn't have are dropped — a CSV cannot grow columns
    after the fact, and a stable header is exactly what makes the file
    loadable into pandas/sheets without surgery.
    """

    def __init__(self, path: str):
        self.path = path
        self._f = open(path, "a", encoding="utf-8", newline="")
        self._writer: csv.DictWriter | None = None
        self._lock = threading.Lock()

    def emit(self, record: Mapping[str, Any]) -> None:
        rec = sanitize(record)
        with self._lock:
            if self._writer is None:
                self._writer = csv.DictWriter(
                    self._f, fieldnames=list(rec), extrasaction="ignore",
                    restval="",
                )
                self._writer.writeheader()
            self._writer.writerow(rec)
            self._f.flush()

    def close(self) -> None:
        with self._lock:
            if not self._f.closed:
                self._f.close()


class RingSink:
    """Thread-safe bounded ring of the most recent records."""

    def __init__(self, capacity: int = 256):
        if capacity <= 0:
            raise ValueError(f"ring capacity must be positive, got {capacity}")
        self.capacity = capacity
        self._ring: deque[dict[str, Any]] = deque(maxlen=capacity)
        self._lock = threading.Lock()

    def emit(self, record: Mapping[str, Any]) -> None:
        with self._lock:
            self._ring.append(sanitize(record))

    def records(self) -> list[dict[str, Any]]:
        with self._lock:
            return list(self._ring)

    def tail(self, n: int) -> list[dict[str, Any]]:
        with self._lock:
            return list(self._ring)[-n:]

    def __len__(self) -> int:
        with self._lock:
            return len(self._ring)

    def close(self) -> None:
        pass


class MultiSink:
    """Fan one emit out to several sinks."""

    def __init__(self, sinks: Iterable[MetricSink]):
        self.sinks = list(sinks)

    def emit(self, record: Mapping[str, Any]) -> None:
        for s in self.sinks:
            s.emit(record)

    def close(self) -> None:
        for s in self.sinks:
            s.close()


class NullSink:
    """Swallows everything. The no-telemetry default."""

    def emit(self, record: Mapping[str, Any]) -> None:
        pass

    def close(self) -> None:
        pass


class StreamSink:
    """One JSON line per record to an arbitrary text stream.

    ``serve_cli`` routes its stdout JSON through this so its output
    and training telemetry share one serialization path (same
    sanitization, same schema fields).
    """

    def __init__(self, stream: io.TextIOBase):
        self.stream = stream

    def emit(self, record: Mapping[str, Any]) -> None:
        self.stream.write(json.dumps(sanitize(record), allow_nan=False) + "\n")
        self.stream.flush()

    def close(self) -> None:
        pass  # never close a borrowed stream (it is usually stdout)


def rank_zero(sink: MetricSink) -> MetricSink:
    """Gate a sink to process 0 on multihost; pass-through otherwise.

    Evaluated lazily per-emit: ``jax.distributed`` may initialize
    *after* telemetry is constructed, and process index is cheap to
    read (cf. the ``utils/logging`` prefix bug this PR also fixes —
    never cache process identity at construction time).
    """
    return _RankZeroSink(sink)


class _RankZeroSink:
    def __init__(self, inner: MetricSink):
        self.inner = inner

    @staticmethod
    def _is_rank0() -> bool:
        import jax

        try:
            return jax.process_index() == 0
        except RuntimeError:  # backend not initialized yet
            return True

    def emit(self, record: Mapping[str, Any]) -> None:
        if self._is_rank0():
            self.inner.emit(record)

    def close(self) -> None:
        self.inner.close()
