"""From a profiler trace (``*.xplane.pb``) to numbers: device busy time
as the union of op intervals, idle gaps attributed to what the host was
doing, sums over named kernels, collectives and their exposed part.

Read with ``jax.profiler.ProfileData`` alone. Checked on a small recorded
trace in ``perfbench/tests``.
"""

from __future__ import annotations

import re
from collections import defaultdict
from pathlib import Path
from typing import Iterable

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all|psum|ppermute"
)
# Host spans that only wrap the whole capture say nothing about a gap.
_NOT_A_CAUSE = re.compile(r"^(\$|Thread|process_|<unknown>)")


def find_xplane(trace_dir) -> Path | None:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    return found[-1] if found else None


def union_length(intervals: Iterable[tuple[float, float]]) -> float:
    total, cur_lo, cur_hi = 0.0, None, None
    for lo, hi in sorted(intervals):
        if cur_hi is None or lo > cur_hi:
            if cur_hi is not None:
                total += cur_hi - cur_lo
            cur_lo, cur_hi = lo, hi
        elif hi > cur_hi:
            cur_hi = hi
    if cur_hi is not None:
        total += cur_hi - cur_lo
    return total


def merge(intervals: Iterable[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(a, b) for a, b in out]


def gaps_of(busy: list[tuple[float, float]]) -> list[tuple[float, float]]:
    return [(a[1], b[0]) for a, b in zip(busy, busy[1:]) if b[0] > a[1]]


def subtract(intervals, cover) -> float:
    """Length of ``intervals`` (merged) not covered by ``cover`` (merged)."""
    total, j = 0.0, 0
    for lo, hi in intervals:
        cur = lo
        while j < len(cover) and cover[j][1] <= cur:
            j += 1
        k = j
        while k < len(cover) and cover[k][0] < hi:
            if cover[k][0] > cur:
                total += cover[k][0] - cur
            cur = max(cur, cover[k][1])
            k += 1
        if cur < hi:
            total += hi - cur
    return total


_SHAPE = re.compile(r"([a-z]+\d+)\[([\d,]*)\]")
_OPCODE = re.compile(r"\)?\s([a-z][a-z-]*)\(")


def op_key(name: str) -> str:
    """``name_opcode_dtype_shape_``: the op's name without its numeric
    suffix, its opcode, and the element type and dimensions of its output
    (of a tuple, the first member that has dimensions), so that the same
    op of two layers reads as one kind. The TPU trace names an op by its
    whole HLO line, ``%attn.71 = (bf16[192,1024,64]{...}, ...)
    custom-call(...)``; a bare name is returned as it is."""
    head, _, rest = name.partition(" = ")
    base = re.sub(r"[.\d]+$", "", head.strip().lstrip("%"))
    if not rest:
        return base
    # The output's shape ends where the opcode's argument list opens.
    m = _OPCODE.search(rest)
    out, opcode = (rest[: m.start() + 1], m.group(1)) if m else (rest, "")
    shapes = _SHAPE.findall(out)
    dtype, dims = next(((d, x) for d, x in shapes if x), shapes[0] if shapes else ("", ""))
    key = "_".join(p for p in (base, opcode, dtype, "_".join(x for x in dims.split(",") if x)) if p)
    return key + "_"


class Trace:
    def __init__(self, device_ops, host_spans, device_programs=None):
        """``device_ops``: {device index: [(name, key, start_s, end_s)]};
        ``host_spans``: [(name, start_s, end_s)]; ``device_programs``:
        {device index: [(name, start_s, end_s)]}, the compiled programs."""
        self.device_programs = device_programs or {}
        self.device_ops = {d: sorted(v, key=lambda e: e[2]) for d, v in device_ops.items() if v}
        self.host_spans = host_spans
        if not self.device_ops:
            raise RuntimeError("the trace holds no device operation")
        self._busy = {
            d: merge((e[2], e[3]) for e in ops) for d, ops in self.device_ops.items()
        }

    # ---- construction ---------------------------------------------------
    @classmethod
    def from_file(cls, path, n_devices: int | None = None) -> "Trace":
        from jax.profiler import ProfileData

        data = ProfileData.from_file(str(path))
        device_ops: dict[int, list] = defaultdict(list)
        programs: dict[int, list] = defaultdict(list)
        host: list = []
        for plane in data.planes:
            m = DEVICE_PLANE.match(plane.name)
            if m:
                for line in plane.lines:
                    if line.name == MODULES_LINE:
                        for ev in line.events:
                            lo = ev.start_ns * 1e-9
                            programs[int(m.group(1))].append((ev.name, lo, lo + ev.duration_ns * 1e-9))
                    if line.name != OPS_LINE:
                        continue
                    for ev in line.events:
                        lo = ev.start_ns * 1e-9
                        device_ops[int(m.group(1))].append(
                            (ev.name, op_key(ev.name), lo, lo + ev.duration_ns * 1e-9)
                        )
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for ev in line.events:
                        lo = ev.start_ns * 1e-9
                        host.append((ev.name, lo, lo + ev.duration_ns * 1e-9))
        if n_devices is not None:
            device_ops = {d: v for d, v in device_ops.items() if d < n_devices}
        return cls(device_ops, host, {d: v for d, v in programs.items() if d in device_ops})

    # ---- busy and idle --------------------------------------------------
    @property
    def window_s(self) -> float:
        return sum(b[-1][1] - b[0][0] for b in self._busy.values()) / len(self._busy)

    @property
    def busy_s(self) -> float:
        return sum(sum(hi - lo for lo, hi in b) for b in self._busy.values()) / len(self._busy)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def ops(self, pattern: str | None = None, device: int | None = None):
        rx = re.compile(pattern) if pattern else None
        for d, ops in self.device_ops.items():
            if device is not None and d != device:
                continue
            for e in ops:
                # Match the op's own key: its HLO line also names its
                # operands, and a consumer of a kernel is not the kernel.
                if rx is None or rx.search(e[1]):
                    yield d, e

    def seconds(self, pattern: str) -> float:
        """Device seconds in ops matching ``pattern``, averaged over chips."""
        return sum(e[3] - e[2] for _, e in self.ops(pattern)) / len(self.device_ops)

    def idle_gaps(self, device: int | None = None) -> list[tuple[float, float]]:
        d = min(self._busy) if device is None else device
        return gaps_of(self._busy[d])

    def attribute_gaps(self, top: int = 10) -> list[list]:
        """Idle seconds by the innermost host span that covers the middle
        of each gap."""
        spans = sorted(
            (s for s in self.host_spans if not _NOT_A_CAUSE.match(s[0])), key=lambda s: s[1]
        )
        starts = [s[1] for s in spans]
        import bisect

        by: dict[str, float] = defaultdict(float)
        for lo, hi in self.idle_gaps():
            mid = (lo + hi) / 2
            best = None
            i = bisect.bisect_right(starts, mid)
            for s in spans[max(0, i - 400):i]:
                if s[1] <= mid <= s[2] and (best is None or s[2] - s[1] < best[2] - best[1]):
                    best = s
            name = re.sub(r"[^A-Za-z0-9_./-]+", "_", best[0])[:60] if best else "_no_host_span_"
            by[name] += hi - lo
        return [[k, v] for k, v in sorted(by.items(), key=lambda kv: -kv[1])[:top]]

    def top_ops(self, top: int = 10) -> list[list]:
        by: dict[str, float] = defaultdict(float)
        for _, e in self.ops():
            by[e[1]] += e[3] - e[2]
        n = len(self.device_ops)
        return [[k, v / n] for k, v in sorted(by.items(), key=lambda kv: -kv[1])[:top]]

    def breakdown(self) -> dict[str, list]:
        return {"device_ops": self.top_ops(), "idle_gaps": self.attribute_gaps()}

    # ---- collectives ----------------------------------------------------
    def collective_seconds(self) -> tuple[float, float]:
        """(seconds in collective ops, seconds of them with no compute
        running on that device), averaged over chips."""
        total = exposed = 0.0
        for d, ops in self.device_ops.items():
            coll = merge((e[2], e[3]) for e in ops if COLLECTIVE.search(e[1]))
            comp = merge((e[2], e[3]) for e in ops if not COLLECTIVE.search(e[1]))
            total += sum(hi - lo for lo, hi in coll)
            exposed += subtract(coll, comp)
        n = len(self.device_ops)
        return total / n, exposed / n

    def program_durations(self, pattern: str, device: int | None = None) -> list[float]:
        """Durations of the compiled programs whose name matches."""
        rx = re.compile(pattern)
        d = min(self.device_programs) if device is None and self.device_programs else device
        return [e[2] - e[1] for e in self.device_programs.get(d, []) if rx.search(e[0])]

    def host_span_durations(self, name: str) -> list[float]:
        return [s[2] - s[1] for s in self.host_spans if s[0] == name]


def dump(path, limit: int = 25) -> None:
    """Print what a trace holds: planes, lines, and a sample of events."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    for plane in data.planes:
        print(f"PLANE {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            print(f"  LINE {line.name!r}: {len(events)} events")
            names: dict[str, list] = defaultdict(lambda: [0, 0.0])
            for ev in events:
                names[ev.name][0] += 1
                names[ev.name][1] += ev.duration_ns * 1e-9
            for name, (n, s) in sorted(names.items(), key=lambda kv: -kv[1][1])[:limit]:
                print(f"    {n:6d} x {s:10.6f} s  {name[:110]}")
            for ev in events[:2]:
                print(f"    e.g. {ev.name[:80]!r} start_ns={ev.start_ns} dur_ns={ev.duration_ns} stats={dict(ev.stats)}")


if __name__ == "__main__":
    import sys

    dump(sys.argv[1])
