"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

What it reads, as the TPU runtime writes it:

- device planes ``/device:TPU:<n>``: line ``XLA Ops`` holds one event per
  executed HLO op (name ``%<op>.<k> = <shape> <opcode>(...)``);
- the host plane ``/host:CPU``: host-to-device copies as a
  ``tpu::System::TransferToDevice`` event, tied by its flow id (``_p``) to
  the ``...=>IssueEvent=>Done`` event (``_c``) that ends it; and the
  benchmark's own ``jax.profiler.TraceAnnotation`` spans, all named
  ``bench.<what>``.

Host and device events share one clock in this file. The window is the
``bench.window`` span when there is one.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field
from typing import Any

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
_H2D_ISSUE = "tpu::System::TransferToDevice"
_H2D_DONE = "tpu::System::TransferToDevice=>IssueEvent=>Done"
_SUFFIX = re.compile(r"\.\d+$")


def op_name(event_name: str) -> str:
    """Stable name of an ``XLA Ops`` event: ``%fusion.18 = f32[..] ...`` ->
    ``fusion``; a Pallas kernel keeps its function's name
    (``%modulus_project.1 = ...`` -> ``modulus_project``)."""
    head = event_name.split(" = ", 1)[0].strip().lstrip("%")
    return _SUFFIX.sub("", head)


def merge(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Union of [start, end) intervals, sorted and disjoint."""
    out: list[tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def covered(merged: list[tuple[float, float]], a: float, b: float) -> float:
    """Length of [a, b) that the merged intervals cover."""
    total = 0.0
    i = max(bisect.bisect_right([s for s, _ in merged], a) - 1, 0)
    for s, e in merged[i:]:
        if s >= b:
            break
        lo, hi = max(s, a), min(e, b)
        if hi > lo:
            total += hi - lo
    return total


@dataclass
class Span:
    name: str
    start_ns: float
    end_ns: float


@dataclass
class Reduction:
    """What a trace says, in nanoseconds on the trace's own clock."""
    window: tuple[float, float]
    chips: int
    busy: list[list[tuple[float, float]]]        # merged op intervals, per chip
    ops: dict[str, list[float]]                  # name -> [ns, count], all chips
    op_events: list[tuple[str, float, float]]    # (name, start, end), chip 0
    h2d: list[tuple[float, float, int]]          # (start, end, bytes)
    spans: list[Span] = field(default_factory=list)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        """Seconds in the window in which an op ran, averaged over chips."""
        a, b = self.window
        return sum(covered(m, a, b) for m in self.busy) / self.chips * 1e-9

    def busy_between(self, a: float, b: float, chip: int = 0) -> float:
        """Busy seconds of one chip inside [a, b) ns."""
        if chip >= len(self.busy):
            return 0.0
        return covered(self.busy[chip], a, b) * 1e-9

    def spans_named(self, name: str) -> list[Span]:
        return [s for s in self.spans if s.name == name]

    def op_seconds(self, pattern: str) -> float:
        """Device seconds of chip-0 ops inside the window whose stable name
        matches ``pattern`` (a regular expression)."""
        rx = re.compile(pattern)
        lo, hi = self.window
        return sum(max(0.0, min(e, hi) - max(s, lo))
                   for n, s, e in self.op_events if rx.search(n)) * 1e-9

    def h2d_seconds(self) -> float:
        """Union of host-to-device copy intervals inside the window."""
        a, b = self.window
        return covered(merge([(s, e) for s, e, _ in self.h2d]), a, b) * 1e-9

    def top_ops(self, k: int = 10) -> list[list[Any]]:
        """The ``k`` device ops that took most time, in seconds."""
        rows = sorted(self.ops.items(), key=lambda kv: -kv[1][0])[:k]
        return [[name, ns * 1e-9] for name, (ns, _) in rows]

    def idle_gaps(self, k: int = 10) -> list[list[Any]]:
        """Idle time of chip 0 in the window, by the innermost benchmark span
        the host was in at each gap's middle; the ``k`` largest."""
        a, b = self.window
        merged = self.busy[0] if self.busy else []
        gaps, cursor = [], a
        for s, e in merged:
            if e <= a:
                continue
            if s >= b:
                break
            if s > cursor:
                gaps.append((cursor, s))
            cursor = max(cursor, e)
        if cursor < b:
            gaps.append((cursor, b))
        spans = [s for s in self.spans if s.name != WINDOW_SPAN]
        by_name: dict[str, float] = {}
        for g0, g1 in gaps:
            mid = 0.5 * (g0 + g1)
            inside = [s for s in spans if s.start_ns <= mid < s.end_ns]
            name = (min(inside, key=lambda s: s.end_ns - s.start_ns).name
                    if inside else "(no span)")
            by_name[name] = by_name.get(name, 0.0) + (g1 - g0) * 1e-9
        rows = sorted(by_name.items(), key=lambda kv: -kv[1])[:k]
        return [[n, v] for n, v in rows]


def find_trace(log_dir: str) -> str:
    """The newest ``.xplane.pb`` under a ``jax.profiler`` log directory."""
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(files, key=os.path.getmtime)


def _stats(event: Any) -> dict[str, Any]:
    return {k: v for k, v in event.stats}


def reduce_trace(path: str) -> Reduction:
    """Read one ``.xplane.pb`` into a :class:`Reduction`."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    busy, op_events = [], []
    ops: dict[str, list[float]] = {}
    spans: list[Span] = []
    issues: dict[Any, tuple[float, int]] = {}
    dones: dict[Any, float] = {}
    lo, hi = float("inf"), float("-inf")
    device_planes = sorted((p for p in data.planes
                            if p.name.startswith("/device:TPU:")),
                           key=lambda p: int(p.name.rsplit(":", 1)[1]))
    for chip, plane in enumerate(device_planes):
        intervals = []
        for line in plane.lines:
            if line.name == "XLA Ops":
                for ev in line.events:
                    s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                    name = op_name(ev.name)
                    intervals.append((s, e))
                    acc = ops.setdefault(name, [0.0, 0])
                    acc[0] += ev.duration_ns
                    acc[1] += 1
                    if chip == 0:
                        op_events.append((name, s, e))
                    lo, hi = min(lo, s), max(hi, e)
        if intervals:
            busy.append(merge(intervals))
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                name = ev.name
                if name.startswith(SPAN_PREFIX):
                    spans.append(Span(name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
                elif name == _H2D_ISSUE:
                    st = _stats(ev)
                    issues[st.get("_p")] = (ev.start_ns,
                                            int(st.get("size", 0)))
                elif name == _H2D_DONE:
                    dones[_stats(ev).get("_c")] = (ev.start_ns
                                                   + ev.duration_ns)
    h2d = [(s, dones[k], size) for k, (s, size) in issues.items()
           if k is not None and k in dones]
    win = [s for s in spans if s.name == WINDOW_SPAN]
    window = (win[0].start_ns, win[0].end_ns) if win else (lo, hi)
    op_events.sort(key=lambda t: t[1])
    return Reduction(window=window, chips=max(len(busy), 1), busy=busy,
                     ops=ops, op_events=op_events, h2d=h2d,
                     spans=sorted(spans, key=lambda s: s.start_ns))
