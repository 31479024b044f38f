"""The program's own marks in a traced run's ``.xplane.pb``.

Two kinds, both written by the program, not by the benchmark:

- the scope path of each device op. ``jax.named_scope`` (the RAAR phases
  ``raar/<phase>`` of ``apps/ptycho/solver.py``) reaches the compiled
  program's op metadata, and from there the ``tf_op`` stat of the op's event
  *metadata* in the device plane. ``jax.profiler.ProfileData`` exposes the
  stats of events but not those of their metadata, so chip 0's ``XLA Ops``
  line is decoded here straight from the protobuf wire format of
  ``XSpace`` -> ``XPlane`` (``lines``, ``event_metadata``,
  ``stat_metadata``). The TPU compiler leaves some ops with no metadata:
  the fusions its scatter rewrite makes (the overlap scatter-adds, most of
  a RAAR step's time), its async copy and slice halves, and the complex
  split and combine calls around the Pallas kernels. Such an op takes the
  phase that most of its operands have, by the operand names in its HLO
  text, within its own program (``program_id``);
- the pipeline's profiler spans, named ``repro.<stage>`` (``repro.batch``,
  ``repro.pump``, ``repro.batch_fn``, ..., ``repro.lane.write``), with their
  arguments (``batch_index``, ...), read through ``ProfileData``.

Times are in nanoseconds on the trace's own clock, as ``chipbench.xplane``
gives them.
"""
from __future__ import annotations

import functools
import os
import re
from dataclasses import dataclass, field
from typing import Any, Iterator

from chipbench import xplane

PROGRAM_PREFIX = "repro."
_PHASE = re.compile(r"(?:^|/)raar/([A-Za-z0-9_]+)(?:/|:|$)")
_OPERAND = re.compile(r"[ (]%([\w.\-]+)")

# field numbers of tsl/profiler/protobuf/xplane.proto
_SPACE_PLANES = 1
_PLANE_NAME, _PLANE_LINES, _PLANE_EVENT_MD, _PLANE_STAT_MD = 2, 3, 4, 5
_LINE_NAME, _LINE_TIMESTAMP_NS, _LINE_EVENTS = 2, 3, 4
_EVENT_MD_ID, _EVENT_OFFSET_PS, _EVENT_DURATION_PS = 1, 2, 3
_MD_NAME, _EVENT_MD_STATS = 2, 5
_STAT_MD_ID, _STAT_UINT, _STAT_INT, _STAT_STR, _STAT_REF = 1, 3, 4, 5, 7
_MAP_KEY, _MAP_VALUE = 1, 2


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf: bytes, i: int, end: int) -> Iterator[tuple[int, Any]]:
    """``(field number, value)`` of one message in ``buf[i:end]``: an int for
    a varint, a ``(start, end)`` pair for a length-delimited field, the raw
    bytes for a fixed-width one."""
    while i < end:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif kind == 1:
            value, i = buf[i:i + 8], i + 8
        elif kind == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {kind} at byte {i} of the trace")
        yield key >> 3, value


def _text(buf: bytes, span: tuple[int, int]) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _map_entry(buf: bytes, span: tuple[int, int]
               ) -> tuple[int, tuple[int, int]]:
    key, value = 0, (span[0], span[0])
    for num, v in _fields(buf, *span):
        if num == _MAP_KEY:
            key = _signed(v)
        elif num == _MAP_VALUE:
            value = v
    return key, value


def phase_of(scope: str) -> str | None:
    """``<phase>`` of the innermost ``raar/<phase>`` in a scope path."""
    found = _PHASE.findall(scope)
    return found[-1] if found else None


@dataclass
class DeviceOp:
    name: str          # the event's name, ``%<op>.<k> = <shape> ...``
    scope: str         # the ``tf_op`` of its metadata; "" where it has none
    phase: str | None  # its RAAR phase: its scope's, else its operands'
    start_ns: float
    end_ns: float


@dataclass
class HostSpan:
    name: str
    start_ns: float
    end_ns: float
    args: dict[str, Any] = field(default_factory=dict)

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9


@dataclass
class ProgramTrace:
    ops: list[DeviceOp]          # chip 0's ``XLA Ops``, by start
    spans: list[HostSpan]        # ``repro.*`` host spans, by start

    def spans_named(self, name: str) -> list[HostSpan]:
        return [s for s in self.spans if s.name == name]

    def phase_ns(self, a: float, b: float) -> dict[str | None, float]:
        """Device ns of chip 0's ops inside [a, b), by RAAR phase; ops
        with no phase count under ``None``."""
        out: dict[str | None, float] = {}
        for op in self.ops:
            if op.end_ns <= a or op.start_ns >= b:
                continue
            ns = min(op.end_ns, b) - max(op.start_ns, a)
            out[op.phase] = out.get(op.phase, 0.0) + ns
        return out


@dataclass
class _OpInfo:
    name: str = ""
    scope: str = ""
    program: int = 0

    @property
    def short(self) -> str:
        return self.name.partition(" = ")[0].strip().lstrip("%")

    @property
    def operands(self) -> list[str]:
        return _OPERAND.findall(self.name.partition(" = ")[2])


def _stat_names(buf: bytes, entries: list[tuple[int, int]]
                ) -> dict[int, str]:
    names: dict[int, str] = {}
    for entry in entries:
        key, md = _map_entry(buf, entry)
        for num, v in _fields(buf, *md):
            if num == _MD_NAME:
                names[key] = _text(buf, v)
    return names


def _op_info(buf: bytes, md: tuple[int, int], stat_names: dict[int, str]
             ) -> _OpInfo:
    """Name, ``tf_op`` and ``program_id`` of one ``XEventMetadata``."""
    info = _OpInfo()
    for num, v in _fields(buf, *md):
        if num == _MD_NAME:
            info.name = _text(buf, v)
        elif num == _EVENT_MD_STATS:
            stat, value = "", None
            for snum, sv in _fields(buf, *v):
                if snum == _STAT_MD_ID:
                    stat = stat_names.get(sv, "")
                elif snum == _STAT_STR:
                    value = _text(buf, sv)
                elif snum == _STAT_REF:
                    value = stat_names.get(sv, "")
                elif snum in (_STAT_UINT, _STAT_INT):
                    value = sv
            if stat == "tf_op" and isinstance(value, str):
                info.scope = value
            elif stat == "program_id" and isinstance(value, int):
                info.program = value
    return info


def _phases(infos: dict[int, _OpInfo]) -> dict[int, str | None]:
    """The phase of each op: its scope's, else the one that most of its
    operands have (the first of them on a tie), through operands that have
    no scope either."""
    by_name = {(i.program, i.short): k for k, i in infos.items()}
    memo: dict[int, str | None] = {}

    def resolve(k: int, seen: frozenset[int]) -> str | None:
        if k in memo:
            return memo[k]
        info = infos[k]
        phase = phase_of(info.scope)
        if phase is None and not info.scope:
            votes: dict[str, int] = {}
            for operand in info.operands:
                j = by_name.get((info.program, operand))
                if j is None or j in seen:
                    continue
                p = resolve(j, seen | {j})
                if p is not None:
                    votes[p] = votes.get(p, 0) + 1
            if votes:
                phase = max(votes, key=lambda p: votes[p])
        memo[k] = phase
        return phase

    return {k: resolve(k, frozenset([k])) for k in infos}


def _device_ops(buf: bytes, plane: tuple[int, int]) -> list[DeviceOp]:
    lines, event_md, stat_md = [], [], []
    for num, v in _fields(buf, *plane):
        if num == _PLANE_LINES:
            lines.append(v)
        elif num == _PLANE_EVENT_MD:
            event_md.append(v)
        elif num == _PLANE_STAT_MD:
            stat_md.append(v)
    stat_names = _stat_names(buf, stat_md)
    infos = {}
    for entry in event_md:
        key, md = _map_entry(buf, entry)
        infos[key] = _op_info(buf, md, stat_names)
    phases = _phases(infos)
    ops: list[DeviceOp] = []
    for line in lines:
        name, t0, events = "", 0, []
        for num, v in _fields(buf, *line):
            if num == _LINE_NAME:
                name = _text(buf, v)
            elif num == _LINE_TIMESTAMP_NS:
                t0 = _signed(v)
            elif num == _LINE_EVENTS:
                events.append(v)
        if name != "XLA Ops":
            continue
        for ev in events:
            md_id, offset, duration = 0, None, 0
            for num, v in _fields(buf, *ev):
                if num == _EVENT_MD_ID:
                    md_id = v
                elif num == _EVENT_OFFSET_PS:
                    offset = _signed(v)
                elif num == _EVENT_DURATION_PS:
                    duration = v
            if offset is None:            # an aggregate: no time of its own
                continue
            info = infos.get(md_id, _OpInfo())
            start = t0 + offset / 1e3
            ops.append(DeviceOp(info.name, info.scope, phases.get(md_id),
                                start, start + duration / 1e3))
    return ops


def _chip0_plane(buf: bytes) -> tuple[int, int] | None:
    for num, plane in _fields(buf, 0, len(buf)):
        if num != _SPACE_PLANES:
            continue
        for pnum, v in _fields(buf, *plane):
            if pnum == _PLANE_NAME:
                if _text(buf, v) == "/device:TPU:0":
                    return plane
                break
    return None


def _host_spans(path: str) -> list[HostSpan]:
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    spans = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PROGRAM_PREFIX):
                    spans.append(HostSpan(ev.name, ev.start_ns,
                                          ev.start_ns + ev.duration_ns,
                                          dict(ev.stats)))
    return spans


@functools.lru_cache(maxsize=4)
def read_file(path: str) -> ProgramTrace:
    """Chip 0's ops with their scope paths and phases, and the program's
    host spans, of one ``.xplane.pb``; read once per path."""
    with open(path, "rb") as f:
        buf = f.read()
    plane = _chip0_plane(buf)
    ops = _device_ops(buf, plane) if plane is not None else []
    ops.sort(key=lambda op: op.start_ns)
    spans = sorted(_host_spans(path), key=lambda s: s.start_ns)
    return ProgramTrace(ops, spans)


def for_run(run: Any) -> ProgramTrace | None:
    """The program trace of a ``--trace 1`` run: the ``.xplane.pb`` the
    harness left under ``<OUT_ROOT>/<workload>/trace``; None without one."""
    from chipbench import harness
    if run.trace is None:
        return None
    try:
        path = xplane.find_trace(os.path.join(harness.OUT_ROOT, run.workload,
                                              "trace"))
    except FileNotFoundError:
        return None
    return read_file(path)


def phase_ms(run: Any, phase: str) -> float | None:
    """Device ms of chip 0's ops in ``raar/<phase>`` inside the
    ``bench.ptycho.refine`` spans, per refinement iteration; None where no
    op there has a RAAR phase (a program without the scopes)."""
    prog = for_run(run)
    spans = run.trace.spans_named("bench.ptycho.refine") if prog else []
    if not spans:
        return None
    by_phase: dict[str | None, float] = {}
    for s in spans:
        for k, ns in prog.phase_ns(s.start_ns, s.end_ns).items():
            by_phase[k] = by_phase.get(k, 0.0) + ns
    if not any(k is not None for k in by_phase):
        return None
    iters = run.facts["refine_iterations"] * len(spans)
    return by_phase.get(phase, 0.0) * 1e-6 / iters
