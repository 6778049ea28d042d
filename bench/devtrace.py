"""Reduce a JAX profiler trace to what the per-layer metrics read.

`jax.profiler` writes an `.xplane.pb`; `jax.profiler.ProfileData` reads it.
On a TPU each chip is a plane `/device:TPU:<n>` whose line `XLA Modules`
holds one event per program run and `XLA Ops` one per operation; the host
plane holds the harness's own spans (`TraceAnnotation`s named `bench:*`).
All share one clock. The window is the harness's `bench:window` span.

From that, per chip and averaged over chips: the union of operation
intervals inside the window (busy), device time per program, collective
time, the operations that took most time, and the idle gaps, each named
by the harness span open at its middle (the latest started that is not a
wait; "unattributed" where none is).
"""
from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench:window"
SPAN_PREFIX = "bench:"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|allreduce|allgather|reducescatter", re.I)

Interval = Tuple[float, float]


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def program_name(event_name: str) -> str:
    """`jit_decode_step(12)` -> `jit_decode_step`."""
    return re.sub(r"\(\d+\)$", "", event_name)


HLO = re.compile(r"^%?(\S+) = (.*?) ([a-z][\w-]*)\(")
#: operations that only hold others (their bodies are events of their own)
CONTAINERS = {"while", "conditional", "call"}


def op_name(event_name: str) -> Optional[str]:
    """`%copy.67 = bf16[1,5]{...} copy(...)` -> `copy bf16[1,5] (copy.67)`;
    None for an operation that only holds others."""
    m = HLO.match(event_name)
    if not m:
        return event_name[:120]
    name, shape, opcode = m.groups()
    if opcode in CONTAINERS:
        return None
    shape = "tuple" if shape.startswith("(") else re.sub(r"\{[^}]*\}", "",
                                                           shape)
    return f"{opcode} {shape[:80]} ({name})"


@dataclass
class Span:
    name: str
    start: float
    end: float
    stats: Dict[str, object] = field(default_factory=dict)


@dataclass
class DeviceTrace:
    """One traced window, in seconds from the window's start."""
    window_s: float
    chips: int
    busy_s: float                                    # mean over chips
    program_s: Dict[str, float]                      # summed over chips
    program_runs: Dict[str, List[Interval]]          # chip 0's runs
    op_s: Dict[str, float]                           # summed over chips
    collective_s: float                              # mean over chips
    gaps: List[Tuple[str, float]]                    # chip 0: (span, s)
    spans: List[Span]                                # harness host spans

    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def program_time(self, substring: str) -> Tuple[float, int]:
        """(device seconds, runs) of the programs whose name holds
        `substring`, chip 0."""
        runs = [iv for name, ivs in self.program_runs.items()
                if substring in name for iv in ivs]
        return sum(e - s for s, e in runs), len(runs)

    def top_ops(self, n: int = 10) -> List[List]:
        return [[k, v] for k, v in sorted(self.op_s.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def top_gaps(self, n: int = 10) -> List[List]:
        by: Dict[str, float] = defaultdict(float)
        for name, s in self.gaps:
            by[name] += s
        return [[k, v] for k, v in sorted(by.items(),
                                          key=lambda kv: -kv[1])[:n]]


def _attribute(spans: List[Span], gaps: List[Interval]) -> List[str]:
    """Name each gap by the harness span open at its middle: the latest
    started one that is not a wait (`*.wait`, where the host has nothing
    to hand the device), else the latest started wait, else
    "unattributed". Spans of all host threads take part."""
    order = sorted(range(len(gaps)), key=lambda k: gaps[k][0] + gaps[k][1])
    names = ["unattributed"] * len(gaps)
    active: List[Span] = []
    nxt = 0
    for k in order:
        t = (gaps[k][0] + gaps[k][1]) / 2
        while nxt < len(spans) and spans[nxt].start <= t:
            if spans[nxt].name != WINDOW_SPAN:
                active.append(spans[nxt])
            nxt += 1
        active = [sp for sp in active if sp.end >= t]
        work = [sp for sp in active if not sp.name.endswith(".wait")]
        pick = max(work or active, key=lambda sp: sp.start, default=None)
        if pick is not None:
            names[k] = pick.name[len(SPAN_PREFIX):]
    return names


def reduce_profile(pd) -> DeviceTrace:
    """`jax.profiler.ProfileData` -> DeviceTrace."""
    spans: List[Span] = []
    devices = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            devices.append(plane)
            continue
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    spans.append(Span(ev.name, ev.start_ns * 1e-9,
                                      ev.end_ns * 1e-9, dict(ev.stats)))
    win = [s for s in spans if s.name == WINDOW_SPAN]
    if not win:
        raise ValueError("the trace holds no bench:window span")
    lo, hi = win[0].start, win[0].end
    spans = sorted((Span(s.name, s.start - lo, s.end - lo, s.stats)
                    for s in spans if s.end > lo and s.start < hi),
                   key=lambda s: s.start)
    window = hi - lo
    devices.sort(key=lambda p: int(p.name.rsplit(":", 1)[1]))

    busy_total = coll_total = 0.0
    program_s: Dict[str, float] = defaultdict(float)
    op_s: Dict[str, float] = defaultdict(float)
    program_runs: Dict[str, List[Interval]] = defaultdict(list)
    gaps: List[Tuple[str, float]] = []
    # an operation's name, and whether it is a collective, parsed once
    kinds: Dict[str, Tuple[Optional[str], bool]] = {}
    for chip, plane in enumerate(devices):
        ops: List[Interval] = []
        coll: List[Interval] = []
        for line in plane.lines:
            is_mod = "Module" in line.name
            is_op = line.name == "XLA Ops"
            if not (is_mod or is_op):
                continue
            for ev in line.events:
                s, e = ev.start_ns * 1e-9 - lo, ev.end_ns * 1e-9 - lo
                if e <= 0 or s >= window:
                    continue
                s, e = max(s, 0.0), min(e, window)
                if is_mod:
                    name = program_name(ev.name)
                    program_s[name] += e - s
                    if chip == 0:
                        program_runs[name].append((s, e))
                else:
                    ops.append((s, e))
                    raw = ev.name
                    kind = kinds.get(raw)
                    if kind is None:
                        kind = kinds[raw] = (op_name(raw),
                                             bool(COLLECTIVE.search(raw)))
                    if kind[0] is not None:
                        op_s[kind[0]] += e - s
                    if kind[1]:
                        coll.append((s, e))
        busy = union(ops) if ops else union(
            [iv for ivs in program_runs.values() for iv in ivs])
        busy_total += sum(e - s for s, e in busy)
        coll_total += sum(e - s for s, e in union(coll))
        if chip == 0:
            t, idle = 0.0, []
            for s, e in busy + [(window, window)]:
                if s > t:
                    idle.append((t, s))
                t = max(t, e)
            gaps = [(name, e - s) for name, (s, e)
                    in zip(_attribute(spans, idle), idle)]
    n = max(len(devices), 1)
    return DeviceTrace(window, len(devices), busy_total / n, dict(program_s),
                       dict(program_runs), dict(op_s), coll_total / n, gaps,
                       spans)


def read_trace_dir(directory: Path,
                   mark: Optional[Callable[[str], None]] = None
                   ) -> DeviceTrace:
    """Reduce the newest `.xplane.pb` under `directory`; `mark`, where
    given, is told when the file has been read and when it is reduced."""
    from jax.profiler import ProfileData
    mark = mark or (lambda what: None)
    files = sorted(Path(directory).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    pd = ProfileData.from_file(str(files[-1]))
    mark(f"trace read ({files[-1].stat().st_size} bytes)")
    tr = reduce_profile(pd)
    mark("trace reduced")
    return tr
