"""The trace reduction, on a hand-built trace and on a small trace
recorded on a TPU v5e chip (`bench/testdata/sample.xplane.pb`)."""
from dataclasses import dataclass, field
from typing import List

import pytest

from conftest import BENCH

from bench.devtrace import read_trace_dir, reduce_profile, union

MS = 1_000_000  # ns


@dataclass
class Ev:
    name: str
    start_ns: float
    end_ns: float
    stats: list = field(default_factory=list)

    @property
    def duration_ns(self):
        return self.end_ns - self.start_ns


@dataclass
class Line:
    name: str
    events: List[Ev]


@dataclass
class Plane:
    name: str
    lines: List[Line]


@dataclass
class Profile:
    planes: List[Plane]


def _profile():
    host = Plane("/host:CPU", [Line("python", [
        Ev("bench:window", 10 * MS, 110 * MS),
        Ev("bench:engine.serve", 20 * MS, 80 * MS, [("width", 2)]),
        Ev("bench:generator.wait", 30 * MS, 50 * MS),
        Ev("bench:generator.wait", 85 * MS, 110 * MS),
        Ev("other", 0, 200 * MS)])])
    dev = Plane("/device:TPU:0", [
        Line("XLA Modules", [Ev("jit_decode_step(3)", 30 * MS, 40 * MS),
                             Ev("jit_decode_step(3)", 50 * MS, 60 * MS),
                             Ev("jit_prefill(1)", 0, 25 * MS)]),
        Line("XLA Ops", [Ev("fusion.1", 30 * MS, 40 * MS),
                         Ev("all-reduce.2", 50 * MS, 55 * MS),
                         Ev("fusion.3", 54 * MS, 60 * MS),
                         Ev("fusion.4", 0, 25 * MS)])])
    return Profile([host, dev])


def test_union_merges_overlaps():
    assert union([(3, 4), (0, 1), (0.5, 2)]) == [(0, 2), (3, 4)]


def test_busy_programs_collectives_and_gaps():
    tr = reduce_profile(_profile())
    assert tr.window_s == pytest.approx(0.1)
    # ops inside the window: [10,25) + [30,40) + [50,60) ms
    assert tr.busy_s == pytest.approx(0.035)
    assert tr.idle_share() == pytest.approx(0.65)
    s, n = tr.program_time("decode_step")
    assert n == 2 and s == pytest.approx(0.02)
    assert tr.collective_s == pytest.approx(0.005)
    gaps = dict((k, v) for k, v in tr.top_gaps())
    # [25,30) and [40,50) have their middles in engine.serve (the second
    # also in a later wait of another thread: work beats waiting);
    # [60,110) has its middle (85 ms) in a wait alone
    assert gaps["engine.serve"] == pytest.approx(0.015)
    assert gaps["generator.wait"] == pytest.approx(0.05)
    assert sum(gaps.values()) == pytest.approx(0.065)
    assert tr.top_ops(1)[0][0] == "fusion.4"


def test_a_trace_without_the_window_span_is_refused():
    p = _profile()
    p.planes[0].lines[0].events.pop(0)
    with pytest.raises(ValueError):
        reduce_profile(p)


def test_recorded_chip_trace():
    """Three runs of a matmul program and of a reduction program, each
    matmul inside a `bench:step` span, recorded on one v5e chip. The
    device's clock runs about 1.2 ms ahead of the host's in this trace,
    so the first matmul lands just before the window span opens and five
    of the six runs count."""
    tr = read_trace_dir(BENCH / "testdata")
    assert tr.chips == 1
    runs = {k: len(v) for k, v in tr.program_runs.items()}
    assert runs == {"jit__lambda": 5}
    assert 0 < tr.busy_s < tr.window_s
    assert any(name == "host.sleep" for name, _ in tr.gaps)
