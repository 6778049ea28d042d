"""The readers of the program's own spans and counters: each on a
hand-built event list with a known answer, `None` where it finds nothing,
and a tiny traced cell that reports all four."""
import pytest

from conftest import last_json

from bench import peaks, run
from bench.spec import BENCH, load_module

NEW = ("frontdoor.queue_wait_p95_ms", "runtime.wave_dispatch_ms",
       "engine.host_ms_per_step", "engine.slot_occupancy")


def reader(name):
    return load_module(BENCH / "metrics" / f"{name}.py", f"metrics_{name}")


def span(name, start, end, **attrs):
    return (start, "span", f"span{start}", "t",
            dict(attrs, name=name, end=end, parent=None))


def queued(request, start, end):
    return span("frontdoor.queued", start, end, request=request,
                attempt=0, wave="w")


def wave(start, end, **counters):
    return span("engine.wave", start, end, **counters)


EVENTS = [
    # waits of 10, 20, ..., 200 ms over requests 0-19
    *[queued(i, 1.0, 1.0 + 0.01 * (i + 1)) for i in range(20)],
    (1.0, "serve_wave", "w", "frontdoor", {"size": 1}),
    span("replica.serve_wave", 1.0215, 2.0, requests=[0, 1]),   # 1.5 ms
    span("replica.serve_wave", 1.2025, 2.0, requests=[19]),     # 2.5 ms
    span("replica.serve_wave", 1.5, 2.0, requests=[99]),        # no join
    wave(1.02, 1.5, steps=9, lane_steps=20, live_lane_steps=15,
         decode_s=0.30, sync_s=0.21, prefill_s=0.1),
    wave(1.6, 1.8, steps=1, lane_steps=4, live_lane_steps=3,
         decode_s=0.05, sync_s=0.04, prefill_s=0.1),
    (1.7, "jit_compile", "jit(prefill)", "jax", {"s": 0.2,
                                                 "cache_s": None}),
]


@pytest.mark.parametrize("name,want", [
    ("frontdoor.queue_wait_p95_ms", 190.5),
    ("runtime.wave_dispatch_ms", 2.0),
    ("engine.host_ms_per_step", 10.0),
    ("engine.slot_occupancy", 75.0),
])
def test_reader_on_known_events(name, want):
    assert reader(name).read({"events": EVENTS}) == pytest.approx(want)


@pytest.mark.parametrize("name", NEW)
def test_reader_finds_nothing(name):
    r = reader(name)
    assert r.read({"events": []}) is None
    assert r.read({}) is None
    # the parent program logs no spans: its events read as nothing
    assert r.read({"events": [e for e in EVENTS if e[1] != "span"]}) \
        is None


def test_tiny_traced_cell_reports_the_program_spans(tiny_root, capsys,
                                                    monkeypatch):
    monkeypatch.setitem(peaks.PEAKS, "cpu", dict(peaks.PEAKS["TPU v5 lite"]))
    rc = run.main(["--workload", "tiny-lm-chat", "--seed", str(2 ** 31 + 9),
                   "--seconds", "2", "--trace", "1"], require_tpu=False,
                  root=tiny_root)
    assert rc == 0
    got = last_json(capsys.readouterr().out)["metrics"]
    assert got["frontdoor.queue_wait_p95_ms"]["value"] >= 0
    assert got["runtime.wave_dispatch_ms"]["value"] > 0
    assert got["engine.host_ms_per_step"]["value"] > 0
    assert 0 < got["engine.slot_occupancy"]["value"] <= 100
    assert got["engine.slot_occupancy"]["unit"] == "%"
