"""The program's own spans and counters (`repro.core.profiler`): span
records in the event log, kept out of the task counters; the serving
path's spans through a tiny model behind the `FrontDoor` on the thread
backend; their trace annotations in a CPU profiler trace; the
`jit_compile` counter; the prefill program's stable name."""
import threading

import jax
import numpy as np
import pytest
from jax.profiler import TraceAnnotation

from repro import core
from repro.configs.registry import get_smoke_config
from repro.core import profiler
from repro.models import build_model
from repro.serving import Request, ServingEngine
from repro.serving.frontdoor import FrontDoor

PROMPT = 8
WARM_WIDTHS = 2


@pytest.fixture(scope="module")
def engine():
    model = build_model(get_smoke_config("stablelm-1.6b"))
    eng = ServingEngine(model, model.init(jax.random.PRNGKey(0)),
                        max_seq=64)
    eng.warm([PROMPT], WARM_WIDTHS)
    return eng


@pytest.fixture()
def cluster():
    c = core.init(num_nodes=1, workers_per_node=2)
    yield c
    core.shutdown()


def spans(gcs, name):
    return [e for e in gcs.events()
            if e[1] == "span" and e[4]["name"] == name]


def prompt(seed):
    return np.random.default_rng(seed).integers(
        1, 200, PROMPT).astype(np.int32)


def test_span_record_parent_and_attrs(cluster):
    with profiler.span("outer", "t", width=2) as outer:
        with profiler.span("inner", "t") as inner:
            inner.set(steps=3)
    (o,), (i,) = spans(cluster.gcs, "outer"), spans(cluster.gcs, "inner")
    assert o[2] == outer.id and o[3] == "t" and o[4]["width"] == 2
    assert o[4]["parent"] is None and i[4]["parent"] == outer.id
    assert i[4]["steps"] == 3
    assert o[0] <= i[0] <= i[4]["end"] <= o[4]["end"]


def test_span_closed_on_another_thread(cluster):
    sp = profiler.open_span("handoff", "t", request=7)
    t = threading.Thread(target=sp.close, kwargs={"wave": "w1"})
    t.start()
    t.join()
    (rec,) = spans(cluster.gcs, "handoff")
    assert rec[4]["request"] == 7 and rec[4]["wave"] == "w1"
    assert rec[4]["end"] >= rec[0]


def test_spans_stay_out_of_the_task_counters(cluster):
    @core.remote
    def f():
        with profiler.span("in_task", "t"):
            return 1

    core.get([f.submit() for _ in range(3)])
    before = profiler.summarize(cluster.gcs)
    for _ in range(5):
        with profiler.span("more", "t"):
            pass
    after = profiler.summarize(cluster.gcs)
    assert len(spans(cluster.gcs, "in_task")) == 3
    for k in ("num_tasks", "spill_fraction", "local_fraction"):
        assert after[k] == before[k]
    assert before["num_tasks"] == 3


def test_no_cluster_no_record():
    """With no cluster running there is no log: spans skip their record
    and do not raise."""
    core.shutdown()
    with profiler.span("alone", "t"):
        pass
    profiler.open_span("alone", "t").close()


def test_every_request_queued_once_before_its_wave(engine, cluster):
    fd = FrontDoor(lambda: engine, num_replicas=1, min_replicas=1,
                   max_replicas=1, max_batch=WARM_WIDTHS)
    try:
        tickets = [fd.submit(prompt(i), max_new_tokens=2 + i % 3)
                   for i in range(6)]
        ids = {t.request_id for t in tickets}
        for t in tickets:
            t.result(120)
    finally:
        fd.close()
    queued = spans(cluster.gcs, "frontdoor.queued")
    waves = spans(cluster.gcs, "replica.serve_wave")
    assert sorted(e[4]["request"] for e in queued) == sorted(ids)
    wave_of = {r: w for w in waves for r in w[4]["requests"]}
    assert set(wave_of) == ids
    for q in queued:
        w = wave_of[q[4]["request"]]
        assert q[0] <= q[4]["end"] <= w[0]
        assert q[4]["wave"]
    # each engine wave nests in its replica span, on the actor's thread
    by_id = {w[2]: w for w in waves}
    for e in spans(cluster.gcs, "engine.wave"):
        assert e[4]["parent"] in by_id


def test_engine_wave_counters_are_exact(engine, cluster):
    budgets = [3, 7, 5]
    engine.serve([Request(i, prompt(i), b) for i, b in enumerate(budgets)],
                 max_wave=len(budgets))
    (w,) = spans(cluster.gcs, "engine.wave")
    c = w[4]
    assert (c["width"], c["prompt_len"], c["requests"]) == (3, PROMPT,
                                                            [0, 1, 2])
    assert c["live_lane_steps"] == sum(budgets)
    assert c["lane_steps"] == len(budgets) * max(budgets)
    assert c["steps"] == max(budgets) - 1
    assert c["donated_steps"] == c["steps"]
    assert 0 <= c["sync_s"] <= c["decode_s"]
    assert c["prefill_s"] > 0
    assert abs(w[0] + c["prefill_s"] + c["decode_s"] - c["end"]) < 1e-3


def test_annotations_nest_on_a_host_plane(engine, tmp_path):
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        with TraceAnnotation("enclosing"):
            engine.serve([Request(0, prompt(0), 3)], max_wave=1)
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    found = {}
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for ev in line.events:
                found.setdefault(ev.name, []).append(
                    (plane.name, ev.start_ns, ev.start_ns + ev.duration_ns))
    (outer,) = found["enclosing"]
    (wave,) = found["engine.wave"]
    assert outer[0].startswith("/host:") and wave[0] == outer[0]
    assert outer[1] <= wave[1] <= wave[2] <= outer[2]
    inside = [x for k in ("engine.prefill", "engine.token_sync",
                          "engine.decode_dispatch") for x in found[k]]
    assert len(inside) == 1 + 2 + 2
    assert all(wave[1] <= s <= e <= wave[2] for _, s, e in inside)


def test_jit_compile_counts_only_unwarmed_shapes(engine, cluster):
    def compiles():
        return sum(1 for e in cluster.gcs.events() if e[1] == "jit_compile")

    for width in range(1, WARM_WIDTHS + 1):
        engine.serve([Request(i, prompt(i), 4) for i in range(width)],
                     max_wave=width)
    assert compiles() == 0
    engine.serve([Request(i, prompt(i), 2) for i in range(5)], max_wave=5)
    assert compiles() >= 1
    names = {e[2] for e in cluster.gcs.events() if e[1] == "jit_compile"}
    assert "jit(prefill)" in names
    assert profiler.summarize(cluster.gcs)["jit_compiles"] == compiles()


def test_prefill_program_has_a_stable_name(engine):
    tokens = jax.numpy.zeros((1, PROMPT), jax.numpy.int32)
    text = engine._prefill.lower(engine.params, {"tokens": tokens}).as_text()
    assert "@jit_prefill" in text
