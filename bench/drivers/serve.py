"""Open-loop serving through the program's own tier.

Set-up makes the weights on the device from the seed, builds the
`ServingEngine`, lets `ServingEngine.warm` compile the shapes of the mix's
prompt lengths at widths 1 to `max_batch`, starts the runtime (thread
backend, one process) and a `FrontDoor` with the configuration's tier
settings, and sends one request per prompt length through it. The window
replays the seeded schedule into `FrontDoor.submit_request` (which goes
through the `ServingReplica` actor to the engine and the model), never
waiting on completions. Each request's latency runs from when it was due
on the schedule to when its ticket resolved.

After the window the run waits for every request that was due in it (a
minute past the close at most; one that never resolves has failed), reads
the process's peak memory, frees the program's state, and checks a seeded
sample of
the finished requests, the longest among them, against the plain float32
reference: at every served position, how far the served token's logit
lies below the reference's best. `checks` turns those gaps into the
numbers compared with the mix's limits; the control's gaps go through the
same function (`calibrate.py serve-limits`).
"""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import Check, Context, Output, free, process_peak, span
from bench.load import serve_schedule
from bench.weights import program_weights, reference_weights

#: a minute past the close, the longest a due request is waited for
DRAIN_S = 60.0
POLL_S = 0.002


@dataclass
class Wave:
    start: float
    end: float
    width: int
    prompt_len: int
    budgets: List[int]


class EngineProbe:
    """The engine as the replica sees it, with a harness span around each
    wave it serves: the wave's shape for the roofline reader, and its host
    interval for the trace."""

    def __init__(self, engine):
        self.engine = engine
        self.waves: List[Wave] = []

    def serve(self, requests, max_wave: int = 8):
        budgets = [int(r.max_new_tokens) for r in requests]
        plen = len(requests[0].prompt)
        t = time.perf_counter()
        with span("engine.serve", width=len(requests), prompt_len=plen,
                  budgets=",".join(map(str, budgets))):
            out = self.engine.serve(requests, max_wave=max_wave)
        self.waves.append(Wave(t, time.perf_counter(), len(requests), plen,
                               budgets))
        return out


@dataclass
class Served:
    due: float
    submitted: float
    prompt: np.ndarray
    resolved: Optional[float] = None
    tokens: Optional[List[int]] = None
    error: Optional[str] = None


def _collect(records: List[Served], tickets: Dict[int, Any],
             lock: threading.Lock, stop: threading.Event) -> None:
    """Stamp each ticket's resolution, polling every POLL_S."""
    while True:
        with lock:
            items = list(tickets.items())
        for i, t in items:
            if t.done():
                now = time.perf_counter()
                r = records[i]
                err = t.exception(0)
                if err is None:
                    r.tokens = list(t.result(0).tokens)
                else:
                    r.error = repr(err)
                r.resolved = now
                with lock:
                    del tickets[i]
        if stop.is_set() and not items:
            return
        time.sleep(POLL_S)


def _frontdoor(probe, serving: Dict[str, Any]):
    from repro.serving import FrontDoor
    from repro.serving.frontdoor import BatchController
    fd_cfg = {k: v["value"] for k, v in serving["frontdoor"].items()}
    initial = fd_cfg.pop("initial_wave_limit")
    target = fd_cfg["target_wave_s"]
    mb = serving["max_batch"]
    return FrontDoor(
        lambda: probe, num_replicas=1, min_replicas=1, max_replicas=1,
        max_batch=mb, resources={"cpu": 0.25},
        controller_factory=lambda: BatchController(target, mb, initial),
        **fd_cfg)


def sample(records: List[Served], seed: int, want_tokens: int) -> List[int]:
    """Finished requests to check: the longest, then others drawn from
    the seed until `want_tokens` served tokens are in."""
    done = [i for i, r in enumerate(records) if r.tokens]
    if not done:
        return []
    longest = max(done, key=lambda i: (len(records[i].prompt)
                                       + len(records[i].tokens), -i))
    rng = np.random.default_rng([int(seed), 0xC4EC])
    rest = [done[j] for j in rng.permutation(len(done)) if done[j] != longest]
    picked, n = [longest], len(records[longest].tokens)
    for i in rest:
        if n >= want_tokens:
            break
        picked.append(i)
        n += len(records[i].tokens)
    return picked


def reference_gaps(fam, model: Dict[str, Any], seed: int,
                   reqs: List[Served], pad_to: int, control: str = ""
                   ) -> Dict[str, float]:
    """How far the served tokens of `reqs` lie below the reference's best
    logit: the widest gap and the mean gap over every served position
    (and the same for the control's first choice, with `control`, a
    precision). Every sequence is padded at its end to `pad_to` positions
    (the engine's `max_seq`), so the reference compiles once; causal, so
    the padding changes nothing before it."""
    w = reference_weights(seed, fam.layout(model))
    fn = jax.jit(partial(fam.serve_gaps, m=model, control=control))
    gaps: Dict[str, List[np.ndarray]] = {}
    try:
        for r in reqs:
            full = np.concatenate([r.prompt, np.asarray(r.tokens[:-1],
                                                        np.int32)])
            toks = np.zeros(pad_to, np.int32)
            toks[:len(full)] = full
            tgt = np.full(pad_to, -1, np.int32)
            s = len(r.prompt)
            tgt[s - 1: s - 1 + len(r.tokens)] = r.tokens
            out = fn(w, tokens=jnp.asarray(toks), targets=jnp.asarray(tgt))
            for k, v in out.items():
                gaps.setdefault(k, []).append(
                    np.asarray(v)[s - 1: s - 1 + len(r.tokens)])
    finally:
        free(w)
    read: Dict[str, float] = {"served_tokens": sum(len(r.tokens)
                                                   for r in reqs)}
    for k, v in gaps.items():
        allg = np.concatenate(v)
        pre = "" if k == "program" else k + "_"
        read[pre + "max_logit_gap"] = float(allg.max())
        read[pre + "mean_logit_gap"] = float(allg.mean())
    return read


def checks(gaps: Dict[str, float], limits: Dict[str, float],
           prefix: str = "") -> List[Check]:
    """The gaps the mix's limits name (`prefix` "control_" reads the
    control's); with nothing served, nothing passes."""
    return [Check(k, gaps.get(prefix + k, float("inf")), v)
            for k, v in limits.items()]


@dataclass
class Server:
    """The program under test, set up for one cell."""
    model: Any
    engine: Any
    probe: EngineProbe
    cluster: Any
    frontdoor: Any
    params: Any


@dataclass
class Window:
    records: List[Served]
    t0: float
    t1: float
    give_up: float
    rejected: int
    unresolved: List[int]
    events: List[tuple]


def set_up(ctx: Context) -> Server:
    """Weights from the seed, the engine warmed for the mix's shapes, the
    runtime and the FrontDoor, and one request per prompt length through
    them."""
    from repro import core
    from repro.models import build_model
    from repro.serving import ServingEngine

    serving, traffic, m = ctx.cell.config["serving"], ctx.cell.traffic, \
        ctx.model
    model = build_model(ctx.program_config())
    params = program_weights(ctx.seed, ctx.family.layout(m), jax.eval_shape(
        model.init, jax.random.PRNGKey(0)))
    engine = ServingEngine(model, params, max_seq=serving["max_seq"])
    lens = sorted(set(traffic["prompt_lens"]))
    engine.warm(lens, serving["max_batch"])
    probe = EngineProbe(engine)
    cluster = core.init(num_nodes=1, workers_per_node=2)
    fd = _frontdoor(probe, serving)
    rng = np.random.default_rng([int(ctx.seed), 0x3A7])
    for t in [fd.submit(rng.integers(1, m["vocab_size"], n, dtype=np.int32),
                        2) for n in lens]:
        t.result(300.0)
    probe.waves.clear()
    return Server(model, engine, probe, cluster, fd, params)


def tear_down(srv: Server) -> None:
    from repro import core
    srv.frontdoor.close()
    core.shutdown()
    free(srv.params)
    srv.engine.params = srv.probe.engine = None


def drive(srv: Server, schedule, seconds: float, deadline_s: float,
          window, drain_s: float = DRAIN_S) -> Window:
    """Replay `schedule` open loop inside `window()`, then wait for every
    request due in it (`drain_s` past the close at most)."""
    from repro.serving.engine import Request
    from repro.serving.frontdoor import AdmissionError

    records: List[Served] = []
    rejected = 0
    tickets: Dict[int, Any] = {}
    lock, stop = threading.Lock(), threading.Event()
    collector = threading.Thread(
        target=_collect, args=(records, tickets, lock, stop),
        name="bench-collector", daemon=True)
    collector.start()
    with window():
        t0 = time.perf_counter()
        for a in schedule:
            due = t0 + a.due_s
            delay = due - time.perf_counter()
            if delay > 0:
                with span("generator.wait"):
                    time.sleep(delay)
            req = Request(len(records), a.prompt, a.max_new_tokens)
            rec = Served(due, time.perf_counter(), a.prompt)
            records.append(rec)
            try:
                with span("frontdoor.submit"):
                    ticket = srv.frontdoor.submit_request(req, deadline_s)
            except AdmissionError as e:
                rejected += 1
                rec.error, rec.resolved = repr(e), rec.submitted
                continue
            with lock:
                tickets[req.request_id] = ticket
        rest = t0 + seconds - time.perf_counter()
        if rest > 0:
            with span("generator.wait"):
                time.sleep(rest)
        t1 = time.perf_counter()
    give_up = t1 + drain_s
    while time.perf_counter() < give_up:
        with lock:
            if not tickets:
                break
        time.sleep(0.05)
    stop.set()
    with lock:
        unresolved = list(tickets)
        tickets.clear()
    collector.join()
    for i in unresolved:
        records[i].error, records[i].resolved = "never resolved", None
    events = [e for e in srv.cluster.gcs.events() if t0 <= e[0] <= t1]
    return Window(records, t0, t1, give_up, rejected, unresolved, events)


def summarize(w: Window, seconds: float) -> Dict[str, Any]:
    """End-to-end numbers of one window, and the ones for stderr."""
    recs = w.records
    # a request that failed or never came counts as waiting to the end
    lat_ms = [((r.resolved if r.tokens else w.give_up) - r.due) * 1e3
              for r in recs]
    in_window = [r for r in recs if r.tokens and r.resolved <= w.t1]
    late = [r.submitted - r.due for r in recs] or [0.0]
    return {
        "serve_p95_ms": float(np.percentile(lat_ms, 95)),
        "serve_tokens_per_s": sum(len(r.tokens) for r in in_window)
        / seconds,
        "p50_ms": float(np.percentile(lat_ms, 50)),
        "requests": len(recs), "completed_in_window": len(in_window),
        "backlog_at_close": sum(1 for r in recs if r.resolved is None
                                or r.resolved > w.t1),
        "failed": sum(1 for r in recs if not r.tokens),
        "rejected": w.rejected,
        "generator_late_ms_max": max(late) * 1e3,
        "generator_late_ms_p95": float(np.percentile(late, 95)) * 1e3,
        "in_window": in_window,
    }


def run(ctx: Context) -> Output:
    cell, traffic = ctx.cell, ctx.cell.traffic
    devices = jax.devices()[: cell.chips]
    srv = set_up(ctx)
    schedule = serve_schedule(traffic, ctx.seconds, ctx.seed,
                              ctx.model["vocab_size"])
    deadline = cell.config["serving"]["frontdoor"]["default_deadline_s"]
    try:
        w = drive(srv, schedule, ctx.seconds, deadline["value"], ctx.window)
        ctx.mark("due requests drained")
        peak = process_peak(devices)
        wave_widths = [x.width for x in srv.probe.waves]
    finally:
        tear_down(srv)
    summ = summarize(w, ctx.seconds)
    picked = sample(w.records, ctx.seed, int(traffic["sample_tokens"]))
    gaps = reference_gaps(ctx.family, ctx.model, ctx.seed,
                          [w.records[i] for i in picked],
                          cell.config["serving"]["max_seq"])
    ctx.mark("reference compared")
    found = checks(gaps, traffic["limits"])
    found.append(Check("requests_never_resolved", len(w.unresolved), 0))
    in_window = summ.pop("in_window")
    notes = dict(summ, wave_width_max=max(wave_widths, default=0),
                 wave_width_mean=(sum(wave_widths) / len(wave_widths)
                                  if wave_widths else 0.0),
                 process_peak_bytes=peak,
                 checked_requests=len(picked),
                 checked_tokens=gaps["served_tokens"])
    return Output(
        e2e={"serve_p95_ms": summ["serve_p95_ms"], "setup_s": ctx.setup_s},
        checks=found, attempted=summ["requests"], failed=summ["failed"],
        memory_peak_bytes=ctx.memory_peak,
        data={"events": w.events, "served_in_window": in_window},
        notes=notes)
