"""Serving engine: batched prefill + iteration-batched greedy decode.

Design: requests are grouped into *waves*. A wave's prompts share one
batched prefill (equal prompt lengths per wave — the batcher groups by
length), then all lanes decode in lock-step with a single jitted
decode_step per token (one shared position clock, so the KV-cache write
slot is uniform across lanes — this is what keeps decode a single SPMD
program). Lanes that reach their token budget are masked out but keep
riding the batch until the wave drains; new requests start the next wave.

This is iteration-level batching (Orca-style) with aligned positions; a
vLLM-style paged KV cache with per-lane clocks remains future work (see
the serving sections of BENCHMARKS.md and the open items in ROADMAP.md).
The open-loop tier above this engine — admission control, deadline
queueing, adaptive batching, autoscaling — lives in
repro.serving.frontdoor; this module stays the closed-loop data plane.

Scale-out: `ReplicaPool` runs N `ServingReplica` *actors* (stateful
`@remote` classes) on the core runtime — each replica holds its own
engine (model state never round-trips through the object store), waves
dispatch to the replica with the fewest outstanding waves (wait-based
straggler routing, R1), and a replica lost to node failure is restarted
and its in-flight waves replayed by the actor runtime (R6). The request
intake/response path in examples/serve_llm.py rides the same futures +
wait machinery, giving the serving loop the paper's R1/R2 properties
(async admission, wait-driven completion).
"""
from __future__ import annotations

import time
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Callable, Dict, List

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import profiler
from repro.models.model import Model, routing_counts


@dataclass
class Request:
    request_id: int
    prompt: np.ndarray                  # (S,) int32
    max_new_tokens: int = 16
    created: float = field(default_factory=time.perf_counter)
    # tenancy class: orders requests *within a deadline bucket* in the
    # front door's EDF queue (higher first) — deadlines still dominate
    # across buckets. 0 = bulk; the streaming pipeline submits
    # learner-feedback traffic at 1 so it outranks bulk under load.
    priority: int = 0


@dataclass
class Response:
    request_id: int
    tokens: List[int]
    latency_s: float


def length_aligned_waves(requests: List["Request"], max_wave: int
                         ) -> List[List["Request"]]:
    """Group requests by prompt length and chunk into waves — the batch
    shape both the single engine and the replica pool dispatch on (equal
    lengths per wave keep prefill/decode a single SPMD program)."""
    by_len: Dict[int, List[Request]] = defaultdict(list)
    for r in requests:
        by_len[len(r.prompt)].append(r)
    waves = []
    for _, group in sorted(by_len.items()):
        for i in range(0, len(group), max_wave):
            waves.append(group[i:i + max_wave])
    return waves


class ServingEngine:
    def __init__(self, model: Model, params, max_seq: int = 512):
        self.model = model
        self.params = params
        self.max_seq = max_seq

        def prefill(p, b):
            return model.prefill(p, b, max_seq=max_seq)
        # the programs' names in a profiler trace: jit_prefill and
        # jit_decode_step. The cache is donated to the decode step, which
        # updates it in place.
        self._prefill = jax.jit(prefill)
        self._decode = jax.jit(model.decode_step, donate_argnums=(1,))

    def _greedy(self, logits):
        """(B, V_padded) -> (B, 1) token ids; the logits past the real
        vocabulary (padding for even sharding) are never chosen."""
        real = logits[:, :self.model.cfg.vocab_size]
        return jnp.argmax(real, axis=-1)[:, None].astype(jnp.int32)

    def _run_wave(self, wave: List[Request]) -> List[Response]:
        """One wave, inside an `engine.wave` span whose counters say
        where its host time went: `prefill_s` (prefill through the first
        token's pull), `decode_s` (the rest), `sync_s` (blocked in the
        later token pulls), `steps` (decode programs dispatched),
        `donated_steps` (those after which the cache passed in was
        deleted: the runtime took the donation), `lane_steps` (width x
        loop iterations) and `live_lane_steps` (lanes still under budget,
        summed over the iterations). A model with expert layers adds the
        routing counts its programs kept in the cache, read once at the
        wave's end: `routed_slots` (token x top-k slots over the fed lanes
        and expert layers of the decode steps), `held_slots` (those that
        chose an expert held here), and `prefill_routed_slots` and
        `prefill_held_slots` for the prefill."""
        prompts = np.stack([r.prompt for r in wave])        # equal lengths
        b, s = prompts.shape
        budgets = np.array([r.max_new_tokens for r in wave])
        with profiler.span("engine.wave", "engine", width=b, prompt_len=s,
                           requests=[r.request_id for r in wave]) as sp:
            t0 = time.perf_counter()
            with TraceAnnotation("engine.prefill"):
                logits, cache = self._prefill(
                    self.params, {"tokens": jnp.asarray(prompts)})
                tok = self._greedy(logits[:, -1])
                host_tok = np.asarray(tok)[:, 0]
            t1 = time.perf_counter()
            outs: List[List[int]] = [[] for _ in wave]
            steps = donated = iters = 0
            sync_s = 0.0
            for step in range(int(budgets.max())):
                if step:
                    t = time.perf_counter()
                    with TraceAnnotation("engine.token_sync"):
                        host_tok = np.asarray(tok)[:, 0]
                    sync_s += time.perf_counter() - t
                alive = step < budgets
                iters += 1
                for i in range(b):
                    if alive[i]:
                        outs[i].append(int(host_tok[i]))
                if step == budgets.max() - 1 or s + step >= self.max_seq - 1:
                    break
                with TraceAnnotation("engine.decode_dispatch"):
                    probe = jax.tree.leaves(cache)[0]
                    logits, cache = self._decode(self.params, cache, tok,
                                                 jnp.int32(s + step))
                    tok = self._greedy(logits[:, 0])
                steps += 1
                donated += probe.is_deleted()
            now = time.perf_counter()
            # a lane is live in the iterations before its budget runs out
            sp.set(steps=steps, donated_steps=donated, lane_steps=b * iters,
                   live_lane_steps=int(np.minimum(budgets, iters).sum()),
                   prefill_s=t1 - t0, decode_s=now - t1, sync_s=sync_s)
            sp.set(**routing_counts(cache))
        return [Response(r.request_id, o, now - r.created)
                for r, o in zip(wave, outs)]

    def serve(self, requests: List[Request], max_wave: int = 8
              ) -> List[Response]:
        """Run length-aligned waves sequentially on this engine."""
        responses: List[Response] = []
        for wave in length_aligned_waves(requests, max_wave):
            responses.extend(self._run_wave(wave))
        return responses

    def warm(self, prompt_lens, max_wave: int) -> None:
        """Compile every prefill and decode shape that waves of up to
        `max_wave` prompts of these lengths use, so none compiles while
        requests wait."""
        for plen in sorted(set(prompt_lens)):
            prompt = np.arange(plen, dtype=np.int32) % 7 + 1
            for width in range(1, max_wave + 1):
                self.serve([Request(0, prompt, max_new_tokens=2)] * width,
                           max_wave=width)

    def generate(self, prompt: np.ndarray, max_new_tokens: int = 16
                 ) -> List[int]:
        r = Request(0, np.asarray(prompt, np.int32), max_new_tokens)
        return self._run_wave([r])[0].tokens


class ServingReplica:
    """Actor body: one engine replica. The factory runs inside the actor's
    constructor, so model/params/jit caches live on the owning node and a
    restarted incarnation rebuilds them from scratch (engine state is
    derivable; request state is replayed by the actor runtime)."""

    def __init__(self, engine_factory: Callable[[], "ServingEngine"]):
        self.engine = engine_factory()
        self.waves_served = 0
        self.requests_served = 0

    def serve_wave(self, requests) -> List[Response]:
        """Run one pre-chunked, length-aligned wave as a single batch —
        the pool already applied its max_wave, so don't re-chunk at the
        engine's default."""
        self.waves_served += 1
        self.requests_served += len(requests)
        with profiler.span("replica.serve_wave", "runtime",
                           requests=[r.request_id for r in requests]):
            return self.engine.serve(list(requests),
                                     max_wave=max(len(requests), 1))

    def stats(self) -> Dict[str, int]:
        return {"waves_served": self.waves_served,
                "requests_served": self.requests_served}


class ReplicaPool:
    """Actor-backed serving tier: N `ServingReplica` actors placed by the
    global scheduler (spread across nodes by the standing-reservation
    penalty), with wait-based straggler routing — each wave goes to the
    replica with the fewest unfinished waves, measured by reaping
    completed futures with a zero-timeout `wait` at dispatch time. Wave
    futures are ordinary ObjectRefs: compose with get/wait downstream.

    Waves are dispatched as *compiled graphs*: one
    `serve_wave.bind(dag.input(0))` plan per replica is compiled at pool
    construction, and every wave replays it — the per-request
    orchestration (spec assembly, registration batching, seq
    reservation) is amortized across the pool's whole serving life,
    which is exactly the high-rate-loop shape `execute()` is built
    for."""

    #: bounded per-wave redispatch: a wave that errors (replica sealed
    #: unrecoverable) is re-run on a respawned replica at most this many
    #: times before the error propagates to the caller
    MAX_REDISPATCH = 2

    def __init__(self, engine_factory: Callable[[], "ServingEngine"],
                 num_replicas: int = 2,
                 resources: Dict[str, float] = None):
        from repro import core, dag
        self._core = core
        self._dag = dag
        self._engine_factory = engine_factory
        actor_cls = core.remote(ServingReplica)
        if resources is not None:
            actor_cls = actor_cls.options(resources=resources)
        self._actor_cls = actor_cls
        self.replicas = [actor_cls.submit(engine_factory)
                         for _ in range(num_replicas)]
        self._wave_graphs = [
            dag.compile(r.serve_wave.bind(dag.input(0)))
            for r in self.replicas]
        self._inflight: Dict[int, List] = {
            i: [] for i in range(num_replicas)}
        # ref.id -> (replica idx, requests, redispatch attempt): names
        # replica assignments in timeout errors and carries what a
        # failed wave needs to re-run on a respawned replica
        self._wave_meta: Dict[str, tuple] = {}

    def submit_wave(self, requests: List[Request], _attempt: int = 0):
        """Dispatch one wave (a compiled-graph invocation on the least
        loaded replica); returns the ObjectRef of its responses."""
        core = self._core
        for i, refs in self._inflight.items():
            if refs:
                _, pending = core.wait(refs, num_returns=len(refs),
                                       timeout=0)
                for r in refs:
                    if r not in pending:
                        self._wave_meta.pop(r.id, None)
                self._inflight[i] = pending
        idx = min(self._inflight, key=lambda i: len(self._inflight[i]))
        ref = self._wave_graphs[idx].execute(tuple(requests))
        self._inflight[idx].append(ref)
        self._wave_meta[ref.id] = (idx, tuple(requests), _attempt)
        return ref

    def respawn_replica(self, idx: int) -> None:
        """Replace a dead replica with a fresh actor (new engine built
        by the stored factory) and recompile its wave plan. The old
        incarnation's in-flight refs stay tracked by their waiters —
        they resolve via actor replay or surface typed errors."""
        self.replicas[idx] = self._actor_cls.submit(self._engine_factory)
        self._wave_graphs[idx] = self._dag.compile(
            self.replicas[idx].serve_wave.bind(self._dag.input(0)))
        self._inflight[idx] = []

    def serve(self, requests: List[Request], max_wave: int = 8,
              timeout: float = 300.0) -> List[Response]:
        """Group by prompt length, fan waves across the replica set, and
        collect responses in completion order (stragglers never gate the
        batch). Raises TimeoutError if the whole batch has not drained
        within `timeout` — a permanently lost wave must surface, not
        spin.

        Consumed wave outputs are freed as soon as their responses are
        extracted: under sustained request churn the replicas' object
        stores hold only in-flight waves (bounded cache), instead of
        accreting every response batch ever served."""
        from repro.core import TaskError
        wave_refs = [self.submit_wave(wave)
                     for wave in length_aligned_waves(requests, max_wave)]
        responses: List[Response] = []
        pending = wave_refs
        deadline = time.perf_counter() + timeout
        while pending:
            remaining = deadline - time.perf_counter()
            if remaining <= 0:
                where = ", ".join(
                    f"{r.id}->replica"
                    f"{self._wave_meta.get(r.id, ('?',))[0]}"
                    for r in pending)
                elapsed = time.perf_counter() - (deadline - timeout)
                queue_depth = sum(
                    len(self._wave_meta.get(r.id, (0, ()))[1])
                    for r in pending)
                # free before raising: an abandoned wave must not pin
                # store memory for the life of the pool
                self._core.free(pending)
                for r in pending:
                    self._wave_meta.pop(r.id, None)
                raise TimeoutError(
                    f"{len(pending)} serving wave(s) ({queue_depth} "
                    f"request(s)) incomplete after {elapsed:.1f}s elapsed "
                    f"vs {timeout}s deadline (pending refs freed): {where}")
            done, pending = self._core.wait(
                pending, num_returns=1, timeout=min(remaining, 30.0))
            for ref in done:
                meta = self._wave_meta.pop(ref.id, None)
                try:
                    responses.extend(self._core.get(ref))
                except TaskError:
                    # replica sealed/unrecoverable: respawn it and
                    # re-run the wave, bounded per wave so a wave that
                    # fails deterministically still surfaces
                    if meta is None or meta[2] >= self.MAX_REDISPATCH:
                        raise
                    idx, reqs, attempt = meta
                    self.respawn_replica(idx)
                    pending.append(
                        self.submit_wave(list(reqs), attempt + 1))
            if done:
                # eager reclaim: the wait() reaping in submit_wave
                # counts freed futures as done, so in-flight accounting
                # stays correct
                self._core.free(done)
        return responses

    def stats(self) -> List[Dict[str, int]]:
        # submit all first so the N round trips overlap
        refs = [r.stats.submit() for r in self.replicas]
        return [self._core.get(ref) for ref in refs]
