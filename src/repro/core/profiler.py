"""Debugging & profiling (R7): every state transition lands in the control
plane's event log; this module summarizes it, and writes the program's own
spans and compile counts into it.

Spans. `span(name, layer, **attrs)` times the `with` block on one thread;
`open_span(name, layer, **attrs)` returns a started `Span` that any thread
may `close()`. A closed span is one record in the running cluster's event
log, stamped at its start on the log's clock (`time.perf_counter`):

    (start, "span", span_id, layer, {"name", "end", "parent", **attrs})

so a reader that keeps a window's events by their time keeps the spans
that started in it. `parent` is the span open around it on the opening
thread. Attributes added before the close (`Span.set`, `close(**attrs)`)
ride the record: request ids, widths, counters. With no cluster running
there is no log, and the record is skipped. Each span also holds a
`jax.profiler.TraceAnnotation` of its name and opening attrs, so in a
profiler trace it lies on a host line on the device trace's clock, beside
the device's operations (a span closed on another thread lands on that
thread's line). Per-step detail inside a span is a bare `TraceAnnotation`:
the always-on log grows by a few records per wave, not per step.

Compiles. `watch_compiles()` (run by `core.init`, once per process)
listens to JAX's backend-compile durations and logs one `jit_compile`
event per compile: the function's name, its seconds, and the seconds of
the persistent compile cache's read where the cache supplied it.
"""
from __future__ import annotations

import itertools
import threading
import time
from collections import defaultdict
from typing import Any, Dict, List, Optional

from repro.core import api
from repro.core.control_plane import ControlPlane
from repro.core.worker import current_node

_span_ids = itertools.count()
_open = threading.local()            # spans entered with `with`, per thread


def _log() -> Optional[ControlPlane]:
    """The event log a record goes to: the node's inside a task, else the
    running cluster's; None when no cluster runs."""
    node = current_node()
    if node is not None:
        return node.gcs
    cluster = api._global["cluster"]
    return None if cluster is None else cluster.gcs


def _annotation(name: str, attrs: Dict[str, Any]):
    from jax.profiler import TraceAnnotation
    # a trace annotation's arguments are scalars: lists go as one string
    return TraceAnnotation(name, **{
        k: " ".join(map(str, v)) if isinstance(v, (list, tuple)) else v
        for k, v in attrs.items()})


def _stack() -> List["Span"]:
    stack = getattr(_open, "stack", None)
    if stack is None:
        stack = _open.stack = []
    return stack


class Span:
    """One span of the program: `with span(...)` on one thread, or
    `open_span(...)` here and `close()` on any thread."""

    __slots__ = ("id", "name", "layer", "attrs", "parent", "start", "_note")

    def __init__(self, name: str, layer: str, attrs: Dict[str, Any]):
        self.id = f"span{next(_span_ids)}"
        self.name, self.layer, self.attrs = name, layer, attrs

    def _begin(self) -> "Span":
        stack = _stack()
        self.parent = stack[-1].id if stack else None
        self._note = _annotation(self.name, self.attrs)
        self._note.__enter__()
        self.start = time.perf_counter()
        return self

    def set(self, **attrs) -> None:
        self.attrs.update(attrs)

    def close(self, end: Optional[float] = None, **attrs) -> None:
        """End the span (at `end`, a `perf_counter` time, if given) and
        log its record."""
        self._note.__exit__(None, None, None)
        if end is None:
            end = time.perf_counter()
        log = _log()
        if log is not None:
            log.log_at(self.start, "span", self.id, self.layer,
                       dict(self.attrs, **attrs, name=self.name, end=end,
                            parent=self.parent))

    def __enter__(self) -> "Span":
        self._begin()
        _stack().append(self)
        return self

    def __exit__(self, *exc) -> None:
        _stack().pop()
        self.close()


def span(name: str, layer: str, **attrs) -> Span:
    """A span over the `with` block it is entered by."""
    return Span(name, layer, attrs)


def open_span(name: str, layer: str, **attrs) -> Span:
    """A span started now, for a `close()` on this or another thread."""
    return Span(name, layer, attrs)._begin()


_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_READ = "/jax/compilation_cache/cache_retrieval_time_sec"
_compiles = threading.local()
_watching = threading.Lock()
_watched = False


def _on_duration(event: str, secs: float, **kw) -> None:
    # a persistent-cache read is reported inside the backend compile
    # it serves, on the same thread
    if event == _CACHE_READ:
        _compiles.cache_s = secs
    elif event == _BACKEND_COMPILE:
        cache_s = getattr(_compiles, "cache_s", None)
        _compiles.cache_s = None
        log = _log()
        if log is not None:
            log.log_at(time.perf_counter() - secs, "jit_compile",
                       str(kw.get("fun_name", "")), "jax",
                       {"s": secs, "cache_s": cache_s})


def watch_compiles() -> None:
    """Log every JAX compile of this process from now on (idempotent)."""
    global _watched
    with _watching:
        if _watched:
            return
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _watched = True


def summarize(gcs: ControlPlane) -> Dict[str, float]:
    """Aggregate scheduling + memory-governance + compiled-graph metrics
    from the event log. The eviction/reclaim counters come from the data
    plane's event kinds: ``evict`` (LRU eviction under store pressure,
    with the freed byte count), ``reclaim`` (refcount-zero GC
    collection), and ``reconstruct`` events tagged ``after_evict``
    (lineage replay repairing an evicted-but-still-referenced object).
    Graph counters come from the dag layer: ``graph_compile`` (plans
    built), ``graph_execute`` (invocations, each carrying the size of
    its single batched registration), and ``graph_chain`` (dependents
    executed inline on the finishing worker, never re-entering the
    scheduler). Failure-hardening counters come from the detector and
    retry machinery: ``node_failure`` (fail-stops, however triggered),
    ``detector_kill`` / ``watchdog_kill`` (failures the heartbeat
    monitor / hung-task watchdog declared), ``retry`` (policy-driven
    exception retries), ``task_unrecoverable`` / ``task_deadline``
    (tasks sealed by budget exhaustion / deadline expiry),
    ``actor_unrecoverable`` (actors past their restart budget), and
    ``chaos`` (injected fault events). Serving counters come from the
    front door's control loop (repro.serving.frontdoor): ``serve_admit``
    / ``serve_reject`` (admission control), ``serve_shed`` (deadline
    shedding), ``serve_wave`` (dispatched waves, with sizes for the mean
    wave width), ``serve_retry`` (re-enqueues after replica failure),
    ``serve_scale_up`` / ``serve_scale_down`` / ``serve_spare``
    (autoscaler decisions), and ``actor_retired`` (planned actor
    scale-down via Cluster.retire_actor). Compute-plane counters come
    from the device-typed kernel path (repro.compute): ``kernel_task``
    spans (kernel-task executions, timed on the host from call to the
    device's finish, for ``kernel_task_ms_mean``), ``device_wait``
    (tasks that stalled for a busy device grant),
    ``task_unschedulable`` (tasks sealed because no declared node can
    ever satisfy their resources), and ``param_publish`` (ParamSet
    versions published, with their total shard bytes). Streaming-plane
    counters come from the train-while-serve loop (repro.streaming):
    ``stream_batch`` (mini-batches produced into the object store),
    ``drift`` (detector fires, from repro.streaming.drift),
    ``learner_reset`` (drift-triggered model resets), and
    ``weight_swap`` (serving replicas hot-swapping to a newer ParamSet
    version between waves, each carrying ``lag`` — the version jump —
    whose mean is ``swap_version_lag_mean``). ``jit_compile`` events
    (``watch_compiles``) give ``jit_compiles``.
    Spans and compiles are not tasks: they stay out of ``num_tasks``
    and the per-task fractions."""
    raw = gcs.events()
    tl: Dict[str, List] = defaultdict(list)
    evictions = reclaims = reconstructs_after_evict = 0
    bytes_freed = 0
    graph_compiles = graph_invocations = graph_chained = 0
    graph_batched_tasks = 0
    node_failures = detector_kills = watchdog_kills = 0
    retries = unrecoverable = deadline_expired = 0
    actor_unrecoverable = chaos_events = 0
    serve_admitted = serve_rejected = serve_shed = serve_retries = 0
    serve_waves = serve_wave_requests = 0
    serve_scale_ups = serve_scale_downs = serve_spares = 0
    actors_retired = 0
    kernel_tasks = device_waits = unschedulable = param_publishes = 0
    kernel_ms_total = 0.0
    jit_compiles = 0
    param_bytes = 0
    stream_batches = drift_events = weight_swaps = learner_resets = 0
    swap_lag_total = 0
    for t, kind, task_id, where, extra in raw:
        if kind == "span":
            if extra["name"] == "kernel_task":
                kernel_tasks += 1
                kernel_ms_total += (extra["end"] - t) * 1e3
            continue
        if kind == "jit_compile":
            jit_compiles += 1
            continue
        tl[task_id].append((t, kind, where, extra))
        if kind == "evict":
            evictions += 1
            bytes_freed += extra.get("bytes", 0)
        elif kind == "reclaim":
            reclaims += 1
            bytes_freed += extra.get("bytes", 0)
        elif kind == "reconstruct" and extra.get("after_evict"):
            reconstructs_after_evict += 1
        elif kind == "graph_compile":
            graph_compiles += 1
        elif kind == "graph_execute":
            graph_invocations += 1
            graph_batched_tasks += extra.get("nodes", 0)
        elif kind == "graph_chain":
            graph_chained += 1
        elif kind == "node_failure":
            node_failures += 1
        elif kind == "detector_kill":
            detector_kills += 1
        elif kind == "watchdog_kill":
            watchdog_kills += 1
        elif kind == "retry":
            retries += 1
        elif kind == "task_unrecoverable":
            unrecoverable += 1
        elif kind == "task_deadline":
            deadline_expired += 1
        elif kind == "actor_unrecoverable":
            actor_unrecoverable += 1
        elif kind == "chaos":
            chaos_events += 1
        elif kind == "serve_admit":
            serve_admitted += 1
        elif kind == "serve_reject":
            serve_rejected += 1
        elif kind == "serve_shed":
            serve_shed += 1
        elif kind == "serve_retry":
            serve_retries += 1
        elif kind == "serve_wave":
            serve_waves += 1
            serve_wave_requests += extra.get("size", 0)
        elif kind == "serve_scale_up":
            serve_scale_ups += 1
        elif kind == "serve_scale_down":
            serve_scale_downs += 1
        elif kind == "serve_spare":
            serve_spares += 1
        elif kind == "actor_retired":
            actors_retired += 1
        elif kind == "device_wait":
            device_waits += 1
        elif kind == "task_unschedulable":
            unschedulable += 1
        elif kind == "param_publish":
            param_publishes += 1
            param_bytes += extra.get("bytes", 0)
        elif kind == "stream_batch":
            stream_batches += 1
        elif kind == "drift":
            drift_events += 1
        elif kind == "weight_swap":
            weight_swaps += 1
            swap_lag_total += extra.get("lag", 0)
        elif kind == "learner_reset":
            learner_resets += 1
    submit_to_start, run_times, spills, locals_ = [], [], 0, 0
    for task_id, events in tl.items():
        events.sort()
        kinds = {k: t for t, k, _, _ in events}
        if "submit" in kinds and "start" in kinds:
            submit_to_start.append(kinds["start"] - kinds["submit"])
        if "start" in kinds and "finish" in kinds:
            run_times.append(kinds["finish"] - kinds["start"])
        spills += any(k == "spill" for _, k, _, _ in events)
        locals_ += any(k == "sched_local" for _, k, _, _ in events)

    def pct(xs, q):
        if not xs:
            return 0.0
        xs = sorted(xs)
        return xs[min(len(xs) - 1, int(q * len(xs)))]

    return {
        "num_tasks": len(tl),
        "sched_latency_p50_us": pct(submit_to_start, 0.5) * 1e6,
        "sched_latency_p99_us": pct(submit_to_start, 0.99) * 1e6,
        "task_runtime_p50_ms": pct(run_times, 0.5) * 1e3,
        "spill_fraction": spills / max(len(tl), 1),
        "local_fraction": locals_ / max(len(tl), 1),
        "evictions": evictions,
        "reclaims": reclaims,
        "bytes_freed": float(bytes_freed),
        "reconstruct_after_evict": reconstructs_after_evict,
        "graph_compiles": graph_compiles,
        "graph_invocations": graph_invocations,
        "graph_batched_tasks_mean": (graph_batched_tasks
                                     / max(graph_invocations, 1)),
        "graph_inline_chained": graph_chained,
        "node_failures": node_failures,
        "detector_kills": detector_kills,
        "watchdog_kills": watchdog_kills,
        "retries": retries,
        "tasks_unrecoverable": unrecoverable,
        "tasks_deadline_expired": deadline_expired,
        "actors_unrecoverable": actor_unrecoverable,
        "chaos_events": chaos_events,
        "serve_admitted": serve_admitted,
        "serve_rejected": serve_rejected,
        "serve_shed": serve_shed,
        "serve_retries": serve_retries,
        "serve_waves": serve_waves,
        "serve_wave_size_mean": (serve_wave_requests
                                 / max(serve_waves, 1)),
        "serve_scale_ups": serve_scale_ups,
        "serve_scale_downs": serve_scale_downs,
        "serve_spares": serve_spares,
        "actors_retired": actors_retired,
        "kernel_tasks": kernel_tasks,
        "kernel_task_ms_mean": kernel_ms_total / max(kernel_tasks, 1),
        "jit_compiles": jit_compiles,
        "device_waits": device_waits,
        "tasks_unschedulable": unschedulable,
        "param_publishes": param_publishes,
        "param_bytes": float(param_bytes),
        "stream_batches": stream_batches,
        "drift_events": drift_events,
        "weight_swaps": weight_swaps,
        "swap_version_lag_mean": swap_lag_total / max(weight_swaps, 1),
        "learner_resets": learner_resets,
    }

