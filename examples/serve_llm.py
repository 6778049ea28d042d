"""Serve a small LM through the open-loop front door: seeded Poisson
arrivals land on their own clock, admission control bounds the queue,
expired requests are shed before dispatch (EDF), the AIMD controller
adapts the wave size to the engine's measured latency, and the
autoscaler grows/reclaims replica actors under queue pressure — the
paper's R1/R2 shape applied end-to-end to LLM serving.

Requests are submitted with a per-request deadline; the run ends with
the SLO tracker's disposition ledger (ok/late/shed/rejected), sliding
latency percentiles, and goodput.

Run:  PYTHONPATH=src python examples/serve_llm.py --rate 20 --duration 3
"""
import argparse

import jax

from repro import core
from repro.configs.registry import get_smoke_config
from repro.models import build_model
from repro.serving import FrontDoor, ServingEngine
from repro.serving import load as serving_load
from repro.serving.frontdoor import AdmissionError, DeadlineShedError


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-1.6b")
    ap.add_argument("--rate", type=float, default=20.0,
                    help="mean open-loop arrival rate (req/s)")
    ap.add_argument("--duration", type=float, default=3.0)
    ap.add_argument("--deadline-ms", type=float, default=2000.0)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cfg = get_smoke_config(args.arch).scaled(param_dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    max_seq = max(serving_load.LENGTH_BUCKETS) + args.max_new + 4

    core.init(num_nodes=2, workers_per_node=2)

    # each replica actor builds its own engine on its node (model state
    # never round-trips through the object store); the front door owns
    # admission, deadline shedding, batching, and autoscaling above them
    max_batch = 2

    def warm_engine():
        # runs inside each replica actor's constructor: pre-compile every
        # (wave width, prompt length) shape the trace can produce, so no
        # cold jit blows deadlines once the open-loop clock starts
        eng = ServingEngine(model, params, max_seq=max_seq)
        eng.warm(serving_load.LENGTH_BUCKETS, max_batch)
        return eng

    # fixed fleet: the example demonstrates the open-loop SLO path;
    # autoscaling under load is exercised by benchmarks/serve_bench.py
    fd = FrontDoor(
        warm_engine,
        num_replicas=args.replicas, min_replicas=args.replicas,
        max_replicas=args.replicas,
        default_deadline_s=args.deadline_ms / 1e3,
        target_wave_s=0.5 * args.deadline_ms / 1e3,
        max_batch=max_batch, resources={"cpu": 0.25})

    # readiness probes: replica constructors (and their jit warmup) run
    # asynchronously — don't start the arrival clock until every replica
    # has served a round
    probe_trace = [(0.0, serving_load.LENGTH_BUCKETS[0], args.max_new)
                   ] * (2 * args.replicas)
    probes = serving_load.materialize(probe_trace, seed=args.seed,
                                      vocab=cfg.vocab_size - 1)
    for t in [fd.submit_request(r, deadline_s=600.0) for _, r in probes]:
        t.result(timeout=600)

    trace = serving_load.poisson_trace(args.rate, args.duration,
                                       seed=args.seed,
                                       max_new_tokens=args.max_new)
    reqs = serving_load.materialize(trace, seed=args.seed,
                                    vocab=cfg.vocab_size - 1)
    tickets = []

    def submit(req):
        try:
            tickets.append(fd.submit_request(req))
        except AdmissionError:
            pass                           # counted by the SLO tracker

    # open loop: replay submits on the trace's clock and never waits on
    # completions — the system keeps up or the ledger shows it didn't
    offered = serving_load.replay(reqs, submit)

    ok = shed = 0
    for t in tickets:
        try:
            t.result(timeout=120)
            ok += 1
        except (DeadlineShedError, core.TaskError, TimeoutError):
            shed += 1
    st = fd.stats()
    print(f"offered {offered} req @ {args.rate:.0f}/s open-loop, "
          f"deadline {args.deadline_ms:.0f}ms")
    print(f"  admitted={st['admitted']} rejected={st['rejected']} "
          f"ok={st['completed_ok']} late={st['completed_late']} "
          f"shed={st['shed']}")
    print(f"  latency p50={st['latency_p50_ms']:.1f}ms "
          f"p99={st['latency_p99_ms']:.1f}ms "
          f"goodput={fd.slo.overall_goodput():.1f}/s")
    print(f"  replicas={st['replicas']} batch_limits={st['batch_limits']} "
          f"dispatched_past_deadline={st['dispatched_past_deadline']}")
    fd.close()
    core.shutdown()
    assert ok + shed == len(tickets)
    assert st["dispatched_past_deadline"] == 0
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
