"""Readings behind the benchmark's settings, taken on the chip.

  python bench/calibrate.py sweep --workload W --rates 2,3,4 --seconds 30
  python bench/calibrate.py serve-limits --workload W --seeds 12 --seconds 20
  python bench/calibrate.py train-limits --workload W --seeds 12
  python bench/calibrate.py trace-sample --out DIR

- sweep: one set-up, then one open-loop window per rate; prints how many
  requests finished in the window, the backlog at its close, and the
  latency percentiles. The knee is the highest rate whose backlog does
  not grow.
- serve-limits: for each seed, that seed's weights and schedule through
  the cell's own path at the cell's rate, then the logit gaps of a
  checked sample under the float32 reference, for the program and for
  the float8 control, each put through the cell's own comparison
  (`correct`, `control_correct`). The readings a limit is set from.
- train-limits: the training driver's readings for the program and for
  the float8 control, and the faults a training cell can have, per seed.
- trace-sample: a small trace of a jitted program inside harness spans,
  kept under `bench/testdata/` for the trace reduction's test.

Nothing here runs in the benchmark's own runs. Prints one JSON object per
reading.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH.parent), str(BENCH.parent / "src"),
                str(BENCH.parent / "examples")]


def _ctx(workload: str, seed: int, seconds: float, trace: bool = False):
    from bench.harness import Context
    from bench.spec import load_cell
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    return Context(load_cell(workload), seed, seconds, trace,
                   started=time.time())


def _serve():
    from bench.spec import load_module
    return load_module(BENCH / "drivers" / "serve.py", "drivers_serve")


def _emit(obj) -> None:
    print(json.dumps(obj), flush=True)


def sweep(args) -> None:
    serve = _serve()
    from bench.load import serve_schedule
    ctx = _ctx(args.workload, args.seed, args.seconds)
    srv = serve.set_up(ctx)
    deadline = ctx.cell.config["serving"]["frontdoor"][
        "default_deadline_s"]["value"]
    over = 0
    try:
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            traffic = dict(ctx.cell.traffic, rate_hz=rate)
            if args.no_bursts:
                traffic.pop("bursts", None)
            sched = serve_schedule(traffic, args.seconds, args.seed + i,
                                   ctx.model["vocab_size"])
            # drain fully, so one rate's backlog never meets the next
            w = serve.drive(srv, sched, args.seconds, deadline, ctx.window,
                            drain_s=600.0)
            s = serve.summarize(w, args.seconds)
            s.pop("in_window")
            waves = srv.probe.waves
            _emit(dict(s, rate_hz=rate, waves=len(waves),
                       wave_width_mean=(sum(x.width for x in waves)
                                        / max(len(waves), 1)),
                       wave_s_mean=(sum(x.end - x.start for x in waves)
                                    / max(len(waves), 1))))
            srv.probe.waves.clear()
            over += s["backlog_at_close"] > 2 * ctx.cell.config[
                "serving"]["max_batch"]
            if over >= 2:
                break
    finally:
        serve.tear_down(srv)


def serve_limits(args) -> None:
    import jax
    serve = _serve()
    from bench.harness import free
    from bench.load import serve_schedule
    from bench.weights import program_weights
    ctx = _ctx(args.workload, args.seed, args.seconds)
    srv = serve.set_up(ctx)
    fam, m, traffic = ctx.family, ctx.model, ctx.cell.traffic
    deadline = ctx.cell.config["serving"]["frontdoor"][
        "default_deadline_s"]["value"]
    shapes = jax.eval_shape(srv.model.init, jax.random.PRNGKey(0))
    try:
        for k in range(args.seeds):
            seed = args.seed + 7919 * k
            free(srv.engine.params)
            srv.engine.params = program_weights(seed, fam.layout(m), shapes)
            sched = serve_schedule(traffic, args.seconds, seed,
                                   m["vocab_size"])
            w = serve.drive(srv, sched, args.seconds, deadline, ctx.window)
            picked = serve.sample(w.records, seed,
                                  int(traffic["sample_tokens"]))
            t = time.perf_counter()
            gaps = serve.reference_gaps(fam, m, seed,
                                        [w.records[i] for i in picked],
                                        ctx.cell.config["serving"]["max_seq"],
                                        control=ctx.control
                                        if k < args.control_seeds else "")
            verdict = {"correct": all(c.ok for c in serve.checks(
                gaps, traffic["limits"]))}
            if "control_max_logit_gap" in gaps:
                verdict["control_correct"] = all(c.ok for c in serve.checks(
                    gaps, traffic["limits"], prefix="control_"))
            _emit(dict(gaps, **verdict, seed=seed, requests=len(w.records),
                       failed=sum(1 for r in w.records if not r.tokens),
                       checked=len(picked),
                       reference_s=time.perf_counter() - t))
            srv.probe.waves.clear()
    finally:
        serve.tear_down(srv)


def train_limits(args) -> None:
    from bench.spec import load_cell
    cell = load_cell(args.workload)
    drv = cell.driver()
    ctx = _ctx(args.workload, args.seed, 0.0)
    for rec in drv.limits(ctx, seeds=args.seeds,
                          control_seeds=args.control_seeds):
        _emit(rec)


def trace_sample(args) -> None:
    """A few runs of two small programs inside harness spans."""
    import jax
    import jax.numpy as jnp
    from bench.harness import span
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    mat = jax.jit(lambda x: jnp.tanh(x @ x))
    red = jax.jit(lambda x: jnp.sum(x * x))
    x = jnp.ones((1024, 1024), jnp.bfloat16)
    mat(x).block_until_ready(), red(x).block_until_ready()
    tmp = tempfile.mkdtemp(prefix="bench-trace-")
    jax.profiler.start_trace(tmp)
    with span("window"):
        for i in range(3):
            with span("step", i=i):
                mat(x).block_until_ready()
            with span("host.sleep"):
                time.sleep(0.002)
            red(x).block_until_ready()
    jax.profiler.stop_trace()
    f = sorted(Path(tmp).rglob("*.xplane.pb"))[-1]
    shutil.copy(f, out / "sample.xplane.pb")
    shutil.rmtree(tmp, ignore_errors=True)
    from bench.devtrace import read_trace_dir
    tr = read_trace_dir(out)
    _emit({"busy_s": tr.busy_s, "window_s": tr.window_s,
           "programs": {k: v for k, v in tr.program_s.items()},
           "runs": {k: len(v) for k, v in tr.program_runs.items()},
           "gaps": tr.top_gaps(), "ops": tr.top_ops(5),
           "bytes": (out / "sample.xplane.pb").stat().st_size})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("what", choices=["sweep", "serve-limits", "train-limits",
                                     "trace-sample"])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=2 ** 31 + 12345)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rates", default="")
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--out", default="chiprun_out/trace")
    ap.add_argument("--no-bursts", action="store_true",
                    help="sweep: the mix's base arrivals only")
    args = ap.parse_args(argv)
    {"sweep": sweep, "serve-limits": serve_limits,
     "train-limits": train_limits,
     "trace-sample": trace_sample}[args.what](args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
