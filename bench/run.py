"""Run one cell of the benchmark once, on the chips of this machine.

  python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic mix are found by name from
`BENCHMARK.json` (see `spec.py`). With no TPU, or fewer chips than the
cell asks for, the run exits non-zero and prints no result. Otherwise the
cell's driver sets the program up (weights and traffic from `--seed`,
every shape of the cell compiled, JAX's persistent compilation cache in
the checkout's `.jax_cache`), measures for `--seconds`, and checks what
the timed path produced against the plain reference.

The last line of standard output is one JSON object: `correct`,
`attempted`, `failed`, `metrics` (the cell's end-to-end metrics with
`--trace 0`, its per-layer metrics with `--trace 1`), `device`, with
`--trace 1` a `breakdown`, and last `checks`: each number compared with
its limit. The same comparisons are the last lines of standard error.
"""
from __future__ import annotations

import time

_T_IMPORT = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src"), str(ROOT / "examples")]


def process_start() -> float:
    """Wall-clock time this process started (Linux), else import time."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/stat") as f:
            btime = next(int(ln.split()[1]) for ln in f
                         if ln.startswith("btime"))
        return btime + ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError, StopIteration):
        return _T_IMPORT


def parse(argv: Optional[List[str]] = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


class NoChipError(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


def find_chips(chips: int):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChipError(f"JAX finds no TPU (platform "
                          f"{devices[0].platform}); nothing was run")
    if len(devices) < chips:
        raise NoChipError(f"the cell needs {chips} chips, JAX sees "
                          f"{len(devices)}")
    return devices[:chips]


def layer_metrics(cell, out, ctx) -> Dict[str, Dict]:
    """Each per-layer reader of the cell; one that finds nothing to read
    returns None and its metric is left out."""
    from bench.peaks import peaks
    run = {"trace": ctx.device_trace, "model": ctx.model,
           "family": ctx.family, "seconds": ctx.seconds,
           "chips": cell.chips, "peaks": peaks(out.data["device_kind"]),
           "traffic": cell.traffic, **out.data}
    got = {}
    for m in cell.per_layer:
        v = cell.metric_reader(m["name"]).read(run)
        if v is not None:
            got[m["name"]] = {"value": v, "unit": m["unit"]}
    return got


def main(argv: Optional[List[str]] = None, require_tpu: bool = True,
         root: Path = ROOT) -> int:
    args = parse(argv)
    from bench.spec import load_cell
    cell = load_cell(args.workload, root)
    import jax
    try:
        devices = find_chips(cell.chips) if require_tpu \
            else jax.devices()[: cell.chips]
    except NoChipError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from bench.harness import Context
    ctx = Context(cell, args.seed, args.seconds, bool(args.trace),
                  started=process_start())
    out = cell.driver().run(ctx)
    dev = devices[0]
    out.data["device_kind"] = dev.device_kind
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": out.memory_peak_bytes}
    if args.trace:
        ctx.read_trace()
        tr = ctx.device_trace
        metrics = layer_metrics(cell, out, ctx)
        ctx.mark("layer metrics read")
        device.update(busy_s=tr.busy_s, window_s=tr.window_s)
    else:
        metrics = {m["name"]: {"value": out.e2e[m["name"]],
                               "unit": m["unit"]} for m in cell.end_to_end}
    result = {"correct": all(c.ok for c in out.checks),
              "attempted": out.attempted, "failed": out.failed,
              "metrics": metrics, "device": device}
    if args.trace:
        result["breakdown"] = {"device_ops": tr.top_ops(),
                               "idle_gaps": tr.top_gaps()}
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in out.checks}
    # a stall of the whole process shows as the generator running late;
    # the collector's longest pause says whether it was the collector
    notes = dict(out.notes, gc_collections=len(ctx.gc_pauses),
                 gc_pause_ms_max=1e3 * max(ctx.gc_pauses, default=0.0))
    for k, v in notes.items():
        print(f"bench: {k} = {v}", file=sys.stderr)
    for c in out.checks:
        print(f"check {c.name}: {c.value} (limit {c.limit}) "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
