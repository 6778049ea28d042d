import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this builds the jitted step (train_step / prefill / serve_step)
with full production shardings, lowers it against ShapeDtypeStruct stand-ins
(no allocation), compiles it for the 16x16 single-pod or 2x16x16 multi-pod
mesh, and records:
  * memory_analysis()  -- per-device argument/output/temp bytes (fits HBM?)
  * cost_analysis()    -- XLA's raw FLOPs/bytes (loop bodies counted once)
  * loop-aware roofline terms from repro.analysis.hlo (FLOPs, HBM bytes,
    collective transfer bytes split ICI vs DCN)

Results are cached as JSON under benchmarks/dryrun_results/ so reruns are
incremental. Usage:

  PYTHONPATH=src python -m repro.launch.dryrun --arch phi3-medium-14b \
      --shape train_4k --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
"""
import argparse
import json
import time
import traceback
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.analysis.hlo import analyze_hlo
from repro.configs.base import ALL_SHAPES, ShapeConfig, shapes_for
from repro.configs.registry import ARCH_IDS, get_config
from repro.launch import mesh as mesh_lib
from repro.launch import train as train_lib
from repro.models import build_model
from repro.parallel.sharding import make_rules

RESULTS_DIR = Path(__file__).resolve().parents[3] / "benchmarks" / "dryrun_results"


def _shape_by_name(cfg, name: str) -> ShapeConfig:
    for s in ALL_SHAPES:
        if s.name == name:
            return s
    raise KeyError(name)


def lower_cell(arch: str, shape: ShapeConfig, mesh, *, opt_overrides=None):
    """Returns (lowered, meta). Pure lowering — no device buffers."""
    cfg = get_config(arch)
    if opt_overrides:
        cfg = cfg.scaled(**opt_overrides)
    rules = make_rules(mesh, cfg, shape)
    model = build_model(cfg, rules)
    specs = model.input_specs(shape)
    in_data_shardings = rules.input_shardings(specs)

    if shape.kind == "train":
        init_fn, step_fn, _ = train_lib.build(cfg, mesh, shape)
        state_shapes = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
        lowered = step_fn.lower(*state_shapes, specs)
    elif shape.kind == "prefill":
        params_shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        p_shard = rules.param_shardings(params_shapes)
        fn = jax.jit(partial(model.prefill, max_seq=shape.seq_len),
                     in_shardings=(p_shard, in_data_shardings))
        lowered = fn.lower(params_shapes, specs)
    else:  # decode
        params_shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        p_shard = rules.param_shardings(params_shapes)
        cache_shapes = jax.eval_shape(
            lambda: model.init_cache(shape.global_batch, shape.seq_len))
        c_shard = rules.cache_shardings(cache_shapes)
        fn = jax.jit(model.decode_step,
                     in_shardings=(p_shard, c_shard,
                                   in_data_shardings["tokens"],
                                   rules.scalar_sharding()),
                     out_shardings=(None, c_shard),
                     donate_argnums=(1,))
        pos = jax.ShapeDtypeStruct((), jnp.int32)
        lowered = fn.lower(params_shapes, cache_shapes, specs["tokens"], pos)
    n_params = sum(x.size for x in jax.tree.leaves(
        jax.eval_shape(model.init, jax.random.PRNGKey(0))))
    return lowered, {"n_params": int(n_params), "cfg": cfg}


def run_cell(arch: str, shape: ShapeConfig, mesh_kind: str, *,
             opt_overrides=None, tag: str = "baseline") -> dict:
    multi = mesh_kind == "multi"
    mesh = mesh_lib.make_production_mesh(multi_pod=multi)
    n_dev = mesh.size
    t0 = time.time()
    rec = {"arch": arch, "shape": shape.name, "mesh": mesh_kind,
           "devices": n_dev, "device_kind": mesh_lib.PRODUCTION_DEVICE_KIND,
           "tag": tag, "ok": False}
    try:
        lowered, meta = lower_cell(arch, shape, mesh, opt_overrides=opt_overrides)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis() or {}
        text = compiled.as_text()
        hlo = analyze_hlo(text, total_devices=n_dev)
        # persist the optimized HLO (gzip) for offline roofline reanalysis
        import gzip
        RESULTS_DIR.mkdir(parents=True, exist_ok=True)
        hlo_path = (RESULTS_DIR /
                    f"{arch}__{shape.name}__{mesh_kind}__{tag}.hlo.gz")
        with gzip.open(hlo_path, "wt") as f:
            f.write(text)
        n_pods = 2 if multi else 1
        rec.update(
            ok=True,
            n_params=meta["n_params"],
            lower_s=round(t_lower, 2), compile_s=round(t_compile, 2),
            xla_flops=float(cost.get("flops", 0.0)),
            xla_bytes=float(cost.get("bytes accessed", 0.0)),
            hlo_flops=hlo.flops, hlo_dot_flops=hlo.dot_flops,
            hlo_bytes=hlo.hbm_bytes,
            collective_bytes_total=hlo.collective_bytes(),
            collective_bytes_dcn=(hlo.collective_bytes(group_size=n_pods)
                                  if multi else 0.0),
            collective_by_kind=hlo.by_kind(),
            unknown_trip_loops=hlo.unknown_trip_loops,
            arg_bytes_per_dev=getattr(mem, "argument_size_in_bytes", 0),
            out_bytes_per_dev=getattr(mem, "output_size_in_bytes", 0),
            temp_bytes_per_dev=getattr(mem, "temp_size_in_bytes", 0),
            alias_bytes_per_dev=getattr(mem, "alias_size_in_bytes", 0),
        )
        # quick memory-fit verdict vs 16 GB/chip HBM (v5e).
        # NOTE: the CPU backend emulates bf16 by upcasting buffers to f32
        # (verified: the StableHLO has a single bf16 residual stack, the
        # post-optimization CPU HLO holds f32 copies), so temp bytes are a
        # ~2x upper bound for bf16-dominant graphs. We report raw (CPU) and
        # a TPU-adjusted estimate (temp/2 when params are bf16).
        tot = (rec["arg_bytes_per_dev"] + rec["out_bytes_per_dev"]
               + rec["temp_bytes_per_dev"] - rec["alias_bytes_per_dev"])
        rec["hbm_per_dev_gb"] = round(tot / 2**30, 3)
        rec["fits_16gb_raw"] = bool(tot < 16 * 2**30)
        bf16 = meta["cfg"].param_dtype == "bfloat16"
        adj = (rec["arg_bytes_per_dev"] + rec["out_bytes_per_dev"]
               + rec["temp_bytes_per_dev"] // (2 if bf16 else 1)
               - rec["alias_bytes_per_dev"])
        rec["hbm_per_dev_gb_tpu_est"] = round(adj / 2**30, 3)
        rec["fits_16gb"] = bool(adj < 16 * 2**30)
    except Exception as e:  # noqa: BLE001 — record failures, they are bugs
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    rec["total_s"] = round(time.time() - t0, 2)
    return rec


def save(rec: dict):
    RESULTS_DIR.mkdir(parents=True, exist_ok=True)
    name = f"{rec['arch']}__{rec['shape']}__{rec['mesh']}__{rec['tag']}.json"
    (RESULTS_DIR / name).write_text(json.dumps(rec, indent=1, default=str))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCH_IDS))
    ap.add_argument("--shape")
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--tag", default="baseline")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    cells = []
    if args.all:
        for arch in ARCH_IDS:
            for sh in shapes_for(get_config(arch)):
                cells.append((arch, sh))
    else:
        cfg = get_config(args.arch)
        shs = ([_shape_by_name(cfg, args.shape)] if args.shape
               else list(shapes_for(cfg)))
        cells = [(args.arch, s) for s in shs]

    n_ok = n_fail = 0
    for arch, sh in cells:
        for mk in meshes:
            out = (RESULTS_DIR /
                   f"{arch}__{sh.name}__{mk}__{args.tag}.json")
            if out.exists() and not args.force:
                prev = json.loads(out.read_text())
                if prev.get("ok"):
                    print(f"[skip] {arch} {sh.name} {mk} (cached ok)")
                    n_ok += 1
                    continue
            rec = run_cell(arch, sh, mk, tag=args.tag)
            save(rec)
            status = "OK " if rec["ok"] else "FAIL"
            n_ok += rec["ok"]
            n_fail += (not rec["ok"])
            print(f"[{status}] {arch} {sh.name} {mk} "
                  f"{rec.get('hbm_per_dev_gb', '?')}GB/dev "
                  f"{rec['total_s']}s {rec.get('error', '')}", flush=True)
    print(f"dry-run: {n_ok} ok, {n_fail} failed")
    return 0 if n_fail == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
