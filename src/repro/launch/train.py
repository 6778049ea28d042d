"""Production train launcher: --arch <id> on the active mesh.

  PYTHONPATH=src python -m repro.launch.train --arch stablelm-1.6b \
      --steps 100 --batch 8 --seq-len 256 --ckpt-dir /tmp/ck

On a real TPU slice this runs under `jax.distributed.initialize()` with the
production mesh; on one host it uses the host mesh (all local devices).
The sharded train_step is exactly the one the dry-run compiles for 512
chips.
"""
import argparse
from functools import partial

import jax

from repro.configs.base import ShapeConfig
from repro.configs.registry import ARCH_IDS, get_config, get_smoke_config
from repro.data.pipeline import DataConfig, Prefetcher
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models import build_model
from repro.optim.adamw import AdamWConfig, adamw_init
from repro.parallel.sharding import make_rules
from repro.checkpoint import Checkpointer
from repro.train.train_step import make_train_step


def build(cfg, mesh, shape: ShapeConfig):
    """The launcher's jitted programs on `mesh`: `init(rng) -> (params,
    opt_state)`, created directly in their shardings so no state passes
    through one device, and the donated `step(params, opt_state, batch)`.
    Returns (init, step, batch_specs)."""
    rules = make_rules(mesh, cfg, shape)
    model = build_model(cfg, rules)
    opt_init = partial(adamw_init, state_dtype=cfg.opt_state_dtype)
    params_shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    p_sh = rules.param_shardings(params_shapes)
    o_sh = rules.opt_shardings(jax.eval_shape(opt_init, params_shapes))
    o_sh["step"] = rules.scalar_sharding()
    specs = model.input_specs(shape)
    b_sh = rules.input_shardings(specs)

    def init(rng):
        params = model.init(rng)
        return params, opt_init(params)

    init_fn = jax.jit(init, out_shardings=(p_sh, o_sh))
    step_fn = jax.jit(
        make_train_step(model, AdamWConfig(state_dtype=cfg.opt_state_dtype)),
        in_shardings=(p_sh, o_sh, b_sh),
        out_shardings=(p_sh, o_sh, None),
        donate_argnums=(0, 1))
    return init_fn, step_fn, specs


def data_config(cfg, *, seq_len: int, batch: int) -> DataConfig:
    """The launcher's synthetic token stream for `cfg`."""
    return DataConfig(vocab_size=cfg.vocab_size, seq_len=seq_len,
                      global_batch=batch, input_mode=cfg.input_mode,
                      d_model=cfg.d_model,
                      num_image_tokens=cfg.num_image_tokens)


def train(cfg, mesh, *, steps: int, batch: int, seq_len: int,
          ckpt_dir: str = "", log_every: int = 10):
    """Train `steps` steps from `PRNGKey(0)` on the synthetic stream.
    Returns (params, per-step losses)."""
    init_fn, step_fn, _ = build(
        cfg, mesh, ShapeConfig("cli", "train", seq_len, batch))
    params, opt_state = init_fn(jax.random.PRNGKey(0))
    ckpt = Checkpointer(ckpt_dir) if ckpt_dir else None
    pf = Prefetcher(data_config(cfg, seq_len=seq_len, batch=batch))
    losses = []
    try:
        for step in range(steps):
            params, opt_state, metrics = step_fn(params, opt_state,
                                                 pf.next())
            losses.append(metrics["loss"])
            if step % log_every == 0 or step == steps - 1:
                print(f"step {step:5d} loss {float(losses[-1]):.4f} "
                      f"gnorm {float(metrics['grad_norm']):.3f}", flush=True)
            if ckpt and (step + 1) % 50 == 0:
                ckpt.save(step + 1, {"params": params, "opt": opt_state},
                          blocking=False)
    finally:
        pf.close()
        if ckpt:
            ckpt.wait()
    return params, [float(x) for x in losses]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCH_IDS), required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-sized)")
    ap.add_argument("--mesh", choices=["host", "single", "multi"],
                    default="host")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if args.smoke:
        cfg = cfg.scaled(param_dtype="float32", train_microbatch=0)
    mesh = (make_host_mesh() if args.mesh == "host"
            else make_production_mesh(multi_pod=args.mesh == "multi"))
    train(cfg, mesh, steps=args.steps, batch=args.batch,
          seq_len=args.seq_len, ckpt_dir=args.ckpt_dir,
          log_every=args.log_every)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
