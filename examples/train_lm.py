"""End-to-end driver: data-parallel LM training as compiled task graphs
over a device-typed cluster (deliverable (b) + the paper's R5).

Run:  PYTHONPATH=src python examples/train_lm.py --steps 40
      PYTHONPATH=src python examples/train_lm.py --steps 40 --shards 4
      PYTHONPATH=src python examples/train_lm.py --arch xlstm-125m --sync

Every step is ONE compiled-graph invocation over the cluster: per-shard
forward/backward kernel tasks (`kernel_task`, `{"gpu": 1}` — placed only
on the gpu-typed nodes and executed on their dedicated device lanes),
a grad-reduce graph node averaging the shard gradients, and an AdamW
apply node. The updated params/opt-state *futures* feed the next step's
execute() directly, so weights never round-trip through the driver on
the hot path; every `--publish-every` steps the driver materializes them
once and publishes a versioned `ParamSet` (sharded, zero-copy readable)
that any consumer can hot-swap from.

Uses the xlstm-125m assigned config at reduced width by default (CPU
container, Pallas kernels in interpret mode); pass --full for the real
125M config (slow on CPU, exact on TPU).
"""
import argparse
import time

import jax
import numpy as np

from repro import core, dag
from repro.compute import ParamSet, kernel_task
from repro.configs.registry import get_config, get_smoke_config
from repro.core import profiler
from repro.data.pipeline import DataConfig, batch_for_step
from repro.models import build_model
from repro.optim.adamw import AdamWConfig, adamw_init, adamw_update


def build_step_fns(model, opt_cfg):
    """The jitted compute payloads of one training step."""
    def shard_loss(params, batch):
        return model.loss_fn(params, batch)[0]

    grad_fn = jax.value_and_grad(shard_loss)

    def grad_shard(params, batch):
        return grad_fn(params, batch)          # (loss, grads)

    def reduce_grads(*shard_grads):
        n = float(len(shard_grads))
        return jax.tree.map(lambda *gs: sum(gs) / n, *shard_grads)

    def apply_update(params, opt_state, grads):
        params, opt_state, _ = adamw_update(opt_cfg, grads, opt_state,
                                            params)
        return params, opt_state

    return grad_shard, reduce_grads, apply_update


def main(argv=None):
    """Train and print the loss curve; returns [(step, loss)]."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="xlstm-125m")
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--shards", type=int, default=2,
                    help="data-parallel gradient shards = gpu-typed nodes")
    ap.add_argument("--publish-every", type=int, default=10,
                    help="publish a versioned ParamSet every N steps")
    ap.add_argument("--full", action="store_true",
                    help="use the full (not reduced) architecture config")
    ap.add_argument("--sync", action="store_true",
                    help="single-process jit loop (no task runtime)")
    args = ap.parse_args(argv)

    cfg = (get_config(args.arch) if args.full
           else get_smoke_config(args.arch).scaled(
               num_layers=4, d_model=256, param_dtype="float32",
               vocab_size=2048))
    cfg = cfg.scaled(train_microbatch=0)
    model = build_model(cfg)
    assert args.batch % args.shards == 0, "--batch must divide --shards"
    data_cfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq_len,
                          global_batch=args.batch,
                          num_shards=args.shards,
                          input_mode=cfg.input_mode, d_model=cfg.d_model,
                          num_image_tokens=cfg.num_image_tokens)
    opt_cfg = AdamWConfig(lr=1e-3)
    grad_shard_fn, reduce_fn, apply_fn = build_step_fns(model, opt_cfg)

    params = model.init(jax.random.PRNGKey(0))
    opt_state = adamw_init(params)
    shard_cfgs = [DataConfig(**{**data_cfg.__dict__, "shard_id": s})
                  for s in range(args.shards)]

    t0 = time.perf_counter()
    losses = []
    if args.sync:
        step_fn = jax.jit(lambda p, o, *bs: (
            lambda lg: apply_fn(p, o, reduce_fn(*[g for _, g in lg]))
            + (sum(l for l, _ in lg) / len(lg),)
        )([grad_shard_fn(p, b) for b in bs]))
        for step in range(args.steps):
            shards = [batch_for_step(c, step) for c in shard_cfgs]
            params, opt_state, loss = step_fn(params, opt_state, *shards)
            losses.append((step, float(loss)))
    else:
        # one gpu-typed node per shard + one cpu node for reduce/apply
        cluster = core.init(node_resources=(
            [{"cpu": 2.0, "gpu": 1.0}] * args.shards + [{"cpu": 2.0}]))

        # forward/backward is a device kernel task: jit-warmed at
        # registration, placed only where a gpu unit exists, timed as
        # profiler `kernel_task` spans
        warm = [batch_for_step(c, 0) for c in shard_cfgs]
        grad_shard = kernel_task(
            grad_shard_fn, resources={"gpu": 1.0}, num_returns=2,
            warmup_args=(params, warm[0]))
        reduce_grads = core.remote(reduce_fn)
        apply_update = core.remote(apply_fn, num_returns=2)

        # compile the step graph once: inputs are (params, opt_state,
        # *batch_shards); outputs are (params', opt_state', *losses)
        gs = [grad_shard.bind(dag.input(0), dag.input(2 + s))
              for s in range(args.shards)]
        red = reduce_grads.bind(*[g[1] for g in gs])
        upd = apply_update.bind(dag.input(0), dag.input(1), red)
        cg = dag.compile([upd[0], upd[1]] + [g[0] for g in gs])

        params_ref = core.put(params)
        opt_ref = core.put(opt_state)
        for step in range(args.steps):
            shards = [batch_for_step(c, step) for c in shard_cfgs]
            refs = cg.execute(params_ref, opt_ref, *shards)
            params_ref, opt_ref = refs[0], refs[1]
            loss = float(np.mean([np.asarray(v)
                                  for v in core.get(list(refs[2:]),
                                                    timeout=120)]))
            losses.append((step, loss))
            if args.publish_every and (step + 1) % args.publish_every == 0:
                ps = ParamSet.publish(
                    "lm", core.get(params_ref, timeout=120),
                    num_shards=args.shards)
                print(f"  step {step:3d}: published ParamSet lm@v"
                      f"{ps.version} ({ps.total_bytes / 1e6:.1f} MB, "
                      f"{len(ps.shard_ids)} shards)")
        stats = profiler.summarize(cluster.gcs)
        print(f"kernel tasks: {stats['kernel_tasks']:.0f}, mean "
              f"{stats['kernel_task_ms_mean']:.1f} ms each, device waits "
              f"{stats['device_waits']:.0f}, param publishes "
              f"{stats['param_publishes']:.0f}")
        core.shutdown()
    dt = time.perf_counter() - t0

    print(f"\ntrained {args.steps} steps in {dt:.1f}s "
          f"({args.steps * args.batch * args.seq_len / dt:.0f} tok/s)")
    print("loss curve:", [(s, round(l, 3))
                          for s, l in losses[:: max(1, len(losses)//8)]])
    first, last = losses[0][1], losses[-1][1]
    print(f"loss {first:.3f} -> {last:.3f} "
          f"({'improved' if last < first else 'NOT improved'})")
    return losses


if __name__ == "__main__":
    curve = main()
    raise SystemExit(0 if curve[-1][1] < curve[0][1] else 1)
