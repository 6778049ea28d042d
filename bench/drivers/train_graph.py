"""Training through the runtime's compiled step graph, one shard.

The graph is the one `examples/train_lm.py` builds, assembled from the same
public calls in the same shape (the example does not expose it): the
forward and backward pass as a device-typed `kernel_task`, the gradient
reduce as a `core.remote` task, the AdamW apply as a `core.remote` task,
compiled once with `dag.compile` and invoked per step with `execute`; the
loss is fetched with `core.get` every step, as the example does.

Set-up makes the weights on the device from the seed, builds that one
graph and its state, and drives it through its first three steps with the
window's own call and feed (rows that all differ). From those steps it
keeps each step's loss, the first gradient as the optimizer got it (AdamW's
first moment after one step, over `1 - b1`), and the parameters' change
after three steps. The window then goes on with the same graph and state.
After the window the state is freed and the plain float32 reference
follows the same three steps from the same weights and rows.

Compared, each by the worst of its parts: the loss of each step, relative
to the reference's; the norm of each leaf's first gradient, and of each
leaf's change over three steps, against the reference's norm of that leaf
or the median leaf's, whichever is larger. Leaves whose reference
gradient is under a thousandth of the median leaf's move by round-off
alone and are left out of the change.
"""
from __future__ import annotations

import math
import time
from dataclasses import dataclass
from typing import Dict, Iterator, List

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import Check, Context, Output, free, span
from bench.load import train_rows
from bench.refmath import lower
from bench.weights import path_str, program_weights, reference_weights

CHECK_STEPS = 3
#: leaves whose reference gradient is under this share of the median
#: leaf's are left out of the change (they move by round-off under Adam)
STILL_LEAF = 1e-3


def leaf_norms(tree) -> Dict[str, float]:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    norms = jax.jit(lambda xs: [jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) for x in xs])([x for _, x in flat])
    return {path_str(p): float(n) for (p, _), n in zip(flat, norms)}


def change_norms(new, old) -> Dict[str, float]:
    flat = jax.tree_util.tree_flatten_with_path(new)[0]
    olds = jax.tree.leaves(old)
    norms = jax.jit(lambda a, b: [jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32) - y.astype(jnp.float32))))
        for x, y in zip(a, b)])([x for _, x in flat], olds)
    return {path_str(p): float(n) for (p, _), n in zip(flat, norms)}


@dataclass
class Readings:
    """What one run's first steps say: per-step loss, per-leaf norms of
    the first gradient and of the change over CHECK_STEPS steps."""
    losses: List[float]
    grad: Dict[str, float]
    change: Dict[str, float]


class GraphTrainer:
    """The program's step graph and its state, on one gpu-typed node."""

    def __init__(self, ctx: Context):
        import train_lm
        from repro import core, dag
        from repro.compute import kernel_task
        from repro.models import build_model
        from repro.optim.adamw import AdamWConfig

        self.ctx, tr = ctx, ctx.cell.traffic
        self.model = build_model(ctx.program_config())
        self.opt = AdamWConfig(lr=float(tr["lr"]))
        grad_fn, reduce_fn, apply_fn = train_lm.build_step_fns(self.model,
                                                               self.opt)
        self.shapes = jax.eval_shape(self.model.init, jax.random.PRNGKey(0))
        self.cluster = core.init(node_resources=[{"cpu": 2.0, "gpu": 1.0},
                                                 {"cpu": 2.0}])
        self.core = core
        params = self.new_params(ctx.seed)
        grad_shard = kernel_task(
            grad_fn, resources={"gpu": 1.0}, num_returns=2,
            warmup_args=(params, self.batch(ctx.seed, 0)))
        reduce_grads = core.remote(reduce_fn)
        apply_update = core.remote(apply_fn, num_returns=2)
        g = grad_shard.bind(dag.input(0), dag.input(2))
        red = reduce_grads.bind(g[1])
        upd = apply_update.bind(dag.input(0), dag.input(1), red)
        self.graph = dag.compile([upd[0], upd[1], g[0]])
        self.p0 = params

    def new_params(self, seed: int):
        return program_weights(seed, self.ctx.family.layout(self.ctx.model),
                               self.shapes)

    def batch(self, seed: int, step: int) -> Dict[str, np.ndarray]:
        return {"tokens": train_rows(self.ctx.cell.traffic,
                                     self.ctx.model["vocab_size"], seed, step)}

    def start(self, seed: int, params=None) -> Readings:
        """Fresh state from `seed`, then the first CHECK_STEPS steps."""
        from repro.optim.adamw import adamw_init
        if params is None:
            params = self.new_params(seed)
        self.p0 = params
        self.seed = seed
        self.params_ref = self.core.put(params)
        self.opt_ref = self.core.put(adamw_init(params))
        self.step_no = 0
        losses, grad = [], {}
        for _ in range(CHECK_STEPS):
            losses.append(self.step()[1])
            if self.step_no == 1:
                m = self.core.get(self.opt_ref, timeout=300)["m"]
                grad = {k: v / (1.0 - self.opt.b1)
                        for k, v in leaf_norms(m).items()}
        change = change_norms(self.core.get(self.params_ref, timeout=300),
                              self.p0)
        return Readings(losses, grad, change)

    def step(self):
        """One step through the graph: (execute seconds, loss)."""
        b = self.batch(self.seed, self.step_no)
        t = time.perf_counter()
        with span("train.execute"):
            refs = self.graph.execute(self.params_ref, self.opt_ref, b)
        ex = time.perf_counter() - t
        self.params_ref, self.opt_ref = refs[0], refs[1]
        with span("train.loss_get"):
            loss = float(np.asarray(self.core.get(refs[2], timeout=300)))
        self.step_no += 1
        return ex, loss

    def close(self) -> None:
        import gc
        self.core.shutdown()
        free(self.p0)
        self.params_ref = self.opt_ref = self.cluster = self.graph = None
        gc.collect()


def reference_readings(ctx: Context, seed: int, low: str = "",
                       half: bool = False) -> Readings:
    """The plain reference's first CHECK_STEPS steps from the same weights
    and rows (float32, AdamW as configured). `low` runs it as the control
    (`refmath.lower`: products in that precision, weights stored as the
    configuration stores them);
    `half` leaves out the second half of every batch (a fault a step can
    have), taking the mean over the rest."""
    fam, m, tr = ctx.family, ctx.model, ctx.cell.traffic
    lr, wd, b1, b2, eps, clip = (float(tr["lr"]), 0.1, 0.9, 0.95, 1e-8,
                                 1.0)
    block = int(tr["reference_rows"])
    # the control keeps its weights as the configuration stores them
    store = (lambda t: jax.tree.map(lambda x: lower(x, "bf16"), t)) \
        if low and m["param_dtype"] == "bfloat16" else (lambda t: t)
    w = store(reference_weights(seed, fam.layout(m)))

    def grads_of(w, rows):
        block_ = math.gcd(block, rows.shape[0])
        nb = rows.shape[0] // block_
        blocks = rows.reshape(nb, block_, rows.shape[1])
        n = rows.shape[0] * (rows.shape[1] - 1)
        vg = jax.value_and_grad(lambda w, r: fam.loss_sum(w, m, r, low))

        def body(acc, r):
            l, g = vg(w, r)
            return (acc[0] + l, jax.tree.map(jnp.add, acc[1], g)), None
        zero = (jnp.zeros(()), jax.tree.map(jnp.zeros_like, w))
        (l, g), _ = jax.lax.scan(body, zero, blocks)
        return l / n, jax.tree.map(lambda x: x / n, g)

    @jax.jit
    def step(w, mom, vel, t, rows):
        loss, g = grads_of(w, rows)
        norm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
        g = jax.tree.map(lambda x: x * jnp.minimum(1.0, clip / jnp.maximum(
            norm, 1e-9)), g)
        mom = jax.tree.map(lambda a, x: b1 * a + (1 - b1) * x, mom, g)
        vel = jax.tree.map(lambda a, x: b2 * a + (1 - b2) * x * x, vel, g)
        bc1, bc2 = 1 - b1 ** t, 1 - b2 ** t
        w = store(jax.tree.map(lambda p, a, v: p - lr * (
            (a / bc1) / (jnp.sqrt(v / bc2) + eps) + wd * p), w, mom, vel))
        gn = {k: jnp.sqrt(jnp.sum(x * x)) for k, x in g.items()}
        return loss, w, mom, vel, gn

    w0 = {k: v.copy() for k, v in w.items()}
    mom = {k: jnp.zeros_like(v) for k, v in w.items()}
    vel = {k: jnp.zeros_like(v) for k, v in w.items()}
    losses, grad = [], {}
    for s in range(CHECK_STEPS):
        rows = train_rows(tr, m["vocab_size"], seed, s)
        if half:
            rows = rows[: rows.shape[0] // 2]
        loss, w, mom, vel, gn = step(w, mom, vel, jnp.float32(s + 1),
                                     jnp.asarray(rows))
        losses.append(float(loss))
        if s == 0:
            grad = {k: float(v) for k, v in gn.items()}
    change = {k: float(jnp.sqrt(jnp.sum(jnp.square(w[k] - w0[k]))))
              for k in w}
    free([w, w0, mom, vel])
    return Readings(losses, grad, change)


def compare(got: Readings, ref: Readings) -> Dict[str, float]:
    """The three numbers compared, each by the worst of its parts."""
    loss = max(abs(a - b) / abs(b) for a, b in zip(got.losses, ref.losses))
    med = float(np.median(list(ref.grad.values())))

    def worst(a: Dict[str, float], b: Dict[str, float], keys) -> float:
        floor = float(np.median([b[k] for k in keys]))
        return max(abs(a[k] - b[k]) / max(b[k], floor) for k in keys)

    moving = [k for k in ref.grad if ref.grad[k] >= STILL_LEAF * med]
    return {"loss_rel_gap": loss,
            "grad_norm_gap": worst(got.grad, ref.grad, list(ref.grad)),
            "change_norm_gap": worst(got.change, ref.change, moving)}


def run(ctx: Context) -> Output:
    tr = ctx.cell.traffic
    tokens_per_step = int(tr["batch"]) * int(tr["seq_len"])
    trainer = GraphTrainer(ctx)
    try:
        got = trainer.start(ctx.seed, trainer.p0)
        execute_s: List[float] = []
        steps = 0
        with ctx.window():
            t0 = time.perf_counter()
            while True:
                ex, _ = trainer.step()
                if time.perf_counter() > t0 + ctx.seconds:
                    break
                execute_s.append(ex)
                steps += 1
    finally:
        trainer.close()
    ref = reference_readings(ctx, ctx.seed)
    nums = compare(got, ref)
    lim = tr["limits"]
    checks = [Check(k, v, lim[k]) for k, v in nums.items()]
    return Output(
        e2e={"train_tokens_per_s": steps * tokens_per_step / ctx.seconds,
             "setup_s": ctx.setup_s},
        checks=checks, attempted=steps, failed=0,
        memory_peak_bytes=ctx.memory_peak,
        data={"execute_s": execute_s, "window_tokens": steps
              * tokens_per_step},
        notes={"steps_in_window": steps, "losses": got.losses,
               "reference_losses": ref.losses})


def worst_leaves(got: Readings, ref: Readings, n: int = 3) -> Dict:
    """The leaves that set the grad and change numbers, with both norms:
    what a look at a seed that reads high starts from."""
    out = {}
    for part in ("grad", "change"):
        a, b = getattr(got, part), getattr(ref, part)
        floor = float(np.median(list(b.values())))
        keys = sorted(b, key=lambda k: -abs(a[k] - b[k]) / max(b[k], floor))
        out[part] = [[k, a[k], b[k]] for k in keys[:n]] + [["median", floor]]
    out["loss_steps"] = [abs(x - y) / abs(y)
                         for x, y in zip(got.losses, ref.losses)]
    return out


def limits(ctx: Context, seeds: int, control_seeds: int) -> Iterator[Dict]:
    """Readings a limit is set from: the program on `seeds` seeds; the
    float8 control and the half-batch fault on the first `control_seeds`."""
    trainer = GraphTrainer(ctx)
    try:
        for k in range(seeds):
            seed = ctx.seed + 7919 * k
            t = time.perf_counter()
            got = trainer.start(seed, trainer.p0 if k == 0 else None)
            free(trainer.p0)
            prog_s = time.perf_counter() - t
            t = time.perf_counter()
            ref = reference_readings(ctx, seed)
            yield dict(compare(got, ref), kind="program", seed=seed,
                       losses=got.losses, reference_losses=ref.losses,
                       program_s=prog_s,
                       reference_s=time.perf_counter() - t,
                       **worst_leaves(got, ref))
            if k < control_seeds:
                for kind, kw in (("control", {"low": ctx.control}),
                                 ("fault_half_batch", {"half": True})):
                    bad = reference_readings(ctx, seed, **kw)
                    yield dict(compare(bad, ref), kind=kind, seed=seed,
                               **worst_leaves(bad, ref))
    finally:
        trainer.close()
