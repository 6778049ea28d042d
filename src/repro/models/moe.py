"""Mixture-of-Experts FFN with three dispatch strategies.

``dense``    — compute every held expert for every token, weight by gates.
               Exact, used for smoke tests and as the oracle in property
               tests.
``dropping`` — GShard/Switch-style capacity-bounded einsum dispatch: the
               (tokens, experts, capacity) one-hot keeps everything MXU-shaped
               and shards cleanly (experts over the `model` axis => XLA emits
               all-to-all). Dry-run default; drops tokens past capacity.
``ragged``   — sort-by-expert + lax.ragged_dot grouped GEMM over the held
               experts: dropless. Serving (`dropless=True`) always takes it
               unless the config asks for ``dense``.

Router: fp32 logits and softmax over all experts; optionally group-limited
(DeepSeek's `group_limited_greedy`: keep the `topk_group` groups whose best
expert scores highest, take the top-k among them); gates renormalised to
sum 1, or the scores times `routed_scaling_factor`. Aux losses (switch
load-balance + router z-loss) are returned for the trainer.

Expert parallelism: a layer may hold a share of the experts
(`MoEConfig.num_experts_held` from `first_expert`). It routes over all of
them and computes only its own experts' part of the result; the shared
experts, which every chip holds, are added whole.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, MoEConfig
from repro.models.layers import dense_init

Params = Dict[str, Any]
#: the routed experts' weights, (E_held, ...) in a layer's parameters
EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")


def moe_init(rng, cfg: ModelConfig) -> Params:
    mc = cfg.moe
    d, f, e = cfg.d_model, mc.d_ff_expert, mc.held
    dt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(rng, 5)
    scale = 1.0 / math.sqrt(d)
    p = {
        "router": dense_init(ks[0], d, mc.num_experts, jnp.float32),
        "w_gate": (jax.random.normal(ks[1], (e, d, f), jnp.float32) * scale).astype(dt),
        "w_up": (jax.random.normal(ks[2], (e, d, f), jnp.float32) * scale).astype(dt),
        "w_down": (jax.random.normal(ks[3], (e, f, d), jnp.float32)
                   / math.sqrt(f)).astype(dt),
    }
    if mc.num_shared_experts:
        from repro.models.layers import swiglu_init
        p["shared"] = swiglu_init(ks[4], d, f * mc.num_shared_experts, dt)
    return p


def route(params: Params, mc: MoEConfig, x2d: jnp.ndarray):
    """x2d: (T, d) -> gates (T, k) float32, expert ids (T, k) over all
    experts, and the float32 logits and scores (T, E)."""
    # HIGHEST: on a TPU a float32 product otherwise runs in bfloat16
    logits = jnp.einsum("td,de->te", x2d.astype(jnp.float32),
                        params["router"].astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    masked = probs
    if mc.n_group > 1:
        t = probs.shape[0]
        by_group = probs.reshape(t, mc.n_group, -1)
        _, keep = jax.lax.top_k(by_group.max(-1), mc.topk_group)   # (T, g)
        kept = jax.nn.one_hot(keep, mc.n_group, dtype=jnp.bool_).any(1)
        masked = jnp.where(kept[:, :, None], by_group, 0.0).reshape(t, -1)
    top_p, top_i = jax.lax.top_k(masked, mc.top_k)
    if mc.norm_topk_prob:
        top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    else:
        top_p = top_p * mc.routed_scaling_factor
    return top_p, top_i, logits, probs


def _aux_losses(mc: MoEConfig, logits, probs, top_i) -> Dict[str, jnp.ndarray]:
    # switch load-balance loss: E * sum_e f_e * P_e
    e = mc.num_experts
    f_e = jnp.zeros((e,), jnp.float32).at[top_i.reshape(-1)].add(1.0)
    f_e = f_e / jnp.maximum(f_e.sum(), 1.0)
    p_e = probs.mean(axis=0)
    lb_loss = e * jnp.sum(f_e * p_e)
    z_loss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    return {"moe_lb_loss": lb_loss, "moe_z_loss": z_loss}


def _held(mc: MoEConfig, idx):
    """(local expert ids, held mask): the chosen experts this layer holds,
    numbered from 0."""
    local = idx - mc.first_expert
    return local, (local >= 0) & (local < mc.held)


def _held_count(mc: MoEConfig, t: int, counted):
    """Slots of `t` tokens that chose a held expert: `counted` where the
    layer holds a share, all t * k where it holds every expert (a count
    on the device would be that constant, and costs the decode step
    megabytes of temporaries at small widths)."""
    return counted if mc.held < mc.num_experts else jnp.int32(t * mc.top_k)


def _expert_ffn(params: Params, h_in: jnp.ndarray) -> jnp.ndarray:
    """h_in: (E, C, d) -> (E, C, d), per-expert SwiGLU."""
    g = jnp.einsum("ecd,edf->ecf", h_in, params["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", h_in, params["w_up"])
    h = jax.nn.silu(g.astype(jnp.float32)).astype(h_in.dtype) * u
    return jnp.einsum("ecf,efd->ecd", h, params["w_down"])


def _dense_moe(params: Params, mc: MoEConfig, x2d, gates, idx,
               count: bool = False):
    """Every held expert on every token, weighted by the gates; with
    `count`, also how many of the T * k slots chose a held expert."""
    t, d = x2d.shape
    local, held = _held(mc, idx)
    # (T, held) combine weights from the held part of the top-k selection
    comb = jnp.einsum("tk,tke->te", jnp.where(held, gates, 0.0),
                      jax.nn.one_hot(local, mc.held, dtype=jnp.float32))
    g = jnp.einsum("td,edf->tef", x2d, params["w_gate"])
    u = jnp.einsum("td,edf->tef", x2d, params["w_up"])
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x2d.dtype) * u
    y = jnp.einsum("tef,efd->ted", h, params["w_down"])
    y = jnp.einsum("ted,te->td", y, comb.astype(x2d.dtype))
    # the gates are positive: a held slot is a nonzero combine weight
    return (y, _held_count(mc, t, jnp.sum(comb > 0, dtype=jnp.int32))) \
        if count else y


def _dropping_moe(params: Params, mc: MoEConfig, x3d, gates, idx,
                  shard_fn=None):
    """GShard dispatch with per-*group* expert capacity.

    x3d: (G, N, d) — G groups of N tokens. Capacity is per (group, expert),
    so the dispatch tensor is (G, N, E, C) with G sharded over `data` and E
    over `model` (the einsum against it becomes XLA's all-to-all). Matches
    the GShard/MaxText "dropping" strategy. shard_fn(name, x) lets the model
    annotate intermediate shardings.
    """
    if mc.held != mc.num_experts:
        raise ValueError("dropping dispatch needs every expert held")
    g_, n, d = x3d.shape
    e = mc.num_experts
    cap = int(math.ceil(n * mc.top_k / e * mc.capacity_factor))
    cap = max(8, -(-cap // 8) * 8)  # round up to 8 for lane alignment
    cap = min(cap, n) if n >= 8 else cap
    sf = shard_fn or (lambda name, a: a)

    # position of each (token, rank) within its (group, expert) queue;
    # earlier ranks get priority, matching GShard.
    dispatch = jnp.zeros((g_, n, e, cap), x3d.dtype)
    combine = jnp.zeros((g_, n, e, cap), jnp.float32)
    counts = jnp.zeros((g_, 1, e), jnp.int32)
    for r in range(mc.top_k):
        mask_r = jax.nn.one_hot(idx[..., r], e, dtype=jnp.int32)   # (G,N,E)
        pos_r = jnp.cumsum(mask_r, axis=1) - 1 + counts
        counts = counts + mask_r.sum(axis=1, keepdims=True)
        keep = (mask_r > 0) & (pos_r < cap)
        oh = jax.nn.one_hot(jnp.where(keep, pos_r, -1), cap, dtype=x3d.dtype)
        dispatch = dispatch + oh * mask_r[..., None].astype(x3d.dtype)
        combine = combine + (oh.astype(jnp.float32)
                             * (mask_r.astype(jnp.float32)
                                * gates[..., r:r + 1])[..., None])
    dispatch = sf("moe_dispatch", dispatch)
    h_in = sf("moe_egcd", jnp.einsum("gnec,gnd->egcd", dispatch, x3d))
    h_out = _expert_ffn(params, h_in.reshape(e, g_ * cap, d))
    h_out = sf("moe_egcd", h_out.reshape(e, g_, cap, d))
    # combine weights in activation dtype: halves the bytes of the combine
    # einsum (gate precision is preserved — gates were computed in fp32)
    return jnp.einsum("gnec,egcd->gnd", combine.astype(x3d.dtype), h_out)


def _ragged_moe(params: Params, mc: MoEConfig, x2d, gates, idx, layer=None,
                count: bool = False):
    """Dropless grouped GEMM over the held experts (sort + lax.ragged_dot).

    The held slots are sorted by expert in front of the rest; the products
    run over the first T * min(k, held) sorted rows, the most that can be
    held (a token picks an expert at most once), and lax.ragged_dot visits
    only the rows its groups cover, so the work and the expert weights
    read follow the held slots. Each slot's row is gathered back and
    summed over the token's k choices in float32.

    With `layer`, the expert weights are stacked over layers (L, E, ...)
    and used where they lie: the groups of the other layers are empty. A
    layer's slice of the stack would be copied out for the grouped
    product every step (it cannot take a slice as its operand). With
    `count`, also how many of the T * k slots chose a held expert."""
    t, d = x2d.shape
    k, n = mc.top_k, mc.held
    local, held = _held(mc, idx)
    flat_e = jnp.where(held, local, n).reshape(-1)       # n: held elsewhere
    order = jnp.argsort(flat_e, stable=True)
    rows = t * min(k, n)
    group_sizes = jnp.bincount(flat_e, length=n + 1)[:n].astype(jnp.int32)
    w = {name: params[name] for name in EXPERT_WEIGHTS}
    sizes = group_sizes
    if layer is not None:
        w = {name: a.reshape((-1,) + a.shape[2:]) for name, a in w.items()}
        sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((w["w_gate"].shape[0],), jnp.int32), group_sizes,
            (layer * n,))
    xs = x2d[order[:rows] // k]                          # (rows, d) by expert
    g = jax.lax.ragged_dot(xs, w["w_gate"], sizes)
    u = jax.lax.ragged_dot(xs, w["w_up"], sizes)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(xs.dtype) * u
    y = jax.lax.ragged_dot(h, w["w_down"], sizes)
    # rows past the held slots are left out (the product does not write
    # them), and a slot held elsewhere reads the zero row appended last
    y = jnp.where((jnp.arange(rows) < group_sizes.sum())[:, None], y, 0)
    y = jnp.concatenate([y, jnp.zeros((1, d), y.dtype)])
    rank = jnp.zeros((t * k,), jnp.int32).at[order].set(
        jnp.arange(t * k, dtype=jnp.int32))
    per_slot = y[jnp.minimum(rank, rows)].reshape(t, k, d)
    w = jnp.where(held, gates, 0.0)
    y = jnp.einsum("tkd,tk->td", per_slot.astype(jnp.float32),
                   w).astype(x2d.dtype)
    return (y, _held_count(mc, t, group_sizes.sum())) if count else y


def moe_apply(params: Params, cfg: ModelConfig, x: jnp.ndarray,
              shard_fn=None, dropless: bool = False, layer=None
              ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """x: (B, S, d) -> (B, S, d), aux. For training, aux holds the router's
    losses. With `dropless` (serving: prefill and decode), no token is
    dropped, a prefill routes one lane at a time (so the sorted rows are
    one lane's), and aux holds `routed_slots` (tokens x top-k) and
    `held_slots` (those that chose an expert held here), int32. With
    `layer` (dropless, ragged dispatch), the routed experts' weights in
    `params` are stacked over layers and this is layer `layer`."""
    mc = cfg.moe
    b, s, d = x.shape
    with jax.named_scope("moe"):
        if dropless:
            y, held = jax.lax.map(
                lambda xb: _dropless(params, mc, xb, layer), x) if s > 1 \
                else _dropless(params, mc, x.reshape(b, d), layer)
            aux = {"routed_slots": jnp.int32(b * s * mc.top_k),
                   "held_slots": jnp.sum(held, dtype=jnp.int32)}
        else:
            y, aux = _train_moe(params, mc, x, shard_fn)
        if mc.num_shared_experts:
            from repro.models.layers import swiglu
            y = y.reshape(b, s, d) + swiglu(params["shared"], x)
    return y.reshape(b, s, d), aux


def _dropless(params: Params, mc: MoEConfig, x2d, layer):
    """(T, d) -> routed experts' part (T, d), and how many of its T * k
    slots chose a held expert."""
    gates, idx, _, _ = route(params, mc, x2d)
    if mc.dispatch == "dense":
        return _dense_moe(params, mc, x2d, gates, idx, count=True)
    return _ragged_moe(params, mc, x2d, gates, idx, layer, count=True)


def _train_moe(params: Params, mc: MoEConfig, x, shard_fn):
    b, s, d = x.shape
    x2d = x.reshape(b * s, d)
    gates, idx, logits, probs = route(params, mc, x2d)
    aux = _aux_losses(mc, logits, probs, idx)
    if mc.dispatch == "dense":
        y = _dense_moe(params, mc, x2d, gates, idx)
    elif mc.dispatch == "dropping":
        # groups of <=4096 tokens: capacity (and the dispatch one-hot) stays
        # bounded regardless of sequence length; one flat group at decode
        if s > 1:
            gsz = math.gcd(s, 4096)
            g_, n = b * (s // gsz), gsz
        else:
            g_, n = 1, b * s
        y = _dropping_moe(params, mc, x2d.reshape(g_, n, d),
                          gates.reshape(g_, n, -1), idx.reshape(g_, n, -1),
                          shard_fn)
        y = y.reshape(b * s, d)
    elif mc.dispatch == "ragged":
        y = _ragged_moe(params, mc, x2d, gates, idx)
    else:
        raise ValueError(f"unknown moe dispatch {mc.dispatch!r}")
    return y, aux
