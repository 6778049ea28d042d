"""DeepSeek-V2 as published, at tiny widths on the CPU: the program against
the benchmark's plain float32 reference (`bench/models/deepseek_v2.py`,
the one copy of it), the expert layer's share of a deployment, its
router, YaRN, and the routing counters the engine reports."""
import dataclasses
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import core
from repro.configs.base import MoEConfig
from repro.configs.registry import get_config, get_smoke_config
from repro.models import build_model, layers
from repro.models.model import routing_counts
from repro.models import moe as moe_lib
from repro.serving import Request, ServingEngine

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.spec import load_module  # noqa: E402
from bench.weights import program_weights, reference_weights  # noqa: E402

REF = load_module(ROOT / "bench" / "models" / "deepseek_v2.py",
                  "models_deepseek_v2")

#: 16 experts in 8 groups of 2, the best 4 groups kept, top 4 of those;
#: this share holds experts 4-7
SHARED_MOE = dict(num_experts=16, top_k=4, d_ff_expert=64,
                  num_shared_experts=1, n_group=8, topk_group=4,
                  norm_topk_prob=False, routed_scaling_factor=16.0)


def _cfg(**moe):
    smoke = get_smoke_config("deepseek-v2-236b")
    return smoke.scaled(param_dtype="float32", moe=dataclasses.replace(
        smoke.moe, **dict(SHARED_MOE, **moe)))


def test_prefill_then_decode_matches_the_reference():
    """Prefill of 24 tokens, then 8 decode steps through the latent cache,
    against the reference's full causal forward at every position. Both
    sides in float32 with the same weights; the program's sums run in
    another order (absorbed decode, blockwise pieces), so logits of size
    ~4 agree to 1e-4 and not to the last bit."""
    cfg = _cfg(num_experts_held=4, first_expert=4, dispatch="dropping")
    m = dataclasses.asdict(cfg)
    model = build_model(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    p = program_weights(11, REF.layout(m), shapes)
    w = reference_weights(11, REF.layout(m))
    toks = jax.random.randint(jax.random.PRNGKey(2), (1, 32), 1,
                              cfg.vocab_size)
    ref = np.asarray(REF.logits(w, m, toks[0]))
    v = cfg.vocab_size
    logits, cache = model.prefill(p, {"tokens": toks[:, :24]}, max_seq=40)
    np.testing.assert_allclose(np.asarray(logits[0, -1, :v]), ref[23],
                               atol=1e-4, rtol=0)
    for t in range(24, 32):
        logits, cache = model.decode_step(p, cache, toks[:, t:t + 1],
                                          jnp.int32(t))
        np.testing.assert_allclose(np.asarray(logits[0, 0, :v]), ref[t],
                                   atol=1e-4, rtol=0, err_msg=f"pos {t}")
    # prefill held ~ 4/16 of its slots; the counts are the layers' total
    slots = routing_counts(cache)
    n_moe = cfg.num_layers - cfg.first_k_dense
    assert slots["prefill_routed_slots"] == 24 * 4 * n_moe
    assert slots["routed_slots"] == 8 * 4 * n_moe
    assert 0 < slots["prefill_held_slots"] < slots["prefill_routed_slots"]


def _moe_params(cfg, seed=0):
    return moe_lib.moe_init(jax.random.PRNGKey(seed), cfg)


def _share(params, first, held):
    return dict(params, **{n: params[n][first:first + held]
                           for n in moe_lib.EXPERT_WEIGHTS})


@pytest.mark.parametrize("dispatch", ["ragged", "dense"])
def test_held_shares_add_up_to_the_whole_layer(dispatch):
    """Four chips holding four experts each: their outputs, with the
    shared expert (which every chip computes) counted once, add up to the
    layer that holds all sixteen."""
    whole = _cfg(dispatch=dispatch)
    params = _moe_params(whole)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 12, whole.d_model))
    y_all, _ = moe_lib.moe_apply(params, whole, x, dropless=True)
    parts = []
    for first in range(0, 16, 4):
        cfg = _cfg(dispatch=dispatch, num_experts_held=4, first_expert=first)
        y, aux = moe_lib.moe_apply(_share(params, first, 4), cfg, x,
                                   dropless=True)
        parts.append((y, int(aux["held_slots"])))
    shared = layers.swiglu(params["shared"], x)
    total = sum(y for y, _ in parts) - 3 * shared
    np.testing.assert_allclose(np.asarray(total), np.asarray(y_all),
                               atol=1e-5, rtol=1e-5)
    assert sum(n for _, n in parts) == 2 * 12 * 4   # every slot held once


def test_no_token_dropped_under_skewed_routing_at_prefill():
    """Every token routed to the same four experts: the serving path,
    under a configuration whose training dispatch drops at capacity 1.0,
    computes every slot (as the dense oracle does); the training dispatch
    drops most of them."""
    cfg = _cfg(dispatch="dropping", capacity_factor=1.0)
    params = _moe_params(cfg)
    bias = jnp.zeros((cfg.d_model, 16)).at[:, :4].set(1.0)
    params["router"] = params["router"] * 0.01 + bias
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(3),
                                  (2, 32, cfg.d_model)))
    _, idx, _, _ = moe_lib.route(params, cfg.moe, x.reshape(-1, cfg.d_model))
    assert set(np.unique(np.asarray(idx))) == {0, 1, 2, 3}
    served, aux = moe_lib.moe_apply(params, cfg, x, dropless=True)
    dense = _cfg(dispatch="dense")
    oracle, _ = moe_lib.moe_apply(params, dense, x, dropless=True)
    np.testing.assert_allclose(np.asarray(served), np.asarray(oracle),
                               atol=1e-5, rtol=1e-5)
    assert int(aux["held_slots"]) == 2 * 32 * 4
    dropped, _ = moe_lib.moe_apply(params, cfg, x)
    assert not np.allclose(np.asarray(dropped), np.asarray(oracle),
                           atol=1e-3)


def test_router_keeps_the_best_groups_and_scales_the_scores():
    """Every chosen expert lies in one of the `topk_group` groups whose
    best expert scores highest, no better expert of those groups is left
    out, and each gate is its softmax score times the scaling factor."""
    cfg = _cfg()
    mc = cfg.moe
    params = _moe_params(cfg, seed=4)
    x = jax.random.normal(jax.random.PRNGKey(5), (64, cfg.d_model))
    gates, idx, _, probs = moe_lib.route(params, mc, x)
    probs, gates, idx = map(np.asarray, (probs, gates, idx))
    size = mc.num_experts // mc.n_group
    for t in range(64):
        best = probs[t].reshape(mc.n_group, size).max(-1)
        kept = set(np.argsort(-best)[:mc.topk_group])
        assert {int(e) // size for e in idx[t]} <= kept
        pool = [e for e in range(mc.num_experts) if e // size in kept]
        top = sorted(pool, key=lambda e: -probs[t, e])[:mc.top_k]
        assert set(idx[t]) == set(top)
        np.testing.assert_allclose(gates[t], probs[t, idx[t]] * 16.0,
                                   rtol=1e-6)


def test_yarn_frequencies_and_scale_match_the_closed_form():
    """DeepSeek-V2's YaRN over its 64 rope dims: the correction range is
    pairs 10-23 (from beta_fast 32 and beta_slow 1 over 4096 positions),
    so pairs 0-10 keep theta^(-2i/64) and pairs 11-31 move towards it over
    40; cos/sin keep magnitude 1 (mscale = mscale_all_dim); the softmax
    scale gains (0.1 * 0.707 * ln 40 + 1)^2 = 1.5896."""
    cfg = get_config("deepseek-v2-236b")
    ys = cfg.rope_scaling
    assert layers.yarn_correction_range(ys, 64, 1e4) == (10, 23)
    i = np.arange(32)
    extrap = 1e4 ** (-2.0 * i / 64)
    ramp = np.clip((i - 10) / 13, 0, 1)
    want = extrap / 40 * ramp + extrap * (1 - ramp)
    got = np.asarray(layers.rope_frequencies(64, 1e4, 64, ys))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert (got[:11] == np.asarray(layers.rope_frequencies(64, 1e4))[:11]
            ).all()
    assert (got[11:] < extrap[11:] * (1 - 1e-6)).all()
    factor = (0.1 * 0.707 * math.log(40) + 1) ** 2
    assert layers.yarn_softmax_factor(ys) == pytest.approx(factor)
    assert factor == pytest.approx(1.5896, abs=1e-4)
    m = dataclasses.asdict(cfg)
    np.testing.assert_allclose(np.asarray(REF.yarn_frequencies(m)), want,
                               rtol=1e-6)
    assert REF.softmax_scale(m) == pytest.approx(factor / math.sqrt(192))


def _rope_before_yarn(x, positions, theta, rotary_fraction=1.0):
    """`layers.apply_rope` as it was before YaRN."""
    hd = x.shape[-1]
    rot = int(hd * rotary_fraction)
    rot -= rot % 2
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    ang = positions[..., :, None].astype(jnp.float32) * inv
    sin, cos = jnp.sin(ang)[..., :, None, :], jnp.cos(ang)[..., :, None, :]
    x1, x2 = jnp.split(x[..., :rot].astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                          axis=-1).astype(x.dtype)
    return jnp.concatenate([out, x[..., rot:]], axis=-1) \
        if rot < hd else out


@pytest.mark.parametrize("fraction", [1.0, 0.25])
def test_rope_without_scaling_is_unchanged_bit_for_bit(fraction):
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 16, 4, 64),
                          jnp.bfloat16)
    pos = jnp.arange(100, 116)
    got = jax.jit(layers.apply_rope, static_argnums=(2, 3))(
        x, pos, 10_000.0, fraction)
    want = jax.jit(_rope_before_yarn, static_argnums=(2, 3))(
        x, pos, 10_000.0, fraction)
    assert (np.asarray(got, np.float32) == np.asarray(want, np.float32)
            ).all()


@pytest.fixture()
def cluster():
    c = core.init(num_nodes=1, workers_per_node=2)
    yield c
    core.shutdown()


def _wave(cluster, cfg, budgets, plen=8):
    model = build_model(cfg)
    eng = ServingEngine(model, model.init(jax.random.PRNGKey(0)), max_seq=32)
    rng = np.random.default_rng(0)
    eng.serve([Request(i, rng.integers(1, 200, plen).astype(np.int32), b)
               for i, b in enumerate(budgets)], max_wave=len(budgets))
    (w,) = [e for e in cluster.gcs.events()
            if e[1] == "span" and e[4]["name"] == "engine.wave"]
    return w[4]


def test_engine_wave_counts_routing_slots(cluster):
    """A wave of 3 lanes, prompts of 8, at most 5 tokens: the routed
    slots are tokens x top-k x expert layers, prefill and decode apart;
    the held slots are the part of them this chip's experts took."""
    cfg = get_smoke_config("deepseek-v2-236b")
    mc = cfg.moe
    cfg = cfg.scaled(moe=dataclasses.replace(mc, num_experts_held=4,
                                             first_expert=2))
    c = _wave(cluster, cfg, [3, 5, 2])
    n_moe = cfg.num_layers - cfg.first_k_dense
    assert c["prefill_routed_slots"] == 3 * 8 * mc.top_k * n_moe
    assert c["routed_slots"] == c["steps"] * 3 * mc.top_k * n_moe
    assert 0 < c["held_slots"] < c["routed_slots"]
    assert 0 < c["prefill_held_slots"] < c["prefill_routed_slots"]


def test_a_model_without_experts_counts_none(cluster):
    c = _wave(cluster, get_smoke_config("stablelm-1.6b"), [3, 2])
    assert not {"routed_slots", "held_slots", "prefill_routed_slots",
                "prefill_held_slots"} & set(c)


def test_registry_entry_states_the_published_model():
    cfg = get_config("deepseek-v2-236b")
    assert (cfg.num_layers, cfg.d_model, cfg.dense_d_ff) == (60, 5120, 12288)
    assert cfg.moe == MoEConfig(
        num_experts=160, top_k=6, d_ff_expert=1536, num_shared_experts=2,
        capacity_factor=1.0, n_group=8, topk_group=3, norm_topk_prob=False,
        routed_scaling_factor=16.0)
    assert cfg.moe.held == 160
    assert cfg.rope_scaling.factor == 40.0
