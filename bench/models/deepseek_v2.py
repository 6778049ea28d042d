"""DeepSeek-V2 family: weight layout, plain float32 reference, and the
operations and bytes its work needs, from shapes alone.

The reference follows DeepSeek-V2's published equations (arXiv:2405.04434
and the released modeling code) for the configuration's share of the
model: pre-norm decoder with RMSNorm; multi-head latent attention,
unabsorbed (the query through its LoRA and norm, K/V expanded per head
from the normed latent, one rope key shared by all heads) with YaRN rotary
frequencies and softmax scale; layer 0 a dense SwiGLU; the others a
mixture of experts whose router takes a float32 softmax over every
expert, keeps the `topk_group` groups whose best expert scores highest,
picks the top-k experts among them and gates them by score times
`routed_scaling_factor` (renormalised instead where the configuration
says), plus the shared experts; an untied head.

Departures, both of arrangement and not of function:
- Rotary pairs: the published code rotates interleaved pairs (it
  permutes q_pe and k_pe to halves first); here dims i and i + 32 form a
  pair. With random weights that is a fixed permutation of the columns of
  the rope projections.
- The cut: the configuration's layers, vocabulary slice and held experts
  (`num_experts_held` from `first_expert`). Experts chosen by the router
  that are held elsewhere add nothing, as in the program.

No cache, kernels or batching: every held expert runs on every token,
masked by its gate, and attention runs in blocks of query positions so
that the float32 forward of 8k positions fits beside its weights. It
imports nothing of the program.
"""
from __future__ import annotations

import math
from typing import Dict, Sequence

import jax
import jax.numpy as jnp

from bench.refmath import control_gaps, mm, rmsnorm, served_gaps
from bench.weights import Leaf

BF16 = 2  # bytes
#: query positions per block of the reference's attention
Q_BLOCK = 64
EXPERT_WEIGHTS = ("ffn/w_gate", "ffn/w_up", "ffn/w_down")


def _dims(m):
    a, e = m["mla"], m["moe"]
    return dict(L=m["num_layers"], d=m["d_model"], h=m["num_heads"],
                V=m["vocab_size"], qr=a["q_lora_rank"], r=a["kv_lora_rank"],
                nope=a["nope_head_dim"], rope=a["rope_head_dim"],
                v=a["v_head_dim"], F=m["dense_d_ff"], f=e["d_ff_expert"],
                E=e["num_experts"], k=e["top_k"],
                n=e["num_experts_held"] or e["num_experts"],
                ns=e["num_shared_experts"], first=m["first_k_dense"])


def padded_vocab(m) -> int:
    return -(-m["vocab_size"] // 512) * 512


def layout(m) -> Dict[str, Leaf]:
    D = _dims(m)
    d, h, qr, r = D["d"], D["h"], D["qr"], D["r"]
    nope, rope, v = D["nope"], D["rope"], D["v"]
    vp = padded_vocab(m)
    dt = m["param_dtype"]
    std = lambda fan_in: 1.0 / math.sqrt(fan_in)

    def norm(*lead, width):
        return Leaf(lead + (width,), dt, 0.1, 1.0)

    def mla(*lead):
        return {
            "pre_norm/scale": norm(*lead, width=d),
            "mixer/w_dq": Leaf(lead + (d, qr), dt, std(d)),
            "mixer/q_norm/scale": norm(*lead, width=qr),
            "mixer/w_uq": Leaf(lead + (qr, h * (nope + rope)), dt, std(qr)),
            "mixer/w_dkv": Leaf(lead + (d, r), dt, std(d)),
            "mixer/kv_norm/scale": norm(*lead, width=r),
            "mixer/w_kr": Leaf(lead + (d, rope), dt, std(d)),
            "mixer/w_uk": Leaf(lead + (h, nope, r), dt, std(r)),
            "mixer/w_uv": Leaf(lead + (h, v, r), dt, std(r)),
            "mixer/w_o": Leaf(lead + (h * v, d), dt, std(h * v)),
            "post_norm/scale": norm(*lead, width=d),
        }

    def swiglu(prefix, *lead, width):
        return {prefix + "w_gate": Leaf(lead + (d, width), dt, std(d)),
                prefix + "w_up": Leaf(lead + (d, width), dt, std(d)),
                prefix + "w_down": Leaf(lead + (width, d), dt, std(width))}

    out = {"embed/table": Leaf((vp, d), dt, 0.02),
           "final_norm/scale": norm(width=d),
           "lm_head": Leaf((d, vp), dt, std(d))}
    for i in range(D["first"]):
        g = f"first/{i}/"
        out.update({g + k: x for k, x in mla().items()})
        out.update({g + k: x for k, x in
                    swiglu("ffn/", width=D["F"]).items()})
    L, n, f = D["L"] - D["first"], D["n"], D["f"]
    g = "groups/0/"
    out.update({g + k: x for k, x in mla(L).items()})
    out[g + "ffn/router"] = Leaf((L, d, D["E"]), "float32", std(d))
    out.update({g + k: x for k, x in swiglu("ffn/", L, n, width=f).items()})
    out.update({g + k: x for k, x in
                swiglu("ffn/shared/", L, width=f * D["ns"]).items()})
    return out


# ------------------------------------------------------------ reference

def yarn_frequencies(m):
    """(rope/2,) float32 inverse frequencies of DeepSeek-V2's YaRN: the
    original ones theta^(-2i/dim) below the correction range, those over
    `factor` above it, a linear ramp in pair index across it."""
    rope = m["mla"]["rope_head_dim"]
    base = 1.0 / m["rope_theta"] ** (jnp.arange(0, rope, 2,
                                                dtype=jnp.float32) / rope)
    ys = m.get("rope_scaling")
    if not ys:
        return base

    def pair(rotations):
        return rope * math.log(ys["original_max_position_embeddings"]
                               / (rotations * 2 * math.pi)) \
            / (2 * math.log(m["rope_theta"]))
    low = max(math.floor(pair(ys["beta_fast"])), 0)
    high = min(math.ceil(pair(ys["beta_slow"])), rope - 1)
    ramp = jnp.clip((jnp.arange(rope // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return base / ys["factor"] * ramp + base * (1.0 - ramp)


def _mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def softmax_scale(m) -> float:
    """1/sqrt(nope + rope), times mscale(factor, mscale_all_dim)^2."""
    a, ys = m["mla"], m.get("rope_scaling")
    s = 1.0 / math.sqrt(a["nope_head_dim"] + a["rope_head_dim"])
    if ys and ys.get("mscale_all_dim"):
        s *= _mscale(ys["factor"], ys["mscale_all_dim"]) ** 2
    return s


def _rope(x, pos, m):
    """x (S, H, rope) rotated in halves by YaRN frequencies; cos and sin
    scaled by mscale(factor, mscale) / mscale(factor, mscale_all_dim)."""
    ys = m.get("rope_scaling")
    mag = 1.0 if not ys else (_mscale(ys["factor"], ys["mscale"])
                              / _mscale(ys["factor"], ys["mscale_all_dim"]))
    ang = pos[:, None].astype(jnp.float32) * yarn_frequencies(m)
    sin, cos = (jnp.sin(ang) * mag)[:, None], (jnp.cos(ang) * mag)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(m, low, x, lw):
    D = _dims(m)
    s, h, nope, v = x.shape[0], D["h"], D["nope"], D["v"]
    eps = m["norm_eps"]
    pos = jnp.arange(s)
    cq = rmsnorm(mm("sd,dr->sr", x, lw["mixer/w_dq"], low),
                 lw["mixer/q_norm/scale"], eps)
    q = mm("sr,re->se", cq, lw["mixer/w_uq"], low).reshape(s, h, -1)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], pos, m)], -1)
    c = rmsnorm(mm("sd,dr->sr", x, lw["mixer/w_dkv"], low),
                lw["mixer/kv_norm/scale"], eps)
    kr = _rope(mm("sd,de->se", x, lw["mixer/w_kr"], low)[:, None], pos, m)
    k = jnp.concatenate([mm("sr,her->she", c, lw["mixer/w_uk"], low),
                         jnp.broadcast_to(kr, (s, h, D["rope"]))], -1)
    val = mm("sr,her->she", c, lw["mixer/w_uv"], low)
    scale = softmax_scale(m)
    nb = -(-s // Q_BLOCK)
    qb = jnp.pad(q, ((0, nb * Q_BLOCK - s), (0, 0), (0, 0))).reshape(
        nb, Q_BLOCK, h, -1)

    def block(args):
        qi, i = args
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        sc = mm("qhe,the->hqt", qi, k, low) * scale
        sc = jnp.where(qpos[None, :, None] >= pos[None, None, :], sc,
                       -jnp.inf)
        return mm("hqt,thv->qhv", jax.nn.softmax(sc, -1), val, low)
    o = jax.lax.map(block, (qb, jnp.arange(nb)))
    o = o.reshape(nb * Q_BLOCK, h * v)[:s]
    return mm("se,ed->sd", o, lw["mixer/w_o"], low)


def _swiglu(x, wg, wu, wd, low):
    u = jax.nn.silu(mm("sd,df->sf", x, wg, low)) * mm("sd,df->sf", x, wu, low)
    return mm("sf,fd->sd", u, wd, low)


def route(m, x, router, low=""):
    """(S, d) -> gates (S, k) and expert ids (S, k) over every expert."""
    e = m["moe"]
    probs = jax.nn.softmax(mm("sd,de->se", x, router, low), -1)
    s = x.shape[0]
    if e["n_group"] > 1:
        by_group = probs.reshape(s, e["n_group"], -1)
        _, keep = jax.lax.top_k(by_group.max(-1), e["topk_group"])
        kept = jax.nn.one_hot(keep, e["n_group"]).sum(1) > 0
        masked = jnp.where(kept[:, :, None], by_group, 0.0).reshape(s, -1)
    else:
        masked = probs
    gates, ids = jax.lax.top_k(masked, e["top_k"])
    if e["norm_topk_prob"]:
        gates = gates / gates.sum(-1, keepdims=True)
    else:
        gates = gates * e["routed_scaling_factor"]
    return gates, ids


def _moe(m, low, x, lw):
    """The held experts' part of the layer, plus the shared experts."""
    e = m["moe"]
    n = e["num_experts_held"] or e["num_experts"]
    gates, ids = route(m, x, lw["ffn/router"], low)

    def expert(y, args):
        j, wg, wu, wd = args
        gate = jnp.sum(jnp.where(ids == e["first_expert"] + j, gates, 0.0),
                       -1)
        return y + gate[:, None] * _swiglu(x, wg, wu, wd, low), None
    y, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                        (jnp.arange(n),) + tuple(lw[w]
                                                 for w in EXPERT_WEIGHTS))
    return y + _swiglu(x, lw["ffn/shared/w_gate"], lw["ffn/shared/w_up"],
                       lw["ffn/shared/w_down"], low)


def _layer(m, low, x, lw, moe: bool):
    eps = m["norm_eps"]
    x = x + _attention(m, low, rmsnorm(x, lw["pre_norm/scale"], eps), lw)
    f = rmsnorm(x, lw["post_norm/scale"], eps)
    if moe:
        return x + _moe(m, low, f, lw)
    return x + _swiglu(f, lw["ffn/w_gate"], lw["ffn/w_up"], lw["ffn/w_down"],
                       low)


def logits(w, m, tokens, low: str = ""):
    """(S,) ids -> (S, vocab) float32 logits of the full causal forward."""
    def sub(prefix):
        return {k[len(prefix):]: a for k, a in w.items()
                if k.startswith(prefix)}
    x = w["embed/table"][tokens]
    for i in range(m["first_k_dense"]):
        x = _layer(m, low, x, sub(f"first/{i}/"), moe=False)
    x, _ = jax.lax.scan(lambda c, lw: (_layer(m, low, c, lw, moe=True),
                                       None), x, sub("groups/0/"))
    x = rmsnorm(x, w["final_norm/scale"], m["norm_eps"])
    return mm("sd,dv->sv", x, w["lm_head"][:, : m["vocab_size"]], low)


def serve_gaps(w, m, tokens, targets, control: str = ""):
    """Gap of each served token below the reference's best logit, and
    with `control` (a precision) the same for the control's first choice."""
    with jax.default_matmul_precision("highest"):
        ref = logits(w, m, tokens)
        out = {"program": served_gaps(ref, targets)}
        if control:
            out["control"] = control_gaps(ref, logits(w, m, tokens, control),
                                          targets)
    return out


# ----------------------------------------------------------- op counts
#
# Routed experts count at their expected share under uniform routing: a
# token runs k * held / total of the held experts' work. Attention counts
# the form the program computes: expanded per head in prefill, absorbed
# into the latent in decode.

def _leaf_bytes(leaf: Leaf) -> int:
    return math.prod(leaf.shape) * jnp.dtype(leaf.dtype).itemsize


def param_bytes(m) -> int:
    """Every weight this chip holds."""
    return sum(_leaf_bytes(x) for x in layout(m).values())


def latent_bytes_per_token(m) -> int:
    """Cache bytes a token leaves in one layer: latent and rope key."""
    a = m["mla"]
    return (a["kv_lora_rank"] + a["rope_head_dim"]) * BF16


def _token_params(m) -> float:
    """Weights each token multiplies in the projections, dense FFN,
    routers, shared and (at their share) routed experts, and head."""
    D = _dims(m)
    d, h = D["d"], D["h"]
    mla = (d * D["qr"] + D["qr"] * h * (D["nope"] + D["rope"]) + d * D["r"]
           + d * D["rope"] + h * D["v"] * d)
    moe_layers = D["L"] - D["first"]
    moe = (d * D["E"] + 3 * d * D["f"] * D["ns"]
           + D["k"] * D["n"] / D["E"] * 3 * d * D["f"])
    return D["L"] * mla + D["first"] * 3 * d * D["F"] + moe_layers * moe \
        + d * D["V"]


def token_flops(m, context: int) -> float:
    """Decode operations of one token that attends to `context` positions
    (itself included): the absorbed form, with W_UK and W_UV applied per
    head to the query and the latent context."""
    D = _dims(m)
    h, r = D["h"], D["r"]
    absorb = 2 * h * r * (D["nope"] + D["v"])
    per_position = 2 * h * (2 * r + D["rope"])
    return 2.0 * _token_params(m) + D["L"] * (absorb + per_position * context)


def prefill_flops(m, s: int) -> float:
    """Prefill operations of one prompt of `s` tokens: the expanded form
    (K and V per head from the latent), causal attention."""
    D = _dims(m)
    h = D["h"]
    expand = 2 * D["r"] * h * (D["nope"] + D["v"])
    pair = 2 * h * (D["nope"] + D["rope"] + D["v"])
    return s * (2.0 * _token_params(m) + D["L"] * expand) \
        + D["L"] * pair * s * (s + 1) / 2.0


def train_flops_per_token(m, seq: int) -> float:
    """Forward and backward (three forwards' worth) per token."""
    return 3.0 * prefill_flops(m, seq) / seq


def experts_touched(m, width: int) -> float:
    """Held experts a step of `width` tokens reaches, per MoE layer, under
    uniform routing: n * (1 - (1 - k/E)^width)."""
    D = _dims(m)
    return D["n"] * (1.0 - (1.0 - D["k"] / D["E"]) ** width)


def weight_bytes(m, width: int, touched: float) -> float:
    """Weights one step reads: every matrix once but the routed experts,
    of which `touched` a layer, the embedding rows of `width` tokens."""
    lay = layout(m)
    D = _dims(m)
    routed = sum(_leaf_bytes(lay["groups/0/" + w]) for w in EXPERT_WEIGHTS)
    rest = param_bytes(m) - routed - _leaf_bytes(lay["embed/table"])
    return rest + routed * touched / D["n"] + width * D["d"] * BF16


def decode_cost(m, contexts: Sequence[int], width: int):
    """(operations, bytes) one decode step needs: `contexts` holds, for
    each live lane, the positions it attends to (the new one included);
    `width` lanes are fed. Bytes: the weights once, of the routed experts
    the ones `width` tokens reach (`experts_touched`), the live lanes'
    cached latents (`context - 1` positions each) and the new tokens'
    writes, in every layer."""
    flops = sum(token_flops(m, c) for c in contexts)
    nbytes = weight_bytes(m, width, experts_touched(m, width)) \
        + sum(contexts) * latent_bytes_per_token(m) * m["num_layers"]
    return flops, nbytes


def prefill_cost(m, s: int, width: int):
    """(operations, bytes) one prefill of `width` prompts of `s` tokens
    needs: the expanded attention and the projections and experts of
    every token (`prefill_flops`); the weights once, every held expert
    among them, and the cache it writes."""
    flops = width * prefill_flops(m, s)
    nbytes = weight_bytes(m, width * s, _dims(m)["n"]) \
        + width * s * latent_bytes_per_token(m) * m["num_layers"]
    return flops, nbytes
