"""StableLM-2 family: weight layout, plain float32 reference, and the
operations and bytes its work needs, from shapes alone.

The reference follows the equations of the configuration as the registry
states it (pre-norm decoder, RMSNorm, multi-head attention with rotary
embedding on the first `partial_rotary_factor` of each head, SwiGLU,
untied output head). StableLM-2's published checkpoint uses LayerNorm with
biases and biased q/k/v projections; the configuration has neither, and
neither has this reference. It imports nothing of the program.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, Sequence

import jax
import jax.numpy as jnp

from bench.refmath import control_gaps, mm, rmsnorm, rope, served_gaps
from bench.weights import Leaf

BF16 = 2  # bytes


def _dims(m):
    hd = m.get("head_dim") or m["d_model"] // m["num_heads"]
    return (m["num_layers"], m["d_model"], m["num_heads"], m["num_kv_heads"],
            hd, m["d_ff"], m["vocab_size"])


def padded_vocab(m) -> int:
    return -(-m["vocab_size"] // 512) * 512


def layout(m) -> Dict[str, Leaf]:
    L, d, h, kv, hd, ff, _ = _dims(m)
    vp = padded_vocab(m)
    dt = m["param_dtype"]
    std = lambda fan_in: 1.0 / math.sqrt(fan_in)
    g = "groups/0/"
    return {
        "embed/table": Leaf((vp, d), dt, 0.02),
        "final_norm/scale": Leaf((d,), dt, 0.1, 1.0),
        "lm_head": Leaf((d, vp), dt, std(d)),
        g + "pre_norm/scale": Leaf((L, d), dt, 0.1, 1.0),
        g + "post_norm/scale": Leaf((L, d), dt, 0.1, 1.0),
        g + "mixer/w_q": Leaf((L, d, h * hd), dt, std(d)),
        g + "mixer/w_k": Leaf((L, d, kv * hd), dt, std(d)),
        g + "mixer/w_v": Leaf((L, d, kv * hd), dt, std(d)),
        g + "mixer/w_o": Leaf((L, h * hd, d), dt, std(h * hd)),
        g + "ffn/w_gate": Leaf((L, d, ff), dt, std(d)),
        g + "ffn/w_up": Leaf((L, d, ff), dt, std(d)),
        g + "ffn/w_down": Leaf((L, ff, d), dt, std(ff)),
    }


# ------------------------------------------------------------ reference

def _layer(m, low, x, lw):
    _, d, h, kv, hd, _, _ = _dims(m)
    s = x.shape[0]
    pos = jnp.arange(s)
    a = rmsnorm(x, lw["pre_norm/scale"], m["norm_eps"])
    q = mm("sd,de->se", a, lw["mixer/w_q"], low).reshape(s, h, hd)
    k = mm("sd,de->se", a, lw["mixer/w_k"], low).reshape(s, kv, hd)
    v = mm("sd,de->se", a, lw["mixer/w_v"], low).reshape(s, kv, hd)
    q = rope(q, pos, m["rope_theta"], m["partial_rotary_factor"])
    k = rope(k, pos, m["rope_theta"], m["partial_rotary_factor"])
    rep = h // kv
    k, v = jnp.repeat(k, rep, 1), jnp.repeat(v, rep, 1)
    sc = mm("shd,thd->hst", q, k, low) / math.sqrt(hd)
    sc = jnp.where(pos[None, :, None] >= pos[None, None, :], sc, -jnp.inf)
    p = jax.nn.softmax(sc, -1)
    o = mm("hst,thd->shd", p, v, low).reshape(s, h * hd)
    x = x + mm("se,ed->sd", o, lw["mixer/w_o"], low)
    f = rmsnorm(x, lw["post_norm/scale"], m["norm_eps"])
    u = jax.nn.silu(mm("sd,df->sf", f, lw["ffn/w_gate"], low)) \
        * mm("sd,df->sf", f, lw["ffn/w_up"], low)
    return x + mm("sf,fd->sd", u, lw["ffn/w_down"], low), None


def logits(w, m, tokens, low: str = ""):
    """(S,) ids -> (S, vocab) float32 logits of the full causal forward."""
    g = "groups/0/"
    layers = {k[len(g):]: v for k, v in w.items() if k.startswith(g)}
    x = w["embed/table"][tokens]
    x, _ = jax.lax.scan(partial(_layer, m, low), x, layers)
    x = rmsnorm(x, w["final_norm/scale"], m["norm_eps"])
    return mm("sd,dv->sv", x, w["lm_head"][:, : m["vocab_size"]], low)


def serve_gaps(w, m, tokens, targets, control: str = ""):
    """Gap of each served token below the reference's best logit, and
    with `control` (a precision) the same for the control's first choice."""
    ref = logits(w, m, tokens)
    out = {"program": served_gaps(ref, targets)}
    if control:
        out["control"] = control_gaps(ref, logits(w, m, tokens, control),
                                      targets)
    return out


# ----------------------------------------------------------- op counts

def matmul_params(m) -> int:
    """Weights that each token multiplies (the embedding is a gather)."""
    L, d, h, kv, hd, ff, V = _dims(m)
    return L * (d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * ff) \
        + d * V


def token_flops(m, context: int) -> float:
    """Forward operations of one token that attends to `context`
    positions (itself included)."""
    L, _, h, _, hd, _, _ = _dims(m)
    return 2.0 * matmul_params(m) + 4.0 * L * h * hd * context


def prefill_flops(m, s: int) -> float:
    return sum(token_flops(m, c) for c in (1, s)) * s / 2.0  # arithmetic


def train_flops_per_token(m, seq: int) -> float:
    """Forward and backward (three forwards' worth) per token, causal
    attention over `seq`; recomputation does not count."""
    return 3.0 * prefill_flops(m, seq) / seq


def kv_bytes_per_token(m) -> int:
    L, _, _, kv, hd, _, _ = _dims(m)
    return L * 2 * kv * hd * BF16


def weight_bytes(m, width: int) -> float:
    """Weights one decode step reads: every matrix once, the embedding
    rows of `width` tokens, the norm scales."""
    L, d, *_ = _dims(m)
    return (matmul_params(m) + width * d + (2 * L + 1) * d) * BF16


def decode_cost(m, contexts: Sequence[int], width: int):
    """(operations, bytes) one decode step needs: `contexts` holds, for
    each live lane, the positions it attends to (the new one included);
    `width` lanes are fed. Bytes: weights once, the live lanes' cached
    K/V (`context - 1` positions each), and the new tokens' K/V writes."""
    flops = sum(token_flops(m, c) for c in contexts)
    nbytes = weight_bytes(m, width) + sum(contexts) * kv_bytes_per_token(m)
    return flops, nbytes
