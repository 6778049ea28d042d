"""xLSTM family (arXiv:2405.04517): weight layout, plain float32 reference,
and the operations and bytes its work needs, from shapes alone.

The reference follows the configuration's equations. Blocks are pre-norm
residual (RMSNorm), with no separate feed-forward layer, alternating
sLSTM and mLSTM; the embedding is tied to the output head.

- mLSTM, in the paper's parallel form: `C_t = f_t C_{t-1} + i_t v_t k_t^T`
  unrolled into a causal (S, S) matrix of decays `D_tj = exp(F_t - F_j +
  log i_j)` (F the running sum of log forget gates), `h_t = (C q_t) /
  max(|n_t^T q_t|, 1)` stabilized by the row maximum. Input gate
  exponential, forget gate sigmoid. q and k from a causal depthwise
  convolution (SiLU) of the up-projected input, v from the input itself;
  per-head RMS group norm, an output gate SiLU(z), and a down projection.
- sLSTM, a step at a time: exponential input gate, sigmoid forget gate,
  stabilizer `m_t = max(log f_t + m_{t-1}, i~_t)`, block-diagonal
  recurrence over 4 heads. As the configuration states it, the causal
  convolution's SiLU output enters the input gate only (the paper feeds
  it to the input and forget gates). Then a per-head group norm and a
  GeLU (tanh form) gated up/down projection with factor 2.

It imports nothing of the program.
"""
from __future__ import annotations

import math
from typing import Dict, Sequence

import jax
import jax.numpy as jnp

from bench.refmath import control_gaps, mm, rmsnorm, served_gaps
from bench.weights import Leaf

BF16, F32 = 2, 4
NEG = -1e30


def _dims(m):
    d = m["d_model"]
    x = m["xlstm"]
    di = int(x["proj_factor_mlstm"] * d)
    return dict(L=m["num_layers"], d=d, V=m["vocab_size"], k=x["conv1d_kernel"],
                di=di, hm=m["num_heads"], hdm=di // m["num_heads"],
                hs=x["num_heads_slstm"], hds=d // x["num_heads_slstm"],
                fs=int(x["proj_factor_slstm"] * d))


def padded_vocab(m) -> int:
    return -(-m["vocab_size"] // 512) * 512


def layout(m) -> Dict[str, Leaf]:
    z = _dims(m)
    G, d, di, k = z["L"] // 2, z["d"], z["di"], z["k"]
    hs, hds, hm, fs = z["hs"], z["hds"], z["hm"], z["fs"]
    dt = m["param_dtype"]
    std = lambda fan_in: 1.0 / math.sqrt(fan_in)
    s, ml = "groups/0/", "groups/1/"
    return {
        "embed/table": Leaf((padded_vocab(m), d), dt, 0.02),
        "final_norm/scale": Leaf((d,), dt, 0.1, 1.0),
        s + "pre_norm/scale": Leaf((G, d), dt, 0.1, 1.0),
        s + "mixer/conv_w": Leaf((G, k, d), dt, std(k)),
        s + "mixer/conv_b": Leaf((G, d), dt, 0.1),
        s + "mixer/w_in": Leaf((G, d, 4 * d), "float32", std(d)),
        s + "mixer/r_rec": Leaf((G, hs, hds, 4 * hds), "float32", std(hds)),
        s + "mixer/b": Leaf((G, 4 * d), "float32", 0.1,
                            ((d, 0.0), (d, 3.0), (2 * d, 0.0))),
        s + "mixer/norm_scale": Leaf((G, d), dt, 0.1, 1.0),
        s + "mixer/up": Leaf((G, d, 2 * fs), dt, std(d)),
        s + "mixer/down": Leaf((G, fs, d), dt, std(fs)),
        ml + "pre_norm/scale": Leaf((G, d), dt, 0.1, 1.0),
        ml + "mixer/up": Leaf((G, d, 2 * di), dt, std(d)),
        ml + "mixer/conv_w": Leaf((G, k, di), dt, std(k)),
        ml + "mixer/conv_b": Leaf((G, di), dt, 0.1),
        ml + "mixer/w_q": Leaf((G, di, di), dt, std(di)),
        ml + "mixer/w_k": Leaf((G, di, di), dt, std(di)),
        ml + "mixer/w_v": Leaf((G, di, di), dt, std(di)),
        ml + "mixer/w_if": Leaf((G, di, 2 * hm), "float32", std(di)),
        ml + "mixer/b_if": Leaf((G, 2 * hm), "float32", 0.1,
                                ((hm, 0.0), (hm, 3.0))),
        ml + "mixer/norm_scale": Leaf((G, di), dt, 0.1, 1.0),
        ml + "mixer/down": Leaf((G, di, d), dt, std(di)),
    }


# ------------------------------------------------------------ reference

def _conv(x, w, b):
    """Causal depthwise convolution over time: x (S, c), w (k, c)."""
    k = w.shape[0]
    pad = jnp.pad(x, ((k - 1, 0), (0, 0)))
    return sum(pad[i: i + x.shape[0]] * w[i] for i in range(k)) + b


def _group_norm(y, heads, scale):
    s = y.shape[0]
    yh = y.reshape(s, heads, -1)
    yh = yh * jax.lax.rsqrt(jnp.mean(yh * yh, -1, keepdims=True) + 1e-6)
    return yh.reshape(s, -1) * scale


def _slstm(z, low, x, p):
    s, d = x.shape
    hs, hds = z["hs"], z["hds"]
    conv = jax.nn.silu(_conv(x, p["conv_w"], p["conv_b"]))
    pre = mm("sd,de->se", x, p["w_in"], low) + p["b"]     # gates i, f, z, o

    def step(carry, xs):
        c, n, mst, h = carry
        pre_t, conv_t = xs
        rec = mm("hx,hxe->he", h, p["r_rec"], low).reshape(hs, 4, hds)
        g = pre_t.reshape(4, hs, hds) + rec.swapaxes(0, 1)
        i_t = g[0] + conv_t.reshape(hs, hds)
        log_f = jax.nn.log_sigmoid(g[1])
        m_new = jnp.maximum(log_f + mst, i_t)
        ig, fg = jnp.exp(i_t - m_new), jnp.exp(log_f + mst - m_new)
        c = fg * c + ig * jnp.tanh(g[2])
        n = fg * n + ig
        h = jax.nn.sigmoid(g[3]) * c / jnp.maximum(n, 1e-6)
        return (c, n, m_new, h), h

    zero = jnp.zeros((hs, hds), jnp.float32)
    _, hs_t = jax.lax.scan(step, (zero, zero, zero + NEG, zero), (pre, conv))
    y = _group_norm(hs_t.reshape(s, d), hs, p["norm_scale"])
    gu = mm("sd,df->sf", y, p["up"], low)
    g, u = jnp.split(gu, 2, -1)
    return mm("sf,fd->sd", jax.nn.gelu(g, approximate=True) * u, p["down"], low)


def _mlstm(z, low, x, p):
    s = x.shape[0]
    hm, hd = z["hm"], z["hdm"]
    xm, zg = jnp.split(mm("sd,de->se", x, p["up"], low), 2, -1)
    c = jax.nn.silu(_conv(xm, p["conv_w"], p["conv_b"]))
    q = mm("sd,de->se", c, p["w_q"], low).reshape(s, hm, hd)
    k = mm("sd,de->se", c, p["w_k"], low).reshape(s, hm, hd)
    v = mm("sd,de->se", xm, p["w_v"], low).reshape(s, hm, hd)
    gates = mm("sd,de->se", c, p["w_if"], low) + p["b_if"]
    log_i, log_f = gates[:, :hm], jax.nn.log_sigmoid(gates[:, hm:])
    F = jnp.cumsum(log_f, 0)                                   # (S, H)
    logd = F[:, None, :] - F[None, :, :] + log_i[None, :, :]   # (t, j, H)
    causal = jnp.tril(jnp.ones((s, s), bool))[:, :, None]
    logd = jnp.where(causal, logd, -jnp.inf)
    mrow = jnp.max(logd, 1)                                    # (t, H)
    dmat = jnp.exp(logd - mrow[:, None, :])
    ct = mm("thd,jhd->tjh", q / math.sqrt(hd), k, low) * dmat
    den = jnp.maximum(jnp.abs(ct.sum(1)), jnp.exp(-mrow))      # (t, H)
    y = mm("tjh,jhd->thd", ct, v, low) / den[..., None]
    y = _group_norm(y.reshape(s, hm * hd), hm, p["norm_scale"])
    return mm("se,ed->sd", y * jax.nn.silu(zg), p["down"], low)


def hidden(w, m, tokens, low: str = ""):
    """(S,) ids -> (S, d) final normed hidden states."""
    z = _dims(m)
    sl = {k[len("groups/0/"):]: v for k, v in w.items()
          if k.startswith("groups/0/")}
    ml = {k[len("groups/1/"):]: v for k, v in w.items()
          if k.startswith("groups/1/")}
    strip = lambda t: {k[len("mixer/"):]: v for k, v in t.items()
                       if k.startswith("mixer/")}

    def pair(x, lw):
        s_w, m_w = lw
        x = x + _slstm(z, low, rmsnorm(x, s_w["pre_norm/scale"],
                                       m["norm_eps"]), strip(s_w))
        x = x + _mlstm(z, low, rmsnorm(x, m_w["pre_norm/scale"],
                                       m["norm_eps"]), strip(m_w))
        return x, None

    x = w["embed/table"][tokens]
    # recomputed in the backward pass, a layer pair at a time, so the
    # training reference holds one pair's intermediates
    x, _ = jax.lax.scan(jax.checkpoint(pair), x, (sl, ml))
    return rmsnorm(x, w["final_norm/scale"], m["norm_eps"])


def logits(w, m, tokens, low: str = ""):
    x = hidden(w, m, tokens, low)
    return mm("sd,vd->sv", x, w["embed/table"][: m["vocab_size"]], low)


def serve_gaps(w, m, tokens, targets, control: str = ""):
    """Gap of each served token below the reference's best logit, and
    with `control` (a precision) the same for the control's first choice."""
    ref = logits(w, m, tokens)
    out = {"program": served_gaps(ref, targets)}
    if control:
        out["control"] = control_gaps(ref, logits(w, m, tokens, control),
                                      targets)
    return out


def loss_sum(w, m, rows, low: str = ""):
    """Summed next-token cross-entropy of (B, S) rows."""
    def one(tokens):
        lg = logits(w, m, tokens, low)[:-1]
        lse = jax.nn.logsumexp(lg, -1)
        gold = jnp.take_along_axis(lg, tokens[1:, None], -1)[:, 0]
        return jnp.sum(lse - gold)
    return jnp.sum(jax.vmap(one)(rows))


# ----------------------------------------------------------- op counts

def matmul_params(m) -> int:
    z = _dims(m)
    d, di, G = z["d"], z["di"], z["L"] // 2
    slstm = d * 4 * d + z["hs"] * z["hds"] * 4 * z["hds"] \
        + d * 2 * z["fs"] + z["fs"] * d
    mlstm = d * 2 * di + 3 * di * di + di * 2 * z["hm"] + di * d
    return G * (slstm + mlstm) + d * z["V"]


def _state_flops_per_token(m) -> float:
    """Recurrent-form mLSTM state work per token: C update and read."""
    z = _dims(m)
    return (z["L"] // 2) * z["hm"] * 6.0 * z["hdm"] ** 2


def token_flops(m, context: int = 0) -> float:
    """Forward operations of one token (independent of the context)."""
    return 2.0 * matmul_params(m) + _state_flops_per_token(m)


def prefill_flops(m, s: int) -> float:
    return s * token_flops(m)


def train_flops_per_token(m, seq: int) -> float:
    return 3.0 * token_flops(m)


def state_bytes_per_lane(m) -> int:
    """Recurrent state one lane holds: mLSTM C, n, m and conv tail, sLSTM
    c, n, m, h and conv tail."""
    z = _dims(m)
    G = z["L"] // 2
    ml = (z["hm"] * z["hdm"] ** 2 + z["hm"] * z["hdm"] + z["hm"]) * F32 \
        + (z["k"] - 1) * z["di"] * BF16
    sl = 4 * z["d"] * F32 + (z["k"] - 1) * z["d"] * BF16
    return G * (ml + sl)


def weight_bytes(m, width: int) -> float:
    z = _dims(m)
    d, di, G = z["d"], z["di"], z["L"] // 2
    f32 = G * (d * 4 * d + z["hs"] * z["hds"] * 4 * z["hds"] + 4 * d
               + di * 2 * z["hm"] + 2 * z["hm"])
    bf = matmul_params(m) - G * (d * 4 * d + z["hs"] * z["hds"] * 4 * z["hds"]
                                 + di * 2 * z["hm"])
    return f32 * F32 + (bf + width * d) * BF16


def decode_cost(m, contexts: Sequence[int], width: int):
    """(operations, bytes) of one decode step: weights once, and each live
    lane's recurrent state read and written."""
    flops = len(contexts) * token_flops(m)
    return flops, weight_bytes(m, width) \
        + 2 * len(contexts) * state_bytes_per_lane(m)
