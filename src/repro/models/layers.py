"""Common layers: norms, RoPE, MLPs, embeddings. Pure-functional JAX.

Params are plain nested dicts of jnp arrays. Initializers take an rng and
return the param subtree; apply functions take (params, inputs). Compute
follows the usual mixed-precision recipe: bf16 matmuls, fp32 softmax /
normalization statistics.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

Params = Dict[str, Any]


def _dtype(name: str):
    return jnp.dtype(name)


# ---------------------------------------------------------------- init utils

def dense_init(rng, in_dim: int, out_dim: int, dtype, scale: Optional[float] = None):
    scale = scale if scale is not None else 1.0 / math.sqrt(in_dim)
    return (jax.random.normal(rng, (in_dim, out_dim), jnp.float32) * scale).astype(dtype)


def embed_init(rng, vocab: int, dim: int, dtype):
    # GPT-style 0.02 std keeps tied-embedding logits at a sane scale
    return (jax.random.normal(rng, (vocab, dim), jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------- norms

def rmsnorm_init(dim: int, dtype) -> Params:
    return {"scale": jnp.ones((dim,), dtype)}


def rmsnorm(params: Params, x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    """Variance in fp32, but the value path stays in x.dtype: multiplying
    x by a cast-down inverse keeps the *cotangent* of x in bf16, so the TP
    activation-grad psums run at 2 bytes/elem instead of 4 (the fp32-
    upcast-first formulation made XLA all-reduce fp32 tensors)."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    inv = (jax.lax.rsqrt(var + eps)
           * params["scale"].astype(jnp.float32)[None, None, :])
    return x * inv.astype(x.dtype)


def l2norm(x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    """Scale-free RMS normalization (qk-norm without learned scale)."""
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(dt)


# ---------------------------------------------------------------- RoPE

def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's magnitude correction for a context stretched by `factor`."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_correction_range(scaling, rotary_dim: int, theta: float):
    """(low, high) rotary pair indices between which YaRN ramps from the
    original frequencies to the interpolated ones."""
    def pair(rotations):
        return rotary_dim * math.log(
            scaling.original_max_position_embeddings
            / (rotations * 2 * math.pi)) / (2 * math.log(theta))
    return (max(math.floor(pair(scaling.beta_fast)), 0),
            min(math.ceil(pair(scaling.beta_slow)), rotary_dim - 1))


def yarn_softmax_factor(scaling) -> float:
    """What YaRN multiplies the attention softmax scale by (1 without)."""
    if scaling is None or not scaling.mscale_all_dim:
        return 1.0
    return yarn_mscale(scaling.factor, scaling.mscale_all_dim) ** 2


def rope_frequencies(head_dim: int, theta: float, rotary_dim: Optional[int] = None,
                     scaling=None):
    """(rotary_dim/2,) inverse frequencies; with `scaling` (a
    `YarnScaling`), YaRN's: pairs past the correction range divided by
    the factor, a linear ramp across it."""
    rotary_dim = rotary_dim or head_dim
    assert rotary_dim % 2 == 0
    exponents = jnp.arange(0, rotary_dim, 2, dtype=jnp.float32) / rotary_dim
    inv_freq = 1.0 / (theta ** exponents)  # (rotary_dim/2,)
    if scaling is None:
        return inv_freq
    low, high = yarn_correction_range(scaling, rotary_dim, theta)
    ramp = jnp.clip((jnp.arange(rotary_dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return inv_freq / scaling.factor * ramp + inv_freq * (1.0 - ramp)


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
               rotary_fraction: float = 1.0, scaling=None) -> jnp.ndarray:
    """x: (..., S, H, hd); positions: broadcastable to (..., S). `scaling`
    (a `YarnScaling`) changes the frequencies and the cos/sin magnitude."""
    hd = x.shape[-1]
    rot = int(hd * rotary_fraction)
    rot -= rot % 2
    if rot == 0:
        return x
    inv_freq = rope_frequencies(hd, theta, rot, scaling)           # (rot/2,)
    angles = positions[..., :, None].astype(jnp.float32) * inv_freq  # (..., S, rot/2)
    sin = jnp.sin(angles)[..., :, None, :]                          # (..., S, 1, rot/2)
    cos = jnp.cos(angles)[..., :, None, :]
    if scaling is not None:
        mag = (yarn_mscale(scaling.factor, scaling.mscale)
               / yarn_mscale(scaling.factor, scaling.mscale_all_dim))
        if mag != 1.0:
            sin, cos = sin * mag, cos * mag
    x_rot, x_pass = x[..., :rot], x[..., rot:]
    x1, x2 = jnp.split(x_rot.astype(jnp.float32), 2, axis=-1)
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    out = jnp.concatenate([y1, y2], axis=-1).astype(x.dtype)
    if x_pass.shape[-1]:
        out = jnp.concatenate([out, x_pass], axis=-1)
    return out


# ---------------------------------------------------------------- MLPs

def swiglu_init(rng, d_model: int, d_ff: int, dtype) -> Params:
    k1, k2, k3 = jax.random.split(rng, 3)
    return {
        "w_gate": dense_init(k1, d_model, d_ff, dtype),
        "w_up": dense_init(k2, d_model, d_ff, dtype),
        "w_down": dense_init(k3, d_ff, d_model, dtype),
    }


def swiglu(params: Params, x: jnp.ndarray) -> jnp.ndarray:
    g = jnp.einsum("...d,df->...f", x, params["w_gate"])
    u = jnp.einsum("...d,df->...f", x, params["w_up"])
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    return jnp.einsum("...f,fd->...d", h, params["w_down"])


# ---------------------------------------------------------------- embeddings

def embedding_init(rng, vocab: int, dim: int, dtype) -> Params:
    return {"table": embed_init(rng, vocab, dim, dtype)}


def embed_tokens(params: Params, tokens: jnp.ndarray, scale_by_dim: bool = False):
    out = jnp.take(params["table"], tokens, axis=0)
    if scale_by_dim:
        out = out * jnp.asarray(math.sqrt(out.shape[-1]), out.dtype)
    return out


def unembed(params: Params, x: jnp.ndarray, tied: bool,
            head: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    if tied:
        return jnp.einsum("...d,vd->...v", x, params["table"])
    return jnp.einsum("...d,dv->...v", x, head)


# ---------------------------------------------------------------- caches
# A decode cache leaf is stacked over layers: (L, ...). A layer reads its
# own slice and writes only what it changed, at its own index, so the
# decode program updates the cache in place.

def layer_read(leaf: jnp.ndarray, layer) -> jnp.ndarray:
    """Layer `layer` of the stacked `leaf`."""
    return jax.lax.dynamic_index_in_dim(leaf, layer, keepdims=False)


def layer_write(leaf: jnp.ndarray, layer, value: jnp.ndarray,
                at=()) -> jnp.ndarray:
    """`leaf` with `value` written into layer `layer`, at offset `at`
    inside the layer (zeros for the dimensions `at` leaves out)."""
    at = tuple(at) + (0,) * (value.ndim - len(at))
    return jax.lax.dynamic_update_slice(
        leaf, value[None].astype(leaf.dtype), (layer,) + at)


# ---------------------------------------------------------------- loss

def softmax_xent(logits: jnp.ndarray, labels: jnp.ndarray,
                 mask: Optional[jnp.ndarray] = None,
                 logit_softcap: float = 0.0) -> jnp.ndarray:
    """Mean next-token cross-entropy. logits (B,S,V) fp-any, labels (B,S)."""
    lf = logits.astype(jnp.float32)
    if logit_softcap:
        lf = jnp.tanh(lf / logit_softcap) * logit_softcap
    lse = jax.nn.logsumexp(lf, axis=-1)
    gold = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
    nll = lse - gold
    if mask is not None:
        nll = nll * mask
        return jnp.sum(nll) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
