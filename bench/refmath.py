"""Plain float32 building blocks of the references, and their control.

Every matrix product of a reference goes through `mm`. In float32 it runs
at `Precision.HIGHEST` (on a TPU a float32 product otherwise runs in
bfloat16 passes). The control is the same reference one precision step
below the products the configuration states (`control_precision`): for
bfloat16 products, both operands of every product quantized to float8
(e4m3, one scale per operand, the step a later change might take); for
float32 products, both operands in bfloat16. The products then run on the
rounded values in float32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def control_precision(products: str) -> str:
    """The precision one step below the products a configuration states."""
    return {"bfloat16": "fp8", "float32": "bf16"}[products]


def lower(x, low: str):
    """x rounded to precision `low` ("" leaves it), back in float32."""
    if low == "fp8":
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
        return (x / scale).astype(F8).astype(jnp.float32) * scale
    if low == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    return x


def mm(spec: str, a, b, low: str = ""):
    """einsum of two float32 operands, exact or rounded to `low` first."""
    return jnp.einsum(spec, lower(a, low), lower(b, low), precision=HIGHEST)


def rmsnorm(x, scale, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, pos, theta: float, fraction: float):
    """Rotary embedding on the first `fraction` of each head's dims, in the
    rotate-half form: x (S, H, hd), pos (S,)."""
    hd = x.shape[-1]
    rot = int(hd * fraction)
    rot -= rot % 2
    inv = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = pos[:, None].astype(jnp.float32) * inv
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., : rot // 2], x[..., rot // 2: rot]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rot:]], axis=-1)


def served_gaps(logits, targets):
    """Per position: how far the served token's logit lies below the best
    one. logits (S, V) float32; targets (S,) int, -1 where nothing was
    served. Returns (S,) with 0 where targets < 0."""
    best = jnp.max(logits, -1)
    got = jnp.take_along_axis(logits, jnp.maximum(targets, 0)[:, None],
                              -1)[:, 0]
    return jnp.where(targets >= 0, best - got, 0.0)


def control_gaps(ref_logits, ctrl_logits, targets):
    """The gap, under the reference, of the token the control puts first,
    at each position where a token was served."""
    return served_gaps(ref_logits, jnp.where(
        targets >= 0, jnp.argmax(ctrl_logits, -1), -1))
