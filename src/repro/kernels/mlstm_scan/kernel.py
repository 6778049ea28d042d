"""Chunkwise-parallel mLSTM Pallas TPU kernel (TFLA-style tiling).

Grid (B, H, S/bc), chunk axis innermost. VMEM scratch carries the matrix
memory C (hd x hd), normalizer n (hd), and max-stabilizer m across chunks.
Within a chunk: quadratic (bc x bc) D-matrix attention (MXU matmuls) plus
the inter-chunk state contribution — identical math to the pure-jnp
chunkwise form in repro.models.xlstm, relocated into VMEM tiles.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _mlstm_kernel(q_ref, k_ref, v_ref, li_ref, lf_ref, y_ref,
                  c_scr, n_scr, m_scr, *, bc: int, scale: float):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        c_scr[...] = jnp.zeros_like(c_scr)
        n_scr[...] = jnp.zeros_like(n_scr)
        m_scr[...] = jnp.zeros_like(m_scr)

    q = q_ref[0, 0].astype(jnp.float32) * scale          # (bc, hd)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    li = li_ref[0, 0].astype(jnp.float32)                # (1, bc) -> (bc,)
    lf = lf_ref[0, 0].astype(jnp.float32)
    li = li.reshape(bc)
    lf = lf.reshape(bc)

    tri = (jax.lax.broadcasted_iota(jnp.int32, (bc, bc), 0)
           >= jax.lax.broadcasted_iota(jnp.int32, (bc, bc), 1))
    # inclusive prefix sum as a masked row sum: Mosaic has no cumsum
    bcum = jnp.sum(jnp.where(tri, lf[None, :], 0.0), axis=1)   # (bc,)
    # the (1, 1) stabilizer scratch is read and written as a vector:
    # Mosaic has no scalar VMEM loads or stores
    m_run = jnp.max(m_scr[...])
    # intra-chunk log-decay matrix
    logd = bcum[:, None] - bcum[None, :] + li[None, :]
    logd = jnp.where(tri, logd, NEG)
    m_intra = logd.max(axis=1)
    m_new = jnp.maximum(m_intra, bcum + m_run)           # (bc,)
    w_intra = jnp.exp(logd - m_new[:, None])
    w_state = jnp.exp(bcum + m_run - m_new)              # (bc,)

    scores = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * w_intra
    num = (jax.lax.dot_general(scores, v, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)
           + w_state[:, None] * jax.lax.dot_general(
               q, c_scr[...], (((1,), (1,)), ((), ())),
               preferred_element_type=jnp.float32))
    den_raw = (scores.sum(axis=1)
               + w_state * jnp.sum(q * n_scr[...], axis=1))
    den = jnp.maximum(jnp.abs(den_raw), jnp.exp(-m_new))
    y_ref[0, 0] = (num / den[:, None]).astype(y_ref.dtype)

    # carry the state to the chunk end
    btot = jnp.sum(lf)
    m_next = jnp.maximum(btot + m_run, (btot - bcum + li).max())
    w_upd = jnp.exp(btot - bcum + li - m_next)           # (bc,)
    decay = jnp.exp(btot + m_run - m_next)
    c_scr[...] = (decay * c_scr[...]
                  + jax.lax.dot_general(v * w_upd[:, None], k,
                                        (((0,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32))
    n_scr[...] = decay * n_scr[...] + jnp.sum(k * w_upd[:, None], axis=0)
    m_scr[...] = jnp.broadcast_to(m_next, (1, 1))


@functools.partial(jax.jit, static_argnames=("bc", "interpret"))
def mlstm_scan(q, k, v, log_i, log_f, *, bc: int = 128,
               interpret: bool = False):
    """q,k,v: (B,H,S,hd); log_i/log_f: (B,H,S) -> (B,H,S,hd)."""
    b, h, s, hd = q.shape
    bc = min(bc, s)
    assert s % bc == 0
    nc = s // bc
    scale = 1.0 / math.sqrt(hd)
    li = log_i.reshape(b, h, 1, s)
    lf = log_f.reshape(b, h, 1, s)

    kernel = functools.partial(_mlstm_kernel, bc=bc, scale=scale)
    return pl.pallas_call(
        kernel,
        grid=(b, h, nc),
        in_specs=[
            pl.BlockSpec((1, 1, bc, hd), lambda b_, h_, j: (b_, h_, j, 0)),
            pl.BlockSpec((1, 1, bc, hd), lambda b_, h_, j: (b_, h_, j, 0)),
            pl.BlockSpec((1, 1, bc, hd), lambda b_, h_, j: (b_, h_, j, 0)),
            pl.BlockSpec((1, 1, 1, bc), lambda b_, h_, j: (b_, h_, 0, j)),
            pl.BlockSpec((1, 1, 1, bc), lambda b_, h_, j: (b_, h_, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, 1, bc, hd),
                               lambda b_, h_, j: (b_, h_, j, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((hd, hd), jnp.float32),
            pltpu.VMEM((hd,), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, li, lf)
