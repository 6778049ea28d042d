"""Attention mixers: GQA (global / sliding-window), MLA, cross-attention.

Three entry points per mixer:
  *_forward        full-sequence (train and prefill)
  *_prefill_cache  full-sequence + returns a decode cache
  *_decode         single-token step against the cache

Long sequences use a blockwise online-softmax formulation (pure-JAX flash)
so the dry-run never materializes an (S, S) score matrix; the Pallas
`flash_attention` kernel is the TPU-optimized version of the same tiling
(kernels/flash_attention). Caches for sliding-window layers are ring buffers
of size `window` with per-slot absolute positions.
"""
from __future__ import annotations

import functools
import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models.layers import (apply_rope, dense_init, l2norm,
                                 layer_read, layer_write, rmsnorm,
                                 yarn_softmax_factor)

Params = Dict[str, Any]
NEG_INF = -1e30
#: `sdpa` runs the blockwise form for more queries than this, in chunks
#: of `CHUNK` queries and keys
BLOCKWISE_THRESHOLD = 2048
CHUNK = 1024


# =============================================================== GQA params

def attention_init(rng, cfg: ModelConfig) -> Params:
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    dt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(rng, 4)
    p = {
        "w_q": dense_init(ks[0], d, h * hd, dt),
        "w_k": dense_init(ks[1], d, kv * hd, dt),
        "w_v": dense_init(ks[2], d, kv * hd, dt),
        "w_o": dense_init(ks[3], h * hd, d, dt),
    }
    return p


def cross_attention_init(rng, cfg: ModelConfig) -> Params:
    return attention_init(rng, cfg)


# ========================================================== core softmax op

def _mask_bias(q_pos, kv_pos, window: int, causal: bool):
    """Additive bias (Sq, Tk) from absolute positions. kv_pos < 0 = invalid."""
    valid = kv_pos[None, :] >= 0
    if causal:
        valid &= kv_pos[None, :] <= q_pos[:, None]
    if window > 0:
        valid &= (q_pos[:, None] - kv_pos[None, :]) < window
    return jnp.where(valid, 0.0, NEG_INF).astype(jnp.float32)


def block_reached(q_pos, kv_pos, window: int, causal: bool):
    """Whether a block of queries at `q_pos` (..., Cq) and keys at `kv_pos`
    (..., Ck) may hold a pair that `_mask_bias` keeps: some key is valid,
    the lowest valid key is not after the last query (causal) and the
    highest key is within `window` of the first query. Read from the
    bounds of the positions, so it is never False where a pair is kept.
    The leading axes broadcast; numpy positions give numpy booleans,
    traced ones traced."""
    xp = jnp if isinstance(kv_pos, jax.Array) else np
    valid = kv_pos >= 0
    reached = valid.any(-1)
    if causal:
        lowest = xp.where(valid, kv_pos, xp.iinfo(kv_pos.dtype).max)
        reached = reached & (lowest.min(-1) <= q_pos.max(-1))
    if window > 0:
        reached = reached & (kv_pos.max(-1) > q_pos.min(-1) - window)
    return reached


def _softmax_weights(s, q_pos, kv_pos, window: int, causal: bool,
                     softcap: float, dtype):
    """float32 scores (B,Kv,G,S,T) -> masked softmax weights in `dtype`."""
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    s = s + _mask_bias(q_pos, kv_pos, window, causal)[None, None, None]
    return jax.nn.softmax(s, axis=-1).astype(dtype)


def naive_sdpa(q, k, v, q_pos, kv_pos, *, window: int = 0, causal: bool = True,
               softcap: float = 0.0, scale: Optional[float] = None
               ) -> jnp.ndarray:
    """q: (B,S,Kv,G,hd); k,v: (B,T,Kv,hd). Returns (B,S,Kv,G,hd). The
    scores are scaled by `scale` (default 1/sqrt(hd))."""
    scale = scale or 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bskgh,btkh->bkgst", q, k,
                   preferred_element_type=jnp.float32) * scale
    w = _softmax_weights(s, q_pos, kv_pos, window, causal, softcap, q.dtype)
    return jnp.einsum("bkgst,btkh->bskgh", w, v)


def cached_sdpa(q, k, v, q_pos, kv_pos, *, window: int = 0,
                softcap: float = 0.0) -> jnp.ndarray:
    """naive_sdpa over a decode cache stored heads before positions.
    q: (B,S,Kv,G,hd); k,v: (B,Kv,hd,T). Returns (B,S,Kv,G,hd)."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    # One lane at a time: the float32 products take K in float32, and all
    # lanes at once would hold a layer's K so (134 MB at width 8).
    s = jax.lax.map(
        lambda kq: jnp.einsum("kht,skgh->kgst", *kq,
                              preferred_element_type=jnp.float32),
        (k, q)) * scale
    w = _softmax_weights(s, q_pos, kv_pos, window, True, softcap, q.dtype)
    return jnp.einsum("bkgst,bkht->bskgh", w, v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def blockwise_sdpa(q, k, v, q_pos, kv_pos, window: int = 0,
                   causal: bool = True, softcap: float = 0.0,
                   q_chunk: int = 1024, kv_chunk: int = 1024,
                   scale: Optional[float] = None) -> jnp.ndarray:
    """Online-softmax attention, scanning KV chunks inside Q chunks, with a
    FlashAttention-style custom VJP: the forward saves only (out, lse); the
    backward recomputes each (q-chunk, kv-chunk) score block. Residual
    memory is O(S), not O(S * n_kv_chunks) as naive scan-of-checkpoint
    differentiation would give (that inner-scan accumulator chain was the
    dominant train-memory term in the first dry-run sweep).
    """
    out, _ = _blockwise_fwd_impl(q, k, v, q_pos, kv_pos, window, causal,
                                 softcap, q_chunk, kv_chunk, scale)
    return out


def _blockwise_fwd_impl(q, k, v, q_pos, kv_pos, window, causal, softcap,
                        q_chunk, kv_chunk, scale):
    B, S, Kv, G, hd = q.shape
    T = k.shape[1]
    hd_v = v.shape[-1]           # may differ from hd (e.g. MLA nope+rope keys)
    q_chunk = min(q_chunk, S)
    kv_chunk = min(kv_chunk, T)
    assert S % q_chunk == 0 and T % kv_chunk == 0, (S, q_chunk, T, kv_chunk)
    nq, nk = S // q_chunk, T // kv_chunk
    scale = scale or 1.0 / math.sqrt(hd)

    qb = q.reshape(B, nq, q_chunk, Kv, G, hd).swapaxes(0, 1)      # (nq,B,Cq,...)
    qp = q_pos.reshape(nq, q_chunk)
    kb = k.reshape(B, nk, kv_chunk, Kv, hd).swapaxes(0, 1)
    vb = v.reshape(B, nk, kv_chunk, Kv, hd_v).swapaxes(0, 1)
    kp = kv_pos.reshape(nk, kv_chunk)

    def kv_body(carry, blk):
        # a block with no pair the mask keeps is skipped: (m, l, acc) stay
        # as they are, where computing it would add weights of 0 to them
        # (or, before any kept key, values the next kept key zeroes)
        q_i, qp_i, j, kp_j = blk

        def attend(carry):
            m, l, acc = carry
            k_j, v_j = kb[j], vb[j]
            s = jnp.einsum("bckgh,btkh->bkgct", q_i, k_j,
                           preferred_element_type=jnp.float32) * scale
            if softcap:
                s = jnp.tanh(s / softcap) * softcap
            s = s + _mask_bias(qp_i, kp_j, window, causal)[None, None, None]
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l = l * corr + p.sum(axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bkgct,btkh->bkgch", p.astype(q_i.dtype), v_j,
                preferred_element_type=jnp.float32)
            return m_new, l, acc

        return jax.lax.cond(block_reached(qp_i, kp_j, window, causal),
                            attend, lambda c: c, carry), None

    def q_body(blk):
        q_i, qp_i = blk
        m0 = jnp.full((B, Kv, G, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Kv, G, q_chunk), jnp.float32)
        a0 = jnp.zeros((B, Kv, G, q_chunk, hd_v), jnp.float32)

        def scan_fn(carry, j_blk):
            return kv_body(carry, (q_i, qp_i) + j_blk)

        (m, l, acc), _ = jax.lax.scan(scan_fn, (m0, l0, a0),
                                      (jnp.arange(nk), kp))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        lse = m + jnp.log(jnp.maximum(l, 1e-30))       # (B,Kv,G,Cq)
        return out.astype(q.dtype), lse

    out, lse = jax.lax.map(q_body, (qb, qp))           # (nq,B,Kv,G,Cq,hd_v)
    out = out.transpose(1, 0, 4, 2, 3, 5).reshape(B, S, Kv, G, hd_v)
    lse = lse.transpose(1, 2, 3, 0, 4).reshape(B, Kv, G, S)
    return out, lse


def _blockwise_fwd(q, k, v, q_pos, kv_pos, window, causal, softcap,
                   q_chunk, kv_chunk, scale):
    out, lse = _blockwise_fwd_impl(q, k, v, q_pos, kv_pos, window, causal,
                                   softcap, q_chunk, kv_chunk, scale)
    return out, (q, k, v, q_pos, kv_pos, out, lse)


def _blockwise_bwd(window, causal, softcap, q_chunk, kv_chunk, scale, res,
                   dout):
    """FlashAttention-2-style backward: per (q-chunk, kv-chunk) block,
    recompute p from the saved lse, accumulate dq/dk/dv. Only O(chunk^2)
    transients; residuals are (q,k,v,out,lse)."""
    q, k, v, q_pos, kv_pos, out, lse = res
    B, S, Kv, G, hd = q.shape
    T = k.shape[1]
    hd_v = v.shape[-1]
    qc = min(q_chunk, S)
    kc = min(kv_chunk, T)
    nq, nk = S // qc, T // kc
    scale = scale or 1.0 / math.sqrt(hd)

    # delta = rowsum(dout * out)  (B,Kv,G,S)
    delta = jnp.einsum("bskgh,bskgh->bkgs", dout.astype(jnp.float32),
                       out.astype(jnp.float32))

    qb = q.reshape(B, nq, qc, Kv, G, hd).swapaxes(0, 1)
    dob = dout.reshape(B, nq, qc, Kv, G, hd_v).swapaxes(0, 1)
    lseb = lse.reshape(B, Kv, G, nq, qc).transpose(3, 0, 1, 2, 4)
    deltab = delta.reshape(B, Kv, G, nq, qc).transpose(3, 0, 1, 2, 4)
    qpb = q_pos.reshape(nq, qc)
    kb = k.reshape(B, nk, kc, Kv, hd).swapaxes(0, 1)
    vb = v.reshape(B, nk, kc, Kv, hd_v).swapaxes(0, 1)
    kpb = kv_pos.reshape(nk, kc)

    def kv_outer(dq_acc, j_blk):
        # outer over kv blocks accumulating dk/dv; inner over q blocks.
        # dq accumulates in the carry (one fp32 dq, not nk stacked copies).
        k_j, v_j, kp_j = j_blk

        def q_inner(carry, i_blk):
            dk_j, dv_j = carry
            q_i, do_i, lse_i, dl_i, qp_i = i_blk
            s = jnp.einsum("bckgh,btkh->bkgct", q_i, k_j,
                           preferred_element_type=jnp.float32) * scale
            s_raw = s
            if softcap:
                s = jnp.tanh(s / softcap) * softcap
            s = s + _mask_bias(qp_i, kp_j, window, causal)[None, None, None]
            p = jnp.exp(s - lse_i[..., None])                    # (B,Kv,G,c,t)
            dv_j = dv_j + jnp.einsum("bkgct,bckgh->btkh",
                                     p, do_i.astype(jnp.float32))
            dp = jnp.einsum("bckgh,btkh->bkgct",
                            do_i.astype(jnp.float32),
                            v_j.astype(jnp.float32))
            ds = p * (dp - dl_i[..., None])
            if softcap:
                ds = ds * (1.0 - jnp.tanh(s_raw / softcap) ** 2)
            dq_i = jnp.einsum("bkgct,btkh->bckgh", ds,
                              k_j.astype(jnp.float32)) * scale
            dk_j = dk_j + jnp.einsum("bkgct,bckgh->btkh", ds,
                                     q_i.astype(jnp.float32)) * scale
            return (dk_j, dv_j), dq_i

        dk0 = jnp.zeros((B, kc, Kv, hd), jnp.float32)
        dv0 = jnp.zeros((B, kc, Kv, hd_v), jnp.float32)
        (dk_j, dv_j), dq_parts = jax.lax.scan(
            q_inner, (dk0, dv0), (qb, dob, lseb, deltab, qpb))
        return dq_acc + dq_parts, (dk_j, dv_j)

    dq0 = jnp.zeros((nq, B, qc, Kv, G, hd), jnp.float32)
    dq_all, (dk_all, dv_all) = jax.lax.scan(kv_outer, dq0, (kb, vb, kpb))
    dq = dq_all.transpose(1, 0, 2, 3, 4, 5).reshape(B, S, Kv, G, hd)
    dk = dk_all.swapaxes(0, 1).reshape(B, T, Kv, hd)
    dv = dv_all.swapaxes(0, 1).reshape(B, T, Kv, hd_v)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            None, None)


blockwise_sdpa.defvjp(_blockwise_fwd, _blockwise_bwd)


def sdpa(q, k, v, q_pos, kv_pos, *, window: int = 0, causal: bool = True,
         softcap: float = 0.0, scale: Optional[float] = None):
    if q.shape[1] > BLOCKWISE_THRESHOLD:
        # nondiff args are positional (custom_vjp)
        return blockwise_sdpa(q, k, v, q_pos, kv_pos, window, causal,
                              softcap, CHUNK, CHUNK, scale)
    return naive_sdpa(q, k, v, q_pos, kv_pos, window=window, causal=causal,
                      softcap=softcap, scale=scale)


def sdpa_blocks(q_pos: np.ndarray, kv_pos: np.ndarray, *, window: int = 0,
                causal: bool = True) -> Tuple[int, int]:
    """The (query chunk, KV chunk) blocks of `sdpa` over these positions,
    and how many of them its blockwise form computes (`block_reached`):
    (0, 0) where it takes the naive path."""
    S, T = len(q_pos), len(kv_pos)
    if S <= BLOCKWISE_THRESHOLD:
        return 0, 0
    cq, ck = min(CHUNK, S), min(CHUNK, T)
    nq, nk = S // cq, T // ck
    reached = block_reached(q_pos.reshape(nq, 1, cq),
                            kv_pos.reshape(1, nk, ck), window, causal)
    return nq * nk, int(np.broadcast_to(reached, (nq, nk)).sum())


# ============================================================ GQA forward

def _qkv(params: Params, cfg: ModelConfig, x, positions, *,
         pin_layout: bool = False):
    B, S, _ = x.shape
    h, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q, k, v = (jnp.einsum("bsd,de->bse", x, params[n])
               for n in ("w_q", "w_k", "w_v"))
    if pin_layout:
        # Decode: keep the projections in the layout their matmuls give.
        # Left free, XLA lets rope and the cache write choose it and, for
        # waves wider than one lane, transposes a layer of w_q and w_k
        # every step to produce it (8 MB each at stablelm-1.6b).
        q, k, v = jax.lax.optimization_barrier((q, k, v))
    q, k, v = (q.reshape(B, S, h, hd), k.reshape(B, S, kv, hd),
               v.reshape(B, S, kv, hd))
    if cfg.qk_norm:
        q, k = l2norm(q), l2norm(k)
    q = apply_rope(q, positions, cfg.rope_theta, cfg.partial_rotary_factor)
    k = apply_rope(k, positions, cfg.rope_theta, cfg.partial_rotary_factor)
    return q, k, v


def _group_for_tp(q, k, v, cfg: ModelConfig, expand_kv: bool, shard_fn):
    """Arrange heads for the sharded attention core. When the TP width
    divides H but not Kv (e.g. Mistral-Large: 96 q heads, 8 kv heads, 16-way
    TP), the (Kv, G) grouping leaves XLA nothing to shard -> replicated
    attention activations + all-reduces. Expanding KV to full heads (G=1)
    restores clean head sharding; the per-device KV copy is tiny because H
    itself is sharded."""
    B, S = q.shape[:2]
    if expand_kv and cfg.q_per_kv > 1:
        k = jnp.repeat(k, cfg.q_per_kv, axis=2)
        v = jnp.repeat(v, cfg.q_per_kv, axis=2)
        if shard_fn is not None:
            q, k, v = (shard_fn(a, "bshd") for a in (q, k, v))
        qg = q.reshape(B, S, cfg.num_heads, 1, cfg.head_dim)
    else:
        qg = q.reshape(B, S, cfg.num_kv_heads, cfg.q_per_kv, cfg.head_dim)
    return qg, k, v


def attention_forward(params: Params, cfg: ModelConfig, x, *, window: int = 0,
                      causal: bool = True, expand_kv: bool = False,
                      shard_fn=None) -> jnp.ndarray:
    B, S, _ = x.shape
    positions = jnp.arange(S)
    q, k, v = _qkv(params, cfg, x, positions)
    qg, k, v = _group_for_tp(q, k, v, cfg, expand_kv, shard_fn)
    out = sdpa(qg, k, v, positions, positions, window=window, causal=causal,
               softcap=cfg.attn_logit_softcap)
    out = out.reshape(B, S, cfg.num_heads * cfg.head_dim)
    return jnp.einsum("bse,ed->bsd", out, params["w_o"])


# ============================================================ decode caches
#
# A layer's K and V are stored heads before positions, (B, Kv*hd, cap), and
# stacked over the layers of a scanned group, (L, B, Kv*hd, cap): a decode
# step writes the new token's column in place and attention reads the
# layer where it lies. `slot_pos` (cap,) holds each slot's absolute
# position (-1 = empty); sliding-window caches are rings of cap = window.

def init_attn_cache(cfg: ModelConfig, batch: int, max_seq: int, *,
                    window: int = 0, dtype=None) -> Params:
    cap = min(window, max_seq) if window > 0 else max_seq
    dt = dtype or jnp.dtype(cfg.param_dtype)
    kv, hd = cfg.num_kv_heads, cfg.head_dim
    return {
        "k": jnp.zeros((batch, kv * hd, cap), dt),
        "v": jnp.zeros((batch, kv * hd, cap), dt),
        "slot_pos": jnp.full((cap,), -1, jnp.int32),
    }


def _ring(x, cap: int, axis: int):
    """The last `cap` entries of `x` along `axis`, entry i at slot i % cap
    (absolute index i); fewer than `cap` entries fill the first slots."""
    n = x.shape[axis]
    if n <= cap:
        return x, n
    x = jax.lax.slice_in_dim(x, n - cap, n, axis=axis)
    return jnp.roll(x, n % cap, axis=axis), cap


def attention_prefill(params: Params, cfg: ModelConfig, x, *, window: int = 0,
                      max_seq: int = 0, expand_kv: bool = False,
                      shard_fn=None) -> Tuple[jnp.ndarray, Params]:
    """Full-sequence attention + build the decode cache."""
    B, S, _ = x.shape
    max_seq = max_seq or S
    positions = jnp.arange(S)
    q, k, v = _qkv(params, cfg, x, positions)
    qg, ke, ve = _group_for_tp(q, k, v, cfg, expand_kv, shard_fn)
    out = sdpa(qg, ke, ve, positions, positions, window=window, causal=True,
               softcap=cfg.attn_logit_softcap)
    out = out.reshape(B, S, cfg.num_heads * cfg.head_dim)
    out = jnp.einsum("bse,ed->bsd", out, params["w_o"])

    cap = min(window, max_seq) if window > 0 else max_seq
    kv_width = cfg.num_kv_heads * cfg.head_dim

    def heads_first(a):                          # (B,S,Kv,hd) -> (B,Kv*hd,cap)
        a, n = _ring(a.reshape(B, S, kv_width).swapaxes(1, 2), cap, 2)
        return jnp.pad(a, ((0, 0), (0, 0), (0, cap - n)))

    slot_pos, n = _ring(positions.astype(jnp.int32), cap, 0)
    cache = {
        "k": heads_first(k),
        "v": heads_first(v),
        "slot_pos": jnp.pad(slot_pos, (0, cap - n), constant_values=-1),
    }
    return out, cache


def attention_decode(params: Params, cfg: ModelConfig, x, cache: Params,
                     layer, pos, *, window: int = 0
                     ) -> Tuple[jnp.ndarray, Params]:
    """x: (B,1,d); cache: leaves stacked over layers, this one `layer`;
    pos: scalar int32 (position of the new token). Writes the new token's
    K/V column and slot position at (layer, ..., pos % cap) and attends
    over the layer's slots."""
    B = x.shape[0]
    kv, hd = cfg.num_kv_heads, cfg.head_dim
    pos1 = jnp.reshape(pos, (1,))
    q, k, v = _qkv(params, cfg, x, pos1, pin_layout=True)
    cap = cache["k"].shape[-1]
    slot = jnp.mod(pos, cap)
    cache = {
        "k": layer_write(cache["k"], layer, k.reshape(B, kv * hd, 1),
                         (0, 0, slot)),
        "v": layer_write(cache["v"], layer, v.reshape(B, kv * hd, 1),
                         (0, 0, slot)),
        "slot_pos": layer_write(cache["slot_pos"], layer,
                                pos1.astype(jnp.int32), (slot,)),
    }
    k_l, v_l = (layer_read(cache[n], layer).reshape(B, kv, hd, cap)
                for n in ("k", "v"))
    qg = q.reshape(B, 1, kv, cfg.q_per_kv, hd)
    out = cached_sdpa(qg, k_l, v_l, pos1, layer_read(cache["slot_pos"], layer),
                      window=window, softcap=cfg.attn_logit_softcap)
    out = out.reshape(B, 1, cfg.num_heads * cfg.head_dim)
    out = jnp.einsum("bse,ed->bsd", out, params["w_o"])
    return out, cache


# ========================================================== cross-attention

def cross_attention_forward(params: Params, cfg: ModelConfig, x, enc_kv):
    """x: (B,S,d) decoder states; enc_kv: dict(k,v) precomputed (B,T,kv,hd)."""
    B, S, _ = x.shape
    q = jnp.einsum("bsd,de->bse", x, params["w_q"]).reshape(
        B, S, cfg.num_heads, cfg.head_dim)
    qg = q.reshape(B, S, cfg.num_kv_heads, cfg.q_per_kv, cfg.head_dim)
    T = enc_kv["k"].shape[1]
    out = sdpa(qg, enc_kv["k"], enc_kv["v"], jnp.full((S,), T - 1),
               jnp.arange(T), causal=False)
    out = out.reshape(B, S, cfg.num_heads * cfg.head_dim)
    return jnp.einsum("bse,ed->bsd", out, params["w_o"])


def encode_cross_kv(params: Params, cfg: ModelConfig, enc_out) -> Params:
    B, T, _ = enc_out.shape
    kv, hd = cfg.num_kv_heads, cfg.head_dim
    k = jnp.einsum("btd,de->bte", enc_out, params["w_k"]).reshape(B, T, kv, hd)
    v = jnp.einsum("btd,de->bte", enc_out, params["w_v"]).reshape(B, T, kv, hd)
    return {"k": k, "v": v}


# ===================================================================== MLA

def mla_init(rng, cfg: ModelConfig) -> Params:
    m = cfg.mla
    d, h = cfg.d_model, cfg.num_heads
    dt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(rng, 7)
    qk_dim = m.nope_head_dim + m.rope_head_dim
    return {
        "w_dq": dense_init(ks[0], d, m.q_lora_rank, dt),
        "q_norm": {"scale": jnp.ones((m.q_lora_rank,), dt)},
        "w_uq": dense_init(ks[1], m.q_lora_rank, h * qk_dim, dt),
        "w_dkv": dense_init(ks[2], d, m.kv_lora_rank, dt),
        "kv_norm": {"scale": jnp.ones((m.kv_lora_rank,), dt)},
        "w_kr": dense_init(ks[3], d, m.rope_head_dim, dt),
        # the latent's expansion, heads first as the published kv_b_proj
        # lays it out: the decode path absorbs them head by head
        "w_uk": (jax.random.normal(ks[4], (h, m.nope_head_dim, m.kv_lora_rank),
                                   jnp.float32) / math.sqrt(m.kv_lora_rank)).astype(dt),
        "w_uv": (jax.random.normal(ks[5], (h, m.v_head_dim, m.kv_lora_rank),
                                   jnp.float32) / math.sqrt(m.kv_lora_rank)).astype(dt),
        "w_o": dense_init(ks[6], h * m.v_head_dim, d, dt),
    }


def _mla_q(params, cfg, x, positions, *, pin_layout: bool = False):
    m = cfg.mla
    B, S, _ = x.shape
    h = cfg.num_heads
    cq = rmsnorm(params["q_norm"], jnp.einsum("bsd,dr->bsr", x, params["w_dq"]),
                 cfg.norm_eps)
    q = jnp.einsum("bsr,re->bse", cq, params["w_uq"])
    if pin_layout:
        # Decode: left free, the split into nope and rope parts makes XLA
        # re-lay a layer of w_uq out every step (75 MB at DeepSeek-V2)
        q = jax.lax.optimization_barrier(q)
    q = q.reshape(B, S, h, m.nope_head_dim + m.rope_head_dim)
    q_nope, q_rope = q[..., :m.nope_head_dim], q[..., m.nope_head_dim:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta,
                        scaling=cfg.rope_scaling)
    return q_nope, q_rope


def _mla_ckv(params, cfg, x, positions):
    """The compressed K/V a token leaves in the cache: the normed latent
    c_kv (B,S,kv_lora) and the rope key shared by all heads (B,S,rope)."""
    c_kv = rmsnorm(params["kv_norm"],
                   jnp.einsum("bsd,dr->bsr", x, params["w_dkv"]), cfg.norm_eps)
    k_rope = jnp.einsum("bsd,dr->bsr", x, params["w_kr"])            # (B,S,rope)
    k_rope = apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta,
                        scaling=cfg.rope_scaling)[:, :, 0, :]
    return c_kv, k_rope


def _mla_scale(cfg: ModelConfig) -> float:
    """Softmax scale: 1/sqrt(nope + rope), times YaRN's mscale^2."""
    m = cfg.mla
    return (yarn_softmax_factor(cfg.rope_scaling)
            / math.sqrt(m.nope_head_dim + m.rope_head_dim))


def _mla_attend(params: Params, cfg: ModelConfig, x, c_kv, k_rope,
                positions) -> jnp.ndarray:
    """Unabsorbed causal MLA of a sequence over itself: K/V expanded per
    head from the latent, flash path for long sequences."""
    m = cfg.mla
    B, S, _ = x.shape
    h = cfg.num_heads
    q_nope, q_rope = _mla_q(params, cfg, x, positions)
    k_nope = jnp.einsum("bsr,her->bshe", c_kv, params["w_uk"])
    v = jnp.einsum("bsr,her->bshe", c_kv, params["w_uv"])
    q = jnp.concatenate([q_nope, q_rope], axis=-1)                  # (B,S,h,nope+rope)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                  (B, S, h, m.rope_head_dim))], axis=-1)
    # MLA has no KV grouping: treat each head as its own KV head (Kv=h, G=1)
    out = sdpa(q[:, :, :, None, :], k, v, positions, positions, causal=True,
               scale=_mla_scale(cfg))
    out = out.reshape(B, S, h * m.v_head_dim)
    return jnp.einsum("bse,ed->bsd", out, params["w_o"])


def mla_forward(params: Params, cfg: ModelConfig, x) -> jnp.ndarray:
    """Unabsorbed (train) MLA over the whole batch."""
    with jax.named_scope("mla"):
        positions = jnp.arange(x.shape[1])
        c_kv, k_rope = _mla_ckv(params, cfg, x, positions)
        return _mla_attend(params, cfg, x, c_kv, k_rope, positions)


def init_mla_cache(cfg: ModelConfig, batch: int, max_seq: int, dtype=None):
    m = cfg.mla
    dt = dtype or jnp.dtype(cfg.param_dtype)
    return {
        "c_kv": jnp.zeros((batch, max_seq, m.kv_lora_rank), dt),
        "k_rope": jnp.zeros((batch, max_seq, m.rope_head_dim), dt),
        "slot_pos": jnp.full((max_seq,), -1, jnp.int32),
    }


def mla_prefill(params: Params, cfg: ModelConfig, x, *, max_seq: int = 0):
    """Full-sequence MLA + the decode cache (the latent and rope key of
    every position, padded to `max_seq`). Attention runs one lane at a
    time, so a wave holds one lane's expanded K/V and score blocks (at
    128 heads and 7k positions about 2 GB a lane) and not the wave's."""
    B, S, _ = x.shape
    max_seq = max_seq or S
    with jax.named_scope("mla"):
        positions = jnp.arange(S)
        c_kv, k_rope = _mla_ckv(params, cfg, x, positions)
        out = jax.lax.map(
            lambda a: _mla_attend(params, cfg, *(t[None] for t in a),
                                  positions)[0], (x, c_kv, k_rope))
        pad = ((0, 0), (0, max_seq - S), (0, 0))
        cache = {
            "c_kv": jnp.pad(c_kv, pad),
            "k_rope": jnp.pad(k_rope, pad),
            "slot_pos": jnp.pad(positions.astype(jnp.int32),
                                (0, max_seq - S), constant_values=-1),
        }
    return out, cache


def _latent_attend(q_c, q_rope, c_kv, k_rope, bias, scale):
    """One lane's absorbed attention: q_c (1,h,r) and q_rope (1,h,rope)
    over the latent c_kv (T,r) and rope keys (T,rope) -> the latent
    context (1,h,r)."""
    s = (jnp.einsum("qhr,tr->hqt", q_c, c_kv,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("qhe,te->hqt", q_rope, k_rope,
                      preferred_element_type=jnp.float32))
    w = jax.nn.softmax(s * scale + bias[None], axis=-1).astype(c_kv.dtype)
    return jnp.einsum("hqt,tr->qhr", w, c_kv)


def mla_decode(params: Params, cfg: ModelConfig, x, cache: Params, layer,
               pos):
    """Absorbed-matmul MLA decode: attention runs entirely in the latent
    space (q absorbed through W_UK, context expanded through W_UV afterwards),
    so per-token KV traffic is kv_lora+rope instead of 2*h*hd. The cache's
    leaves are stacked over layers; the new token is written at
    (layer, ..., pos) in place.
    """
    m = cfg.mla
    B = x.shape[0]
    h = cfg.num_heads
    pos1 = jnp.reshape(pos, (1,))
    with jax.named_scope("mla"):
        q_nope, q_rope = _mla_q(params, cfg, x, pos1,
                                pin_layout=True)                   # (B,1,h,*)
        c_kv_new, k_rope_new = _mla_ckv(params, cfg, x, pos1)
        cache = {
            "c_kv": layer_write(cache["c_kv"], layer, c_kv_new, (0, pos)),
            "k_rope": layer_write(cache["k_rope"], layer, k_rope_new,
                                  (0, pos)),
            "slot_pos": layer_write(cache["slot_pos"], layer,
                                    pos1.astype(jnp.int32), (pos,)),
        }
        c_kv, k_rope, slot_pos = (layer_read(cache[n], layer)
                                  for n in ("c_kv", "k_rope", "slot_pos"))

        if m.absorb_decode:
            # q_c[b,h,r] = sum_e q_nope[b,h,e] W_uk[h,e,r]
            q_c = jnp.einsum("bqhe,her->bqhr", q_nope, params["w_uk"])
            bias = _mask_bias(pos1, slot_pos, 0, True)               # (1,T)
            # One lane at a time: batched over lanes, XLA lays the cache
            # out positions before lanes and copies it whole every step
            ctx_c = jax.lax.map(
                lambda a: _latent_attend(*a, bias, _mla_scale(cfg)),
                (q_c, q_rope, c_kv, k_rope))                       # (B,1,h,r)
            out = jnp.einsum("bqhr,her->bqhe", ctx_c, params["w_uv"])
        else:
            k_nope = jnp.einsum("btr,her->bthe", c_kv, params["w_uk"])
            v = jnp.einsum("btr,her->bthe", c_kv, params["w_uv"])
            k = jnp.concatenate(
                [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                          k_nope.shape[:3]
                                          + (m.rope_head_dim,))], axis=-1)
            q = jnp.concatenate([q_nope, q_rope], axis=-1)
            out = naive_sdpa(q[:, :, :, None, :], k, v, pos1, slot_pos,
                             causal=True, scale=_mla_scale(cfg))
            out = out.reshape(B, 1, h, m.v_head_dim)
        out = out.reshape(B, 1, h * m.v_head_dim)
        out = jnp.einsum("bse,ed->bsd", out, params["w_o"])
    return out, cache
