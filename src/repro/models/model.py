"""Unified model builder: every assigned architecture is an instance of this
composable decoder (optionally with an encoder stack and modality stubs).

Layers are organized as repeated *pattern groups* (cfg.pattern/ffn_pattern);
the forward pass lax.scans over group repetitions with stacked parameters,
keeping HLO size and compile time independent of depth. Mixer kinds: attn,
swa, mla, mamba, mlstm, slstm. FFN kinds: dense (SwiGLU), moe, none.

Public surface (all pure functions, jit/pjit-friendly):
    model = build_model(cfg, rules=None)
    params = model.init(rng)
    loss, aux = model.loss_fn(params, batch)
    logits, cache = model.prefill(params, batch)        # builds decode cache
    logits, cache = model.decode_step(params, cache, tokens, pos)
    cache = model.init_cache(batch_size, max_seq)
    specs = model.input_specs(shape_cfg)                # ShapeDtypeStructs
"""
from __future__ import annotations

import functools
import math
from collections import Counter
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import (ATTN, DENSE, MAMBA, MLA, MLSTM, MOE, NONE,
                                SLSTM, SWA, ModelConfig, ShapeConfig)
from repro.models import attention as attn
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models import xlstm as xlstm_lib
from repro.models.layers import (dense_init, embedding_init, embed_tokens,
                                 layer_read, layer_write, rmsnorm,
                                 rmsnorm_init, softmax_xent, swiglu,
                                 swiglu_init, unembed)

Params = Dict[str, Any]


def padded_vocab(cfg: ModelConfig) -> int:
    """Round vocab up so embedding/lm-head shard evenly (Megatron-style)."""
    return -(-cfg.vocab_size // 512) * 512


# ================================================================== layers

def _init_layer(rng, cfg: ModelConfig, kind: str, ffn_kind: str,
                with_cross: bool) -> Params:
    ks = jax.random.split(rng, 4)
    dt = jnp.dtype(cfg.param_dtype)
    p: Params = {"pre_norm": rmsnorm_init(cfg.d_model, dt)}
    if kind in (ATTN, SWA):
        p["mixer"] = attn.attention_init(ks[0], cfg)
    elif kind == MLA:
        p["mixer"] = attn.mla_init(ks[0], cfg)
    elif kind == MAMBA:
        p["mixer"] = ssm_lib.mamba_init(ks[0], cfg)
    elif kind == MLSTM:
        p["mixer"] = xlstm_lib.mlstm_init(ks[0], cfg)
    elif kind == SLSTM:
        p["mixer"] = xlstm_lib.slstm_init(ks[0], cfg)
    else:
        raise ValueError(kind)
    if with_cross:
        p["cross_norm"] = rmsnorm_init(cfg.d_model, dt)
        p["cross"] = attn.cross_attention_init(ks[1], cfg)
    if ffn_kind == DENSE:
        p["post_norm"] = rmsnorm_init(cfg.d_model, dt)
        p["ffn"] = swiglu_init(ks[2], cfg.d_model, cfg.d_ff or 4 * cfg.d_model, dt)
    elif ffn_kind == MOE:
        p["post_norm"] = rmsnorm_init(cfg.d_model, dt)
        p["ffn"] = moe_lib.moe_init(ks[2], cfg)
    return p


def _init_first_layer(rng, cfg: ModelConfig, with_cross: bool) -> Params:
    """first_k_dense layers: pattern[0] mixer + dense FFN of `dense_d_ff`."""
    ks = jax.random.split(rng, 3)
    dt = jnp.dtype(cfg.param_dtype)
    p = _init_layer(ks[0], cfg, cfg.pattern[0], NONE, with_cross)
    p["post_norm"] = rmsnorm_init(cfg.d_model, dt)
    p["ffn"] = swiglu_init(ks[1], cfg.d_model,
                           cfg.dense_d_ff or cfg.d_ff or 4 * cfg.d_model, dt)
    return p


class Model:
    def __init__(self, cfg: ModelConfig, rules=None):
        self.cfg = cfg
        self.rules = rules

    # ---------------------------------------------------------------- init

    def init(self, rng) -> Params:
        cfg = self.cfg
        dt = jnp.dtype(cfg.param_dtype)
        vp = padded_vocab(cfg)
        keys = jax.random.split(rng, 8)
        p: Params = {
            "embed": embedding_init(keys[0], vp, cfg.d_model, dt),
            "final_norm": rmsnorm_init(cfg.d_model, dt),
        }
        if not cfg.tie_embeddings:
            p["lm_head"] = dense_init(keys[1], cfg.d_model, vp, dt)
        if cfg.input_mode == "frames":
            p["frame_proj"] = dense_init(keys[2], cfg.frame_dim or cfg.d_model,
                                         cfg.d_model, dt)
        if cfg.input_mode == "tokens+image":
            p["img_proj"] = dense_init(keys[2], cfg.d_model, cfg.d_model, dt)

        with_cross = cfg.encoder_layers > 0

        def init_group(rng_g):
            ks = jax.random.split(rng_g, len(cfg.pattern))
            return tuple(
                _init_layer(ks[i], cfg, cfg.pattern[i], cfg.ffn_pattern[i],
                            with_cross)
                for i in range(len(cfg.pattern)))

        p["groups"] = jax.vmap(init_group)(
            jax.random.split(keys[3], cfg.num_groups))
        if cfg.first_k_dense:
            fks = jax.random.split(keys[4], cfg.first_k_dense)
            p["first"] = [
                _init_first_layer(fks[i], cfg, with_cross)
                for i in range(cfg.first_k_dense)]
        if cfg.encoder_layers:
            def init_enc_layer(rng_e):
                return _init_layer(rng_e, cfg, ATTN, DENSE, False)
            p["encoder"] = {
                "layers": jax.vmap(init_enc_layer)(
                    jax.random.split(keys[5], cfg.encoder_layers)),
                "final_norm": rmsnorm_init(cfg.d_model, dt),
            }
        return p

    # -------------------------------------------------------------- shards

    def _act(self, x, name="btd"):
        if self.rules is not None:
            return self.rules.constrain_act(x, name)
        return x

    def _moe_shard(self):
        if self.rules is not None:
            return self.rules.constrain_moe
        return None

    def _attn_tp(self):
        """(expand_kv, shard_fn): expand KV to full heads when TP divides H
        but not Kv (see attention._group_for_tp)."""
        cfg = self.cfg
        if self.rules is None:
            return False, None
        tp = self.rules.tp_size
        expand = (cfg.num_heads % tp == 0 and cfg.num_kv_heads % tp != 0
                  and cfg.q_per_kv > 1)
        return expand, (lambda a, nm: self.rules.constrain_act(a, nm))

    # ------------------------------------------------------------- forward

    def _layer_forward(self, lp: Params, kind: str, ffn_kind: str, h, aux,
                       enc_out=None):
        cfg = self.cfg
        mix_in = rmsnorm(lp["pre_norm"], h, cfg.norm_eps)
        if kind in (ATTN, SWA):
            window = cfg.window_size if kind == SWA else 0
            expand, sf = self._attn_tp()
            out = attn.attention_forward(lp["mixer"], cfg, mix_in,
                                         window=window, expand_kv=expand,
                                         shard_fn=sf)
        elif kind == MLA:
            out = attn.mla_forward(lp["mixer"], cfg, mix_in)
        elif kind == MAMBA:
            out, _ = ssm_lib.mamba_mix(lp["mixer"], cfg, mix_in)
        elif kind == MLSTM:
            out, _ = xlstm_lib.mlstm_mix(lp["mixer"], cfg, mix_in)
        elif kind == SLSTM:
            out, _ = xlstm_lib.slstm_mix(lp["mixer"], cfg, mix_in)
        else:
            raise ValueError(kind)
        h = self._act(h + out)
        if enc_out is not None and "cross" in lp:
            kv = attn.encode_cross_kv(lp["cross"], cfg, enc_out)
            c_in = rmsnorm(lp["cross_norm"], h, cfg.norm_eps)
            h = self._act(h + attn.cross_attention_forward(lp["cross"], cfg,
                                                           c_in, kv))
        if "ffn" in lp and ffn_kind != NONE:
            y, moe_aux = self._ffn(lp, ffn_kind, h)
            aux = {k: aux[k] + moe_aux[k] for k in aux} if moe_aux else aux
            h = self._act(h + y)
        return h, aux

    def _ffn(self, lp: Params, ffn_kind: str, h, dropless=False,
             experts=None, layer=None):
        """The layer's FFN on its normed input, and the MoE layer's aux
        (`moe.moe_apply`; none for a dense FFN): the router's losses in
        training, the routing counts when `dropless` (serving).
        `experts`: the routed experts' weights stacked over layers, of
        which this is `layer` (`lp` then lacks them)."""
        f_in = rmsnorm(lp["post_norm"], h, self.cfg.norm_eps)
        if ffn_kind == MOE and "router" in lp["ffn"]:
            return moe_lib.moe_apply(
                dict(lp["ffn"], **(experts or {})), self.cfg, f_in,
                self._moe_shard(), dropless, layer if experts else None)
        return swiglu(lp["ffn"], f_in), {}

    def _unstack_experts(self, groups):
        """The scanned groups' parameters without the routed experts'
        weights, and those weights whole (stacked over the layers, None
        where a pattern position has none): the decode step's grouped
        product reads its layer's experts where they lie in the stack.
        The dense dispatch takes its layer's slice as the scan gives it."""
        xs, whole = [], []
        for g in groups:
            f = g.get("ffn", {})
            if "router" not in f or self.cfg.moe.dispatch == "dense":
                xs.append(g)
                whole.append(None)
                continue
            xs.append(dict(g, ffn={k: v for k, v in f.items()
                                   if k not in moe_lib.EXPERT_WEIGHTS}))
            whole.append({k: f[k] for k in moe_lib.EXPERT_WEIGHTS})
        return tuple(xs), whole


    def _remat(self, fn):
        pol = self.cfg.remat_policy
        if pol == "full":
            return fn
        if pol == "dots":
            policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        else:
            policy = jax.checkpoint_policies.nothing_saveable
        return jax.checkpoint(fn, policy=policy)

    def _first_layers_forward(self, params, h, aux, enc_out=None):
        cfg = self.cfg
        for lp in params.get("first", []):
            h, aux = self._layer_forward(lp, cfg.pattern[0], DENSE, h, aux,
                                         enc_out)
        return h, aux

    def _backbone(self, params: Params, h, enc_out=None):
        cfg = self.cfg
        aux0 = {"moe_lb_loss": jnp.zeros((), jnp.float32),
                "moe_z_loss": jnp.zeros((), jnp.float32)}
        h, aux0 = self._first_layers_forward(params, h, aux0, enc_out)

        def group_body(carry, g_params):
            hh, aux = carry
            for i, kind in enumerate(cfg.pattern):
                hh, aux = self._layer_forward(g_params[i], kind,
                                              cfg.ffn_pattern[i], hh, aux,
                                              enc_out)
            return (hh, aux), None

        body = self._remat(group_body)
        (h, aux), _ = jax.lax.scan(body, (h, aux0), params["groups"])
        h = rmsnorm(params["final_norm"], h, cfg.norm_eps)
        return h, aux

    def _encode(self, params: Params, frames):
        cfg = self.cfg
        h = jnp.einsum("btf,fd->btd", frames, params["frame_proj"])
        h = self._act(h)

        def enc_body(hh, lp):
            mix_in = rmsnorm(lp["pre_norm"], hh, cfg.norm_eps)
            out = attn.attention_forward(lp["mixer"], cfg, mix_in,
                                         causal=False)
            hh = self._act(hh + out)
            f_in = rmsnorm(lp["post_norm"], hh, cfg.norm_eps)
            hh = self._act(hh + swiglu(lp["ffn"], f_in))
            return hh, None

        h, _ = jax.lax.scan(self._remat(enc_body), h,
                            params["encoder"]["layers"])
        return rmsnorm(params["encoder"]["final_norm"], h, cfg.norm_eps)

    def _embed_inputs(self, params: Params, batch: Dict[str, jnp.ndarray]):
        """Returns (decoder-input hidden states, enc_out or None)."""
        cfg = self.cfg
        enc_out = None
        if cfg.input_mode == "frames":
            enc_out = self._encode(params, batch["frames"])
            h = embed_tokens(params["embed"], batch["tokens"])
        elif cfg.input_mode == "tokens+image":
            img = jnp.einsum("bpd,de->bpe", batch["image_embeds"],
                             params["img_proj"])
            tok = embed_tokens(params["embed"], batch["tokens"])
            h = jnp.concatenate([img.astype(tok.dtype), tok], axis=1)
        else:
            h = embed_tokens(params["embed"], batch["tokens"])
        return self._act(h), enc_out

    def _hidden(self, params: Params, batch):
        h, enc_out = self._embed_inputs(params, batch)
        return self._backbone(params, h, enc_out)

    def forward(self, params: Params, batch) -> Tuple[jnp.ndarray, Dict]:
        h, aux = self._hidden(params, batch)
        logits = unembed(params["embed"], h, self.cfg.tie_embeddings,
                         params.get("lm_head"))
        return self._act(logits, "logits"), aux

    def _labels_and_mask(self, batch, s: int):
        """Per-position next-token labels + validity mask, aligned to the
        full hidden-state sequence (so the loss can chunk over S)."""
        cfg = self.cfg
        tokens = batch["tokens"]
        b = tokens.shape[0]
        if cfg.input_mode == "tokens+image":
            p = cfg.num_image_tokens
            # position p-1+j predicts tokens[:, j]
            labels = jnp.zeros((b, s), jnp.int32)
            labels = jax.lax.dynamic_update_slice(labels, tokens, (0, p - 1))
            pos = jnp.arange(s)
            mask = ((pos >= p - 1) & (pos < p - 1 + tokens.shape[1])
                    ).astype(jnp.float32)[None, :].repeat(b, 0)
            return labels, mask
        labels = jnp.concatenate(
            [tokens[:, 1:], jnp.zeros((b, 1), tokens.dtype)], axis=1)
        mask = jnp.concatenate(
            [jnp.ones((b, s - 1), jnp.float32), jnp.zeros((b, 1))], axis=1)
        return labels, mask

    def _chunked_xent(self, params: Params, h, labels, mask,
                      chunk: int = 1024):
        """Never materializes the full (B,S,V) logits: scans S-chunks with
        per-chunk remat (the vocab-chunked-loss lever for 262k vocabs)."""
        cfg = self.cfg
        b, s, d = h.shape
        chunk = math.gcd(s, chunk)
        n = s // chunk
        vp = padded_vocab(cfg)
        pad = (jnp.arange(vp) >= cfg.vocab_size) if vp != cfg.vocab_size \
            else None

        @jax.checkpoint
        def body(carry, xs):
            hc, lc, mc = xs
            logits = unembed(params["embed"], hc, cfg.tie_embeddings,
                             params.get("lm_head")).astype(jnp.float32)
            if cfg.logit_softcap:
                logits = jnp.tanh(logits / cfg.logit_softcap) \
                    * cfg.logit_softcap
            if pad is not None:
                logits = jnp.where(pad, -1e30, logits)
            lse = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
            return carry + jnp.sum((lse - gold) * mc), None

        xs = (h.reshape(b, n, chunk, d).swapaxes(0, 1),
              labels.reshape(b, n, chunk).swapaxes(0, 1),
              mask.reshape(b, n, chunk).swapaxes(0, 1))
        tot, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), xs)
        return tot / jnp.maximum(jnp.sum(mask), 1.0)

    # vocabularies at/above this size use the chunked loss
    CHUNKED_LOSS_VOCAB = 131_072

    def loss_fn(self, params: Params, batch) -> Tuple[jnp.ndarray, Dict]:
        cfg = self.cfg
        vp = padded_vocab(cfg)
        h, aux = self._hidden(params, batch)
        s = h.shape[1]
        if vp >= self.CHUNKED_LOSS_VOCAB and s > 1024:
            labels, mask = self._labels_and_mask(batch, s)
            loss = self._chunked_xent(params, h, labels, mask)
        else:
            logits = self._act(unembed(params["embed"], h,
                                       cfg.tie_embeddings,
                                       params.get("lm_head")), "logits")
            if vp != cfg.vocab_size:
                pad_mask = jnp.arange(vp) >= cfg.vocab_size
                logits = jnp.where(pad_mask, -1e30,
                                   logits.astype(jnp.float32))
            tokens = batch["tokens"]
            if cfg.input_mode == "tokens+image":
                p = cfg.num_image_tokens
                loss = softmax_xent(logits[:, p - 1:-1], tokens,
                                    logit_softcap=cfg.logit_softcap)
            else:
                loss = softmax_xent(logits[:, :-1], tokens[:, 1:],
                                    logit_softcap=cfg.logit_softcap)
        total = (loss + 0.01 * aux["moe_lb_loss"] + 1e-3 * aux["moe_z_loss"])
        aux = dict(aux, xent=loss)
        return total, aux

    # ------------------------------------------------------------- caches

    def _init_layer_cache(self, kind: str, batch: int, max_seq: int,
                          with_cross: bool):
        cfg = self.cfg
        dt = jnp.dtype(cfg.param_dtype)
        if kind in (ATTN, SWA):
            window = cfg.window_size if kind == SWA else 0
            c = attn.init_attn_cache(cfg, batch, max_seq, window=window, dtype=dt)
        elif kind == MLA:
            c = attn.init_mla_cache(cfg, batch, max_seq, dtype=dt)
        elif kind == MAMBA:
            c = ssm_lib.init_mamba_cache(cfg, batch, dtype=dt)
        elif kind == MLSTM:
            c = xlstm_lib.init_mlstm_cache(cfg, batch, dtype=dt)
        elif kind == SLSTM:
            c = xlstm_lib.init_slstm_cache(cfg, batch, dtype=dt)
        else:
            raise ValueError(kind)
        if with_cross:
            kv, hd = cfg.num_kv_heads, cfg.head_dim
            c = dict(c, cross_k=jnp.zeros((batch, max_seq, kv, hd), dt),
                     cross_v=jnp.zeros((batch, max_seq, kv, hd), dt))
        return c

    def init_cache(self, batch: int, max_seq: int) -> Params:
        cfg = self.cfg
        with_cross = cfg.encoder_layers > 0

        def group_cache(_):
            return tuple(
                dict(self._init_layer_cache(k, batch, max_seq, with_cross),
                     **(_slots_cache({}) if f == MOE else {}))
                for k, f in zip(cfg.pattern, cfg.ffn_pattern))

        cache: Params = {
            "groups": jax.vmap(group_cache)(jnp.arange(cfg.num_groups))}
        if cfg.first_k_dense:
            cache["first"] = [
                self._init_layer_cache(cfg.pattern[0], batch, max_seq,
                                       with_cross)
                for _ in range(cfg.first_k_dense)]
        return cache

    # ------------------------------------------------------------- decode

    def _layer_decode(self, lp: Params, kind: str, ffn_kind: str, h, cache,
                      layer, pos, experts=None):
        """One layer's step. `cache`: this pattern position's leaves,
        stacked over layers; the layer writes what it changed at `layer`
        and returns the updated stack (an expert layer adds its routing
        counts to its own)."""
        cfg = self.cfg
        mix_in = rmsnorm(lp["pre_norm"], h, cfg.norm_eps)
        core = {k: v for k, v in cache.items()
                if not k.startswith("cross_") and k not in SLOTS}
        if kind in (ATTN, SWA):
            window = cfg.window_size if kind == SWA else 0
            out, core = attn.attention_decode(lp["mixer"], cfg, mix_in, core,
                                              layer, pos, window=window)
        elif kind == MLA:
            out, core = attn.mla_decode(lp["mixer"], cfg, mix_in, core, layer,
                                        pos)
        else:
            # a recurrent state is small: the layer writes it whole
            step = {MAMBA: ssm_lib.mamba_decode, MLSTM: xlstm_lib.mlstm_decode,
                    SLSTM: xlstm_lib.slstm_decode}[kind]
            state = {n: layer_read(a, layer) for n, a in core.items()}
            out, state = step(lp["mixer"], cfg, mix_in, state)
            core = {n: layer_write(a, layer, state[n])
                    for n, a in core.items()}
        h = h + out
        if "cross_k" in cache:
            c_in = rmsnorm(lp["cross_norm"], h, cfg.norm_eps)
            enc_kv = {n: layer_read(cache[f"cross_{n}"], layer)
                      for n in ("k", "v")}
            h = h + attn.cross_attention_forward(lp["cross"], cfg, c_in,
                                                 enc_kv)
        if "ffn" in lp and ffn_kind != NONE:
            y, counts = self._ffn(lp, ffn_kind, h, dropless=True,
                                  experts=experts, layer=layer)
            h = h + y
            if ffn_kind == MOE:
                core.update({k: layer_write(cache[k], layer, counts[k]
                                            + layer_read(cache[k], layer))
                             for k in ("routed_slots", "held_slots")})
        return h, dict(cache, **core)

    def decode_step(self, params: Params, cache: Params, tokens, pos):
        """One token for every lane: tokens (B,1) int32, pos a scalar int32
        (the new token's position) -> (logits (B,1,V), cache).

        The cache passed in is consumed: the step updates it in place
        (`ServingEngine` donates it), so keep only the one returned. Its
        scanned groups' leaves are stacked over the groups and carried
        through the layer scan; each layer writes only its new state at its
        own index. Attention's K and V are (L, B, Kv*hd, cap), heads x
        head-dim before positions, one column written per step; MLA's
        c_kv/k_rope (L, B, cap, r); recurrent states whole per layer; an
        expert layer's routing counts (`SLOTS`), one each per layer."""
        cfg = self.cfg
        h = embed_tokens(params["embed"], tokens)
        new_cache: Params = {}
        if cfg.first_k_dense:
            new_first = []
            for lp, c in zip(params["first"], cache["first"]):
                h, c = self._layer_decode(
                    lp, cfg.pattern[0], DENSE, h,
                    jax.tree.map(lambda a: a[None], c), 0, pos)
                new_first.append(jax.tree.map(lambda a: a[0], c))
            new_cache["first"] = new_first

        groups, experts = self._unstack_experts(params["groups"])

        def group_body(carry, xs):
            hh, g_cache = carry
            g_params, layer = xs
            g_cache = list(g_cache)
            for i, kind in enumerate(cfg.pattern):
                hh, g_cache[i] = self._layer_decode(
                    g_params[i], kind, cfg.ffn_pattern[i], hh, g_cache[i],
                    layer, pos, experts[i])
            return (hh, tuple(g_cache)), None

        (h, new_cache["groups"]), _ = jax.lax.scan(
            group_body, (h, cache["groups"]),
            (groups, jnp.arange(cfg.num_groups, dtype=jnp.int32)))
        h = rmsnorm(params["final_norm"], h, cfg.norm_eps)
        logits = unembed(params["embed"], h, cfg.tie_embeddings,
                         params.get("lm_head"))
        return logits, new_cache

    # ------------------------------------------------------------ prefill

    def prefill(self, params: Params, batch, max_seq: int = 0):
        """Full-sequence forward that also builds the decode cache."""
        cfg = self.cfg
        h, enc_out = self._embed_inputs(params, batch)
        max_seq = max_seq or h.shape[1]
        new_cache: Params = {}
        if cfg.first_k_dense:
            firsts = []
            for lp in params["first"]:
                h, c = self._layer_prefill(lp, cfg.pattern[0], DENSE, h,
                                           enc_out, max_seq)
                firsts.append(c)
            new_cache["first"] = firsts

        def group_body(hh, g_params):
            caches = []
            for i, kind in enumerate(cfg.pattern):
                hh, c = self._layer_prefill(
                    g_params[i], kind, cfg.ffn_pattern[i], hh, enc_out,
                    max_seq)
                caches.append(c)
            return hh, tuple(caches)

        h, groups_cache = jax.lax.scan(group_body, h, params["groups"])
        new_cache["groups"] = groups_cache
        h = rmsnorm(params["final_norm"], h, cfg.norm_eps)
        logits = unembed(params["embed"], h[:, -1:], cfg.tie_embeddings,
                         params.get("lm_head"))
        return logits, new_cache

    def prefill_attention_blocks(self, prompt_len: int) -> Tuple[int, int]:
        """The blocks of blockwise attention that one lane's prefill of
        `prompt_len` tokens forms, and how many of them it computes, summed
        over the self-attention layers (`attn.sdpa_blocks`): (0, 0) where
        every layer takes the naive path. An encoder's attention and
        cross-attention, which depend on the encoder's input, are not
        counted."""
        cfg = self.cfg
        pos = np.arange(prompt_len)
        kinds = Counter([cfg.pattern[0]] * cfg.first_k_dense
                        + list(cfg.pattern) * cfg.num_groups)
        blocks = run = 0
        for kind, layers in kinds.items():
            if kind in (ATTN, SWA, MLA):
                window = cfg.window_size if kind == SWA else 0
                b, r = attn.sdpa_blocks(pos, pos, window=window)
                blocks, run = blocks + layers * b, run + layers * r
        return blocks, run

    def _layer_prefill(self, lp, kind, ffn_kind, h, enc_out, max_seq):
        cfg = self.cfg
        mix_in = rmsnorm(lp["pre_norm"], h, cfg.norm_eps)
        if kind in (ATTN, SWA):
            window = cfg.window_size if kind == SWA else 0
            expand, sf = self._attn_tp()
            out, core = attn.attention_prefill(lp["mixer"], cfg, mix_in,
                                               window=window, max_seq=max_seq,
                                               expand_kv=expand, shard_fn=sf)
        elif kind == MLA:
            out, core = attn.mla_prefill(lp["mixer"], cfg, mix_in,
                                         max_seq=max_seq)
        elif kind == MAMBA:
            out, (h_last, conv_tail) = ssm_lib.mamba_mix(lp["mixer"], cfg,
                                                         mix_in)
            core = {"h": h_last, "conv": conv_tail}
        elif kind == MLSTM:
            out, (st, conv_tail) = xlstm_lib.mlstm_mix(lp["mixer"], cfg,
                                                       mix_in)
            core = {"C": st[0], "n": st[1], "m": st[2], "conv": conv_tail}
        elif kind == SLSTM:
            out, (st, conv_tail) = xlstm_lib.slstm_mix(lp["mixer"], cfg,
                                                       mix_in)
            core = {"c": st[0], "n": st[1], "m": st[2], "h": st[3],
                    "conv": conv_tail}
        else:
            raise ValueError(kind)
        h = self._act(h + out)
        if enc_out is not None and "cross" in lp:
            kv = attn.encode_cross_kv(lp["cross"], cfg, enc_out)
            c_in = rmsnorm(lp["cross_norm"], h, cfg.norm_eps)
            h = self._act(h + attn.cross_attention_forward(lp["cross"], cfg,
                                                           c_in, kv))
            # pad/crop encoder KV to max_seq for a fixed-size cache
            t = kv["k"].shape[1]
            if t < max_seq:
                padw = ((0, 0), (0, max_seq - t), (0, 0), (0, 0))
                kv = {k: jnp.pad(v, padw) for k, v in kv.items()}
            core = dict(core, cross_k=kv["k"][:, :max_seq],
                        cross_v=kv["v"][:, :max_seq])
        if "ffn" in lp and ffn_kind != NONE:
            y, counts = self._ffn(lp, ffn_kind, h, dropless=True)
            h = self._act(h + y)
            if ffn_kind == MOE:
                core = dict(core, **_slots_cache(counts))
        return h, core

    # -------------------------------------------------------------- specs

    def input_specs(self, shape: ShapeConfig) -> Dict[str, jax.ShapeDtypeStruct]:
        """ShapeDtypeStruct stand-ins for every model input of this cell."""
        cfg = self.cfg
        b, s = shape.global_batch, shape.seq_len
        i32 = jnp.int32
        if shape.kind in ("train", "prefill"):
            if cfg.input_mode == "frames":
                return {"frames": jax.ShapeDtypeStruct(
                            (b, s, cfg.frame_dim or cfg.d_model),
                            jnp.dtype(cfg.param_dtype)),
                        "tokens": jax.ShapeDtypeStruct((b, s), i32)}
            if cfg.input_mode == "tokens+image":
                p = cfg.num_image_tokens
                return {"image_embeds": jax.ShapeDtypeStruct(
                            (b, p, cfg.d_model), jnp.dtype(cfg.param_dtype)),
                        "tokens": jax.ShapeDtypeStruct((b, s - p), i32)}
            return {"tokens": jax.ShapeDtypeStruct((b, s), i32)}
        # decode: one new token against a cache of length seq_len
        return {"tokens": jax.ShapeDtypeStruct((b, 1), i32)}


#: an expert layer's routing counts in the decode cache, one each per
#: layer: its prefill's token x top-k slots and those that chose an
#: expert held here, and the same summed over the decode steps since
SLOTS = ("prefill_routed_slots", "prefill_held_slots", "routed_slots",
         "held_slots")


def _slots_cache(prefill_counts) -> Params:
    """An expert layer's cache counters, from its prefill's counts (zero
    where none are given): the decode steps' start at zero."""
    zero = jnp.zeros((), jnp.int32)
    return {"prefill_routed_slots": prefill_counts.get("routed_slots", zero),
            "prefill_held_slots": prefill_counts.get("held_slots", zero),
            "routed_slots": zero, "held_slots": zero}


def routing_counts(cache) -> Dict[str, int]:
    """The routing counts a decode cache holds (`SLOTS`), summed over its
    expert layers, read from the device at once; empty for a model
    without expert layers."""
    per = jax.device_get([{k: c[k] for k in SLOTS}
                          for c in cache.get("groups", ()) if SLOTS[0] in c])
    return {k: int(sum(c[k].sum() for c in per)) for k in SLOTS} if per \
        else {}


def build_model(cfg: ModelConfig, rules=None) -> Model:
    return Model(cfg, rules)
