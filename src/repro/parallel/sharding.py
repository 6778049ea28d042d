"""Sharding rules: logical-axis mapping from parameter/activation/cache
pytrees to PartitionSpecs over the (pod, data, model) production mesh.

Strategy (DESIGN.md §5):
  * DP/FSDP — batch over (pod, data); every 2-D weight shards its non-TP
    dimension over `data` (ZeRO-3), Adam state mirrors parameters.
  * TP — Megatron column/row parallel over `model`; vocab-parallel
    embedding/LM head.
  * EP — MoE expert dimension over `model` when divisible, else expert-
    internal TP.
  * SP — long-context decode (batch=1) shards cache sequence over `data`.
  * Multi-pod — parameters replicated across pods (gradient all-reduce over
    the DCN `pod` axis); batch sharded over pod×data.

Rules are name+shape driven with a divisibility filter: any mesh axis that
does not divide its dimension is dropped (never an invalid spec).
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig


def _axis_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    size = 1
    for a in axes:
        size *= mesh.shape[a]
    return size


def _flat_axes(axes):
    if axes is None:
        return ()
    if isinstance(axes, str):
        return (axes,)
    out = []
    for a in axes:
        out.extend(_flat_axes(a))
    return tuple(out)


def _fit(mesh: Mesh, spec_axes, shape) -> P:
    """Drop axes that don't divide their dim; returns a valid PartitionSpec."""
    fixed = []
    for dim, axes in zip(shape, spec_axes):
        if axes is None:
            fixed.append(None)
            continue
        tup = _flat_axes(axes)
        keep = []
        rem = dim
        for a in tup:
            n = mesh.shape[a]
            if rem % n == 0:
                keep.append(a)
                rem //= n
        fixed.append(tuple(keep) if len(keep) > 1 else (keep[0] if keep else None))
    return P(*fixed)


# parameter names that are row-parallel (input dim on `model`)
_ROW_2D = {"w_o", "down", "w_down", "out_proj", "dt_proj"}
# names that live on the inner (d_inner/model-sharded) dimension
_DI_VECTORS = {"D", "dt_bias", "conv_b"}
_REPLICATED = {"scale", "b", "b_if", "router", "r_rec"}


@dataclass
class ShardingRules:
    mesh: Mesh
    cfg: ModelConfig
    shape: ShapeConfig

    def __post_init__(self):
        names = self.mesh.axis_names
        self.batch_axes: Tuple[str, ...] = tuple(
            a for a in ("pod", "data") if a in names)
        self.tp = "model"
        # ZeRO-3 param sharding; optionally across pods too (DCN gathers,
        # the memory-vs-bandwidth tradeoff for the 100B+ archs)
        if self.cfg.fsdp_over_pod and "pod" in names:
            self.fsdp: Any = ("pod", "data")
        else:
            self.fsdp = "data"
        # long-context decode with batch=1: shard sequence instead of batch
        self.seq_shard = (self.shape.kind == "decode"
                          and self.shape.global_batch == 1)

    # ----------------------------------------------------------- parameters

    def _param_spec(self, path: str, shape: Tuple[int, ...]) -> P:
        mesh, tp, fsdp = self.mesh, self.tp, self.fsdp
        stacked = bool(re.search(r"(groups|encoder/layers)", path))
        base_shape = shape[1:] if stacked else shape
        name = path.rsplit("/", 1)[-1]

        def out(*axes):
            spec = _fit(mesh, axes, base_shape)
            return P(None, *spec) if stacked else spec

        nd = len(base_shape)
        if name in _REPLICATED or nd == 0:
            return out(*([None] * nd))
        if name in _DI_VECTORS and nd == 1:
            return out(tp)
        if name == "A_log":
            return out(tp, None)
        if name == "conv_w":
            return out(None, tp)
        if name == "table":                      # (vocab, d)
            return out(tp, fsdp)
        if name == "lm_head":
            return out(fsdp, tp)
        if name in ("w_uk", "w_uv"):             # (H, e, r) MLA per-head
            return out(tp, None, None)
        if nd == 3 and name in ("w_gate", "w_up", "w_down"):
            e = base_shape[0]
            if e % mesh.shape[tp] == 0:          # expert parallel
                if name == "w_down":
                    return out(tp, None, fsdp)
                return out(tp, fsdp, None)
            # expert-internal TP fallback
            if name == "w_down":
                return out(None, tp, fsdp)
            return out(None, fsdp, tp)
        if nd == 2:
            if name in _ROW_2D:
                return out(tp, fsdp)
            return out(fsdp, tp)                 # column-parallel default
        if nd == 1:
            return out(None)
        return out(*([None] * nd))

    def param_shardings(self, params_shapes) -> Any:
        def one(path, leaf):
            pstr = "/".join(_key_str(k) for k in path)
            return NamedSharding(self.mesh, self._param_spec(pstr, leaf.shape))
        return jax.tree_util.tree_map_with_path(one, params_shapes)

    def opt_shardings(self, opt_shapes) -> Any:
        return self.param_shardings(opt_shapes)

    # ---------------------------------------------------------- activations

    @property
    def tp_size(self) -> int:
        return self.mesh.shape[self.tp]

    def constrain_act(self, x, name: str = "btd"):
        if name == "bshd":   # (B, S, H, hd) attention heads over `model`
            spec = _fit(self.mesh,
                        ((None if self.seq_shard else self.batch_axes),
                         None, self.tp, None), x.shape)
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(self.mesh, spec))
        spec = self._act_spec(name, x.shape)
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, spec))

    def _act_spec(self, name: str, shape) -> P:
        bat = self.batch_axes
        decode = self.shape.kind == "decode"
        if name == "logits":
            if self.seq_shard:
                return _fit(self.mesh, (None, self.fsdp, self.tp), shape)
            if decode:
                return _fit(self.mesh, (bat, None, self.tp), shape)
            # train/prefill: loss is per-token -> sequence-parallel logits
            return _fit(self.mesh, (bat, self.tp, None), shape)
        # (B, S, d) hidden states
        if self.seq_shard:
            return _fit(self.mesh, (None, "data", None), shape)
        if decode or not self.cfg.sequence_parallel:
            return _fit(self.mesh, (bat, None, None), shape)
        # Megatron-SP: residual stream (and the remat residual stack that
        # the scan saves) shards its sequence dim over `model`
        return _fit(self.mesh, (bat, self.tp, None), shape)

    def constrain_moe(self, name: str, x):
        mesh, tp, bat = self.mesh, self.tp, self.batch_axes
        if name == "moe_dispatch":               # (G, N, E, C)
            g, n, e, c = x.shape
            if g % _axis_size(mesh, bat) == 0:
                spec = _fit(mesh, (bat, None, tp, None), x.shape)
            else:                                # decode: one flat group
                spec = _fit(mesh, (None, bat, tp, None), x.shape)
        elif name == "moe_egcd":                 # (E, G, C, d)
            e, g, c, d = x.shape
            if g % _axis_size(mesh, bat) == 0:
                spec = _fit(mesh, (tp, bat, None, None), x.shape)
            else:
                spec = _fit(mesh, (tp, None, bat, None), x.shape)
        else:
            return x
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

    # --------------------------------------------------------------- inputs

    def input_shardings(self, specs: Dict[str, jax.ShapeDtypeStruct]):
        out = {}
        for k, v in specs.items():
            if k == "tokens" and self.shape.kind == "decode":
                axes = (bat_or_none(self.batch_axes, v.shape[0]), None)
            elif k == "tokens":
                axes = (self.batch_axes, None)
            elif k in ("frames", "image_embeds"):
                axes = (self.batch_axes, None, None)
            else:
                axes = tuple([None] * len(v.shape))
            out[k] = NamedSharding(self.mesh, _fit(self.mesh, axes, v.shape))
        return out

    # --------------------------------------------------------------- caches

    def _cache_spec(self, path: str, shape) -> P:
        mesh, tp = self.mesh, self.tp
        stacked = "groups" in path
        base = shape[1:] if stacked else shape
        name = path.rsplit("/", 1)[-1]
        bat = None if self.seq_shard else self.batch_axes
        seq = self.fsdp if self.seq_shard else None

        def out(*axes):
            spec = _fit(mesh, axes, base)
            return P(None, *spec) if stacked else spec

        if name == "slot_pos" or name.endswith("_slots"):   # + counters
            return out(*([None] * len(base)))
        if name in ("k", "v"):                         # (B, kv*hd, cap)
            if self.cfg.num_kv_heads % mesh.shape[tp] == 0:
                return out(bat, tp, seq)
            # kv heads don't divide TP: shard the positions over `model`
            cap_axes = ((self.fsdp, tp) if self.seq_shard else tp)
            return out(bat, None, cap_axes)
        if name in ("cross_k", "cross_v"):             # (B, T, kv, hd)
            kv = base[2]
            if kv % mesh.shape[tp] == 0:
                return out(bat, seq, tp, None)
            # kv heads don't divide TP: shard the sequence dim over `model`
            # instead (flash-decoding-style split-KV; see DESIGN.md §5)
            cap_axes = ((self.fsdp, tp) if self.seq_shard else tp)
            return out(bat, cap_axes, None, None)
        if name in ("c_kv", "k_rope"):                 # (B, cap, r)
            cap_axes = ((self.fsdp, tp) if self.seq_shard
                        else (tp if not seq else seq))
            return out(bat, cap_axes, None)
        if name == "h" and len(base) == 3:             # mamba (B, di, ds)
            return out(bat, tp, None)
        if name == "conv":                             # (B, K, di)
            return out(bat, None, tp)
        if name == "C":                                # mlstm (B,H,hd,hd)
            return out(bat, tp, None, None)
        if name in ("n", "m", "c"):
            return out(*([bat] + [None] * (len(base) - 1)))
        return out(*([bat] + [None] * (len(base) - 1)))

    def cache_shardings(self, cache_shapes) -> Any:
        def one(path, leaf):
            pstr = "/".join(_key_str(k) for k in path)
            return NamedSharding(self.mesh, self._cache_spec(pstr, leaf.shape))
        return jax.tree_util.tree_map_with_path(one, cache_shapes)

    def scalar_sharding(self):
        return NamedSharding(self.mesh, P())


def bat_or_none(bat, dim):
    return bat if dim > 1 else None


def _key_str(k) -> str:
    if hasattr(k, "key"):
        return str(k.key)
    if hasattr(k, "idx"):
        return str(k.idx)
    if hasattr(k, "name"):
        return str(k.name)
    return str(k)


def make_rules(mesh: Mesh, cfg: ModelConfig, shape: ShapeConfig) -> ShardingRules:
    return ShardingRules(mesh, cfg, shape)
