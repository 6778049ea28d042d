"""Weights from a seed, by name, the same for the program and the reference.

A family module states its weight layout: `layout(model) -> {path: Leaf}`,
with `path` the leaf's place in the program's parameter tree
(`groups/0/mixer/w_q`). Each leaf is drawn from its own key, folded from
the seed and the path, so the program's tree (made on the device in one
jitted call, in the type it is served in) and the reference's float32 copy
(the same values, rounded to that type first) agree leaf for leaf without
either seeing the other's arrays.
"""
from __future__ import annotations

import zlib
from dataclasses import dataclass
from typing import Dict, List, Sequence, Tuple, Union

import jax
import jax.numpy as jnp

Mean = Union[float, Sequence[Tuple[int, float]]]


@dataclass(frozen=True)
class Leaf:
    shape: Tuple[int, ...]
    dtype: str
    std: float                 # normal noise around the mean
    mean: Mean = 0.0           # a number, or [(count, value), ...] segments
                               # along the last axis


def base_key(seed: int) -> jax.Array:
    """A key from any whole number up to 64 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def _draw(key, path: str, leaf: Leaf) -> jax.Array:
    k = jax.random.fold_in(key, zlib.crc32(path.encode()))
    x = jax.random.normal(k, leaf.shape, jnp.float32) * leaf.std
    if isinstance(leaf.mean, (int, float)):
        x = x + leaf.mean
    else:
        seg = jnp.concatenate([jnp.full((n,), v, jnp.float32)
                               for n, v in leaf.mean])
        x = x + seg
    return x


def path_str(path) -> str:
    parts: List[str] = []
    for p in path:
        parts.append(str(getattr(p, "key", getattr(p, "idx", p))))
    return "/".join(parts)


def check_layout(layout: Dict[str, Leaf], shapes) -> None:
    """The layout must name every leaf of the program's tree, with its
    shape and type, and nothing else."""
    found = {path_str(p): (tuple(s.shape), str(s.dtype))
             for p, s in jax.tree_util.tree_flatten_with_path(shapes)[0]}
    want = {p: (tuple(l.shape), l.dtype) for p, l in layout.items()}
    if found != want:
        diff = sorted(set(found.items()) ^ set(want.items()))
        raise ValueError(f"weight layout does not match the program's "
                         f"parameters: {diff[:8]}")


def program_weights(seed: int, layout: Dict[str, Leaf], shapes):
    """The program's parameter tree (structure of `shapes`), on the
    device, each leaf in its own type: one jitted call."""
    check_layout(layout, shapes)

    def make(key):
        return jax.tree_util.tree_map_with_path(
            lambda p, s: _draw(key, path_str(p), layout[path_str(p)]
                               ).astype(s.dtype), shapes)
    return jax.jit(make)(base_key(seed))


def reference_weights(seed: int, layout: Dict[str, Leaf],
                      names: Sequence[str] = ()) -> Dict[str, jax.Array]:
    """{path: float32 array}: the values the program holds, rounded to
    their stored type and widened again. `names` limits the set."""
    names = list(names) or sorted(layout)

    def make(key):
        return {p: _draw(key, p, layout[p]).astype(layout[p].dtype)
                .astype(jnp.float32) for p in names}
    return jax.jit(make)(base_key(seed))
