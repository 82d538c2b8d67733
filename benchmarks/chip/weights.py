"""Seeded weights, made on the device by the benchmark.

The served weights come from one jitted call (`make_params`) in the
configuration's dtype, laid out as the program's parameter tree by the
configuration's architecture module (``arch.param_tree``). The plain
reference regenerates any one layer alone (`layer_params`) with the same
values, so it needs nothing the program holds.

The architecture names each leaf with its shape and spread
(``arch.layer_shapes`` / ``arch.top_shapes``: path -> (shape, std), or
(shape, std, dtype) for a leaf the program keeps in another dtype than the
configuration's). Every leaf is uniform with that standard deviation (the
program's own initialiser gives projections 1/sqrt(fan_in) and the
embedding 0.02); norm gains are small and non-zero so that a path that
drops them shows. Values are drawn per (leaf, layer) from keys folded from
the seed, the leaf's index being its place in ``layer_shapes`` (the top
leaves follow the longest layer's), with integer key arithmetic and one
scale, so a layer drawn alone equals its slice of the stacked draw bit for
bit. Layers with the same leaves are drawn stacked, in one vmap.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

NORM_STD = 0.05
EMBED_STD = 0.02

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32, "float16": jnp.float16}

# one layer's leaves as a hashable tuple: ((path, (shape, std[, dtype])), ...)
Leaves = Tuple[Tuple[str, tuple], ...]


def seed_key(seed: int) -> jax.Array:
    """A key from any non-negative seed below 2**64 (folded in 32-bit halves)."""
    if not 0 <= seed < 1 << 64:
        raise ValueError(f"seed {seed} outside [0, 2**64)")
    k = jax.random.key(0)
    k = jax.random.fold_in(k, jnp.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(k, jnp.uint32(seed >> 32))


def _draw(key, idx, layer, shape, std, dtype):
    k = jax.random.fold_in(jax.random.fold_in(key, idx), layer)
    a = std * math.sqrt(3.0)
    return (jax.random.uniform(k, shape, jnp.float32, -1.0, 1.0) * a).astype(dtype)


def nest(flat: Dict[str, jax.Array]) -> Dict:
    """"group/name" paths -> nested dicts."""
    out: Dict = {}
    for path, x in flat.items():
        *groups, name = path.split("/")
        node = out
        for g in groups:
            node = node.setdefault(g, {})
        node[name] = x
    return out


def _leaves(leaves: Leaves, key, layer, dtype, base: int = 0) -> Dict[str, jax.Array]:
    return {
        path: _draw(key, base + i, layer, shape, std, DTYPES[own[0]] if own else dtype)
        for i, (path, (shape, std, *own)) in enumerate(leaves)
    }


def layer_leaves(arch, s, layer: int) -> Leaves:
    return tuple(arch.layer_shapes(s, layer).items())


def groups(arch, s) -> List[Tuple[Leaves, Tuple[int, ...]]]:
    """The layers grouped by their leaves, in order of each group's first
    layer: (leaves, layer indices)."""
    out: Dict[Leaves, List[int]] = {}
    for layer in range(s.layers):
        out.setdefault(layer_leaves(arch, s, layer), []).append(layer)
    return [(k, tuple(v)) for k, v in out.items()]


def _top(arch, s, key, dtype) -> Dict[str, jax.Array]:
    base = max(len(leaves) for leaves, _ in groups(arch, s))
    return _leaves(tuple(arch.top_shapes(s).items()), key, 0, dtype, base)


@partial(jax.jit, static_argnums=(0, 1))
def _params(arch, s, key) -> Dict:
    dt = DTYPES[s.dtype]
    layers = [
        (idx, jax.vmap(lambda l, leaves=leaves: _leaves(leaves, key, l, dt))(
            jnp.asarray(idx, jnp.uint32)))
        for leaves, idx in groups(arch, s)
    ]
    return arch.param_tree(s, layers, _top(arch, s, key, dt))


def make_params(arch, s, seed: int, device=None) -> Dict:
    """The served parameter tree, on `device`, in the configuration's dtype.
    ``arch.param_tree(s, layers, top)`` arranges it: `layers` lists, per
    group of layers with the same leaves, (layer indices, flat leaves
    stacked over those layers)."""
    key = seed_key(seed)
    if device is not None:
        key = jax.device_put(key, device)
    return _params(arch, s, key)


@partial(jax.jit, static_argnums=(0, 1))
def _layer_one(s, leaves: Leaves, key, layer) -> Dict[str, jax.Array]:
    return _leaves(leaves, key, layer, DTYPES[s.dtype])


@partial(jax.jit, static_argnums=(0, 1))
def _top_one(arch, s, key) -> Dict[str, jax.Array]:
    return _top(arch, s, key, DTYPES[s.dtype])


# Both return the leaves' dtypes, as served: a conversion to float32 inside
# the same program could be folded away with the rounding before it.
def layer_params(arch, s, seed: int, layer: int) -> Dict[str, Any]:
    """Layer `layer`'s leaves, flat ("attn/wq", ...), as served."""
    return _layer_one(s, layer_leaves(arch, s, layer), seed_key(seed), jnp.uint32(layer))


def top_params(arch, s, seed: int) -> Dict[str, jax.Array]:
    """The leaves outside the layers (embedding, final norm, head), as served."""
    return _top_one(arch, s, seed_key(seed))
