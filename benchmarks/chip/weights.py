"""Seeded weights, made on the device by the benchmark.

The served weights come from one jitted call (`make_params`) in the
configuration's dtype, laid out as the program's parameter tree. The plain
reference regenerates any one layer alone (`layer_params`) with the same
values, so it needs nothing the program holds. Every leaf is uniform with
the spread the program's own initialiser gives it (standard deviation
1/sqrt(fan_in) for projections, 0.02 for the embedding); norm gains are
small and non-zero so that a path that drops them shows. Values are drawn
per (leaf, layer) from keys folded from the seed, with integer key
arithmetic and one scale, so a layer drawn alone equals its slice of the
stacked draw bit for bit.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from modelspec import Spec

NORM_STD = 0.05
EMBED_STD = 0.02

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32, "float16": jnp.float16}


def layer_shapes(s: Spec) -> Dict[str, Tuple[Tuple[int, ...], float]]:
    """Per-layer leaf -> (shape, std), as "group/name" paths."""
    return {
        "attn/wq": ((s.d, s.q_dim), 1 / math.sqrt(s.d)),
        "attn/wk": ((s.d, s.kv_dim), 1 / math.sqrt(s.d)),
        "attn/wv": ((s.d, s.kv_dim), 1 / math.sqrt(s.d)),
        "attn/wo": ((s.q_dim, s.d), 1 / math.sqrt(s.q_dim)),
        "mlp/w_gate": ((s.d, s.ffn), 1 / math.sqrt(s.d)),
        "mlp/w_up": ((s.d, s.ffn), 1 / math.sqrt(s.d)),
        "mlp/w_down": ((s.ffn, s.d), 1 / math.sqrt(s.ffn)),
        "pre_attn_norm": ((s.d,), NORM_STD),
        "pre_mlp_norm": ((s.d,), NORM_STD),
    }


def top_shapes(s: Spec) -> Dict[str, Tuple[Tuple[int, ...], float]]:
    out = {"embed": ((s.vocab, s.d), EMBED_STD), "final_norm": ((s.d,), NORM_STD)}
    if not s.tied:
        out["lm_head"] = ((s.d, s.vocab), 1 / math.sqrt(s.d))
    return out


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


def _nest(flat: Dict[str, jax.Array]) -> Dict:
    out: Dict = {}
    for path, x in flat.items():
        *groups, name = path.split("/")
        node = out
        for g in groups:
            node = node.setdefault(g, {})
        node[name] = x
    return out


def _layer(s: Spec, key, layer, dtype) -> Dict[str, jax.Array]:
    return {
        path: _draw(key, i, layer, shape, std, dtype)
        for i, (path, (shape, std)) in enumerate(layer_shapes(s).items())
    }


def _top(s: Spec, key, dtype) -> Dict[str, jax.Array]:
    base = len(layer_shapes(s))
    return {
        path: _draw(key, base + i, 0, shape, std, dtype)
        for i, (path, (shape, std)) in enumerate(top_shapes(s).items())
    }


@partial(jax.jit, static_argnums=(0,))
def _params(s: Spec, key) -> Dict:
    dt = DTYPES[s.dtype]
    layers = jax.vmap(lambda l: _layer(s, key, l, dt))(jnp.arange(s.layers, dtype=jnp.uint32))
    top = _top(s, key, dt)
    top["layers"] = _nest(layers)
    return top


def make_params(s: Spec, seed: int, device=None) -> Dict:
    """The served parameter tree, on `device`, in the configuration's dtype."""
    key = seed_key(seed)
    if device is not None:
        key = jax.device_put(key, device)
    return _params(s, key)


@partial(jax.jit, static_argnums=(0,))
def _layer_one(s: Spec, key, layer) -> Dict[str, jax.Array]:
    return _layer(s, key, layer, DTYPES[s.dtype])


@partial(jax.jit, static_argnums=(0,))
def _top_one(s: Spec, key) -> Dict[str, jax.Array]:
    return _top(s, key, DTYPES[s.dtype])


# Both return the configuration's dtype, as served: a conversion to float32
# inside the same program could be folded away with the rounding before it.
def layer_params(s: Spec, seed: int, layer: int) -> Dict[str, jax.Array]:
    """Layer `layer`'s leaves, flat ("attn/wq", ...), as served."""
    return _layer_one(s, seed_key(seed), jnp.uint32(layer))


def top_params(s: Spec, seed: int) -> Dict[str, jax.Array]:
    """Embedding, final norm and (untied) head, as served."""
    return _top_one(s, seed_key(seed))
