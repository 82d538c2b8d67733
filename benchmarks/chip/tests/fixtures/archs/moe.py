"""A mixture-of-experts decoder, for the CPU rehearsal of an architecture
that exists only as files beside its cells: attention as the dense
architecture has it, then in every layer a router over ``experts`` gated
MLPs, of which each token takes the ``top_k`` most probable with their
probabilities renormalised to sum to one (the program's ``moe`` family).

The program drops a token that finds its expert full. With
``capacity_factor`` >= experts / top_k an expert's capacity is at least the
tokens of its group (``ModelConfig.moe_capacity``), so no token is dropped
and the program computes what the reference does:

    per layer:  h += attention(rms(h) * (1 + g_attn))        (dense's)
                m = rms(h) * (1 + g_mlp)
                p = softmax(m Wrouter);  w_j, e_j = top_k of p, w / sum(w)
                h += sum_j w_j (silu(m Wgate[e_j]) * m Wup[e_j]) Wdown[e_j]

The router is kept in float32, as the program keeps it.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

import modelspec
from reference import f32, mm, rms
from weights import NORM_STD
from work import itemsize

dense = modelspec.arch("dense")


@dataclasses.dataclass(frozen=True)
class Spec:
    name: str
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ffn: int  # one expert's
    vocab: int
    rope_theta: float
    eps: float
    tied: bool
    act: str
    dtype: str
    experts: int
    top_k: int
    capacity_factor: float

    @property
    def q_dim(self) -> int:
        return self.heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.kv_heads * self.head_dim


def spec_of(conf: Dict[str, Any]) -> Spec:
    return Spec(**dataclasses.asdict(dense.spec_of(conf)), experts=conf["num_local_experts"],
                top_k=conf["num_experts_per_tok"], capacity_factor=float(conf["capacity_factor"]))


def model_config(s: Spec):
    return dense.model_config(s).replace(
        family="moe", num_experts=s.experts, experts_per_token=s.top_k,
        capacity_factor=s.capacity_factor,
    )


def layer_shapes(s: Spec, layer: int) -> Dict[str, tuple]:
    d, e, f = s.d, s.experts, s.ffn
    return {
        **dense.attention_shapes(s),
        "moe/router": ((d, e), 1 / math.sqrt(d), "float32"),
        "moe/w_gate": ((e, d, f), 1 / math.sqrt(d)),
        "moe/w_up": ((e, d, f), 1 / math.sqrt(d)),
        "moe/w_down": ((e, f, d), 1 / math.sqrt(f)),
        "pre_attn_norm": ((d,), NORM_STD),
        "pre_mlp_norm": ((d,), NORM_STD),
    }


top_shapes = dense.top_shapes
param_tree = dense.param_tree
reference_embed = dense.reference_embed
reference_head = dense.reference_head


def _experts(s: Spec, m, w: Dict[str, jax.Array], quant: Optional[str]):
    p = jax.nn.softmax(mm(m, w["moe/router"], quant), axis=-1)
    top, idx = jax.lax.top_k(p, s.top_k)
    top = top / jnp.sum(top, axis=-1, keepdims=True)
    gate = jnp.sum(jax.nn.one_hot(idx, s.experts) * top[..., None], axis=-2)
    act = jax.nn.silu if s.act == "silu" else partial(jax.nn.gelu, approximate=True)
    y = jnp.zeros_like(m)
    for e in range(s.experts):
        f = act(mm(m, w["moe/w_gate"][e], quant)) * mm(m, w["moe/w_up"][e], quant)
        y = y + gate[..., e : e + 1] * mm(f, w["moe/w_down"][e], quant)
    return y


@partial(jax.jit, static_argnums=(0, 3))
def _layer(s: Spec, h, w: Dict[str, jax.Array], quant: Optional[str]):
    w = f32(w)
    h = h + dense.attention(s, rms(h, w["pre_attn_norm"], s.eps), w, quant)
    return h + _experts(s, rms(h, w["pre_mlp_norm"], s.eps), w, quant)


def reference_layer(s: Spec, layer: int, h, w: Dict[str, jax.Array], quant: Optional[str]):
    return _layer(s, h, w, quant)


def _active_params(s: Spec) -> int:
    """Projection weights one token passes through in one layer."""
    return dense.attention_params(s) + s.d * s.experts + s.top_k * 3 * s.d * s.ffn


def _weight_bytes(s: Spec, tokens: int) -> int:
    """Weights a step over `tokens` tokens reads: attention, router and norms
    of every layer, as many experts as its tokens can reach, and the head."""
    reach = min(s.experts, tokens * s.top_k)
    per_layer = (dense.attention_params(s) + reach * 3 * s.d * s.ffn + 2 * s.d) * itemsize(s)
    per_layer += s.d * s.experts * 4
    return s.layers * per_layer + (s.vocab * s.d + s.d) * itemsize(s)


def chunk_work(s: Spec, start: int, valid: int) -> Tuple[int, int]:
    flops = 2 * valid * s.layers * _active_params(s) + 2 * s.d * s.vocab
    flops += dense.attn_flops(s, range(start, start + valid))
    byts = _weight_bytes(s, valid) + (start + valid) * dense.kv_bytes_per_token(s)
    byts += valid * 4 + valid * s.d * itemsize(s) + s.vocab * 4
    return flops, byts


def decode_work(s: Spec, positions: Sequence[int]) -> Tuple[int, int]:
    n = len(positions)
    flops = 2 * n * (s.layers * _active_params(s) + s.d * s.vocab)
    flops += dense.attn_flops(s, positions)
    byts = _weight_bytes(s, n) + (sum(positions) + n) * dense.kv_bytes_per_token(s)
    byts += n * 8 + n * s.d * itemsize(s) + n * s.vocab * 4
    return flops, byts
