"""The dense decoder: one attention and one gated MLP in every layer.

The architecture of every configuration file without an ``arch`` key. It
reads the published ``config.json`` keys, names the program's
``ModelConfig``, lays out the weights, gives the plain reference's layer
and head, and counts the work a serving step needs. The attention and MLP
parts (their leaves, equations and counts) are for other architectures
that have them to import.

The reference's equations, in float32 at ``highest`` matmul precision:

    h = E[x]
    per layer:  a = rms(h) * (1 + g_attn)
                q, k, v = a Wq, a Wk, a Wv;  rotary (half split) on q, k
                h += softmax(q k^T / sqrt(head_dim), causal) v  Wo
                m = rms(h) * (1 + g_mlp)
                h += (silu(m Wgate) * m Wup) Wdown
    logits = (rms(h) * (1 + g_final)) (E^T if tied else Whead)
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from reference import f32, mm, rms, rope
from weights import EMBED_STD, NORM_STD, nest
from work import itemsize


@dataclass(frozen=True)
class Spec:
    name: str
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ffn: int
    vocab: int
    rope_theta: float
    eps: float
    tied: bool
    act: str
    dtype: str

    @property
    def q_dim(self) -> int:
        return self.heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.kv_heads * self.head_dim


def spec_of(conf: Dict[str, Any]) -> Spec:
    heads = conf["num_attention_heads"]
    return Spec(
        name=conf["name"],
        layers=conf["num_hidden_layers"],
        d=conf["hidden_size"],
        heads=heads,
        kv_heads=conf.get("num_key_value_heads", heads),
        head_dim=conf.get("head_dim") or conf["hidden_size"] // heads,
        ffn=conf["intermediate_size"],
        vocab=conf["vocab_size"],
        rope_theta=float(conf.get("rope_theta", 10000.0)),
        eps=float(conf["rms_norm_eps"]),
        tied=bool(conf["tie_word_embeddings"]),
        act=conf["hidden_act"],
        dtype=conf["torch_dtype"],
    )


def model_config(spec: Spec):
    """The program's ModelConfig for this spec (imports the program)."""
    from repro.configs.base import ModelConfig

    return ModelConfig(
        name=spec.name, family="dense", num_layers=spec.layers, d_model=spec.d,
        num_heads=spec.heads, num_kv_heads=spec.kv_heads, head_dim=spec.head_dim,
        d_ff=spec.ffn, vocab_size=spec.vocab, rope_theta=spec.rope_theta,
        norm_eps=spec.eps, tie_embeddings=spec.tied, act=spec.act, dtype=spec.dtype,
    )


# ------------------------------------------------------------------ weights
def attention_shapes(s) -> Dict[str, Tuple[Tuple[int, ...], float]]:
    return {
        "attn/wq": ((s.d, s.q_dim), 1 / math.sqrt(s.d)),
        "attn/wk": ((s.d, s.kv_dim), 1 / math.sqrt(s.d)),
        "attn/wv": ((s.d, s.kv_dim), 1 / math.sqrt(s.d)),
        "attn/wo": ((s.q_dim, s.d), 1 / math.sqrt(s.q_dim)),
    }


def mlp_shapes(s) -> Dict[str, Tuple[Tuple[int, ...], float]]:
    return {
        "mlp/w_gate": ((s.d, s.ffn), 1 / math.sqrt(s.d)),
        "mlp/w_up": ((s.d, s.ffn), 1 / math.sqrt(s.d)),
        "mlp/w_down": ((s.ffn, s.d), 1 / math.sqrt(s.ffn)),
    }


def layer_shapes(s: Spec, layer: int) -> Dict[str, Tuple[Tuple[int, ...], float]]:
    """Layer `layer`'s leaf -> (shape, std), as "group/name" paths; the
    order is each leaf's key index."""
    return {
        **attention_shapes(s),
        **mlp_shapes(s),
        "pre_attn_norm": ((s.d,), NORM_STD),
        "pre_mlp_norm": ((s.d,), NORM_STD),
    }


def top_shapes(s) -> Dict[str, Tuple[Tuple[int, ...], float]]:
    out = {"embed": ((s.vocab, s.d), EMBED_STD), "final_norm": ((s.d,), NORM_STD)}
    if not s.tied:
        out["lm_head"] = ((s.d, s.vocab), 1 / math.sqrt(s.d))
    return out


def param_tree(s, layers, top: Dict[str, jax.Array]) -> Dict:
    """The program's tree: every layer alike, stacked under ``layers``."""
    ((_, flat),) = layers
    top["layers"] = nest(flat)
    return top


# ---------------------------------------------------------------- reference
def attention(s, a, w: Dict[str, jax.Array], quant: Optional[str]):
    """Causal softmax attention of the normed input `a` (b, n, d), through Wo."""
    b, n, _ = a.shape
    pos = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), (b, n))
    q = rope(mm(a, w["attn/wq"], quant).reshape(b, n, s.heads, s.head_dim), pos, s.rope_theta)
    k = rope(mm(a, w["attn/wk"], quant).reshape(b, n, s.kv_heads, s.head_dim), pos, s.rope_theta)
    v = mm(a, w["attn/wv"], quant).reshape(b, n, s.kv_heads, s.head_dim)
    g = s.heads // s.kv_heads
    k = jnp.repeat(k, g, axis=2)
    v = jnp.repeat(v, g, axis=2)
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision="highest") / math.sqrt(s.head_dim)
    causal = jnp.tril(jnp.ones((n, n), bool))
    sc = jnp.where(causal[None, None], sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v, precision="highest").reshape(b, n, s.q_dim)
    return mm(o, w["attn/wo"], quant)


def mlp(s, m, w: Dict[str, jax.Array], quant: Optional[str]):
    act = jax.nn.silu if s.act == "silu" else partial(jax.nn.gelu, approximate=True)
    f = act(mm(m, w["mlp/w_gate"], quant)) * mm(m, w["mlp/w_up"], quant)
    return mm(f, w["mlp/w_down"], quant)


@partial(jax.jit, static_argnums=(0, 3))
def _layer(s: Spec, h, w: Dict[str, jax.Array], quant: Optional[str]):
    w = f32(w)
    h = h + attention(s, rms(h, w["pre_attn_norm"], s.eps), w, quant)
    return h + mlp(s, rms(h, w["pre_mlp_norm"], s.eps), w, quant)


def reference_layer(s: Spec, layer: int, h, w: Dict[str, jax.Array], quant: Optional[str]):
    """Layer `layer` over the hidden rows `h` (b, n, d), float32."""
    return _layer(s, h, w, quant)


def reference_embed(s, tokens, top: Dict[str, jax.Array]):
    return top["embed"][tokens].astype(jnp.float32)


@partial(jax.jit, static_argnums=(0, 4))
def reference_head(s, h, sel, top: Dict[str, jax.Array], quant: Optional[str]):
    """Logits at the selected (row, position) pairs: (n_sel, vocab)."""
    top = f32(top)
    x = h[sel[:, 0], sel[:, 1]]
    x = rms(x, top["final_norm"], s.eps)
    w = top["embed"].T if s.tied else top["lm_head"]
    return mm(x, w, quant)


# --------------------------------------------------------------------- work
def attention_params(s) -> int:
    return 2 * s.d * s.q_dim + 2 * s.d * s.kv_dim


def layer_params(s: Spec) -> int:
    """Projection weights of one layer (norm gains apart)."""
    return attention_params(s) + 3 * s.d * s.ffn


def weight_bytes(s: Spec) -> int:
    """Every weight one step reads: layers, norms and the head."""
    per_layer = layer_params(s) + 2 * s.d
    head = s.vocab * s.d
    return itemsize(s) * (s.layers * per_layer + head + s.d)


def kv_bytes_per_token(s) -> int:
    """K and V of one token in every attention layer."""
    return 2 * s.layers * s.kv_dim * itemsize(s)


def attn_flops(s, positions: Sequence[int]) -> int:
    """Scores and values of queries at `positions` over [0, position]."""
    return 4 * s.layers * s.heads * s.head_dim * sum(p + 1 for p in positions)


def chunk_work(s: Spec, start: int, valid: int) -> Tuple[int, int]:
    """(FLOPs, bytes) of one chunk-prefill call: `valid` tokens after
    `start` already in the cache; logits for the last token only."""
    flops = 2 * valid * s.layers * layer_params(s) + 2 * s.d * s.vocab
    flops += attn_flops(s, range(start, start + valid))
    kv = kv_bytes_per_token(s)
    byts = weight_bytes(s) + start * kv + valid * kv
    byts += valid * 4 + valid * s.d * itemsize(s) + s.vocab * 4
    return flops, byts


def decode_work(s: Spec, positions: Sequence[int]) -> Tuple[int, int]:
    """(FLOPs, bytes) of one decode step over the real lanes, each writing
    the token at `positions[i]` and attending to [0, positions[i]]."""
    n = len(positions)
    flops = 2 * n * (s.layers * layer_params(s) + s.d * s.vocab)
    flops += attn_flops(s, positions)
    kv = kv_bytes_per_token(s)
    byts = weight_bytes(s) + sum(positions) * kv + n * kv
    byts += n * 8 + n * s.d * itemsize(s) + n * s.vocab * 4
    return flops, byts
