"""Plain float32 reference of the served model, run layer by layer.

It imports nothing of the program. It regenerates each layer's weights from
the seed (`weights.layer_params`), as served, upcasts them to float32, and runs the decoder
equations the configuration states, at ``highest`` matmul precision:

    h = E[x]
    per layer:  a = rms(h) * (1 + g_attn)
                q, k, v = a Wq, a Wk, a Wv;  rotary (half split) on q, k
                h += softmax(q k^T / sqrt(head_dim), causal) v  Wo
                m = rms(h) * (1 + g_mlp)
                h += (silu(m Wgate) * m Wup) Wdown
    logits = (rms(h) * (1 + g_final)) (E^T if tied else Whead)

over whole sequences (prompt + served tokens), teacher-forced, in blocks of
rows so that it fits beside nothing. `score` returns, at every position
whose next token was served, the reference's best logit less the logit of
the served token.

``quant="int8"`` is the control: every projection and the head computed
from int8 weights (per output column) and int8 activations (per token),
the rest as above. `control_tokens` gives, at the same positions, the token
the int8 model puts first; put in the served tokens' place, they go through
the same comparison as the program's.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from modelspec import Spec
from weights import layer_params, top_params

# tokens per block of rows: bounds the (rows, heads, S, S) score tensor
BLOCK_TOKENS = 4096
LEN_BUCKET = 256


def _q8(x: jax.Array, axis: int) -> jax.Array:
    """Symmetric int8 round trip with one scale per slice along `axis`."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _mm(x: jax.Array, w: jax.Array, quant: Optional[str]) -> jax.Array:
    if quant == "int8":
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.einsum("...d,de->...e", x, w, precision="highest")


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + g)


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[..., None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _f32(w: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    # the served values, already rounded to their dtype outside this program
    return {k: v.astype(jnp.float32) for k, v in w.items()}


@partial(jax.jit, static_argnums=(0, 3))
def _layer(s: Spec, h, w: Dict[str, jax.Array], quant: Optional[str]):
    w = _f32(w)
    b, n, _ = h.shape
    pos = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), (b, n))
    a = _rms(h, w["pre_attn_norm"], s.eps)
    q = _rope(_mm(a, w["attn/wq"], quant).reshape(b, n, s.heads, s.head_dim), pos, s.rope_theta)
    k = _rope(_mm(a, w["attn/wk"], quant).reshape(b, n, s.kv_heads, s.head_dim), pos, s.rope_theta)
    v = _mm(a, w["attn/wv"], quant).reshape(b, n, s.kv_heads, s.head_dim)
    g = s.heads // s.kv_heads
    k = jnp.repeat(k, g, axis=2)
    v = jnp.repeat(v, g, axis=2)
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision="highest") / math.sqrt(s.head_dim)
    causal = jnp.tril(jnp.ones((n, n), bool))
    sc = jnp.where(causal[None, None], sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v, precision="highest").reshape(b, n, s.q_dim)
    h = h + _mm(o, w["attn/wo"], quant)
    m = _rms(h, w["pre_mlp_norm"], s.eps)
    act = jax.nn.silu if s.act == "silu" else partial(jax.nn.gelu, approximate=True)
    f = act(_mm(m, w["mlp/w_gate"], quant)) * _mm(m, w["mlp/w_up"], quant)
    return h + _mm(f, w["mlp/w_down"], quant)


@partial(jax.jit, static_argnums=(0, 4))
def _head(s: Spec, h, sel, top: Dict[str, jax.Array], quant: Optional[str]):
    """Logits at the selected (row, position) pairs: (n_sel, vocab)."""
    top = _f32(top)
    x = h[sel[:, 0], sel[:, 1]]
    x = _rms(x, top["final_norm"], s.eps)
    w = top["embed"].T if s.tied else top["lm_head"]
    return _mm(x, w, quant)


def _blocks(lengths: Sequence[int]) -> List[Tuple[List[int], int]]:
    """Row indices grouped by padded length, at most BLOCK_TOKENS // pad
    rows to a block."""
    groups: Dict[int, List[int]] = {}
    for i in sorted(range(len(lengths)), key=lambda i: lengths[i]):
        groups.setdefault(-(-lengths[i] // LEN_BUCKET) * LEN_BUCKET, []).append(i)
    out: List[Tuple[List[int], int]] = []
    for pad, idx in groups.items():
        n = max(1, BLOCK_TOKENS // pad)
        out += [(idx[j : j + n], pad) for j in range(0, len(idx), n)]
    return out


def logits_at(s: Spec, seed: int, seqs: Sequence[Sequence[int]],
              positions: Sequence[Sequence[int]], quant: Optional[str] = None
              ) -> List[np.ndarray]:
    """Logits (len(positions[i]), vocab) for each sequence, in float32."""
    out: List[Optional[np.ndarray]] = [None] * len(seqs)
    top = top_params(s, seed)
    for rows, pad in _blocks([len(x) for x in seqs]):
        # a fixed number of rows per padded length: one compiled program
        # per length bucket, whichever requests a run samples
        toks = np.zeros((max(1, BLOCK_TOKENS // pad), pad), np.int32)
        sel = []
        for r, i in enumerate(rows):
            toks[r, : len(seqs[i])] = seqs[i]
            sel += [(r, p) for p in positions[i]]
        h = top["embed"][jnp.asarray(toks)].astype(jnp.float32)
        for layer in range(s.layers):
            h = _layer(s, h, layer_params(s, seed, layer), quant)
        n_sel = len(sel)
        sel += [(0, 0)] * (-n_sel % LEN_BUCKET)  # one compiled head per bucket
        lg = np.asarray(_head(s, h, jnp.asarray(sel, jnp.int32), top, quant))[:n_sel]
        at = 0
        for i in rows:
            n = len(positions[i])
            out[i] = lg[at : at + n]
            at += n
    return out  # type: ignore[return-value]


def _teacher(prompts: Sequence[Sequence[int]], served: Sequence[Sequence[int]]):
    """Each prompt with its served tokens but the last, and the positions
    whose next token was served."""
    seqs = [list(p) + list(t[:-1]) for p, t in zip(prompts, served, strict=True)]
    pos = [list(range(len(p) - 1, len(p) - 1 + len(t))) for p, t in zip(prompts, served, strict=True)]
    return seqs, pos


def score(s: Spec, seed: int, prompts: Sequence[Sequence[int]],
          served: Sequence[Sequence[int]], tokens: Optional[Sequence[Sequence[int]]] = None,
          ) -> np.ndarray:
    """At every position whose next token was served, teacher-forced on the
    served tokens: the reference's best logit less the logit of the token in
    `tokens` there (the served token itself by default)."""
    seqs, pos = _teacher(prompts, served)
    ref = logits_at(s, seed, seqs, pos)
    tokens = served if tokens is None else tokens
    return np.concatenate([
        r.max(-1) - r[np.arange(len(t)), np.asarray(t)] for r, t in zip(ref, tokens, strict=True)
    ])


def control_tokens(s: Spec, seed: int, prompts: Sequence[Sequence[int]],
                   served: Sequence[Sequence[int]], quant: str = "int8") -> List[List[int]]:
    """The control: at the same positions, teacher-forced on the same served
    tokens, the token the `quant` model puts first."""
    seqs, pos = _teacher(prompts, served)
    return [lg.argmax(-1).tolist() for lg in logits_at(s, seed, seqs, pos, quant)]
