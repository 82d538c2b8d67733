"""Plain float32 reference of the served model, run layer by layer.

It imports nothing of the program. It regenerates each layer's weights from
the seed (`weights.layer_params`), as served, and runs the equations the
configuration's architecture module states (``arch.reference_embed``,
``arch.reference_layer``, ``arch.reference_head``: float32, ``highest``
matmul precision; the helpers below are theirs to use) over whole sequences
(prompt + served tokens), teacher-forced, in blocks of rows so that it fits
beside nothing. `score` returns, at every position whose next token was
served, the reference's best logit less the logit of the served token.

``quant="int8"`` is the control: every projection and the head computed
from int8 weights (per output column) and int8 activations (per token),
the rest as above. `control_tokens` gives, at the same positions, the token
the int8 model puts first; put in the served tokens' place, they go through
the same comparison as the program's.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from weights import layer_params, top_params

# tokens per block of rows: bounds the (rows, heads, S, S) score tensor
BLOCK_TOKENS = 4096
LEN_BUCKET = 256


def _q8(x: jax.Array, axis: int) -> jax.Array:
    """Symmetric int8 round trip with one scale per slice along `axis`."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def mm(x: jax.Array, w: jax.Array, quant: Optional[str]) -> jax.Array:
    """x @ w over the last axis of x, at ``highest`` precision; in int8 for
    the control."""
    if quant == "int8":
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.einsum("...d,de->...e", x, w, precision="highest")


def rms(x, g, eps):
    """RMSNorm with the gain parameterised as 1 + g."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + g)


def rope(x, pos, theta):
    """Rotary embedding, half split: x (b, n, heads, dim), pos (b, n)."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[..., None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def f32(w: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    # the served values, already rounded to their dtype outside this program
    return {k: v.astype(jnp.float32) for k, v in w.items()}


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


def logits_at(arch, s, seed: int, seqs: Sequence[Sequence[int]],
              positions: Sequence[Sequence[int]], quant: Optional[str] = None
              ) -> List[np.ndarray]:
    """Logits (len(positions[i]), vocab) for each sequence, in float32."""
    out: List[Optional[np.ndarray]] = [None] * len(seqs)
    top = top_params(arch, s, seed)
    for rows, pad in _blocks([len(x) for x in seqs]):
        # a fixed number of rows per padded length: one compiled program
        # per length bucket, whichever requests a run samples
        toks = np.zeros((max(1, BLOCK_TOKENS // pad), pad), np.int32)
        sel = []
        for r, i in enumerate(rows):
            toks[r, : len(seqs[i])] = seqs[i]
            sel += [(r, p) for p in positions[i]]
        h = arch.reference_embed(s, jnp.asarray(toks), top)
        for layer in range(s.layers):
            h = arch.reference_layer(s, layer, h, layer_params(arch, s, seed, layer), quant)
        n_sel = len(sel)
        sel += [(0, 0)] * (-n_sel % LEN_BUCKET)  # one compiled head per bucket
        lg = np.asarray(arch.reference_head(s, h, jnp.asarray(sel, jnp.int32), top, quant))
        lg = lg[:n_sel]
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


def teacher_logits(arch, s, seed: int, prompts: Sequence[Sequence[int]],
                   served: Sequence[Sequence[int]], quant: Optional[str] = None
                   ) -> List[np.ndarray]:
    """Teacher-forced on the served tokens: the logits at every position
    whose next token was served."""
    seqs, pos = _teacher(prompts, served)
    return logits_at(arch, s, seed, seqs, pos, quant)


def gaps(ref: Sequence[np.ndarray], tokens: Sequence[Sequence[int]]) -> np.ndarray:
    """At each position: the best logit of `ref` less the logit of the token
    in `tokens` there."""
    if not ref:
        return np.zeros((0,))
    return np.concatenate([
        r.max(-1) - r[np.arange(len(t)), np.asarray(t)] for r, t in zip(ref, tokens, strict=True)
    ])


def score(arch, s, seed: int, prompts: Sequence[Sequence[int]],
          served: Sequence[Sequence[int]]) -> np.ndarray:
    """At every position whose next token was served, teacher-forced on the
    served tokens: the reference's best logit less the served token's."""
    return gaps(teacher_logits(arch, s, seed, prompts, served), served)


def control_tokens(arch, s, seed: int, prompts: Sequence[Sequence[int]],
                   served: Sequence[Sequence[int]], quant: str = "int8") -> List[List[int]]:
    """The control: at the same positions, teacher-forced on the same served
    tokens, the token the `quant` model puts first."""
    return [lg.argmax(-1).tolist()
            for lg in teacher_logits(arch, s, seed, prompts, served, quant)]
