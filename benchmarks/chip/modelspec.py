"""A configuration file's model, read in the source's own vocabulary.

``configs/<name>.json`` keeps the published ``config.json`` keys (cut where
``reduced`` says). `Spec` is what the weights and the plain reference read;
`model_config` maps it onto the program's ``ModelConfig`` for serving.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Dict


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


def load(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


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
