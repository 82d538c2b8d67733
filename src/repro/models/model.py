"""Unified model API over all families.

  model = build_model(cfg)
  params = model.init(key)
  logits = model.forward_train(params, batch)       # batch: dict
  logits, kv = model.prefill(params, batch)
  logits, cache = model.decode(params, tokens, positions, cache)
  cache = model.init_cache(batch, max_len[, device]) # zeros, allocated
  spec  = cache_struct(cfg, batch, max_len)         # ShapeDtypeStructs only
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import encdec, transformer
from repro.models.layers import dtype_of


def _materialize(shapes: Dict, make_leaf):
    return jax.tree.map(
        make_leaf,
        shapes,
        is_leaf=lambda x: isinstance(x, tuple) and len(x) == 2 and isinstance(x[1], str),
    )


def _cache_shapes(cfg: ModelConfig, batch: int, max_len: int) -> Dict:
    if cfg.is_encdec:
        return encdec.cache_shapes(cfg, batch, max_len)
    return transformer.cache_shapes(cfg, batch, max_len)


def cache_struct(cfg: ModelConfig, batch: int, max_len: int) -> Dict:
    """ShapeDtypeStruct pytree (dry-run: no allocation)."""
    return _materialize(
        _cache_shapes(cfg, batch, max_len),
        lambda sd: jax.ShapeDtypeStruct(sd[0], dtype_of(sd[1])),
    )


@dataclass(frozen=True)
class Model:
    cfg: ModelConfig

    # ------------------------------------------------------------------ init
    def init(self, key) -> Dict:
        if self.cfg.is_encdec:
            return encdec.init_params(self.cfg, key)
        return transformer.init_params(self.cfg, key)

    def init_cache(self, batch: int, max_len: int, device=None) -> Dict:
        """Zeroed cache, allocated on `device` (the default device if None)."""
        return _materialize(
            _cache_shapes(self.cfg, batch, max_len),
            lambda sd: jnp.zeros(sd[0], dtype_of(sd[1]), device=device),
        )

    # ----------------------------------------------------------------- train
    def forward_train(self, params: Dict, batch: Dict, remat: bool = True) -> jax.Array:
        if self.cfg.is_encdec:
            return encdec.forward_train(params, batch["src"], batch["tgt"], self.cfg)
        return transformer.forward_train(params, batch["inputs"], self.cfg, remat=remat)

    def loss(self, params: Dict, batch: Dict, remat: bool = True) -> jax.Array:
        logits = self.forward_train(params, batch, remat=remat)
        labels = batch["labels"]
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
        mask = (labels >= 0).astype(jnp.float32)
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)

    # --------------------------------------------------------------- serving
    def prefill(self, params: Dict, batch: Dict, valid_len: Optional[jax.Array] = None):
        if self.cfg.is_encdec:
            return encdec.prefill_step(params, batch["src"], batch["tgt"], self.cfg, tgt_valid=valid_len)
        return transformer.prefill_step(params, batch["inputs"], self.cfg, valid_len)

    def decode(self, params: Dict, tokens: jax.Array, positions: jax.Array, cache: Dict):
        if self.cfg.is_encdec:
            return encdec.decode_step(params, tokens, positions, self.cfg, cache)
        return transformer.decode_step(params, tokens, positions, self.cfg, cache)

    # ------------------------------------------------------------------ misc
    def param_struct(self, key=None) -> Dict:
        """ShapeDtypeStruct pytree of params via eval_shape (no allocation)."""
        k = jax.random.key(0) if key is None else key
        return jax.eval_shape(self.init, k)


def build_model(cfg: ModelConfig) -> Model:
    return Model(cfg)
