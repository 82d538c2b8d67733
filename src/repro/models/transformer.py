"""Decoder trunk: dense / MoE / SSM / hybrid families, scan-over-layers.

Entry points (all shape-polymorphic over batch):
  forward_train(params, inputs, cfg)                -> logits (B, S, V)
  prefill_step(params, inputs, cfg, valid_len)      -> (last_logits (B,V), kv_out)
  decode_step(params, tokens, positions, cfg, cache)-> (logits (B,V), cache')
  decode_rows(params, tokens, positions, cfg, cache, view)
                                                    -> (logits (B,V), new K/V rows)

Prefill produces the KV pytree that a disaggregated deployment ships to the
decode instance; decode consumes/updates a preallocated cache. The serving
engine decodes with `decode_rows`, which only reads the cache and leaves the
writing of each lane's new row to the engine (serving/kvcache.py).
"""
from __future__ import annotations

from functools import partial
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.dist.act_sharding import constrain_batch
from repro.models import ssm as ssm_mod
from repro.models.layers import (
    attention,
    attention_mask,
    attention_with_new_row,
    dense_init,
    dtype_of,
    embed_init,
    gated_mlp,
    rms_norm,
    rope,
    softcap,
)
from repro.models.moe import init_moe_params, moe_ffn

_GLOBAL_WINDOW = 1 << 30  # "no window" sentinel for traced window values


# ----------------------------------------------------------------------------
# Param init
# ----------------------------------------------------------------------------

def _init_attn(key, cfg: ModelConfig, dtype) -> Dict:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    hq, hkv = cfg.num_heads, cfg.num_kv_heads
    ks = jax.random.split(key, 4)
    p = dict(
        wq=dense_init(ks[0], (d, hq * hd), dtype),
        wk=dense_init(ks[1], (d, hkv * hd), dtype),
        wv=dense_init(ks[2], (d, hkv * hd), dtype),
        wo=dense_init(ks[3], (hq * hd, d), dtype),
    )
    if cfg.use_qk_norm:
        p["q_norm"] = jnp.zeros((hd,), dtype)
        p["k_norm"] = jnp.zeros((hd,), dtype)
    return p


def _init_mlp(key, cfg: ModelConfig, dtype) -> Dict:
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    return dict(
        w_gate=dense_init(ks[0], (d, f), dtype),
        w_up=dense_init(ks[1], (d, f), dtype),
        w_down=dense_init(ks[2], (f, d), dtype),
    )


def _init_dense_layer(key, cfg: ModelConfig, dtype) -> Dict:
    k1, k2 = jax.random.split(key)
    return dict(
        attn=_init_attn(k1, cfg, dtype),
        mlp=_init_mlp(k2, cfg, dtype),
        pre_attn_norm=jnp.zeros((cfg.d_model,), dtype),
        pre_mlp_norm=jnp.zeros((cfg.d_model,), dtype),
    )


def _init_moe_layer(key, cfg: ModelConfig, dtype) -> Dict:
    k1, k2 = jax.random.split(key)
    return dict(
        attn=_init_attn(k1, cfg, dtype),
        moe=init_moe_params(k2, cfg, dtype),
        pre_attn_norm=jnp.zeros((cfg.d_model,), dtype),
        pre_mlp_norm=jnp.zeros((cfg.d_model,), dtype),
    )


def _init_ssm_layer(key, cfg: ModelConfig, dtype) -> Dict:
    return dict(
        ssm=ssm_mod.init_ssm_params(key, cfg, dtype),
        pre_norm=jnp.zeros((cfg.d_model,), dtype),
    )


def _stack_layers(key, n: int, init_fn):
    """Init `n` layers directly in stacked (n, ...) form: vmapping over the
    keys draws the same values as n separate calls, without holding every
    layer's leaves beside a stacked copy (two full copies of the trunk)."""
    return jax.vmap(init_fn)(jax.random.split(key, n))


def init_params(cfg: ModelConfig, key) -> Dict:
    dtype = dtype_of(cfg.dtype)
    ks = jax.random.split(key, 5)
    params: Dict = dict(
        embed=embed_init(ks[0], (cfg.vocab_size, cfg.d_model), dtype),
        final_norm=jnp.zeros((cfg.d_model,), dtype),
    )
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(ks[1], (cfg.d_model, cfg.vocab_size), dtype)

    if cfg.family in ("dense", "vlm"):
        params["layers"] = _stack_layers(
            ks[2], cfg.num_layers, partial(_init_dense_layer, cfg=cfg, dtype=dtype)
        )
    elif cfg.family == "moe":
        params["layers"] = _stack_layers(
            ks[2], cfg.num_layers, partial(_init_moe_layer, cfg=cfg, dtype=dtype)
        )
    elif cfg.family == "ssm":
        params["layers"] = _stack_layers(
            ks[2], cfg.num_layers, partial(_init_ssm_layer, cfg=cfg, dtype=dtype)
        )
    elif cfg.family == "hybrid":
        ns, per = _hybrid_blocks(cfg)
        inner = _stack_layers(
            ks[2], ns * per, partial(_init_ssm_layer, cfg=cfg, dtype=dtype)
        )
        params["layers"] = jax.tree.map(
            lambda x: x.reshape(ns, per, *x.shape[1:]), inner
        )
        params["shared_attn"] = _init_dense_layer(ks[3], cfg, dtype)
    else:
        raise ValueError(f"family {cfg.family} not handled by transformer trunk")
    return params


def _hybrid_blocks(cfg: ModelConfig) -> Tuple[int, int]:
    per = cfg.hybrid_period
    assert cfg.num_layers % per == 0, "hybrid depth must divide period"
    return cfg.num_layers // per, per


# ----------------------------------------------------------------------------
# Blocks
# ----------------------------------------------------------------------------

def _attn_qkv(p: Dict, x: jax.Array, positions: jax.Array, cfg: ModelConfig):
    b, s, _ = x.shape
    hd = cfg.resolved_head_dim
    q = jnp.einsum("bsd,de->bse", x, p["wq"]).reshape(b, s, cfg.num_heads, hd)
    k = jnp.einsum("bsd,de->bse", x, p["wk"]).reshape(b, s, cfg.num_kv_heads, hd)
    v = jnp.einsum("bsd,de->bse", x, p["wv"]).reshape(b, s, cfg.num_kv_heads, hd)
    if cfg.use_qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def attn_block(
    p: Dict,
    x: jax.Array,
    positions: jax.Array,  # (B, S)
    cfg: ModelConfig,
    *,
    window=None,
    kv_cache: Optional[Tuple[jax.Array, jax.Array]] = None,  # (B,M,Hkv,Dh) x2
    kv_valid: Optional[jax.Array] = None,  # (B,)
    q_seg=None,
    kv_seg=None,
) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Attention sublayer. Returns (out, (k, v)).

    Without kv_cache: self-attention within the chunk; returns chunk K/V.
    With kv_cache: scatter this chunk's K/V into the cache at `positions`,
    attend against the whole cache; returns the updated cache K/V.
    """
    b, s, _ = x.shape
    q, k, v = _attn_qkv(p, x, positions, cfg)
    if kv_cache is None:
        out = attention(
            q, k, v, positions, kv_valid,
            window=window, causal=True, logit_cap=cfg.attn_logit_softcap,
            q_seg=q_seg, kv_seg=kv_seg, impl=cfg.attn_impl,
        )
        new_kv = (k, v)
    else:
        ck, cv = kv_cache
        if s == 1:
            # one-hot (select) update instead of scatter: SPMD cannot
            # partition a per-batch scatter into a sharded cache and falls
            # back to all-gathering the whole cache every step (measured
            # 170 GB/chip/step); the elementwise select partitions cleanly.
            m = ck.shape[1]
            hit = (
                jax.lax.broadcasted_iota(jnp.int32, (b, m), 1)
                == positions[:, :1]
            )[:, :, None, None]
            ck = jnp.where(hit, k[:, 0][:, None], ck)
            cv = jnp.where(hit, v[:, 0][:, None], cv)
        else:
            with jax.named_scope("cache_write"):
                write = jax.vmap(partial(_write_chunk, rows=s))
                ck = write(ck, k, positions[:, 0])
                cv = write(cv, v, positions[:, 0])
        out = attention(
            q, ck, cv, positions, kv_valid,
            window=window, causal=True, logit_cap=cfg.attn_logit_softcap,
            impl=cfg.attn_impl,
        )
        new_kv = (ck, cv)
    out = out.reshape(b, s, cfg.num_heads * cfg.resolved_head_dim)
    return jnp.einsum("bse,ed->bsd", out, p["wo"]), new_kv


def _write_chunk(cache: jax.Array, new: jax.Array, start: jax.Array, *, rows: int) -> jax.Array:
    """Write one request's chunk (rows, ...) into its cache (M, ...) so that
    chunk row j lands at cache row start + j.

    ``dynamic_update_slice`` clamps a block that would run past the cache's
    end, which would land the whole chunk early, over earlier rows. So the
    block is written at ``at = min(start, M - rows)``: the chunk shifted by
    ``start - at`` rows within it, and the cache's own rows below that
    shift kept. Chunk rows past the end are pad and drop out.
    """
    at = jnp.minimum(start, cache.shape[0] - rows)
    shift = start - at
    corner = (at, *[0] * (cache.ndim - 1))
    old = jax.lax.dynamic_slice(cache, corner, new.shape)
    keep = jnp.expand_dims(jnp.arange(rows) < shift, tuple(range(1, new.ndim)))
    block = jnp.where(keep, old, jnp.roll(new, shift, axis=0))
    return jax.lax.dynamic_update_slice(cache, block, corner)


def _ffn(layer: Dict, h: jax.Array, cfg: ModelConfig) -> jax.Array:
    hn = rms_norm(h, layer["pre_mlp_norm"], cfg.norm_eps)
    if cfg.family == "moe":
        return moe_ffn(hn, layer["moe"], cfg)
    return gated_mlp(hn, layer["mlp"]["w_gate"], layer["mlp"]["w_up"], layer["mlp"]["w_down"], cfg.act)


def _layer_window(cfg: ModelConfig, is_local):
    """Per-layer effective window (traced int32) for alternating local/global."""
    if not cfg.alternate_local_global:
        return cfg.sliding_window if cfg.sliding_window else None
    return jnp.where(is_local, cfg.sliding_window, _GLOBAL_WINDOW).astype(jnp.int32)


def _layer_flags(cfg: ModelConfig, n: int) -> jax.Array:
    """is_local flag per layer (gemma2: even layers local)."""
    if cfg.alternate_local_global:
        return (jnp.arange(n) % 2 == 0)
    return jnp.zeros((n,), bool)


# ----------------------------------------------------------------------------
# Trunk application (train / prefill: no external cache)
# ----------------------------------------------------------------------------

def _embed_inputs(params: Dict, inputs: jax.Array, cfg: ModelConfig) -> jax.Array:
    if cfg.input_mode == "embeddings":
        return inputs.astype(dtype_of(cfg.dtype))
    return params["embed"][inputs]


def logits_from_hidden(params: Dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = jnp.einsum("...d,vd->...v", x, params["embed"])
    else:
        logits = jnp.einsum("...d,dv->...v", x, params["lm_head"])
    logits = softcap(logits.astype(jnp.float32), cfg.final_logit_softcap)
    return logits


def _trunk_nocache(
    params: Dict,
    x: jax.Array,
    positions: jax.Array,
    cfg: ModelConfig,
    valid_len: Optional[jax.Array],
    collect_kv: bool,
    remat: bool,
    q_seg=None,
    kv_seg=None,
):
    """Scan over layers without an external cache. Returns (x, kv_stack)."""

    if cfg.family in ("dense", "vlm", "moe"):

        def body(h, xs):
            layer, is_local = xs
            win = _layer_window(cfg, is_local)
            a_out, (k, v) = attn_block(
                layer["attn"],
                rms_norm(h, layer["pre_attn_norm"], cfg.norm_eps),
                positions, cfg, window=win, kv_valid=valid_len,
                q_seg=q_seg, kv_seg=kv_seg,
            )
            h = h + a_out
            h = h + _ffn(layer, h, cfg)
            ys = (k, v) if collect_kv else None
            return h, ys

        if remat:
            body = jax.checkpoint(body)
        x, kv = jax.lax.scan(body, x, (params["layers"], _layer_flags(cfg, cfg.num_layers)))
        return x, kv

    if cfg.family == "ssm":

        def body(h, layer):
            o, cache = ssm_mod.ssm_forward(
                layer["ssm"], rms_norm(h, layer["pre_norm"], cfg.norm_eps), cfg, valid_len
            )
            return h + o, cache if collect_kv else None

        if remat:
            body = jax.checkpoint(body)
        x, caches = jax.lax.scan(body, x, params["layers"])
        return x, caches

    if cfg.family == "hybrid":
        shared = params["shared_attn"]

        def inner(h, layer):
            o, cache = ssm_mod.ssm_forward(
                layer["ssm"], rms_norm(h, layer["pre_norm"], cfg.norm_eps), cfg, valid_len
            )
            return h + o, cache if collect_kv else None

        def super_body(h, xs):
            layers_blk = xs
            h, ssm_caches = jax.lax.scan(inner, h, layers_blk)
            a_out, (k, v) = attn_block(
                shared["attn"],
                rms_norm(h, shared["pre_attn_norm"], cfg.norm_eps),
                positions, cfg, kv_valid=valid_len,
            )
            h = h + a_out
            h = h + gated_mlp(
                rms_norm(h, shared["pre_mlp_norm"], cfg.norm_eps),
                shared["mlp"]["w_gate"], shared["mlp"]["w_up"], shared["mlp"]["w_down"], cfg.act,
            )
            ys = (ssm_caches, (k, v)) if collect_kv else None
            return h, ys

        if remat:
            super_body = jax.checkpoint(super_body)
        x, caches = jax.lax.scan(super_body, x, params["layers"])
        return x, caches

    raise ValueError(cfg.family)


def forward_train(
    params: Dict, inputs: jax.Array, cfg: ModelConfig, remat: bool = True
) -> jax.Array:
    """Full causal forward; returns logits (B, S, V)."""
    x = constrain_batch(_embed_inputs(params, inputs, cfg))
    b, s = x.shape[0], x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    x, _ = _trunk_nocache(params, x, positions, cfg, None, collect_kv=False, remat=remat)
    return logits_from_hidden(params, x, cfg)


def prefill_step(
    params: Dict,
    inputs: jax.Array,
    cfg: ModelConfig,
    valid_len: Optional[jax.Array] = None,
):
    """One-shot prefill: returns (last-token logits (B, V), kv pytree).

    The kv pytree is what gets transferred to the decode instance:
      attention families: (k, v) stacked (L, B, S, Hkv, Dh)
      ssm: dict(conv=(L,B,W-1,C), state=(L,B,H,P,N))
      hybrid: (ssm_caches, attn_kv) stacked by super-block
    """
    x = constrain_batch(_embed_inputs(params, inputs, cfg))
    b, s = x.shape[0], x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    if valid_len is None:
        valid_len = jnp.full((b,), s, jnp.int32)
    x, kv = _trunk_nocache(params, x, positions, cfg, valid_len, collect_kv=True, remat=False)
    last = jnp.take_along_axis(x, (valid_len - 1)[:, None, None].astype(jnp.int32), axis=1)[:, 0]
    return logits_from_hidden(params, last, cfg), kv


# ----------------------------------------------------------------------------
# Decode (external cache)
# ----------------------------------------------------------------------------

def decode_step(
    params: Dict,
    tokens: jax.Array,  # (B, 1) int32
    positions: jax.Array,  # (B,) current write position (= tokens generated so far + prompt len)
    cfg: ModelConfig,
    cache: Dict,
):
    """Single-token decode. Returns (logits (B, V), updated cache)."""
    x = constrain_batch(params["embed"][tokens])
    b = tokens.shape[0]
    pos2 = positions[:, None]
    kv_valid = positions + 1

    if cfg.family in ("dense", "vlm", "moe"):
        if "k_local" in cache:
            return _decode_step_windowed(params, x, positions, cfg, cache)

        def body(h, xs):
            layer, is_local, ck, cv = xs
            win = _layer_window(cfg, is_local)
            a_out, (ck2, cv2) = attn_block(
                layer["attn"],
                rms_norm(h, layer["pre_attn_norm"], cfg.norm_eps),
                pos2, cfg, window=win, kv_cache=(ck, cv), kv_valid=kv_valid,
            )
            h = h + a_out
            h = h + _ffn(layer, h, cfg)
            return h, (ck2, cv2)

        x, (ck, cv) = jax.lax.scan(
            body, x, (params["layers"], _layer_flags(cfg, cfg.num_layers), cache["k"], cache["v"])
        )
        new_cache = dict(k=ck, v=cv)

    elif cfg.family == "ssm":

        def body(h, xs):
            layer, conv, state = xs
            o, c2 = ssm_mod.ssm_decode_step(
                layer["ssm"], rms_norm(h, layer["pre_norm"], cfg.norm_eps), cfg,
                dict(conv=conv, state=state),
            )
            return h + o, (c2["conv"], c2["state"])

        x, (conv, state) = jax.lax.scan(body, x, (params["layers"], cache["conv"], cache["state"]))
        new_cache = dict(conv=conv, state=state)

    elif cfg.family == "hybrid":
        shared = params["shared_attn"]

        def inner(h, xs):
            layer, conv, state = xs
            o, c2 = ssm_mod.ssm_decode_step(
                layer["ssm"], rms_norm(h, layer["pre_norm"], cfg.norm_eps), cfg,
                dict(conv=conv, state=state),
            )
            return h + o, (c2["conv"], c2["state"])

        def super_body(h, xs):
            layers_blk, conv_blk, state_blk, ck, cv = xs
            h, (conv2, state2) = jax.lax.scan(inner, h, (layers_blk, conv_blk, state_blk))
            a_out, (ck2, cv2) = attn_block(
                shared["attn"],
                rms_norm(h, shared["pre_attn_norm"], cfg.norm_eps),
                pos2, cfg, kv_cache=(ck, cv), kv_valid=kv_valid,
            )
            h = h + a_out
            h = h + gated_mlp(
                rms_norm(h, shared["pre_mlp_norm"], cfg.norm_eps),
                shared["mlp"]["w_gate"], shared["mlp"]["w_up"], shared["mlp"]["w_down"], cfg.act,
            )
            return h, (conv2, state2, ck2, cv2)

        x, (conv, state, ck, cv) = jax.lax.scan(
            super_body, x,
            (params["layers"], cache["conv"], cache["state"], cache["k"], cache["v"]),
        )
        new_cache = dict(conv=conv, state=state, k=ck, v=cv)
    else:
        raise ValueError(cfg.family)

    logits = logits_from_hidden(params, x[:, 0], cfg)
    return logits, new_cache


def decode_rows(
    params: Dict,
    tokens: jax.Array,  # (B, 1) int32
    positions: jax.Array,  # (B,) each lane's position: its new row goes there
    cfg: ModelConfig,
    cache: Dict,  # plain {"k", "v"}, leaves (L, ...)
    view: Callable[[jax.Array], jax.Array],
):
    """Single-token decode that only reads the cache: the serving engine's
    path, which writes each lane's new K/V row itself, in place.

    Each layer's attention reads ``view(leaf[l])`` -> (B, M, Hkv, Dh), the
    lanes' cached rows, at positions below each lane's own, plus the new
    token's K/V, which is not in the cache. No cache view is rewritten, so
    unlike `decode_step` there is no per-layer select for SPMD to
    partition; this path is for an unsharded cache. Returns
    (logits (B, V), rows) with rows ``dict(k, v)`` of (L, B, Hkv, Dh).
    """
    assert cfg.family in ("dense", "vlm", "moe") and set(cache) == {"k", "v"}
    x = constrain_batch(params["embed"][tokens])
    b = tokens.shape[0]
    pos2 = positions[:, None]

    def body(h, xs):
        layer, is_local, ck, cv = xs
        win = _layer_window(cfg, is_local)
        q, k, v = _attn_qkv(layer["attn"], rms_norm(h, layer["pre_attn_norm"], cfg.norm_eps), pos2, cfg)
        ck, cv = view(ck), view(cv)
        if cfg.attn_impl == "pallas":
            # the kernel reads one materialized view: the row goes into it
            lane = jnp.arange(b)
            ck = ck.at[lane, positions].set(k[:, 0])
            cv = cv.at[lane, positions].set(v[:, 0])
            out = attention(
                q, ck, cv, pos2, positions + 1, window=win,
                logit_cap=cfg.attn_logit_softcap, impl="pallas",
            )
        else:
            seen = attention_mask(pos2, ck.shape[1], positions, win)[:, 0]
            out = attention_with_new_row(q, ck, cv, k, v, seen, cfg.attn_logit_softcap)
        out = out.reshape(b, 1, cfg.num_heads * cfg.resolved_head_dim)
        h = h + jnp.einsum("bse,ed->bsd", out, layer["attn"]["wo"])
        h = h + _ffn(layer, h, cfg)
        return h, (k[:, 0], v[:, 0])

    x, (k, v) = jax.lax.scan(
        body, x, (params["layers"], _layer_flags(cfg, cfg.num_layers), cache["k"], cache["v"])
    )
    return logits_from_hidden(params, x[:, 0], cfg), dict(k=k, v=v)


def chunk_prefill_step(
    params: Dict,
    tokens: jax.Array,  # (B, C) — one chunk per request, right-padded
    start: jax.Array,  # (B,) context offset (tokens already prefilled)
    valid: jax.Array,  # (B,) valid tokens in this chunk (<= C)
    cfg: ModelConfig,
    cache: Dict,
):
    """Chunked prefill (Sarathi-style): writes this chunk's KV into the cache
    at `start` and attends to cache[0 : start+valid]. Returns
    (last-valid-token logits (B, V), updated cache). Attention families only
    (the SSM prefill path carries state through ssm_forward instead)."""
    assert cfg.family in ("dense", "vlm", "moe"), cfg.family
    x = constrain_batch(params["embed"][tokens])
    b, c = tokens.shape
    positions = start[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]
    kv_valid = start + valid

    def body(h, xs):
        layer, is_local, ck, cv = xs
        win = _layer_window(cfg, is_local)
        a_out, (ck2, cv2) = attn_block(
            layer["attn"],
            rms_norm(h, layer["pre_attn_norm"], cfg.norm_eps),
            positions, cfg, window=win, kv_cache=(ck, cv), kv_valid=kv_valid,
        )
        h = h + a_out
        h = h + _ffn(layer, h, cfg)
        return h, (ck2, cv2)

    x, (ck, cv) = jax.lax.scan(
        body, x, (params["layers"], _layer_flags(cfg, cfg.num_layers), cache["k"], cache["v"])
    )
    last = jnp.take_along_axis(
        x, jnp.maximum(valid - 1, 0)[:, None, None].astype(jnp.int32), axis=1
    )[:, 0]
    return logits_from_hidden(params, last, cfg), dict(k=ck, v=cv)


# ----------------------------------------------------------------------------
# Cache structure
# ----------------------------------------------------------------------------

def _decode_step_windowed(params: Dict, x: jax.Array, positions: jax.Array, cfg: ModelConfig, cache: Dict):
    """Decode for alternating local/global archs with a ring cache for the
    local layers: scan over (local, global) layer pairs. The ring stores the
    last `W = sliding_window` positions; slot j holds absolute position
    a_j = pos - ((pos - j) mod W), valid iff a_j >= 0."""
    from repro.models.layers import naive_attention  # local import, no cycle

    b = x.shape[0]
    W = cfg.sliding_window
    half = cfg.num_layers // 2
    pos2 = positions[:, None]
    kv_valid = positions + 1
    pairs = jax.tree.map(lambda a: a.reshape(half, 2, *a.shape[1:]), params["layers"])

    def local_attn(layer, h):
        hn = rms_norm(h, layer["pre_attn_norm"], cfg.norm_eps)
        q, k, v = _attn_qkv(layer["attn"], hn, pos2, cfg)
        return q, k, v

    def body(h, xs):
        pair, ck_l, cv_l, ck_g, cv_g = xs
        loc = jax.tree.map(lambda a: a[0], pair)
        glo = jax.tree.map(lambda a: a[1], pair)

        # ---- local layer: ring cache ------------------------------------
        q, k, v = local_attn(loc, h)
        slot = jnp.mod(positions, W)  # (B,)
        hit = (
            jax.lax.broadcasted_iota(jnp.int32, (b, W), 1) == slot[:, None]
        )[:, :, None, None]
        ck_l = jnp.where(hit, k[:, 0][:, None], ck_l)
        cv_l = jnp.where(hit, v[:, 0][:, None], cv_l)
        jj = jax.lax.broadcasted_iota(jnp.int32, (b, W), 1)
        a_j = positions[:, None] - jnp.mod(positions[:, None] - jj, W)
        mask = (a_j >= 0)[:, None, :]  # (B, 1, W); causality is structural
        a_out = naive_attention(q, ck_l, cv_l, mask, cfg.attn_logit_softcap)
        a_out = a_out.reshape(b, 1, cfg.num_heads * cfg.resolved_head_dim)
        h = h + jnp.einsum("bse,ed->bsd", a_out, loc["attn"]["wo"])
        h = h + _ffn(loc, h, cfg)

        # ---- global layer: standard full cache ---------------------------
        a_out, (ck_g2, cv_g2) = attn_block(
            glo["attn"],
            rms_norm(h, glo["pre_attn_norm"], cfg.norm_eps),
            pos2, cfg, kv_cache=(ck_g, cv_g), kv_valid=kv_valid,
        )
        h = h + a_out
        h = h + _ffn(glo, h, cfg)
        return h, (ck_l, cv_l, ck_g2, cv_g2)

    x, (ck_l, cv_l, ck_g, cv_g) = jax.lax.scan(
        body, x, (pairs, cache["k_local"], cache["v_local"], cache["k"], cache["v"])
    )
    logits = logits_from_hidden(params, x[:, 0], cfg)
    return logits, dict(k=ck_g, v=cv_g, k_local=ck_l, v_local=cv_l)


def _use_windowed_cache(cfg: ModelConfig, max_len: int) -> bool:
    return (
        cfg.alternate_local_global
        and cfg.sliding_window > 0
        and max_len > cfg.sliding_window
        and cfg.num_layers % 2 == 0
    )


def cache_shapes(cfg: ModelConfig, batch: int, max_len: int) -> Dict:
    """Returns a pytree of (shape, dtype-name) describing the decode cache.

    Alternating local/global archs (gemma2) get a windowed ring cache for
    the local layers: half the layers only ever attend to the last
    `sliding_window` positions, so storing (and more importantly *reading*,
    every decode step) their full-context KV wastes ~0.5x of the decode
    memory roofline (§Perf iteration D6)."""
    hd = cfg.resolved_head_dim
    if cfg.family in ("dense", "vlm", "moe"):
        if _use_windowed_cache(cfg, max_len):
            half = cfg.num_layers // 2
            kv_g = (half, batch, max_len, cfg.num_kv_heads, hd)
            kv_l = (half, batch, cfg.sliding_window, cfg.num_kv_heads, hd)
            return dict(
                k=(kv_g, cfg.dtype),
                v=(kv_g, cfg.dtype),
                k_local=(kv_l, cfg.dtype),
                v_local=(kv_l, cfg.dtype),
            )
        kv = (cfg.num_layers, batch, max_len, cfg.num_kv_heads, hd)
        return dict(k=(kv, cfg.dtype), v=(kv, cfg.dtype))
    if cfg.family == "ssm":
        s = ssm_mod.ssm_cache_shape(cfg, batch)
        return dict(
            conv=((cfg.num_layers, *s["conv"][0]), s["conv"][1]),
            state=((cfg.num_layers, *s["state"][0]), s["state"][1]),
        )
    if cfg.family == "hybrid":
        ns, per = _hybrid_blocks(cfg)
        s = ssm_mod.ssm_cache_shape(cfg, batch)
        kv = (ns, batch, max_len, cfg.num_kv_heads, hd)
        return dict(
            conv=((ns, per, *s["conv"][0]), s["conv"][1]),
            state=((ns, per, *s["state"][0]), s["state"][1]),
            k=(kv, cfg.dtype),
            v=(kv, cfg.dtype),
        )
    raise ValueError(cfg.family)
