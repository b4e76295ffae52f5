"""Composable decoder stack covering every assigned architecture.

Heterogeneous depth patterns (RecurrentGemma's rec/rec/attn, Gemma-2's
local/global alternation, xLSTM's sLSTM positions) are handled by scanning
over *pattern units*: the smallest repeating unit is laid out explicitly (no
`lax.switch`, so HLO cost analysis counts exactly the FLOPs that run), and
parameters are stacked over unit repeats. Aperiodic leading layers (DeepSeek's
first dense layer) and trailing remainders run unrolled.

Entry points:
- `Model.forward`      full-sequence hidden states (training)
- `Model.prefill`      full-sequence + populated KV/recurrent caches
- `Model.decode_step`  one token against the cache
- `Model.encode`       encoder stack (whisper)

The runtime engine (`repro.runtime.engine`) reuses `layer_forward` /
`layer_decode` directly for its trace-collecting per-layer loop.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import constrain, gather_for_compute
from repro.models import attention as attn_mod
from repro.models import moe as moe_mod
from repro.models import recurrent as rec_mod
from repro.models import xlstm as xlstm_mod
from repro.models.layers import (dense_init, embed_init, rms_norm, rope,
                                 softcap, swiglu)


class LayerSpec(NamedTuple):
    kind: str          # attn | rec | mlstm | slstm
    window: int        # sliding window (attn only; 0 = global)
    is_moe: bool
    layer_idx: int     # absolute depth index (first occurrence)


# Parameter keys that belong to a layer's FFN half. The slot-path runtime
# splits every layer here: attention/mixing (+ cache update) runs in one
# jitted `pre` dispatch, the FFN through the slot buffer in another.
FFN_PARAM_KEYS = ("ffn_norm", "moe", "ffn", "post_ffn_norm")


def split_ffn_params(p, spec: LayerSpec):
    """(attention-only params, FFN-stripped spec) for a layer param dict.

    `layer_forward` / `layer_prefill` / `layer_decode` on the returned pair
    compute exactly the layer's attention/mixing half (residual included)
    and skip the FFN, which the caller dispatches separately."""
    stripped = {k: v for k, v in p.items() if k not in FFN_PARAM_KEYS}
    return stripped, LayerSpec(spec.kind, spec.window, False, spec.layer_idx)


def build_layout(cfg: ModelConfig):
    """Layout: (prefix, unit, num_units, tail).

    prefix = leading aperiodic layers (unrolled), unit = smallest repeating
    pattern (scanned `num_units` times), tail = trailing remainder (unrolled).
    """
    kinds = [cfg.layer_kind(i) for i in range(cfg.num_layers)]
    windows = [cfg.attn_window(i) if kinds[i] == "attn" else 0
               for i in range(cfg.num_layers)]
    moes = [cfg.is_moe_layer(i) for i in range(cfg.num_layers)]
    specs = [LayerSpec(kinds[i], windows[i], moes[i], i)
             for i in range(cfg.num_layers)]
    prefix: List[LayerSpec] = []
    if cfg.moe is not None and cfg.moe.first_dense_layers:
        prefix = specs[:cfg.moe.first_dense_layers]
        specs = specs[cfg.moe.first_dense_layers:]

    def key(s: LayerSpec):
        return (s.kind, s.window, s.is_moe)

    n = len(specs)
    period = max(n, 1)
    for p in range(1, n + 1):
        k = n // p
        if k >= 1 and all(key(specs[i]) == key(specs[i % p])
                          for i in range(k * p)):
            period = p
            break
    num_units = n // period if n else 0
    unit = specs[:period] if n else []
    tail = specs[num_units * period:]
    return prefix, unit, num_units, tail


def _zc(cfg: ModelConfig) -> bool:
    """Gemma-family norms are zero-centered ((1+w)·x̂) and embeddings scaled."""
    return cfg.name.startswith(("gemma", "recurrentgemma"))


def sinusoidal_pos(positions: jnp.ndarray, d_model: int) -> jnp.ndarray:
    """Standard sinusoidal absolute position embedding. positions: (...,)."""
    half = d_model // 2
    freq = jnp.exp(-jnp.log(10000.0) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freq
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# Per-layer parameter init
# ---------------------------------------------------------------------------

def init_layer(key, cfg: ModelConfig, spec: LayerSpec, dtype,
               with_cross: Optional[bool] = None):
    ks = jax.random.split(key, 8)
    p: Dict[str, Any] = {"pre_norm": jnp.ones((cfg.d_model,), dtype)}
    hd = cfg.resolved_head_dim
    if spec.kind == "attn":
        if cfg.attention == "mla":
            p["attn"] = attn_mod.init_mla_params(ks[0], cfg.d_model,
                                                 cfg.num_heads, cfg.mla, dtype)
        else:
            p["attn"] = attn_mod.init_gqa_params(
                ks[0], cfg.d_model, cfg.num_heads, cfg.num_kv_heads, hd,
                dtype, qk_norm=cfg.qk_norm)
    elif spec.kind == "rec":
        p["rec"] = rec_mod.init_rglru_block(
            ks[0], cfg.d_model, cfg.lru_width or cfg.d_model,
            cfg.conv1d_width, dtype)
    elif spec.kind == "mlstm":
        p["mix"] = xlstm_mod.init_mlstm_block(ks[0], cfg.d_model, cfg.num_heads,
                                              cfg.proj_factor, dtype)
    elif spec.kind == "slstm":
        p["mix"] = xlstm_mod.init_slstm_block(ks[0], cfg.d_model, cfg.num_heads,
                                              cfg.proj_factor, dtype)
    if with_cross if with_cross is not None else cfg.is_encoder_decoder:
        p["cross_norm"] = jnp.ones((cfg.d_model,), dtype)
        p["cross"] = attn_mod.init_gqa_params(
            ks[1], cfg.d_model, cfg.num_heads, cfg.num_kv_heads, hd, dtype)
    has_ffn = spec.is_moe or cfg.d_ff > 0
    if has_ffn:
        p["ffn_norm"] = jnp.ones((cfg.d_model,), dtype)
        if spec.is_moe:
            p["moe"] = moe_mod.init_moe_params(ks[2], cfg.d_model, cfg.moe, dtype)
        else:
            p["ffn"] = {
                "w_gate": dense_init(ks[2], cfg.d_model, cfg.d_ff, dtype),
                "w_up": dense_init(ks[3], cfg.d_model, cfg.d_ff, dtype),
                "w_down": dense_init(ks[4], cfg.d_ff, cfg.d_model, dtype),
            }
    if cfg.attn_logit_softcap > 0:   # gemma-2 family: post-norms too
        p["post_attn_norm"] = jnp.ones((cfg.d_model,), dtype)
        if has_ffn:
            p["post_ffn_norm"] = jnp.ones((cfg.d_model,), dtype)
    return p


# ---------------------------------------------------------------------------
# Per-layer forward (train / prefill path)
# ---------------------------------------------------------------------------

def _ffn_part(p, cfg: ModelConfig, spec: LayerSpec, x: jnp.ndarray,
              router_sink: Optional[list]) -> jnp.ndarray:
    if "ffn_norm" not in p:
        return x
    B, T, d = x.shape
    h2 = rms_norm(x, p["ffn_norm"], cfg.norm_eps, zero_centered=_zc(cfg))
    if spec.is_moe:
        out, r = moe_mod.moe_grouped(p["moe"], h2, cfg.moe)
        if router_sink is not None:
            router_sink.append(r)
        ff = out
    else:
        act = "gelu" if cfg.family == "encdec" else "silu"
        ff = swiglu(h2, p["ffn"]["w_gate"], p["ffn"]["w_up"],
                    p["ffn"]["w_down"], act=act)
    if "post_ffn_norm" in p:
        ff = rms_norm(ff, p["post_ffn_norm"], cfg.norm_eps, zero_centered=_zc(cfg))
    x = x + ff
    return constrain(x, ("data", None, None))


def _cross_part(p, cfg: ModelConfig, x: jnp.ndarray, enc_out, enc_pos):
    if enc_out is None or "cross" not in p:
        return x
    hc = rms_norm(x, p["cross_norm"], cfg.norm_eps)
    k = jnp.einsum("bsd,dhk->bshk", enc_out, p["cross"]["wk"])
    v = jnp.einsum("bsd,dhk->bshk", enc_out, p["cross"]["wv"])
    cmix = attn_mod.gqa_attention(
        p["cross"], hc, positions=enc_pos, rope_theta=0.0, causal=False,
        kv_override=(k, v, enc_pos))
    return x + cmix


def layer_forward(p, cfg: ModelConfig, spec: LayerSpec, x: jnp.ndarray,
                  positions: jnp.ndarray, *, causal: bool = True,
                  enc_out: Optional[jnp.ndarray] = None,
                  enc_pos: Optional[jnp.ndarray] = None,
                  router_sink: Optional[list] = None) -> jnp.ndarray:
    """Full-sequence layer (train / prefill). x: (B, T, d)."""
    p = gather_for_compute(p)   # FSDP: weight all-gather, not act all-reduce
    h = rms_norm(x, p["pre_norm"], cfg.norm_eps, zero_centered=_zc(cfg))
    if spec.kind == "attn":
        if cfg.attention == "mla":
            mix = attn_mod.mla_attention(
                p["attn"], h, positions=positions, mla=cfg.mla,
                rope_theta=cfg.rope_theta, norm_eps=cfg.norm_eps,
                causal=causal, window=spec.window)
        else:
            mix = attn_mod.gqa_attention(
                p["attn"], h, positions=positions, rope_theta=cfg.rope_theta,
                window=spec.window, causal=causal,
                logit_softcap=cfg.attn_logit_softcap, norm_eps=cfg.norm_eps)
    elif spec.kind == "rec":
        mix, _, _ = rec_mod.rglru_block(p["rec"], h)
    elif spec.kind == "mlstm":
        mix, _ = xlstm_mod.mlstm_block(p["mix"], h, cfg.num_heads)
    elif spec.kind == "slstm":
        mix, _ = xlstm_mod.slstm_block(p["mix"], h, cfg.num_heads)
    else:
        raise ValueError(spec.kind)
    if "post_attn_norm" in p:
        mix = rms_norm(mix, p["post_attn_norm"], cfg.norm_eps, zero_centered=_zc(cfg))
    x = x + mix
    x = constrain(x, ("data", None, None))
    x = _cross_part(p, cfg, x, enc_out, enc_pos)
    return _ffn_part(p, cfg, spec, x, router_sink)


# ---------------------------------------------------------------------------
# Caches
# ---------------------------------------------------------------------------

def init_layer_cache(cfg: ModelConfig, spec: LayerSpec, batch: int,
                     max_seq: int, dtype, src_len: int = 0):
    hd = cfg.resolved_head_dim
    c: Dict[str, Any] = {}
    if spec.kind == "attn":
        if cfg.attention == "mla":
            c = {"latent": jnp.zeros((batch, max_seq, cfg.mla.kv_lora_rank), dtype),
                 "pe": jnp.zeros((batch, max_seq, 1, cfg.mla.qk_rope_head_dim),
                                 dtype)}
        else:
            size = min(max_seq, spec.window) if spec.window else max_seq
            c = {"k": jnp.zeros((batch, size, cfg.num_kv_heads, hd), dtype),
                 "v": jnp.zeros((batch, size, cfg.num_kv_heads, hd), dtype)}
    elif spec.kind == "rec":
        w = cfg.lru_width or cfg.d_model
        c = {"conv": jnp.zeros((batch, cfg.conv1d_width - 1, w), dtype),
             "rec": jnp.zeros((batch, w), jnp.float32)}
    elif spec.kind == "mlstm":
        up = int(cfg.d_model * cfg.proj_factor)
        H, D = cfg.num_heads, int(cfg.d_model * cfg.proj_factor) // cfg.num_heads
        c = {"c": jnp.zeros((batch, H, D, D), jnp.float32),
             "n": jnp.zeros((batch, H, D), jnp.float32),
             "m": jnp.full((batch, H), -1e30, jnp.float32)}
    elif spec.kind == "slstm":
        H, D = cfg.num_heads, cfg.d_model // cfg.num_heads
        z = jnp.zeros((batch, H, D), jnp.float32)
        c = {"c": z, "n": z, "h": z, "m": jnp.full((batch, H), -1e30, jnp.float32)}
    if cfg.is_encoder_decoder and src_len:
        c["xk"] = jnp.zeros((batch, src_len, cfg.num_kv_heads, hd), dtype)
        c["xv"] = jnp.zeros((batch, src_len, cfg.num_kv_heads, hd), dtype)
    return c


def layer_prefill(p, cfg: ModelConfig, spec: LayerSpec, x: jnp.ndarray,
                  positions: jnp.ndarray, max_seq: int, *,
                  enc_out=None, enc_pos=None,
                  router_sink: Optional[list] = None):
    """Like layer_forward but also returns a populated cache entry."""
    p = gather_for_compute(p)
    B, T, d = x.shape
    dtype = x.dtype
    h = rms_norm(x, p["pre_norm"], cfg.norm_eps, zero_centered=_zc(cfg))
    cache: Dict[str, Any] = {}
    if spec.kind == "attn":
        if cfg.attention == "mla":
            q, k, v, (c_kv, k_pe) = attn_mod._mla_qkv(
                p["attn"], h, positions, cfg.mla, cfg.rope_theta, cfg.norm_eps)
            scale = (cfg.mla.qk_nope_head_dim + cfg.mla.qk_rope_head_dim) ** -0.5
            mix = attn_mod.flash_attention(q, k, v, causal=True, scale=scale,
                                           window=spec.window)
            mix = jnp.einsum("bthk,hkd->btd", mix, p["attn"]["wo"])
            lat = jnp.zeros((B, max_seq, cfg.mla.kv_lora_rank), dtype)
            pe = jnp.zeros((B, max_seq, 1, cfg.mla.qk_rope_head_dim), dtype)
            cache = {"latent": lat.at[:, :T].set(c_kv.astype(dtype)),
                     "pe": pe.at[:, :T].set(k_pe.astype(dtype))}
        else:
            q = jnp.einsum("btd,dhk->bthk", h, p["attn"]["wq"])
            if "q_norm" in p["attn"]:
                q = rms_norm(q, p["attn"]["q_norm"], cfg.norm_eps)
            if cfg.rope_theta > 0:
                q = rope(q, positions, cfg.rope_theta)
            k, v = attn_mod.gqa_project_kv(p["attn"], h, positions,
                                           cfg.rope_theta, cfg.norm_eps)
            mix = attn_mod.flash_attention(
                q, k, v, causal=True, window=spec.window,
                logit_softcap=cfg.attn_logit_softcap)
            mix = jnp.einsum("bthk,hkd->btd", mix, p["attn"]["wo"])
            size = min(max_seq, spec.window) if spec.window else max_seq
            kc = jnp.zeros((B, size, cfg.num_kv_heads, cfg.resolved_head_dim), dtype)
            vc = jnp.zeros_like(kc)
            if T >= size:
                # ring discipline: slot(pos) = pos % size, keep last `size`
                keep = jnp.arange(T - size, T)
                slots = keep % size
                kc = kc.at[:, slots].set(k[:, T - size:])
                vc = vc.at[:, slots].set(v[:, T - size:])
            else:
                kc = kc.at[:, :T].set(k)
                vc = vc.at[:, :T].set(v)
            cache = {"k": kc, "v": vc}
    elif spec.kind == "rec":
        mix, conv_s, rec_s = rec_mod.rglru_block(p["rec"], h)
        cache = {"conv": conv_s, "rec": rec_s}
    elif spec.kind == "mlstm":
        mix, st = xlstm_mod.mlstm_block(p["mix"], h, cfg.num_heads)
        cache = {"c": st.c, "n": st.n, "m": st.m}
    elif spec.kind == "slstm":
        mix, st = xlstm_mod.slstm_block(p["mix"], h, cfg.num_heads)
        cache = {"c": st.c, "n": st.n, "h": st.h, "m": st.m}
    else:
        raise ValueError(spec.kind)
    if "post_attn_norm" in p:
        mix = rms_norm(mix, p["post_attn_norm"], cfg.norm_eps, zero_centered=_zc(cfg))
    x = x + mix
    x = constrain(x, ("data", None, None))
    if enc_out is not None and "cross" in p:
        cache["xk"] = jnp.einsum("bsd,dhk->bshk", enc_out, p["cross"]["wk"])
        cache["xv"] = jnp.einsum("bsd,dhk->bshk", enc_out, p["cross"]["wv"])
        hc = rms_norm(x, p["cross_norm"], cfg.norm_eps)
        cmix = attn_mod.gqa_attention(
            p["cross"], hc, positions=enc_pos, rope_theta=0.0, causal=False,
            kv_override=(cache["xk"], cache["xv"], enc_pos))
        x = x + cmix
    return _ffn_part(p, cfg, spec, x, router_sink), cache


def layer_prefill_chunk(p, cfg: ModelConfig, spec: LayerSpec, x: jnp.ndarray,
                        positions: jnp.ndarray, cache, cache_len, n_valid,
                        *, kv_bucket: Optional[int] = None,
                        router_sink: Optional[list] = None):
    """One fixed-shape prompt chunk through a layer, resuming at `cache_len`.

    x: (B, C, d) padded chunk (first `n_valid` rows real tokens, the rest
    padding whose K/V writes drop and whose outputs are garbage the caller
    ignores); positions: (B, C) absolute positions; cache: this layer's
    cache entry (from `init_layer_cache`, already holding the previous
    chunks); cache_len: tokens already ingested. Returns (x, new_cache).

    Because the chunk shape (B, C) is fixed, a jit of this function compiles
    once per (layer spec, `kv_bucket`) — prompt-length diversity costs zero
    recompiles; `kv_bucket` (a static power-of-two prefix covering
    cache_len + C) bounds the attended/expanded cache slice so per-chunk
    cost tracks the ingested prefix, at log2(max_seq) specializations.
    Only position-addressable attention layers support chunked ingestion:
    recurrent/xLSTM mixers carry sequential state through the whole prompt,
    and sliding windows smaller than max_seq ring-wrap the cache (absolute
    positions would collide), so both raise.
    """
    if spec.kind != "attn":
        raise NotImplementedError(
            f"chunked prefill supports attention layers only, got {spec.kind}")
    if spec.window:
        raise NotImplementedError(
            "chunked prefill requires global attention (ring-wrapped sliding-"
            "window caches lose the absolute positions chunks address)")
    p = gather_for_compute(p)
    h = rms_norm(x, p["pre_norm"], cfg.norm_eps, zero_centered=_zc(cfg))
    new_cache = dict(cache)
    if cfg.attention == "mla":
        mix, lat, pe = attn_mod.mla_prefill_chunk(
            p["attn"], h, positions, cache["latent"], cache["pe"], cache_len,
            n_valid, mla=cfg.mla, rope_theta=cfg.rope_theta,
            norm_eps=cfg.norm_eps, kv_bucket=kv_bucket)
        new_cache.update(latent=lat, pe=pe)
    else:
        mix, kc, vc = attn_mod.gqa_prefill_chunk(
            p["attn"], h, positions, cache["k"], cache["v"], cache_len,
            n_valid, rope_theta=cfg.rope_theta,
            logit_softcap=cfg.attn_logit_softcap, norm_eps=cfg.norm_eps,
            kv_bucket=kv_bucket)
        new_cache.update(k=kc, v=vc)
    if "post_attn_norm" in p:
        mix = rms_norm(mix, p["post_attn_norm"], cfg.norm_eps,
                       zero_centered=_zc(cfg))
    x = x + mix
    x = constrain(x, ("data", None, None))
    return _ffn_part(p, cfg, spec, x, router_sink), new_cache


def layer_decode(p, cfg: ModelConfig, spec: LayerSpec, x: jnp.ndarray,
                 cache, cache_len, *, src_len=None):
    """One-token layer step. x: (B, 1, d). Returns (x, new_cache).

    `cache_len` may be a scalar (all rows at one position — the single-
    request decode path) or a (B,) int32 vector (continuous batching: each
    row sits at its own position; KV insertion and attention masking are
    then per-row).
    """
    p = gather_for_compute(p)
    B = x.shape[0]
    h = rms_norm(x, p["pre_norm"], cfg.norm_eps, zero_centered=_zc(cfg))
    new_cache = dict(cache)
    if spec.kind == "attn":
        if cfg.attention == "mla":
            mix, lat, pe = attn_mod.mla_decode(
                p["attn"], h, cache["latent"], cache["pe"], cache_len,
                mla=cfg.mla, rope_theta=cfg.rope_theta, norm_eps=cfg.norm_eps)
            new_cache.update(latent=lat, pe=pe)
        else:
            size = cache["k"].shape[1]
            slot = jnp.mod(cache_len, size)
            positions = jnp.broadcast_to(
                jnp.asarray(cache_len).reshape(-1, 1), (B, 1))
            q = jnp.einsum("btd,dhk->bthk", h, p["attn"]["wq"])
            if "q_norm" in p["attn"]:
                q = rms_norm(q, p["attn"]["q_norm"], cfg.norm_eps)
            if cfg.rope_theta > 0:
                q = rope(q, positions, cfg.rope_theta)
            k, v = attn_mod.gqa_project_kv(p["attn"], h, positions,
                                           cfg.rope_theta, cfg.norm_eps)
            if jnp.asarray(cache_len).ndim:      # (B,): per-row ring slots
                rows = jnp.arange(B)
                kc = cache["k"].at[rows, jnp.asarray(slot, jnp.int32)].set(
                    k[:, 0])
                vc = cache["v"].at[rows, jnp.asarray(slot, jnp.int32)].set(
                    v[:, 0])
            else:
                kc = jax.lax.dynamic_update_slice_in_dim(
                    cache["k"], k, jnp.asarray(slot, jnp.int32), axis=1)
                vc = jax.lax.dynamic_update_slice_in_dim(
                    cache["v"], v, jnp.asarray(slot, jnp.int32), axis=1)
            valid = jnp.minimum(cache_len + 1, size)
            mix = attn_mod.decode_attention(
                q, kc, vc, valid, window=0,
                logit_softcap=cfg.attn_logit_softcap)
            mix = jnp.einsum("bthk,hkd->btd", mix, p["attn"]["wo"])
            new_cache.update(k=kc, v=vc)
    elif spec.kind == "rec":
        mix, conv_s, rec_s = rec_mod.rglru_block(
            p["rec"], h, conv_state=cache["conv"], rec_state=cache["rec"],
            decode=True)
        new_cache.update(conv=conv_s, rec=rec_s)
    elif spec.kind == "mlstm":
        st = xlstm_mod.MLSTMState(cache["c"], cache["n"], cache["m"])
        mix, st = xlstm_mod.mlstm_block(p["mix"], h, cfg.num_heads,
                                        state=st, decode=True)
        new_cache.update(c=st.c, n=st.n, m=st.m)
    elif spec.kind == "slstm":
        st = xlstm_mod.SLSTMState(cache["c"], cache["n"], cache["h"], cache["m"])
        mix, st = xlstm_mod.slstm_block(p["mix"], h, cfg.num_heads,
                                        state=st, decode=True)
        new_cache.update(c=st.c, n=st.n, h=st.h, m=st.m)
    else:
        raise ValueError(spec.kind)
    if "post_attn_norm" in p:
        mix = rms_norm(mix, p["post_attn_norm"], cfg.norm_eps, zero_centered=_zc(cfg))
    x = x + mix

    if "xk" in cache and "cross" in p:
        hc = rms_norm(x, p["cross_norm"], cfg.norm_eps)
        slen = src_len if src_len is not None else cache["xk"].shape[1]
        cmix, _, _ = attn_mod.gqa_decode(
            p["cross"], hc, cache["xk"], cache["xv"], slen,
            rope_theta=0.0, cross=True)
        x = x + cmix

    if "ffn_norm" in p:
        h2 = rms_norm(x, p["ffn_norm"], cfg.norm_eps, zero_centered=_zc(cfg))
        if spec.is_moe:
            flat = h2.reshape(B, -1)
            # capacity sized to expected load (4x slack), not worst case:
            # B*top_k made the decode dispatch buffer 32x oversized (qwen3
            # decode_32k: ~0.25 GB/layer of collectives on its einsums)
            m = cfg.moe
            cap = min(B * m.top_k,
                      max(8, -(-B * m.top_k // m.num_experts) * 4))
            out, _ = moe_mod.moe_grouped(p["moe"], flat, cfg.moe,
                                         capacity=cap)
            ff = out.reshape(B, 1, -1)
        else:
            act = "gelu" if cfg.family == "encdec" else "silu"
            ff = swiglu(h2, p["ffn"]["w_gate"], p["ffn"]["w_up"],
                        p["ffn"]["w_down"], act=act)
        if "post_ffn_norm" in p:
            ff = rms_norm(ff, p["post_ffn_norm"], cfg.norm_eps, zero_centered=_zc(cfg))
        x = x + ff
    return x, new_cache


# ---------------------------------------------------------------------------
# Whole model
# ---------------------------------------------------------------------------

class Model:
    """Config-driven decoder-only (or encoder-decoder) LM."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.prefix, self.unit, self.num_units, self.tail = build_layout(cfg)
        self.dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32

    # -- init ---------------------------------------------------------------
    def init_top(self, key) -> Dict[str, Any]:
        """The non-layer parameters: embedding, final norm, LM head."""
        cfg = self.cfg
        ks = jax.random.split(key, 8)
        params: Dict[str, Any] = {
            "embed": embed_init(ks[0], cfg.vocab_size, cfg.d_model, self.dtype),
            "final_norm": jnp.ones((cfg.d_model,), self.dtype),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = dense_init(ks[1], cfg.d_model, cfg.vocab_size,
                                           self.dtype)
        return params

    def layer_key(self, key, i: int):
        """Init key of absolute layer i — the key `init` builds it from, so
        a layer initialised alone equals its slice of the stacked tree."""
        ks = jax.random.split(key, 8)
        np_, nu = len(self.prefix), len(self.unit)
        if i < np_:
            return jax.random.fold_in(ks[2], i)
        j = i - np_
        if j < self.num_units * nu:
            u, k = divmod(j, nu)
            return jax.random.fold_in(ks[3], u * 131 + k)
        return jax.random.fold_in(ks[5], j - self.num_units * nu)

    def init(self, key) -> Dict[str, Any]:
        cfg = self.cfg
        params = self.init_top(key)
        np_, nu = len(self.prefix), len(self.unit)
        params["prefix"] = [init_layer(self.layer_key(key, i), cfg, s,
                                       self.dtype)
                            for i, s in enumerate(self.prefix)]
        t0 = np_ + self.num_units * nu
        params["tail"] = [init_layer(self.layer_key(key, t0 + i), cfg, s,
                                     self.dtype)
                          for i, s in enumerate(self.tail)]
        unit_params = []
        for j, spec in enumerate(self.unit):
            # vmapped over the repeats: the stacked tree is built in place,
            # never as a list of layers plus a stacked copy
            keys = jnp.stack([self.layer_key(key, np_ + u * nu + j)
                              for u in range(self.num_units)])
            unit_params.append(jax.vmap(
                lambda k, spec=spec: init_layer(k, cfg, spec, self.dtype))(
                    keys))
        params["unit"] = unit_params
        if cfg.is_encoder_decoder:
            params["encoder"] = self._init_encoder(
                jax.random.split(key, 8)[4])
        return params

    def _init_encoder(self, key):
        cfg = self.cfg
        spec = LayerSpec("attn", 0, False, 0)
        layers = [init_layer(jax.random.fold_in(key, i), cfg, spec, self.dtype,
                             with_cross=False)
                  for i in range(cfg.encoder_layers)]
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *layers)
        return {"layers": stacked,
                "final_norm": jnp.ones((cfg.d_model,), self.dtype)}

    # -- embedding / head -----------------------------------------------------
    def embed(self, params, tokens: jnp.ndarray,
              positions: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        x = params["embed"][tokens]
        if _zc(self.cfg):
            x = x * jnp.asarray(self.cfg.d_model ** 0.5, x.dtype)
        if self.cfg.abs_pos:
            if positions is None:
                positions = jnp.arange(tokens.shape[-1])
            x = x + sinusoidal_pos(positions, self.cfg.d_model).astype(x.dtype)
        return x

    def final_hidden(self, params, h):
        return rms_norm(h, params["final_norm"], self.cfg.norm_eps,
                        zero_centered=_zc(self.cfg))

    def lm_head_weight(self, params):
        return params["embed"].T if self.cfg.tie_embeddings else params["lm_head"]

    def logits(self, params, h: jnp.ndarray) -> jnp.ndarray:
        h = self.final_hidden(params, h)
        out = jnp.einsum("...d,dv->...v", h,
                         self.lm_head_weight(params)).astype(jnp.float32)
        if self.cfg.final_logit_softcap > 0:
            out = softcap(out, self.cfg.final_logit_softcap)
        return out

    # -- encoder (whisper) ------------------------------------------------------
    def encode(self, params, frames: jnp.ndarray) -> jnp.ndarray:
        """frames: (B, S_src, d) — stub frontend output (precomputed embeds)."""
        cfg = self.cfg
        enc = params["encoder"]
        positions = jnp.broadcast_to(jnp.arange(frames.shape[1])[None, :],
                                     frames.shape[:2])
        if cfg.abs_pos:
            frames = frames + sinusoidal_pos(positions, cfg.d_model).astype(
                frames.dtype)
        spec = LayerSpec("attn", 0, False, 0)

        def body(x, lp):
            return layer_forward(lp, cfg, spec, x, positions, causal=False), None

        x, _ = jax.lax.scan(body, frames, enc["layers"])
        return rms_norm(x, enc["final_norm"], cfg.norm_eps)

    # -- full-sequence forward ----------------------------------------------------
    def forward(self, params, tokens: Optional[jnp.ndarray] = None, *,
                embeds: Optional[jnp.ndarray] = None,
                enc_out: Optional[jnp.ndarray] = None,
                remat: bool = False) -> jnp.ndarray:
        """Returns final hidden states (B, T, d) (pre final-norm)."""
        cfg = self.cfg
        x = self.embed(params, tokens) if embeds is None else embeds
        x = constrain(x, ("data", None, None))
        B, T, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
        enc_pos = None
        if enc_out is not None:
            enc_pos = jnp.broadcast_to(jnp.arange(enc_out.shape[1])[None, :],
                                       enc_out.shape[:2])

        for p, spec in zip(params["prefix"], self.prefix):
            x = layer_forward(p, cfg, spec, x, positions,
                              enc_out=enc_out, enc_pos=enc_pos)

        def unit_body(x, unit_p):
            for j, spec in enumerate(self.unit):
                x = layer_forward(unit_p[j], cfg, spec, x, positions,
                                  enc_out=enc_out, enc_pos=enc_pos)
            return x, None

        if remat:
            unit_body = jax.checkpoint(unit_body)
        if self.num_units:
            x, _ = jax.lax.scan(unit_body, x, tuple(params["unit"]))
        for p, spec in zip(params["tail"], self.tail):
            x = layer_forward(p, cfg, spec, x, positions,
                              enc_out=enc_out, enc_pos=enc_pos)
        return x

    # -- prefill --------------------------------------------------------------
    def prefill(self, params, tokens: Optional[jnp.ndarray] = None, *,
                embeds: Optional[jnp.ndarray] = None, max_seq: int,
                enc_out: Optional[jnp.ndarray] = None):
        """Run the prompt, returning (last_logits, cache)."""
        cfg = self.cfg
        x = self.embed(params, tokens) if embeds is None else embeds
        x = constrain(x, ("data", None, None))
        B, T, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
        enc_pos = None
        if enc_out is not None:
            enc_pos = jnp.broadcast_to(jnp.arange(enc_out.shape[1])[None, :],
                                       enc_out.shape[:2])

        prefix_cache = []
        for p, spec in zip(params["prefix"], self.prefix):
            x, c = layer_prefill(p, cfg, spec, x, positions, max_seq,
                                 enc_out=enc_out, enc_pos=enc_pos)
            prefix_cache.append(c)

        def unit_body(x, unit_p):
            cs = []
            for j, spec in enumerate(self.unit):
                x, c = layer_prefill(unit_p[j], cfg, spec, x, positions,
                                     max_seq, enc_out=enc_out, enc_pos=enc_pos)
                cs.append(c)
            return x, tuple(cs)

        if self.num_units:
            x, unit_cache = jax.lax.scan(unit_body, x, tuple(params["unit"]))
            unit_cache = list(unit_cache)
        else:
            unit_cache = []
        tail_cache = []
        for p, spec in zip(params["tail"], self.tail):
            x, c = layer_prefill(p, cfg, spec, x, positions, max_seq,
                                 enc_out=enc_out, enc_pos=enc_pos)
            tail_cache.append(c)
        logits = self.logits(params, x[:, -1])
        cache = {"prefix": prefix_cache, "unit": unit_cache,
                 "tail": tail_cache, "len": jnp.asarray(T, jnp.int32)}
        return logits, cache

    # -- cache allocation (decode-only entry, e.g. dry-run serve_step) ---------
    def init_cache(self, batch: int, max_seq: int, src_len: int = 0):
        cfg = self.cfg
        cache = {
            "prefix": [init_layer_cache(cfg, s, batch, max_seq, self.dtype,
                                        src_len) for s in self.prefix],
            "tail": [init_layer_cache(cfg, s, batch, max_seq, self.dtype,
                                      src_len) for s in self.tail],
            "unit": [],
            "len": jnp.zeros((), jnp.int32),
        }
        for spec in self.unit:
            per = init_layer_cache(cfg, spec, batch, max_seq, self.dtype, src_len)
            cache["unit"].append(jax.tree.map(
                lambda x: jnp.broadcast_to(x[None], (self.num_units,) + x.shape),
                per))
        return cache

    # -- decode step -------------------------------------------------------------
    def decode_step(self, params, token: jnp.ndarray, cache, *,
                    src_len=None):
        """token: (B,) int32 (or (B, d) embeds). Returns (logits, new_cache)."""
        cfg = self.cfg
        cache_len = cache["len"]
        if token.ndim == 1:
            pos = jnp.broadcast_to(jnp.asarray(cache_len).reshape(-1, 1),
                                   (token.shape[0], 1))
            x = self.embed(params, token[:, None], positions=pos)
        else:
            x = token[:, None, :]

        new_prefix = []
        for p, spec, c in zip(params["prefix"], self.prefix, cache["prefix"]):
            x, c2 = layer_decode(p, cfg, spec, x, c, cache_len, src_len=src_len)
            new_prefix.append(c2)

        def unit_body(x, scanned):
            unit_p, unit_c = scanned
            new_cs = []
            for j, spec in enumerate(self.unit):
                x, c2 = layer_decode(unit_p[j], cfg, spec, x, unit_c[j],
                                     cache_len, src_len=src_len)
                new_cs.append(c2)
            return x, tuple(new_cs)

        if self.num_units:
            x, new_unit = jax.lax.scan(
                unit_body, x, (tuple(params["unit"]), tuple(cache["unit"])))
            new_unit = list(new_unit)
        else:
            new_unit = []

        new_tail = []
        for p, spec, c in zip(params["tail"], self.tail, cache["tail"]):
            x, c2 = layer_decode(p, cfg, spec, x, c, cache_len, src_len=src_len)
            new_tail.append(c2)
        logits = self.logits(params, x[:, 0])
        new_cache = {"prefix": new_prefix, "unit": new_unit, "tail": new_tail,
                     "len": cache_len + 1}
        return logits, new_cache
