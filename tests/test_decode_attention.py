"""One-token decode attention, as batched serving runs it: rows at ragged
cache lengths, each row's new K/V (or MLA latent and rotary key) inserted at
its own position, then attention over that row's valid positions only.

GQA runs inside `layer_decode` (the served `pre_decode` programs call it on
the FFN-stripped layer); MLA through `attention.mla_decode`, weight-absorbed.
Each is checked against attention written out here in float32 numpy from the
layer's parameters: a plain softmax over the valid positions, and for MLA
the keys and values re-expanded from the latent cache."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import reduce_config
from repro.configs.registry import get_config
from repro.models import attention as attn_mod
from repro.models.transformer import (LayerSpec, init_layer, layer_decode,
                                      split_ffn_params)

CLENS = [[0, 0], [3, 7], [15, 1], [16, 16]]
CLEN_IDS = ["empty", "ragged", "mixed", "full"]


def _rms(x, w, eps):
    x = np.asarray(x, np.float32)
    return (x / np.sqrt(np.mean(x * x, -1, keepdims=True) + eps)
            * np.asarray(w, np.float32))


def _rope(x, pos, theta):
    """x: (B, H, D) at positions pos (B,): rotary halves, as the model's."""
    half = x.shape[-1] // 2
    freq = theta ** (-np.arange(half, dtype=np.float32) / half)
    ang = np.asarray(pos, np.float32)[:, None, None] * freq
    x1, x2 = x[..., :half], x[..., half:]
    return np.concatenate([x1 * np.cos(ang) - x2 * np.sin(ang),
                           x2 * np.cos(ang) + x1 * np.sin(ang)], -1)


def _softmax_rows(s, n_valid):
    """Softmax over the last axis, positions >= n_valid[b] excluded."""
    mask = np.arange(s.shape[-1])[None, None, :] < np.asarray(
        n_valid)[:, None, None]
    s = np.where(mask, s, -np.inf)
    e = np.exp(s - s.max(-1, keepdims=True))
    return e / e.sum(-1, keepdims=True)


# ---------------------------------------------------------------------------
# GQA
# ---------------------------------------------------------------------------


def _gqa_layer(softcap, window=0):
    cfg = reduce_config(get_config("olmoe-1b-7b"), layers=1, d_model=64,
                        heads=4, kv_heads=2, vocab=64, experts=4)
    cfg = dataclasses.replace(cfg, attn_logit_softcap=softcap)
    spec = LayerSpec("attn", window, False, 0)
    p = init_layer(jax.random.PRNGKey(5), cfg, spec, jnp.float32)
    p, spec = split_ffn_params(p, spec)
    return cfg, spec, p


def _gqa_oracle(cfg, p, x, kc, vc, clen):
    """Attention half of one decode step: ring insert at clen mod S, then a
    float32 softmax over each row's min(clen + 1, S) valid slots."""
    a = {k: np.asarray(v, np.float32) for k, v in p["attn"].items()}
    B, S = kc.shape[:2]
    eps, theta = cfg.norm_eps, cfg.rope_theta
    h = _rms(x[:, 0], p["pre_norm"], eps)                       # (B, d)
    q = np.einsum("bd,dhk->bhk", h, a["wq"])
    k = np.einsum("bd,dhk->bhk", h, a["wk"])
    v = np.einsum("bd,dhk->bhk", h, a["wv"])
    if "q_norm" in a:
        q, k = _rms(q, a["q_norm"], eps), _rms(k, a["k_norm"], eps)
    q, k = _rope(q, clen, theta), _rope(k, clen, theta)
    kc, vc = np.array(kc, np.float32), np.array(vc, np.float32)
    rows, slot = np.arange(B), np.asarray(clen) % S
    kc[rows, slot], vc[rows, slot] = k, v
    Hq, Hkv, D = q.shape[1], kc.shape[2], q.shape[2]
    kv_of = np.arange(Hq) // (Hq // Hkv)
    s = np.einsum("bhd,bshd->bhs", q, kc[:, :, kv_of]) * D ** -0.5
    if cfg.attn_logit_softcap > 0:
        cap = cfg.attn_logit_softcap
        s = cap * np.tanh(s / cap)
    pr = _softmax_rows(s, np.minimum(np.asarray(clen) + 1, S))
    out = np.einsum("bhs,bshd->bhd", pr, vc[:, :, kv_of])
    mix = np.einsum("bhd,hde->be", out, a["wo"])
    if "post_attn_norm" in p:               # soft-capped families post-norm
        mix = _rms(mix, p["post_attn_norm"], eps)
    return np.asarray(x, np.float32) + mix[:, None], kc, vc


def _check_gqa(softcap, clens, window=0, S=16):
    cfg, spec, p = _gqa_layer(softcap, window)
    B, Hkv, D = len(clens), cfg.num_kv_heads, cfg.resolved_head_dim
    rng = np.random.default_rng(sum(clens) + int(softcap) + 1)
    x = jnp.asarray(rng.standard_normal((B, 1, cfg.d_model)), jnp.float32)
    kc = jnp.asarray(rng.standard_normal((B, S, Hkv, D)), jnp.float32)
    vc = jnp.asarray(rng.standard_normal((B, S, Hkv, D)), jnp.float32)
    clen = jnp.asarray(clens, jnp.int32)
    y, cache = jax.jit(lambda p, x, c, n: layer_decode(p, cfg, spec, x, c, n)
                       )(p, x, {"k": kc, "v": vc}, clen)
    want, wk, wv = _gqa_oracle(cfg, p, x, kc, vc, np.asarray(clens))
    # the new row within f32 rounding of the oracle's projection of it,
    # every other row untouched
    np.testing.assert_allclose(np.asarray(cache["k"]), wk, rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(cache["v"]), wv, rtol=1e-5,
                               atol=1e-5)
    untouched = np.ones((B, S), bool)
    untouched[np.arange(B), np.asarray(clens) % S] = False
    np.testing.assert_array_equal(np.asarray(cache["k"])[untouched],
                                  np.asarray(kc)[untouched])
    np.testing.assert_allclose(np.asarray(y), want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("clens", CLENS, ids=CLEN_IDS)
@pytest.mark.parametrize("softcap", [0.0, 30.0])
def test_gqa_decode_ragged_rows_match_softmax_over_valid_positions(clens,
                                                                   softcap):
    """Ragged (B,) cache lengths, empty caches included, and the full-cache
    edge: at clen == S the ring wraps the insert to slot 0."""
    _check_gqa(softcap, clens)


def test_gqa_decode_sliding_window_ring():
    """A cache sized to the sliding window is the window: past a full wrap
    the insert overwrites the oldest entry and attention covers the S
    surviving ones."""
    _check_gqa(0.0, [11, 25], window=8, S=8)


# ---------------------------------------------------------------------------
# MLA
# ---------------------------------------------------------------------------


def _mla_oracle(cfg, a, h, lat, pe, clen):
    """Latent re-expansion: each cached latent row expanded through wkv_b to
    per-head keys and values, the rotary key shared by every head."""
    m = cfg.mla
    R, nope = m.kv_lora_rank, m.qk_nope_head_dim
    eps, theta = cfg.norm_eps, cfg.rope_theta
    a = {k: np.asarray(v, np.float32) for k, v in a.items()}
    h = np.asarray(h, np.float32)[:, 0]
    B = h.shape[0]
    kv_a = h @ a["wkv_a"]
    c_new = _rms(kv_a[:, :R], a["kv_a_norm"], eps)
    pe_new = _rope(kv_a[:, None, R:], clen, theta)[:, 0]
    lat, pe = np.array(lat, np.float32), np.array(pe, np.float32)
    rows = np.arange(B)
    lat[rows, clen], pe[rows, clen, 0] = c_new, pe_new
    if "wq_a" in a:
        q = np.einsum("br,rhk->bhk", _rms(h @ a["wq_a"], a["q_a_norm"], eps),
                      a["wq_b"])
    else:
        q = np.einsum("bd,dhk->bhk", h, a["wq"])
    q_nope, q_pe = q[..., :nope], _rope(q[..., nope:], clen, theta)
    k_nope = np.einsum("bsr,rhk->bshk", lat, a["wkv_b"][..., :nope])
    v = np.einsum("bsr,rhk->bshk", lat, a["wkv_b"][..., nope:])
    s = (np.einsum("bhk,bshk->bhs", q_nope, k_nope)
         + np.einsum("bhk,bsk->bhs", q_pe, pe[:, :, 0]))
    s = s * (nope + m.qk_rope_head_dim) ** -0.5
    pr = _softmax_rows(s, np.asarray(clen) + 1)
    out = np.einsum("bhs,bshv->bhv", pr, v)
    return np.einsum("bhv,hvd->bd", out, a["wo"])[:, None], lat, pe


@pytest.mark.parametrize("clens", CLENS, ids=CLEN_IDS)
def test_mla_decode_absorbed_matches_latent_reexpansion(clens):
    """Weight-absorbed MLA decode at ragged (B,) cache lengths equals
    attention over keys and values re-expanded from the latent cache, with
    each row's new latent and rotary key written at its own position."""
    cfg = reduce_config(get_config("deepseek-v2-lite"), layers=1,
                        d_model=64, heads=4, vocab=64, experts=4)
    spec = LayerSpec("attn", 0, False, 0)
    a = init_layer(jax.random.PRNGKey(6), cfg, spec, jnp.float32)["attn"]
    B, S, m = len(clens), 32, cfg.mla
    rng = np.random.default_rng(sum(clens) + 11)
    h = jnp.asarray(rng.standard_normal((B, 1, cfg.d_model)), jnp.float32)
    lat = jnp.asarray(rng.standard_normal((B, S, m.kv_lora_rank)),
                      jnp.float32)
    pe = jnp.asarray(rng.standard_normal((B, S, 1, m.qk_rope_head_dim)),
                     jnp.float32)
    clen = jnp.asarray(clens, jnp.int32)
    out, lat2, pe2 = jax.jit(lambda a, h, l, p, n: attn_mod.mla_decode(
        a, h, l, p, n, mla=m, rope_theta=cfg.rope_theta,
        norm_eps=cfg.norm_eps))(a, h, lat, pe, clen)
    want, wl, wp = _mla_oracle(cfg, a, h, lat, pe, np.asarray(clens))
    np.testing.assert_allclose(np.asarray(lat2), wl, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(pe2), wp, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-4, atol=1e-4)
