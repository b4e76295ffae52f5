"""Attention: chunked (flash-style) GQA, sliding windows, softcap, MLA.

All prefill/train attention goes through `flash_attention`, a pure-JAX
online-softmax implementation that scans over query and key/value blocks so
the (T x S) score matrix is never materialized — this is what makes the 32k
prefill shapes compile within HBM budgets in the dry-run, and it mirrors the
structure a Pallas flash kernel would use on real TPU.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.layers import rope, rms_norm, softcap

NEG_INF = -2.0 ** 30  # large-finite: avoids NaN from (-inf) - (-inf)


def _pad_to(x: jnp.ndarray, axis: int, multiple: int):
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x, size
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), size


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True,
                    window: int = 0,
                    logit_softcap: float = 0.0,
                    scale: Optional[float] = None,
                    q_offset=0,
                    q_chunk: int = 512,
                    kv_chunk: int = 1024) -> jnp.ndarray:
    """Online-softmax attention.

    q: (B, Tq, Hq, D); k, v: (B, S, Hkv, D); returns (B, Tq, Hq, D).
    Hq must be a multiple of Hkv (GQA). `window > 0` = sliding window.
    `q_offset`: absolute position of q[0] (prefill continuation / decode);
    may be a traced int32 scalar (chunked prefill passes the cursor offset
    as an operand so the chunk jit never re-specializes on position).
    """
    B, Tq, Hq, D = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    Dv = v.shape[-1]                      # may differ from D (MLA)
    G = Hq // Hkv
    if scale is None:
        scale = D ** -0.5

    q_chunk = min(q_chunk, Tq)
    kv_chunk = min(kv_chunk, S)

    qp, Tq0 = _pad_to(q, 1, q_chunk)
    kp, S0 = _pad_to(k, 1, kv_chunk)
    vp, _ = _pad_to(v, 1, kv_chunk)
    nq = qp.shape[1] // q_chunk
    nk = kp.shape[1] // kv_chunk

    # (nq, B, qc, Hkv, G, D)
    qb = qp.reshape(B, nq, q_chunk, Hkv, G, D).transpose(1, 0, 2, 3, 4, 5)
    kb = kp.reshape(B, nk, kv_chunk, Hkv, D).transpose(1, 0, 2, 3, 4)
    vb = vp.reshape(B, nk, kv_chunk, Hkv, Dv).transpose(1, 0, 2, 3, 4)

    q_pos_base = jnp.arange(q_chunk)
    k_pos_base = jnp.arange(kv_chunk)

    def q_step(_, qi_qblk):
        qi, qblk = qi_qblk
        qblk = qblk.astype(jnp.float32) * scale
        q_pos = q_offset + qi * q_chunk + q_pos_base          # (qc,)
        q_valid = (qi * q_chunk + q_pos_base) < Tq0

        def kv_step(carry, ki_kv):
            acc, m, l = carry
            ki, kblk, vblk = ki_kv
            k_pos = ki * kv_chunk + k_pos_base                # (kc,)
            k_valid = k_pos < S0

            def compute(carry):
                acc, m, l = carry
                # scores: (B, Hkv, G, qc, kc)
                s = jnp.einsum("bqhgd,bkhd->bhgqk", qblk,
                               kblk.astype(jnp.float32))
                if logit_softcap > 0.0:
                    s = softcap(s, logit_softcap)
                mask = k_valid[None, :]
                if causal:
                    mask = mask & (k_pos[None, :] <= q_pos[:, None])
                if window > 0:
                    mask = mask & (k_pos[None, :] > q_pos[:, None] - window)
                mask = mask & q_valid[:, None]
                s = jnp.where(mask[None, None, None], s, NEG_INF)
                m_new = jnp.maximum(m, jnp.max(s, axis=-1))
                p = jnp.exp(s - m_new[..., None])
                corr = jnp.exp(m - m_new)
                l_new = l * corr + jnp.sum(p, axis=-1)
                pv = jnp.einsum("bhgqk,bkhd->bhgqd", p,
                                vblk.astype(jnp.float32))
                return acc * corr[..., None] + pv, m_new, l_new

            # block skipping: fully-masked (future / out-of-window) kv blocks
            # never execute — the MXU work drops to the active-block count
            k_lo = ki * kv_chunk
            k_hi = k_lo + kv_chunk - 1
            q_lo = q_pos[0]
            q_hi = q_pos[-1]
            needed = jnp.asarray(True)
            if causal:
                needed = needed & (k_lo <= q_hi)
            if window > 0:
                needed = needed & (k_hi > q_lo - window)
            new_carry = jax.lax.cond(needed, compute, lambda c: c, carry)
            return new_carry, None

        acc0 = jnp.zeros((B, Hkv, G, q_chunk, Dv), jnp.float32)
        m0 = jnp.full((B, Hkv, G, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Hkv, G, q_chunk), jnp.float32)
        (acc, m, l), _ = jax.lax.scan(
            kv_step, (acc0, m0, l0), (jnp.arange(nk), kb, vb))
        out = acc / jnp.maximum(l[..., None], 1e-20)
        # (B, Hkv, G, qc, D) -> (B, qc, Hkv, G, D)
        return None, out.transpose(0, 3, 1, 2, 4)

    _, outs = jax.lax.scan(q_step, None, (jnp.arange(nq), qb))
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, nq * q_chunk, Hq, Dv)
    return out[:, :Tq0].astype(q.dtype)


def decode_attention(q: jnp.ndarray, k_cache: jnp.ndarray, v_cache: jnp.ndarray,
                     cache_len: jnp.ndarray, *,
                     window: int = 0,
                     logit_softcap: float = 0.0,
                     scale: Optional[float] = None) -> jnp.ndarray:
    """One-token attention against a KV cache.

    q: (B, 1, Hq, D); caches: (B, S, Hkv, D); cache_len: () or (B,) int32 —
    number of valid cache entries *including* the current token's K/V
    (caller inserts before attending). Returns (B, 1, Hq, D).
    """
    B, _, Hq, D = q.shape
    S, Hkv = k_cache.shape[1], k_cache.shape[2]
    Dv = v_cache.shape[-1]
    G = Hq // Hkv
    if scale is None:
        scale = D ** -0.5
    cache_len = jnp.asarray(cache_len)
    if cache_len.ndim == 0:
        cache_len = jnp.broadcast_to(cache_len, (B,))

    qf = q.reshape(B, Hkv, G, D).astype(jnp.float32) * scale
    s = jnp.einsum("bhgd,bkhd->bhgk", qf, k_cache.astype(jnp.float32))
    if logit_softcap > 0.0:
        s = softcap(s, logit_softcap)
    pos = jnp.arange(S)[None, :]                       # (1, S)
    valid = pos < cache_len[:, None]
    if window > 0:
        valid = valid & (pos >= cache_len[:, None] - window)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgk,bkhd->bhgd", p, v_cache.astype(jnp.float32))
    return out.reshape(B, 1, Hq, Dv).astype(q.dtype)


# ---------------------------------------------------------------------------
# Full GQA attention layer (projections + rope + flash / decode)
# ---------------------------------------------------------------------------

def init_gqa_params(key, d_model: int, num_heads: int, num_kv_heads: int,
                    head_dim: int, dtype=jnp.bfloat16, qk_norm: bool = False):
    from repro.models.layers import trunc_normal
    ks = jax.random.split(key, 4)
    p = {
        "wq": trunc_normal(ks[0], (d_model, num_heads, head_dim), d_model ** -0.5, dtype),
        "wk": trunc_normal(ks[1], (d_model, num_kv_heads, head_dim), d_model ** -0.5, dtype),
        "wv": trunc_normal(ks[2], (d_model, num_kv_heads, head_dim), d_model ** -0.5, dtype),
        "wo": trunc_normal(ks[3], (num_heads, head_dim, d_model),
                           (num_heads * head_dim) ** -0.5, dtype),
    }
    if qk_norm:
        p["q_norm"] = jnp.ones((head_dim,), dtype)
        p["k_norm"] = jnp.ones((head_dim,), dtype)
    return p


def gqa_attention(params, x: jnp.ndarray, *, positions: jnp.ndarray,
                  rope_theta: float, window: int = 0, causal: bool = True,
                  logit_softcap: float = 0.0, scale: Optional[float] = None,
                  norm_eps: float = 1e-6,
                  kv_override: Optional[tuple] = None) -> jnp.ndarray:
    """Prefill/train attention. x: (B, T, d). kv_override: cross-attention."""
    q = jnp.einsum("btd,dhk->bthk", x, params["wq"])
    if kv_override is None:
        k = jnp.einsum("btd,dhk->bthk", x, params["wk"])
        v = jnp.einsum("btd,dhk->bthk", x, params["wv"])
        kv_pos = positions
    else:
        k, v, kv_pos = kv_override
    if "q_norm" in params:
        q = rms_norm(q, params["q_norm"], norm_eps)
        if kv_override is None:
            k = rms_norm(k, params["k_norm"], norm_eps)
    if rope_theta > 0:
        q = rope(q, positions, rope_theta)
        if kv_override is None:
            k = rope(k, kv_pos, rope_theta)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          logit_softcap=logit_softcap, scale=scale)
    return jnp.einsum("bthk,hkd->btd", out, params["wo"])


def gqa_project_kv(params, x: jnp.ndarray, positions: jnp.ndarray,
                   rope_theta: float, norm_eps: float = 1e-6):
    """Project k/v for cache insertion (decode path)."""
    k = jnp.einsum("btd,dhk->bthk", x, params["wk"])
    v = jnp.einsum("btd,dhk->bthk", x, params["wv"])
    if "k_norm" in params:
        k = rms_norm(k, params["k_norm"], norm_eps)
    if rope_theta > 0:
        k = rope(k, positions, rope_theta)
    return k, v


def gqa_decode(params, x: jnp.ndarray, k_cache, v_cache, cache_len, *,
               rope_theta: float, window: int = 0, logit_softcap: float = 0.0,
               scale: Optional[float] = None, norm_eps: float = 1e-6,
               cross: bool = False):
    """One-token attention. x: (B, 1, d). Returns (out, k_cache, v_cache).

    For self-attention the new token's K/V is inserted at `cache_len`.
    For cross-attention (`cross=True`) the caches are read-only.
    """
    B = x.shape[0]
    positions = jnp.broadcast_to(jnp.asarray(cache_len).reshape(-1, 1), (B, 1))
    q = jnp.einsum("btd,dhk->bthk", x, params["wq"])
    if "q_norm" in params:
        q = rms_norm(q, params["q_norm"], norm_eps)
    if rope_theta > 0 and not cross:
        q = rope(q, positions, rope_theta)
    if not cross:
        k, v = gqa_project_kv(params, x, positions, rope_theta, norm_eps)
        idx = jnp.asarray(cache_len, jnp.int32)
        k_cache = jax.lax.dynamic_update_slice_in_dim(k_cache, k, idx, axis=1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(v_cache, v, idx, axis=1)
        valid = idx + 1
    else:
        valid = cache_len
    out = decode_attention(q, k_cache, v_cache, valid, window=window,
                           logit_softcap=logit_softcap, scale=scale)
    out = jnp.einsum("bthk,hkd->btd", out, params["wo"])
    return out, k_cache, v_cache


def gqa_prefill_chunk(params, h: jnp.ndarray, positions: jnp.ndarray,
                      k_cache: jnp.ndarray, v_cache: jnp.ndarray,
                      cache_len, n_valid, *,
                      rope_theta: float, logit_softcap: float = 0.0,
                      scale: Optional[float] = None, norm_eps: float = 1e-6,
                      kv_bucket: Optional[int] = None):
    """One fixed-shape prompt chunk of GQA attention, resuming at `cache_len`.

    h: (B, C, d) normed hidden states of a padded chunk whose first `n_valid`
    rows are real tokens; positions: (B, C) absolute positions
    (cache_len .. cache_len + C - 1, shared across rows); caches:
    (B, S, Hkv, D) addressed by absolute position (S = max_seq, no ring
    reuse). The chunk's K/V rows are scattered at their absolute positions —
    padding rows write out of range and DROP, so later chunks and decode can
    never read garbage — then the chunk's queries attend causally over
    everything ingested so far through the SAME `flash_attention` kernel the
    monolithic prefill uses (`q_offset` supplies the chunk's start offset).
    Identical kernel + fp32 accumulation over a zero-padded tail is what
    keeps chunked logits bit-exact versus the monolithic path.

    `kv_bucket` (static): attend over only the leading `kv_bucket` cache
    rows instead of all S — the caller picks a power-of-two prefix covering
    `cache_len + C`, so attention cost tracks the INGESTED prefix, not
    max_seq, at a log-bounded number of extra jit specializations.

    Returns (mix (B, C, d), k_cache, v_cache).
    """
    B, C, _ = h.shape
    S = k_cache.shape[1]
    q = jnp.einsum("btd,dhk->bthk", h, params["wq"])
    if "q_norm" in params:
        q = rms_norm(q, params["q_norm"], norm_eps)
    if rope_theta > 0:
        q = rope(q, positions, rope_theta)
    k, v = gqa_project_kv(params, h, positions, rope_theta, norm_eps)
    idx = jnp.where(jnp.arange(C) < n_valid, positions[0], S)   # pad -> drop
    k_cache = k_cache.at[:, idx].set(k, mode="drop")
    v_cache = v_cache.at[:, idx].set(v, mode="drop")
    kb = S if kv_bucket is None else min(kv_bucket, S)
    out = flash_attention(q, k_cache[:, :kb], v_cache[:, :kb], causal=True,
                          logit_softcap=logit_softcap, scale=scale,
                          q_offset=cache_len)
    return jnp.einsum("bthk,hkd->btd", out, params["wo"]), k_cache, v_cache


# ---------------------------------------------------------------------------
# Multi-head Latent Attention (DeepSeek-V2 / MiniCPM3)
# ---------------------------------------------------------------------------

def init_mla_params(key, d_model: int, num_heads: int, mla, dtype=jnp.bfloat16):
    from repro.models.layers import trunc_normal
    ks = jax.random.split(key, 8)
    qk_head = mla.qk_nope_head_dim + mla.qk_rope_head_dim
    p = {}
    if mla.q_lora_rank:
        p["wq_a"] = trunc_normal(ks[0], (d_model, mla.q_lora_rank), d_model ** -0.5, dtype)
        p["q_a_norm"] = jnp.ones((mla.q_lora_rank,), dtype)
        p["wq_b"] = trunc_normal(ks[1], (mla.q_lora_rank, num_heads, qk_head),
                                 mla.q_lora_rank ** -0.5, dtype)
    else:
        p["wq"] = trunc_normal(ks[0], (d_model, num_heads, qk_head), d_model ** -0.5, dtype)
    # joint KV down-projection: latent + shared rope key
    p["wkv_a"] = trunc_normal(ks[2], (d_model, mla.kv_lora_rank + mla.qk_rope_head_dim),
                              d_model ** -0.5, dtype)
    p["kv_a_norm"] = jnp.ones((mla.kv_lora_rank,), dtype)
    p["wkv_b"] = trunc_normal(
        ks[3], (mla.kv_lora_rank, num_heads, mla.qk_nope_head_dim + mla.v_head_dim),
        mla.kv_lora_rank ** -0.5, dtype)
    p["wo"] = trunc_normal(ks[4], (num_heads, mla.v_head_dim, d_model),
                           (num_heads * mla.v_head_dim) ** -0.5, dtype)
    return p


def _mla_q(params, x, positions, mla, rope_theta, norm_eps):
    """The MLA query path (LoRA or dense projection, nope/pe split, rope on
    the pe half) — shared by the monolithic and chunked prefill paths so a
    query-side change can never diverge them (decode keeps the halves
    separate for the weight-absorbed trick)."""
    nope = mla.qk_nope_head_dim
    if "wq_a" in params:
        qa = rms_norm(jnp.einsum("btd,dr->btr", x, params["wq_a"]),
                      params["q_a_norm"], norm_eps)
        q = jnp.einsum("btr,rhk->bthk", qa, params["wq_b"])
    else:
        q = jnp.einsum("btd,dhk->bthk", x, params["wq"])
    q_nope, q_pe = q[..., :nope], q[..., nope:]
    q_pe = rope(q_pe, positions, rope_theta)
    return jnp.concatenate([q_nope, q_pe], axis=-1)


def _mla_qkv(params, x, positions, mla, rope_theta, norm_eps,
             latent=None, latent_pos=None):
    """Compute q, k, v from hidden states (and optionally a cached latent)."""
    nope, rope_d = mla.qk_nope_head_dim, mla.qk_rope_head_dim
    q = _mla_q(params, x, positions, mla, rope_theta, norm_eps)

    if latent is None:
        kv_a = jnp.einsum("btd,dr->btr", x, params["wkv_a"])
        c_kv, k_pe_flat = kv_a[..., :mla.kv_lora_rank], kv_a[..., mla.kv_lora_rank:]
        c_kv = rms_norm(c_kv, params["kv_a_norm"], norm_eps)
        k_pe = rope(k_pe_flat[..., None, :], positions, rope_theta)  # (B,T,1,rope)
        latent_out = (c_kv, k_pe)
    else:
        c_kv, k_pe = latent
        latent_out = latent
    kv = jnp.einsum("btr,rhk->bthk", c_kv, params["wkv_b"])
    k_nope, v = kv[..., :nope], kv[..., nope:]
    S = k_nope.shape[1]
    H = k_nope.shape[2]
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (k_pe.shape[0], S, H, rope_d))],
                        axis=-1)
    return q, k, v, latent_out


def mla_attention(params, x: jnp.ndarray, *, positions, mla, rope_theta: float,
                  norm_eps: float = 1e-6, causal: bool = True,
                  window: int = 0) -> jnp.ndarray:
    q, k, v, _ = _mla_qkv(params, x, positions, mla, rope_theta, norm_eps)
    scale = (mla.qk_nope_head_dim + mla.qk_rope_head_dim) ** -0.5
    out = flash_attention(q, k, v, causal=causal, window=window, scale=scale)
    return jnp.einsum("bthk,hkd->btd", out, params["wo"])


def mla_prefill_chunk(params, h: jnp.ndarray, positions: jnp.ndarray,
                      latent_cache, pe_cache, cache_len, n_valid, *,
                      mla, rope_theta: float, norm_eps: float = 1e-6,
                      kv_bucket: Optional[int] = None):
    """One fixed-shape prompt chunk of MLA attention, resuming at `cache_len`.

    h: (B, C, d) normed hidden states of a padded chunk (first `n_valid` rows
    real); latent_cache: (B, S, kv_lora_rank); pe_cache: (B, S, 1, rope_dim).
    The chunk's compressed latent + shared rope key rows land at their
    absolute positions (padding rows drop), then K/V for the ingested
    positions are re-expanded from the latent cache — the prefill-side
    expansion, not decode's weight-absorbed trick — and the chunk's queries
    attend with `flash_attention(q_offset=cache_len)`. The cache stores the
    same post-norm bf16 latent the monolithic path attends with, so the two
    paths stay bit-exact.

    `kv_bucket` (static): expand/attend over only the leading `kv_bucket`
    cache rows — a power-of-two prefix covering `cache_len + C` — so the
    per-chunk expansion einsum is O(ingested prefix), not O(max_seq), at a
    log-bounded number of extra jit specializations.

    Returns (mix (B, C, d), latent_cache, pe_cache).
    """
    B, C, _ = h.shape
    nope, rope_d = mla.qk_nope_head_dim, mla.qk_rope_head_dim
    R = mla.kv_lora_rank
    S = latent_cache.shape[1]
    q = _mla_q(params, h, positions, mla, rope_theta, norm_eps)

    kv_a = jnp.einsum("btd,dr->btr", h, params["wkv_a"])
    c_kv = rms_norm(kv_a[..., :R], params["kv_a_norm"], norm_eps)
    k_pe = rope(kv_a[..., R:][..., None, :], positions, rope_theta)
    idx = jnp.where(jnp.arange(C) < n_valid, positions[0], S)   # pad -> drop
    latent_cache = latent_cache.at[:, idx].set(
        c_kv.astype(latent_cache.dtype), mode="drop")
    pe_cache = pe_cache.at[:, idx].set(k_pe.astype(pe_cache.dtype),
                                       mode="drop")

    kb = S if kv_bucket is None else min(kv_bucket, S)
    kv = jnp.einsum("bsr,rhk->bshk", latent_cache[:, :kb], params["wkv_b"])
    k_nope, v = kv[..., :nope], kv[..., nope:]
    H = k_nope.shape[2]
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(pe_cache[:, :kb], (B, kb, H, rope_d))],
        axis=-1)
    scale = (nope + rope_d) ** -0.5
    out = flash_attention(q, k, v, causal=True, scale=scale,
                          q_offset=cache_len)
    mix = jnp.einsum("bthk,hkd->btd", out, params["wo"])
    return mix, latent_cache, pe_cache


def mla_decode(params, x: jnp.ndarray, latent_cache, pe_cache, cache_len, *,
               mla, rope_theta: float, norm_eps: float = 1e-6):
    """MLA decode with compressed cache, WEIGHT-ABSORBED (DeepSeek-V2 trick).

    latent_cache: (B, S, kv_lora_rank); pe_cache: (B, S, 1, rope_dim).
    Instead of re-expanding K/V from the latent over the whole cache each
    token (O(S * H * (nope+v)) per cached row — measured 8.4 s/token of
    collective+compute on minicpm3 decode_32k), the up-projection wkv_b is
    absorbed into the query/output sides:

      score_nope[h,s] = (q_nope[h] @ Wk[h]) @ c[s]       (q-side absorb)
      out[h] = (sum_s p[h,s] c[s]) @ Wv[h]               (o-side absorb)

    so per-token work on the cache is O(S * H * R) with R = kv_lora_rank.
    """
    B = x.shape[0]
    nope, rope_d = mla.qk_nope_head_dim, mla.qk_rope_head_dim
    R = mla.kv_lora_rank
    positions = jnp.broadcast_to(jnp.asarray(cache_len).reshape(-1, 1), (B, 1))
    kv_a = jnp.einsum("btd,dr->btr", x, params["wkv_a"])
    c_new = rms_norm(kv_a[..., :R], params["kv_a_norm"], norm_eps)
    pe_new = rope(kv_a[..., R:][..., None, :], positions, rope_theta)
    idx = jnp.asarray(cache_len, jnp.int32)
    if idx.ndim:                              # (B,): per-row cache positions
        rows = jnp.arange(B)
        latent_cache = latent_cache.at[rows, idx].set(c_new[:, 0])
        pe_cache = pe_cache.at[rows, idx].set(pe_new[:, 0])
    else:
        latent_cache = jax.lax.dynamic_update_slice_in_dim(
            latent_cache, c_new, idx, axis=1)
        pe_cache = jax.lax.dynamic_update_slice_in_dim(pe_cache, pe_new,
                                                       idx, axis=1)

    # query
    if "wq_a" in params:
        qa = rms_norm(jnp.einsum("btd,dr->btr", x, params["wq_a"]),
                      params["q_a_norm"], norm_eps)
        q = jnp.einsum("btr,rhk->bthk", qa, params["wq_b"])
    else:
        q = jnp.einsum("btd,dhk->bthk", x, params["wq"])
    q_nope, q_pe = q[..., :nope], q[..., nope:]
    q_pe = rope(q_pe, positions, rope_theta)

    wk = params["wkv_b"][..., :nope]         # (R, H, nope)
    wv = params["wkv_b"][..., nope:]         # (R, H, v)
    scale = (nope + rope_d) ** -0.5

    # absorbed attention over the latent cache (fp32: the reassociated
    # contraction order would otherwise add bf16 rounding vs the prefill path)
    q_abs = jnp.einsum("bthk,rhk->bhr", q_nope.astype(jnp.float32),
                       wk.astype(jnp.float32))               # (B, H, R)
    s_nope = jnp.einsum("bhr,bsr->bhs", q_abs,
                        latent_cache.astype(jnp.float32))
    s_pe = jnp.einsum("bthk,bsxk->bhs", q_pe.astype(jnp.float32),
                      pe_cache.astype(jnp.float32))
    s = (s_nope + s_pe) * scale
    S = latent_cache.shape[1]
    n_valid = (idx + 1).reshape(-1, 1) if idx.ndim else (idx + 1)
    valid = jnp.arange(S)[None, :] < n_valid
    s = jnp.where(valid[:, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("bhs,bsr->bhr", p, latent_cache.astype(jnp.float32))
    out = jnp.einsum("bhr,rhv->bhv", ctx, wv.astype(jnp.float32))
    out = jnp.einsum("bhv,hvd->bd", out,
                     params["wo"].astype(jnp.float32))[:, None, :]
    return out.astype(x.dtype), latent_cache, pe_cache
