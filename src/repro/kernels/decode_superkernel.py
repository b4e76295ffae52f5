"""Pallas TPU decode superkernels: the per-layer decode hot path in one launch.

Two kernels, both shaped for batched single-token decode where dispatch
overhead (not FLOPs) dominates the reduced bench configs:

- `fused_moe_entry`: router logits (+ optional residency logit bias), softmax,
  iterative top-k, slot-table lookup with the dead-sentinel miss rule, and the
  per-expert gate/up/down FFN with gate-weighted fp32 accumulation — the whole
  route -> dispatch -> `slot_ffn` sequence of `models.moe.moe_slotbuf` in ONE
  `pallas_call`. The (layer, expert) -> slot table rides as a scalar-prefetch
  operand (stacked clamped/raw rows) so the BlockSpec index maps stream each
  expert's weights straight from its slot, and the raw row zeroes gates of
  non-resident experts (the sentinel rule) inside the kernel.

- `fused_decode_attention` / `fused_mla_decode_attention`: one-token attention
  that inserts the new K/V (or MLA latent/pe) row into the ring at
  `cache_len % size` and runs chunked online-softmax over only the chunks the
  per-row `cache_len` reaches — replacing the separate cache-scatter +
  masked full-window softmax of `models.attention.decode_attention` /
  `mla_decode` with a single launch per layer.

All three compile to Mosaic on TPU and run in interpret mode on CPU
(`kernels/ops.py`); `kernels/ref.py` and the einsum paths stay the numerics
oracles. The expert FFN is tiled over d_ff and attention over KV blocks of
the cache, so no block holds a whole expert or a whole cache row: at
olmoe-1b-7b widths (12.6 MB per expert, 4 MB of K per row at 1024
positions) either would overrun the chip's scoped VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from repro.kernels.slot_gather import _fit_block

NEG = -1e30                 # top-k masking (matches kernels/topk_gating.py)
NEG_INF = -2.0 ** 30        # attention masking (matches models/attention.py)
BLOCK_F = 128               # fused MoE entry's d_ff tile (fitted to d_ff)


# ---------------------------------------------------------------------------
# Fused MoE entry: route + top-k + slot lookup + expert FFN, one launch
# ---------------------------------------------------------------------------

def _fused_moe_kernel(slot_ref, x_ref, rw_ref, bias_ref, raw_ref, wg_ref,
                      wu_ref, wd_ref, y_ref, gates_ref, ids_ref, *, k: int,
                      norm: bool):
    """Grid step (e, f) adds d_ff tile f of expert e's gate-weighted FFN for
    every token. Routing runs once, at the first step, into the gates/ids
    outputs, which stay in VMEM for the whole grid."""
    e, ft = pl.program_id(0), pl.program_id(1)

    @pl.when((e == 0) & (ft == 0))
    def _route():
        xf = x_ref[...].astype(jnp.float32)           # (T, d)
        logits = jnp.dot(xf, rw_ref[...].astype(jnp.float32)) \
            + bias_ref[...].astype(jnp.float32)
        T, E = logits.shape
        m = jnp.max(logits, axis=-1, keepdims=True)
        p = jnp.exp(logits - m)
        probs = p / jnp.sum(p, axis=-1, keepdims=True)
        iota = jax.lax.broadcasted_iota(jnp.int32, (T, E), 1)
        # dead-sentinel rule: a non-resident expert (raw slot < 0)
        # contributes nothing — its assignments' gates zero exactly as in
        # moe_slotbuf
        res = (raw_ref[...] >= 0).astype(jnp.float32)             # (1, E)
        total = jnp.zeros((T, 1), jnp.float32)
        work = probs
        vals, idxs, resk = [], [], []
        for _ in range(k):             # same first-max rule as _topk_kernel
            v = jnp.max(work, axis=-1, keepdims=True)
            idx = jnp.min(jnp.where(work == v, iota, E), axis=-1,
                          keepdims=True)
            resk.append(jnp.sum(jnp.where(iota == idx, res, 0.0), axis=-1,
                                keepdims=True))
            work = jnp.where(iota == idx, NEG, work)
            vals.append(v)
            idxs.append(idx)
            total = total + v
        gates = jnp.concatenate(vals, axis=-1)        # (T, k)
        if norm:
            gates = gates / jnp.maximum(total, 1e-9)
        gates_ref[...] = gates * jnp.concatenate(resk, axis=-1)
        ids_ref[...] = jnp.concatenate(idxs, axis=-1).astype(jnp.int32)
        y_ref[...] = jnp.zeros_like(y_ref)

    x = x_ref[...]
    ge = jnp.sum(jnp.where(ids_ref[...] == e, gates_ref[...], 0.0),
                 axis=-1, keepdims=True)                          # (T, 1)
    # every op's result rounded to x.dtype, as the einsum path rounds it;
    # the arithmetic itself runs in f32 (the v5e VPU has no bf16)
    dt, f32 = x.dtype, jnp.float32

    def rnd(v):
        return v.astype(dt).astype(f32)

    g = rnd(jnp.dot(x, wg_ref[0], preferred_element_type=f32))
    u = rnd(jnp.dot(x, wu_ref[0], preferred_element_type=f32))
    h = rnd(rnd(g * rnd(jax.nn.sigmoid(g))) * u).astype(dt)
    part = rnd(jnp.dot(h, wd_ref[0], preferred_element_type=f32))
    y_ref[...] += ge * part


@functools.partial(jax.jit, static_argnames=("top_k", "norm_topk",
                                             "interpret"))
def fused_moe_entry(x: jnp.ndarray, router_w: jnp.ndarray,
                    logit_bias: jnp.ndarray, slot_of_expert: jnp.ndarray,
                    s_gate: jnp.ndarray, s_up: jnp.ndarray,
                    s_down: jnp.ndarray, *, top_k: int,
                    norm_topk: bool = True, interpret: bool = False):
    """x: (T, d) tokens; router_w: (d, E); logit_bias: (E,) additive fp32
    (zeros when cache-aware routing is off — bit-exact); slot_of_expert:
    (E,) int32, -1 = non-resident; slot buffers (S, d, f)/(S, f, d).

    Returns (y (T, d) float32, gates (T, top_k) float32, ids (T, top_k)
    int32) — gates already zeroed for non-resident assignments, so the
    caller's needed-mask derives from ids alone.
    """
    T, d = x.shape
    E = router_w.shape[1]
    f = s_gate.shape[-1]
    bf = _fit_block(f, BLOCK_F)
    raw = slot_of_expert.astype(jnp.int32)
    # scalar prefetch: the clamped slot row (a valid BlockSpec index even
    # for misses, whose output is gate-zeroed); the raw row rides in VMEM
    # for the sentinel rule (Mosaic loads only scalars from SMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(E, f // bf),
        in_specs=[
            pl.BlockSpec((T, d), lambda e, j, s: (0, 0)),
            pl.BlockSpec((d, E), lambda e, j, s: (0, 0)),
            pl.BlockSpec((1, E), lambda e, j, s: (0, 0)),
            pl.BlockSpec((1, E), lambda e, j, s: (0, 0)),
            pl.BlockSpec((1, d, bf), lambda e, j, s: (s[e], 0, j)),
            pl.BlockSpec((1, d, bf), lambda e, j, s: (s[e], 0, j)),
            pl.BlockSpec((1, bf, d), lambda e, j, s: (s[e], j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((T, d), lambda e, j, s: (0, 0)),
            pl.BlockSpec((T, top_k), lambda e, j, s: (0, 0)),
            pl.BlockSpec((T, top_k), lambda e, j, s: (0, 0)),
        ],
    )
    return pl.pallas_call(
        functools.partial(_fused_moe_kernel, k=top_k, norm=norm_topk),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((T, d), jnp.float32),
                   jax.ShapeDtypeStruct((T, top_k), jnp.float32),
                   jax.ShapeDtypeStruct((T, top_k), jnp.int32)],
        interpret=interpret,
    )(jnp.maximum(raw, 0), x, router_w, logit_bias.reshape(1, E),
      raw.reshape(1, E), s_gate, s_up, s_down)


# ---------------------------------------------------------------------------
# Fused single-token attention: ring insert + online softmax, one launch
# ---------------------------------------------------------------------------

def _gqa_decode_kernel(clen_ref, q_ref, kn_ref, vn_ref, kc_ref, vc_ref,
                       o_ref, ko_ref, vo_ref, m_sc, l_sc, acc_sc, *,
                       scale: float, logit_softcap: float, block_s: int):
    """Grid step (b, s): KV block s of row b. The block is copied through
    with the new K/V row inserted, and — if it holds any of the row's
    valid positions — folded into the online softmax kept in scratch.
    Scores are per-head lane reductions (no MXU): one query per head."""
    b, s = pl.program_id(0), pl.program_id(1)
    n_blocks = pl.num_programs(1)
    clen = clen_ref[b]
    S = block_s * n_blocks
    start = s * block_s
    # ring insert: slot(pos) = pos % size (layer_decode's discipline; for
    # unwrapped caches clen < S makes this a plain positional insert)
    pos = start + jax.lax.broadcasted_iota(jnp.int32, (block_s, 1, 1), 0)
    ins = pos == jax.lax.rem(clen, S)
    kb = jnp.where(ins, kn_ref[0], kc_ref[0])         # (bs, Hkv, D)
    vb = jnp.where(ins, vn_ref[0], vc_ref[0])
    ko_ref[0] = kb
    vo_ref[0] = vb
    valid = jnp.minimum(clen + 1, S)

    @pl.when(s == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    @pl.when(start < valid)
    def _attend():
        kf = kb.astype(jnp.float32)
        vf = vb.astype(jnp.float32)
        for g in range(q_ref.shape[1]):
            qg = q_ref[0, g].astype(jnp.float32) * scale          # (Hkv, D)
            sc = jnp.sum(kf * qg[None], axis=-1, keepdims=True)   # (bs,Hkv,1)
            if logit_softcap > 0.0:
                sc = logit_softcap * jnp.tanh(sc / logit_softcap)
            sc = jnp.where(pos < valid, sc, NEG_INF)
            m_prev = m_sc[g]                                       # (Hkv, 1)
            m_new = jnp.maximum(m_prev, jnp.max(sc, axis=0))
            p = jnp.exp(sc - m_new[None])
            corr = jnp.exp(m_prev - m_new)
            l_sc[g] = l_sc[g] * corr + jnp.sum(p, axis=0)
            acc_sc[g] = acc_sc[g] * corr + jnp.sum(p * vf, axis=0)
            m_sc[g] = m_new

    @pl.when(s == n_blocks - 1)
    def _finish():
        o_ref[0] = acc_sc[...] / jnp.maximum(l_sc[...], 1e-20)


@functools.partial(jax.jit, static_argnames=("logit_softcap", "scale",
                                             "block_s", "interpret"))
def fused_decode_attention(q: jnp.ndarray, k_new: jnp.ndarray,
                           v_new: jnp.ndarray, k_cache: jnp.ndarray,
                           v_cache: jnp.ndarray, cache_len: jnp.ndarray, *,
                           logit_softcap: float = 0.0, scale=None,
                           block_s: int = 128, interpret: bool = False):
    """q: (B, 1, Hq, D); k_new/v_new: (B, 1, Hkv, D); caches: (B, S, Hkv, D)
    ring buffers; cache_len: (B,) int32 = entries cached BEFORE this token
    (the kernel inserts at `cache_len % S` and attends over
    `min(cache_len + 1, S)`). Returns (out (B, 1, Hq, D), k_cache', v_cache').
    """
    B, _, Hq, D = q.shape
    S, Hkv = k_cache.shape[1], k_cache.shape[2]
    G = Hq // Hkv
    if scale is None:
        scale = D ** -0.5
    bs = _fit_block(S, block_s)
    # query head h * G + g -> [g, h]: every kernel array is (.., Hkv, D)
    qg = q[:, 0].reshape(B, Hkv, G, D).transpose(0, 2, 1, 3)
    row = lambda b, s, c: (b, 0, 0, 0)                      # noqa: E731
    blk = lambda b, s, c: (b, s, 0, 0)                      # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, S // bs),
        in_specs=[
            pl.BlockSpec((1, G, Hkv, D), row),
            pl.BlockSpec((1, 1, Hkv, D), row),
            pl.BlockSpec((1, 1, Hkv, D), row),
            pl.BlockSpec((1, bs, Hkv, D), blk),
            pl.BlockSpec((1, bs, Hkv, D), blk),
        ],
        out_specs=[
            pl.BlockSpec((1, G, Hkv, D), row),
            pl.BlockSpec((1, bs, Hkv, D), blk),
            pl.BlockSpec((1, bs, Hkv, D), blk),
        ],
        scratch_shapes=[pltpu.VMEM((G, Hkv, 1), jnp.float32),
                        pltpu.VMEM((G, Hkv, 1), jnp.float32),
                        pltpu.VMEM((G, Hkv, D), jnp.float32)],
    )
    out, kc, vc = pl.pallas_call(
        functools.partial(_gqa_decode_kernel, scale=float(scale),
                          logit_softcap=float(logit_softcap), block_s=bs),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, G, Hkv, D), jnp.float32),
                   jax.ShapeDtypeStruct(k_cache.shape, k_cache.dtype),
                   jax.ShapeDtypeStruct(v_cache.shape, v_cache.dtype)],
        input_output_aliases={4: 1, 5: 2},
        interpret=interpret,
    )(cache_len.astype(jnp.int32), qg, k_new, v_new, k_cache, v_cache)
    out = out.transpose(0, 2, 1, 3).reshape(B, 1, Hq, D).astype(q.dtype)
    return out, kc, vc


def _mla_decode_kernel(clen_ref, qa_ref, qp_ref, cn_ref, pn_ref, lat_ref,
                       pe_ref, ctx_ref, lat_o_ref, pe_o_ref, m_sc, l_sc,
                       acc_sc, *, scale: float, block_s: int):
    """Grid step (b, s): latent block s of row b, copied through with the
    new row inserted and folded into the online softmax in scratch."""
    b, s = pl.program_id(0), pl.program_id(1)
    clen = clen_ref[b]
    start = s * block_s
    # MLA latent cache is positional (no ring): insert at clen
    pos = start + jax.lax.broadcasted_iota(jnp.int32, (block_s, 1), 0)
    ins = pos == clen
    lb = jnp.where(ins, cn_ref[0], lat_ref[0])        # (bs, R)
    pb = jnp.where(ins, pn_ref[0], pe_ref[0])         # (bs, P)
    lat_o_ref[0] = lb
    pe_o_ref[0] = pb
    valid = clen + 1

    @pl.when(s == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    @pl.when(start < valid)
    def _attend():
        lf = lb.astype(jnp.float32)
        nt = (((1,), (1,)), ((), ()))                 # contract last dims
        sc = (jax.lax.dot_general(qa_ref[0], lf, nt)
              + jax.lax.dot_general(qp_ref[0], pb.astype(jnp.float32),
                                    nt)) * scale      # (H, bs)
        kpos = start + jax.lax.broadcasted_iota(jnp.int32, (1, block_s), 1)
        sc = jnp.where(kpos < valid, sc, NEG_INF)
        m_prev = m_sc[...]                            # (H, 1)
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
        p = jnp.exp(sc - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_sc[...] = l_sc[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_sc[...] = acc_sc[...] * corr + jnp.dot(p, lf)
        m_sc[...] = m_new

    @pl.when(s == pl.num_programs(1) - 1)
    def _finish():
        ctx_ref[0] = acc_sc[...] / jnp.maximum(l_sc[...], 1e-20)


@functools.partial(jax.jit, static_argnames=("scale", "block_s", "interpret"))
def fused_mla_decode_attention(q_abs: jnp.ndarray, q_pe: jnp.ndarray,
                               c_new: jnp.ndarray, pe_new: jnp.ndarray,
                               latent: jnp.ndarray, pe: jnp.ndarray,
                               cache_len: jnp.ndarray, *, scale: float,
                               block_s: int = 128, interpret: bool = False):
    """Weight-absorbed MLA decode attention over the compressed cache.

    q_abs: (B, H, R) fp32 (q_nope already absorbed through wkv_b's key half);
    q_pe: (B, H, P); c_new: (B, R); pe_new: (B, P); latent: (B, S, R);
    pe: (B, S, P); cache_len: (B,) int32 (insert at cache_len, positional).
    Returns (ctx (B, H, R) float32, latent', pe') — the o-side absorb
    (ctx @ wv @ wo) stays outside, it is batch-size work only.
    """
    B, H, R = q_abs.shape
    P = q_pe.shape[-1]
    S = latent.shape[1]
    bs = _fit_block(S, block_s)
    row = lambda b, s, c: (b, 0, 0)                         # noqa: E731
    blk = lambda b, s, c: (b, s, 0)                         # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, S // bs),
        in_specs=[
            pl.BlockSpec((1, H, R), row),
            pl.BlockSpec((1, H, P), row),
            pl.BlockSpec((1, 1, R), row),
            pl.BlockSpec((1, 1, P), row),
            pl.BlockSpec((1, bs, R), blk),
            pl.BlockSpec((1, bs, P), blk),
        ],
        out_specs=[
            pl.BlockSpec((1, H, R), row),
            pl.BlockSpec((1, bs, R), blk),
            pl.BlockSpec((1, bs, P), blk),
        ],
        scratch_shapes=[pltpu.VMEM((H, 1), jnp.float32),
                        pltpu.VMEM((H, 1), jnp.float32),
                        pltpu.VMEM((H, R), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_mla_decode_kernel, scale=float(scale),
                          block_s=bs),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, H, R), jnp.float32),
                   jax.ShapeDtypeStruct(latent.shape, latent.dtype),
                   jax.ShapeDtypeStruct(pe.shape, pe.dtype)],
        input_output_aliases={5: 1, 6: 2},
        interpret=interpret,
    )(cache_len.astype(jnp.int32), q_abs.astype(jnp.float32),
      q_pe.astype(jnp.float32), c_new.reshape(B, 1, R).astype(latent.dtype),
      pe_new.reshape(B, 1, P).astype(pe.dtype), latent, pe)
