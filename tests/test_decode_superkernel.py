"""Decode superkernel validation: the fused MoE-entry kernel (router ->
top-k -> slot lookup -> grouped expert FFN in one launch) and the fused
single-token attention kernels (ragged ring/positional KV insert + online
softmax) against pure-jnp oracles, plus engine-level greedy-token parity of
the segment-fused decode path versus the einsum-oracle engine under
eviction churn."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import reduce_config
from repro.configs.registry import get_config
from repro.kernels import ops
from repro.models.attention import decode_attention
from repro.runtime.engine import Engine, SlotBufferEngine

# ---------------------------------------------------------------------------
# fused MoE entry vs einsum oracle
# ---------------------------------------------------------------------------


def _moe_inputs(rng, T, E, n_slots, d, f, n_dead=0):
    x = jnp.asarray(rng.standard_normal((T, d)), jnp.bfloat16) * 0.5
    rw = jnp.asarray(rng.standard_normal((d, E)), jnp.float32) * 0.3
    sg = jnp.asarray(rng.standard_normal((n_slots, d, f)), jnp.bfloat16) * 0.1
    su = jnp.asarray(rng.standard_normal((n_slots, d, f)), jnp.bfloat16) * 0.1
    sd = jnp.asarray(rng.standard_normal((n_slots, f, d)), jnp.bfloat16) * 0.1
    # slot table: a random subset of experts resident, the rest dead (-1)
    perm = rng.permutation(E)
    soe = np.full(E, -1, np.int64)
    for s, e in enumerate(perm[: E - n_dead]):
        if s < n_slots:
            soe[e] = s
    return x, rw, sg, su, sd, jnp.asarray(soe, jnp.int32)


@pytest.mark.parametrize("T,E,n_slots,k", [(8, 8, 8, 2), (16, 8, 6, 2),
                                           (4, 16, 5, 4), (1, 8, 3, 8)])
@pytest.mark.parametrize("norm", [True, False])
def test_fused_moe_entry_matches_ref(T, E, n_slots, k, norm):
    rng = np.random.default_rng(T * E + k)
    x, rw, sg, su, sd, soe = _moe_inputs(rng, T, E, n_slots, 64, 128,
                                         n_dead=max(0, E - n_slots))
    bias = jnp.zeros((E,), jnp.float32)
    k = min(k, E)
    y, g, i = ops.fused_moe_entry(x, rw, bias, soe, sg, su, sd, top_k=k,
                                  norm_topk=norm, interpret=True)
    yr, gr, ir = ops.fused_moe_entry_ref(x, rw, bias, soe, sg, su, sd,
                                         top_k=k, norm_topk=norm)
    assert np.array_equal(np.asarray(i), np.asarray(ir))
    np.testing.assert_allclose(np.asarray(g), np.asarray(gr),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=3e-2, atol=3e-2)


def test_fused_moe_entry_dead_slots_zero_their_gates():
    """Non-resident experts (slot -1) contribute NOTHING and their gates
    come back zeroed — the mask the engine's verification consumes."""
    rng = np.random.default_rng(0)
    E = 8
    x, rw, sg, su, sd, _ = _moe_inputs(rng, 8, E, E, 64, 128)
    all_dead = jnp.full((E,), -1, jnp.int32)
    y, g, _ = ops.fused_moe_entry(x, rw, jnp.zeros((E,), jnp.float32),
                                  all_dead, sg, su, sd, top_k=2,
                                  interpret=True)
    np.testing.assert_array_equal(np.asarray(y), 0.0)
    np.testing.assert_array_equal(np.asarray(g), 0.0)


@pytest.mark.parametrize("delta", [0.0, 1.5])
def test_fused_moe_entry_logit_bias(delta):
    """Residency logit-bias rides the kernel's router: at delta=0 the bias
    row is all zeros and must be a bit-exact no-op; at delta>0 routing
    matches the biased oracle."""
    rng = np.random.default_rng(3)
    E, n_slots = 8, 5
    x, rw, sg, su, sd, soe = _moe_inputs(rng, 8, E, n_slots, 64, 128,
                                         n_dead=E - n_slots)
    bias = jnp.where(soe >= 0, 0.0, -delta).astype(jnp.float32)
    y, g, i = ops.fused_moe_entry(x, rw, bias, soe, sg, su, sd, top_k=2,
                                  interpret=True)
    yr, gr, ir = ops.fused_moe_entry_ref(x, rw, bias, soe, sg, su, sd,
                                         top_k=2, norm_topk=True)
    assert np.array_equal(np.asarray(i), np.asarray(ir))
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=3e-2, atol=3e-2)
    if delta == 0.0:
        y0, g0, i0 = ops.fused_moe_entry(
            x, rw, jnp.zeros((E,), jnp.float32), soe, sg, su, sd, top_k=2,
            interpret=True)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(y0))
        np.testing.assert_array_equal(np.asarray(i), np.asarray(i0))


def test_fused_moe_entry_non_tile_aligned():
    """d_model / d_expert off the 128-lane tile; interpret-mode shape
    handling must not require padding by the caller."""
    rng = np.random.default_rng(9)
    x, rw, sg, su, sd, soe = _moe_inputs(rng, 5, 4, 4, 48, 72)
    x = x.astype(jnp.float32)
    sg, su, sd = (w.astype(jnp.float32) for w in (sg, su, sd))
    y, g, i = ops.fused_moe_entry(x, rw, jnp.zeros((4,), jnp.float32), soe,
                                  sg, su, sd, top_k=2, interpret=True)
    yr, gr, ir = ops.fused_moe_entry_ref(x, rw, jnp.zeros((4,), jnp.float32),
                                         soe, sg, su, sd, top_k=2,
                                         norm_topk=True)
    assert np.array_equal(np.asarray(i), np.asarray(ir))
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# fused decode attention vs masked full-window oracle
# ---------------------------------------------------------------------------


def _attn_oracle(q, k_new, v_new, k_cache, v_cache, clen, softcap=0.0):
    """Host ring insert + masked full-window decode_attention."""
    B, S = k_cache.shape[0], k_cache.shape[1]
    slot = np.asarray(clen) % S
    kc = np.asarray(k_cache).copy()
    vc = np.asarray(v_cache).copy()
    kc[np.arange(B), slot] = np.asarray(k_new)[:, 0]
    vc[np.arange(B), slot] = np.asarray(v_new)[:, 0]
    kc, vc = jnp.asarray(kc), jnp.asarray(vc)
    valid = jnp.minimum(jnp.asarray(clen) + 1, S)
    out = decode_attention(q, kc, vc, valid, logit_softcap=softcap)
    return out, kc, vc


@pytest.mark.parametrize("clens", [[0, 0], [3, 7], [15, 1], [16, 16]],
                         ids=["empty", "ragged", "mixed", "full"])
@pytest.mark.parametrize("softcap", [0.0, 30.0])
def test_fused_decode_attention_matches_oracle(clens, softcap):
    """Ragged (B,) cache lengths including empty caches and the cache-full
    ring-wrap edge (clen == S wraps the insert to slot 0)."""
    B, S, Hq, Hkv, D = len(clens), 16, 4, 2, 32
    rng = np.random.default_rng(sum(clens) + 1)
    q = jnp.asarray(rng.standard_normal((B, 1, Hq, D)), jnp.float32)
    kn = jnp.asarray(rng.standard_normal((B, 1, Hkv, D)), jnp.float32)
    vn = jnp.asarray(rng.standard_normal((B, 1, Hkv, D)), jnp.float32)
    kc = jnp.asarray(rng.standard_normal((B, S, Hkv, D)), jnp.float32)
    vc = jnp.asarray(rng.standard_normal((B, S, Hkv, D)), jnp.float32)
    clen = jnp.asarray(clens, jnp.int32)
    out, kc2, vc2 = ops.fused_decode_attention(q, kn, vn, kc, vc, clen,
                                               logit_softcap=softcap,
                                               interpret=True)
    ro, rk, rv = _attn_oracle(q, kn, vn, kc, vc, clen, softcap)
    np.testing.assert_array_equal(np.asarray(kc2), np.asarray(rk))
    np.testing.assert_array_equal(np.asarray(vc2), np.asarray(rv))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ro),
                               rtol=2e-5, atol=2e-5)


def test_fused_decode_attention_sliding_window_ring():
    """A cache sized to the sliding window IS the window: once clen
    exceeds S the ring overwrite drops the oldest entry, matching the
    oracle attending over the surviving S entries."""
    B, S, Hq, Hkv, D = 2, 8, 2, 2, 16
    rng = np.random.default_rng(4)
    q = jnp.asarray(rng.standard_normal((B, 1, Hq, D)), jnp.float32)
    kn = jnp.asarray(rng.standard_normal((B, 1, Hkv, D)), jnp.float32)
    vn = jnp.asarray(rng.standard_normal((B, 1, Hkv, D)), jnp.float32)
    kc = jnp.asarray(rng.standard_normal((B, S, Hkv, D)), jnp.float32)
    vc = jnp.asarray(rng.standard_normal((B, S, Hkv, D)), jnp.float32)
    clen = jnp.asarray([11, 25], jnp.int32)    # both past one full wrap
    out, kc2, vc2 = ops.fused_decode_attention(q, kn, vn, kc, vc, clen,
                                               interpret=True)
    ro, rk, rv = _attn_oracle(q, kn, vn, kc, vc, clen)
    np.testing.assert_array_equal(np.asarray(kc2), np.asarray(rk))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ro),
                               rtol=2e-5, atol=2e-5)


def test_fused_mla_decode_attention_matches_oracle():
    """Weight-absorbed MLA decode: scores over (latent, pe) caches with the
    new token's latent inserted at its position in the same launch."""
    B, S, H, R, P = 3, 16, 4, 32, 8
    rng = np.random.default_rng(11)
    q_abs = jnp.asarray(rng.standard_normal((B, H, R)), jnp.float32)
    q_pe = jnp.asarray(rng.standard_normal((B, H, P)), jnp.float32)
    c_new = jnp.asarray(rng.standard_normal((B, R)), jnp.float32)
    pe_new = jnp.asarray(rng.standard_normal((B, P)), jnp.float32)
    lat = jnp.asarray(rng.standard_normal((B, S, R)), jnp.float32)
    pe = jnp.asarray(rng.standard_normal((B, S, P)), jnp.float32)
    clen = jnp.asarray([0, 5, 15], jnp.int32)
    scale = (R + P) ** -0.5
    ctx, lat2, pe2 = ops.fused_mla_decode_attention(
        q_abs, q_pe, c_new, pe_new, lat, pe, clen, scale=scale,
        interpret=True)
    # oracle: positional insert + masked softmax over the latent cache
    lath, peh = np.asarray(lat).copy(), np.asarray(pe).copy()
    lath[np.arange(B), np.asarray(clen)] = np.asarray(c_new)
    peh[np.arange(B), np.asarray(clen)] = np.asarray(pe_new)
    s = (jnp.einsum("bhr,bkr->bhk", q_abs, jnp.asarray(lath))
         + jnp.einsum("bhp,bkp->bhk", q_pe, jnp.asarray(peh))) * scale
    mask = jnp.arange(S)[None, None, :] < (clen + 1)[:, None, None]
    p = jax.nn.softmax(jnp.where(mask, s, -2.0 ** 30), axis=-1)
    ref = jnp.einsum("bhk,bkr->bhr", p, jnp.asarray(lath))
    np.testing.assert_array_equal(np.asarray(lat2), lath)
    np.testing.assert_array_equal(np.asarray(pe2), peh)
    np.testing.assert_allclose(np.asarray(ctx), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# engine: segment-fused decode vs einsum oracle
# ---------------------------------------------------------------------------


def _small_cfg(arch="olmoe-1b-7b"):
    return reduce_config(get_config(arch), layers=4, d_model=64, heads=4,
                         kv_heads=4, d_ff=128, vocab=512, experts=8,
                         top_k=2, d_expert=32)


# The fused kernels reassociate bf16 expert/attention math, so their logits
# sit within this bound of the einsum oracle's, not on them: at most
# 2.1e-2 (GQA) and 2.9e-2 (MLA) over the 32 teacher-forced row-steps of the
# churn test below. A router whose top-k is as close can pick another
# expert for one token: that row-step then jumps (0.21 on GQA, step 5 of
# row 0) and the next is back within the bound. A kernel that drops one
# expert puts 20 of the 32 row-steps past it (up to 0.55).
SK_ATOL = 5e-2


def _churn_oracle(arch):
    """Config, engine, prompts, the fully-resident einsum oracle's greedy
    tokens (2, 16) and its teacher-forced logits, at 3 slots per layer."""
    cfg = _small_cfg(arch)
    eng = Engine(cfg, max_seq=64)
    prompt = np.random.default_rng(7).integers(
        0, cfg.vocab_size, (2, 10)).astype(np.int32)
    oracle = SlotBufferEngine(cfg, eng.params, eng.model, max_seq=64,
                              n_slots_per_layer=3)
    want = np.asarray(oracle.generate(prompt, 16, reference=True))
    want_lo = _teacher_forced_logits(oracle, prompt, want, reference=True)
    return cfg, eng, prompt, want, want_lo


def _teacher_forced_logits(sb, prompt, tokens, reference=False):
    """Per-step logits of `sb` fed the given token stream (B, n)."""
    prefill = sb.reference_prefill if reference else sb.prefill
    step = sb.reference_decode_step if reference else sb.decode_step
    lo, st = prefill(prompt)
    out = [np.asarray(lo)]
    for j in range(tokens.shape[1] - 1):
        lo, st = step(jnp.asarray(tokens[:, j], jnp.int32), st)
        out.append(np.asarray(lo))
    return out


def _off_oracle(got_lo, want_lo):
    """(step, row) pairs whose logits are past SK_ATOL of the oracle's."""
    return [(j, int(r)) for j, (g, w) in enumerate(zip(got_lo, want_lo))
            for r in np.nonzero(np.max(np.abs(g - w), -1) > SK_ATOL)[0]]


@pytest.mark.slow
@pytest.mark.parametrize("arch", ["olmoe-1b-7b", "deepseek-v2-lite"],
                         ids=["gqa", "mla"])
def test_superkernel_greedy_tokens_match_oracle_under_churn(arch):
    """THE acceptance contract: with fewer slots than the per-step working
    set (forced eviction churn + hinted replays), the segment-fused decode
    path fed the fully-resident einsum oracle's greedy tokens stays within
    SK_ATOL of its logits at every step but at most one (a routing
    near-tie), and picks the same token wherever the oracle's choice is
    not a near-tie. On GQA its own greedy tokens equal the oracle's. On
    MLA row 1 takes another token at step 5, where the oracle's top-2
    logits are 2.8e-3 apart and the superkernel's logits are 1.7e-2 off
    (inside SK_ATOL): a near-tie, so only logits are compared there."""
    cfg, eng, prompt, want, want_lo = _churn_oracle(arch)
    sk = SlotBufferEngine(cfg, eng.params, eng.model, max_seq=64,
                          n_slots_per_layer=3, use_superkernel=True)
    if arch == "olmoe-1b-7b":
        np.testing.assert_array_equal(np.asarray(sk.generate(prompt, 16)),
                                      want)
    got_lo = _teacher_forced_logits(sk, prompt, want)
    for j, (g, w) in enumerate(zip(got_lo, want_lo)):
        err = np.max(np.abs(g - w), axis=-1)
        top2 = np.sort(w, axis=-1)[:, -2:]
        clear = (top2[:, 1] - top2[:, 0] > 2 * SK_ATOL) & (err <= SK_ATOL)
        np.testing.assert_array_equal(np.argmax(g, -1)[clear],
                                      want[clear, j], err_msg=f"step {j}")
    off = _off_oracle(got_lo, want_lo)
    assert len(off) <= 1, f"row-steps off the oracle: {off}"
    assert sk.stats.replays > 0          # churn actually forced replays
    assert sk.stats.spec_layers > 0      # speculative segments ran


@pytest.mark.parametrize("expert", [0, 3])
def test_superkernel_oracle_check_catches_a_dropped_expert(monkeypatch,
                                                           expert):
    """Control for SK_ATOL: a fused MoE entry that treats one resident
    expert as missing (its gate zeroed in every MoE layer) puts many
    row-steps past the bound, so the check above would fail it."""
    cfg, eng, prompt, want, want_lo = _churn_oracle("olmoe-1b-7b")
    real = ops.fused_moe_entry

    def dropping(x, router_w, bias, slot_of_expert, *args, **kwargs):
        return real(x, router_w, bias, slot_of_expert.at[expert].set(-1),
                    *args, **kwargs)

    monkeypatch.setattr(ops, "fused_moe_entry", dropping)
    sk = SlotBufferEngine(cfg, eng.params, eng.model, max_seq=64,
                          n_slots_per_layer=3, use_superkernel=True)
    off = _off_oracle(_teacher_forced_logits(sk, prompt, want), want_lo)
    assert len(off) > 4, off


@pytest.mark.slow
def test_superkernel_halves_dispatches_per_step():
    """The tentpole claim: segment fusion cuts warm jitted dispatches per
    decode step by >= 2x versus the unfused slot path at the same horizon,
    without changing the token stream."""
    cfg = _small_cfg()
    eng = Engine(cfg, max_seq=64)
    prompt = np.random.default_rng(7).integers(
        0, cfg.vocab_size, (2, 10)).astype(np.int32)
    kw = dict(max_seq=64, n_slots_per_layer=6, step_size=3)
    base = SlotBufferEngine(cfg, eng.params, eng.model, **kw)
    toks_b = np.asarray(base.generate(prompt, 16))
    sk = SlotBufferEngine(cfg, eng.params, eng.model, use_superkernel=True,
                          **kw)
    toks_s = np.asarray(sk.generate(prompt, 16))
    np.testing.assert_array_equal(toks_s, toks_b)
    per_base = base.stats.jit_calls / base.stats.steps
    per_sk = sk.stats.jit_calls / sk.stats.steps
    assert per_base / per_sk >= 2.0, (per_base, per_sk)


@pytest.mark.slow
def test_superkernel_batched_step_matches_standard_path():
    """Batched ragged-cache decode: one superkernel step from a state built
    by the standard engine stays within bf16 kernel-reassociation noise of
    the standard step (same tokens, same cache-length advance)."""
    import copy
    cfg = _small_cfg()
    eng = Engine(cfg, max_seq=64)
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab_size, L).astype(np.int32)
               for L in (8, 12, 10)]
    kw = dict(max_seq=64, n_slots_per_layer=6, step_size=2)
    base = SlotBufferEngine(cfg, eng.params, eng.model, **kw)
    state = base.alloc_decode_state(3)
    toks = np.zeros(3, np.int32)
    for slot in range(3):
        lo = base.prefill_into(state, slot, prompts[slot][None, :])
        toks[slot] = int(jnp.argmax(lo, -1)[0])
    for _ in range(3):                       # ragged histories
        lo, state = base.decode_step(jnp.asarray(toks), state)
        toks = np.asarray(jnp.argmax(lo, -1))
    lo_b, st_b = base.decode_step(jnp.asarray(toks), copy.deepcopy(state))
    sk = SlotBufferEngine(cfg, eng.params, eng.model, use_superkernel=True,
                          **kw)
    lo_s, st_s = sk.decode_step(jnp.asarray(toks), copy.deepcopy(state))
    np.testing.assert_array_equal(np.asarray(st_s.cache_len),
                                  np.asarray(st_b.cache_len))
    np.testing.assert_allclose(np.asarray(lo_s), np.asarray(lo_b),
                               rtol=5e-2, atol=5e-2)
    np.testing.assert_array_equal(np.asarray(jnp.argmax(lo_s, -1)),
                                  np.asarray(jnp.argmax(lo_b, -1)))
