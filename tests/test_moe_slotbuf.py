"""moe_slotbuf unit tests (fast lane): per-expert dispatch that never
drops, non-resident and unrouted experts streamed by nothing, parity with
the grouped path, the per-slot einsum oracle and the dense reference."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import MoEConfig
from repro.kernels import ref, slot_gather
from repro.models import moe as moe_mod


def _forced_router(d: int, E: int) -> jnp.ndarray:
    """Router weights that route one-hot token x=onehot(e) to expert e."""
    r = np.zeros((d, E), np.float32)
    r[:E, :E] = np.eye(E) * 8.0
    return jnp.asarray(r)


def _mk_params(rng, d, E, f, dtype=jnp.float32):
    return {
        "router": _forced_router(d, E),
        "w_gate": jnp.asarray(rng.standard_normal((E, d, f)), dtype) * 0.1,
        "w_up": jnp.asarray(rng.standard_normal((E, d, f)), dtype) * 0.1,
        "w_down": jnp.asarray(rng.standard_normal((E, f, d)), dtype) * 0.1,
    }


def _onehot_tokens(experts, d):
    x = np.zeros((len(experts), d), np.float32)
    for t, e in enumerate(experts):
        x[t, e] = 1.0
    return jnp.asarray(x)


def _expert_ffn_rows(params, x, e):
    g = x @ params["w_gate"][e]
    u = x @ params["w_up"][e]
    return (jax.nn.silu(g) * u) @ params["w_down"][e]


def test_non_resident_misses_cannot_evict_slot0_tokens():
    """Regression (sentinel slot): tokens routed to a NON-resident expert
    used to be clamped onto slot 0 and, gates zeroed or not, consumed slot
    0's dispatch capacity — evicting the resident slot-0 expert's own
    tokens. A non-resident expert's group now streams nothing and its
    assignments read a zero row."""
    d, E, f, C = 16, 4, 8, 4
    moe = MoEConfig(num_experts=E, top_k=1, d_expert=f)
    rng = np.random.default_rng(0)
    params = _mk_params(rng, d, E, f)
    slot_weights = {
        "w_gate": params["w_gate"][:2], "w_up": params["w_up"][:2],
        "w_down": params["w_down"][:2],
    }  # slot s holds expert s for s in {0, 1}
    slot_of_expert = jnp.asarray([0, 1, -1, -1], jnp.int32)
    # first C tokens -> MISSING expert 2, then C tokens -> expert 0 (slot 0,
    # exactly filling its capacity). The misses sort BEFORE the real slot-0
    # tokens, so under the old clamping they stole all of slot 0's capacity.
    x = _onehot_tokens([2] * C + [0] * C, d)
    out, r = moe_mod.moe_slotbuf(params, slot_weights, slot_of_expert, x,
                                 moe, interpret=True)
    assert np.array_equal(np.asarray(r.expert_ids).reshape(-1),
                          [2] * C + [0] * C)
    expected = np.asarray(_expert_ffn_rows(params, x[C:], 0))
    # slot-0 tokens are fully served (top-1 normalized gate == 1)...
    np.testing.assert_allclose(np.asarray(out[C:]), expected,
                               rtol=1e-5, atol=1e-6)
    # ...and missed tokens contribute exactly nothing
    np.testing.assert_array_equal(np.asarray(out[:C]),
                                  np.zeros((C, d), np.float32))


def test_over_capacity_drop_does_not_clobber_last_kept_token():
    """Regression (gather dispatch): assignments dropped for exceeding a
    group's capacity must write OUT of range — not onto (group,
    capacity-1), where a duplicate-index set could zero the kept occupant
    of the last row. moe_slotbuf's capacity of T rows an expert cannot
    drop; the per-slot oracle, which shares the dispatch, can."""
    d, E, f, C = 16, 4, 8, 4
    moe = MoEConfig(num_experts=E, top_k=1, d_expert=f)
    rng = np.random.default_rng(4)
    params = _mk_params(rng, d, E, f)
    sw = {kk: params[kk] for kk in ("w_gate", "w_up", "w_down")}
    ident = jnp.arange(E, dtype=jnp.int32)
    # 5 tokens onto expert 0 with capacity 4: the first 4 (stable sort) are
    # kept — INCLUDING the one at position capacity-1 — and the 5th drops
    x = _onehot_tokens([0] * 5, d)
    out, _ = ref.moe_slotbuf_einsum_ref(params, sw, ident, x, moe,
                                        capacity=C)
    expected = np.asarray(_expert_ffn_rows(params, x[:C], 0))
    np.testing.assert_allclose(np.asarray(out[:C]), expected,
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(out[C:]),
                                  np.zeros((1, d), np.float32))


def test_full_residency_matches_grouped_bitwise():
    """With every expert resident (arbitrary slot permutation), the slot
    path must reproduce moe_grouped BIT-exactly — gather dispatch and the
    indirection add no rounding."""
    d, E, f, T, k = 32, 8, 16, 24, 2
    moe = MoEConfig(num_experts=E, top_k=k, d_expert=f)
    rng = np.random.default_rng(1)
    params = {
        "router": jnp.asarray(rng.standard_normal((d, E)), jnp.float32),
        "w_gate": jnp.asarray(rng.standard_normal((E, d, f)), jnp.bfloat16) * 0.1,
        "w_up": jnp.asarray(rng.standard_normal((E, d, f)), jnp.bfloat16) * 0.1,
        "w_down": jnp.asarray(rng.standard_normal((E, f, d)), jnp.bfloat16) * 0.1,
    }
    x = jnp.asarray(rng.standard_normal((T, d)), jnp.bfloat16)
    perm = rng.permutation(E)
    slot_of_expert = jnp.asarray(np.argsort(perm), jnp.int32)
    slot_weights = {kk: params[kk][jnp.asarray(perm)]
                    for kk in ("w_gate", "w_up", "w_down")}
    out_s, _ = moe_mod.moe_slotbuf(params, slot_weights, slot_of_expert, x,
                                   moe, interpret=True)
    out_g, _ = moe_mod.moe_grouped(params, x, moe, capacity=T * k)
    np.testing.assert_array_equal(np.asarray(out_s, np.float32),
                                  np.asarray(out_g, np.float32))


def test_kernel_path_matches_einsum_path():
    """The kernel path (per-expert dispatch + Pallas slot indirection over
    the routed, resident experts only) must agree bit for bit with the
    per-slot einsum oracle over the whole pool, including with non-resident
    experts — and so must the CPU's default, the kernel's XLA reference."""
    d, E, f, T, k = 32, 6, 16, 20, 2
    moe = MoEConfig(num_experts=E, top_k=k, d_expert=f)
    rng = np.random.default_rng(2)
    params = {
        "router": jnp.asarray(rng.standard_normal((d, E)), jnp.float32),
        "w_gate": jnp.asarray(rng.standard_normal((E, d, f)), jnp.bfloat16) * 0.1,
        "w_up": jnp.asarray(rng.standard_normal((E, d, f)), jnp.bfloat16) * 0.1,
        "w_down": jnp.asarray(rng.standard_normal((E, f, d)), jnp.bfloat16) * 0.1,
    }
    x = jnp.asarray(rng.standard_normal((T, d)), jnp.bfloat16)
    # 4 of 6 experts resident, permuted into 5 slots
    slots = [3, 0, -1, 4, 1, -1]
    slot_of_expert = jnp.asarray(slots, jnp.int32)
    S = 5
    sw = {kk: jnp.zeros((S,) + params[kk].shape[1:], jnp.bfloat16)
          for kk in ("w_gate", "w_up", "w_down")}
    for e, s in enumerate(slots):
        if s >= 0:
            sw = {kk: sw[kk].at[s].set(params[kk][e]) for kk in sw}
    out_e, _ = ref.moe_slotbuf_einsum_ref(params, sw, slot_of_expert, x,
                                          moe)
    out_k, _ = moe_mod.moe_slotbuf(params, sw, slot_of_expert, x, moe,
                                   interpret=True)
    out_r, _ = moe_mod.moe_slotbuf(params, sw, slot_of_expert, x, moe)
    for out in (out_k, out_r):
        np.testing.assert_array_equal(np.asarray(out, np.float32),
                                      np.asarray(out_e, np.float32))


def test_router_out_skips_rerouting():
    """Passing router_out reproduces the internally-routed result exactly
    (the fused engine routes once on device and reuses the result)."""
    d, E, f, T, k = 16, 4, 8, 12, 2
    moe = MoEConfig(num_experts=E, top_k=k, d_expert=f)
    rng = np.random.default_rng(3)
    params = _mk_params(rng, d, E, f)
    sw = {kk: params[kk] for kk in ("w_gate", "w_up", "w_down")}
    ident = jnp.arange(E, dtype=jnp.int32)
    x = jnp.asarray(rng.standard_normal((T, d)), jnp.float32)
    out_a, r = moe_mod.moe_slotbuf(params, sw, ident, x, moe)
    out_b, _ = moe_mod.moe_slotbuf(params, sw, ident, x, moe, router_out=r)
    np.testing.assert_array_equal(np.asarray(out_a), np.asarray(out_b))


@pytest.mark.parametrize("impl", [ref.moe_slotbuf_einsum_ref,
                                  functools.partial(moe_mod.moe_slotbuf,
                                                    interpret=True)],
                         ids=["einsum", "kernel"])
def test_output_independent_of_slot_placement(impl):
    """Which slots hold a layer's experts must not change its output: each
    token's k expert outputs are summed in the router's order, not in slot
    order (in f32 a different order of the sum changes the last bits) —
    in the per-slot oracle and in the kernel path alike."""
    rng = np.random.default_rng(4)
    d, E, f, T = 32, 8, 16, 24
    moe = MoEConfig(num_experts=E, top_k=4, d_expert=f, capacity_factor=2.0)
    p = _mk_params(rng, d, E, f)
    p["router"] = jnp.asarray(rng.standard_normal((d, E)), jnp.float32)
    x = jnp.asarray(rng.standard_normal((T, d)), jnp.float32)
    ws = {k: p[k] for k in ("w_gate", "w_up", "w_down")}
    outs = []
    for perm in (np.arange(E), rng.permutation(E), rng.permutation(E)[::-1]):
        # expert e in slot perm[e] of a buffer with two spare slots
        slots = {k: jnp.zeros((E + 2,) + w.shape[1:], w.dtype)
                 .at[perm].set(w) for k, w in ws.items()}
        out, _ = impl(p, slots, jnp.asarray(perm, jnp.int32), x, moe)
        outs.append(np.asarray(out))
    for o in outs[1:]:
        np.testing.assert_array_equal(o, outs[0])


def _olmoe_like(rng, d=64, E=64, f=32, dtype=jnp.float32):
    """olmoe-1b-7b's routing shape (64 experts, top-8) at small widths."""
    p = _mk_params(rng, d, E, f, dtype)
    p["router"] = jnp.asarray(rng.standard_normal((d, E)), jnp.float32)
    return p


def _pool(rng, params, slot_of_expert, n_slots):
    """A pool of n_slots slots (noise in the unused ones) holding expert e
    in slot slot_of_expert[e] where that is >= 0."""
    pool = {}
    for kk in ("w_gate", "w_up", "w_down"):
        w = params[kk]
        buf = jnp.asarray(rng.standard_normal((n_slots,) + w.shape[1:]),
                          w.dtype)
        for e, s in enumerate(slot_of_expert):
            if s >= 0:
                buf = buf.at[s].set(w[e])
        pool[kk] = buf
    return pool


@pytest.mark.parametrize("T", [8, 32], ids=["decode", "chunk"])
def test_matches_einsum_oracle_and_dense_reference(T):
    """At a decode shape (8 tokens, top-8 of 64) and a chunk shape (32
    tokens): bit-exact in bf16 with the per-slot einsum over the whole pool;
    in f32, where a dot of another shape sums in another order, within f32
    rounding of it and of the dense all-experts reference."""
    rng = np.random.default_rng(20 + T)
    E, k, S = 64, 8, 80
    moe = MoEConfig(num_experts=E, top_k=k, d_expert=32)
    soe = jnp.asarray(rng.permutation(S)[:E], jnp.int32)
    outs = {}
    for dtype in (jnp.bfloat16, jnp.float32):
        p = _olmoe_like(rng, dtype=dtype)
        pool = _pool(rng, p, np.asarray(soe), S)
        x = jnp.asarray(rng.standard_normal((T, 64)), dtype)
        out, _ = moe_mod.moe_slotbuf(p, pool, soe, x, moe, interpret=True)
        oracle, _ = ref.moe_slotbuf_einsum_ref(p, pool, soe, x, moe)
        outs[dtype] = np.asarray(out, np.float32), np.asarray(oracle,
                                                              np.float32)
    np.testing.assert_array_equal(*outs[jnp.bfloat16])
    np.testing.assert_allclose(*outs[jnp.float32], rtol=1e-5, atol=1e-6)
    dense, _ = moe_mod.moe_reference(p, x, moe)
    np.testing.assert_allclose(outs[jnp.float32][0], np.asarray(dense),
                               rtol=1e-5, atol=1e-6)


def test_non_resident_experts_contribute_zero_and_clobber_nothing():
    """Half of the routed experts not resident: every token gets exactly
    the gate-weighted sum of its RESIDENT experts' outputs (a missing
    expert adds zero), whichever slots the resident ones sit in — the
    missing experts' groups are dead and cannot overwrite a live one."""
    rng = np.random.default_rng(31)
    d, E, f, T, k, S = 32, 8, 16, 12, 4, 6
    moe = MoEConfig(num_experts=E, top_k=k, d_expert=f)
    p = _olmoe_like(rng, d=d, E=E, f=f)
    soe = np.array([4, -1, 0, -1, 5, -1, 2, -1])   # odd experts missing
    pool = _pool(rng, p, soe, S)
    x = jnp.asarray(rng.standard_normal((T, d)), jnp.float32)
    out, r = moe_mod.moe_slotbuf(p, pool, jnp.asarray(soe, jnp.int32), x,
                                 moe, interpret=True)
    ids, gates = np.asarray(r.expert_ids), np.asarray(r.gates)
    assert (soe[ids] < 0).any() and (soe[ids] >= 0).any()
    want = np.zeros((T, d), np.float32)
    for t in range(T):
        for j in range(k):
            e = int(ids[t, j])
            if soe[e] >= 0:
                want[t] += gates[t, j] * np.asarray(
                    _expert_ffn_rows(p, x[t:t + 1], e))[0]
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-5, atol=1e-6)


def test_empty_groups_stream_nothing():
    """Unrouted and non-resident experts are dead groups: the kernel's
    compacted group count is the number of distinct experts that are both
    routed and resident, and the output still equals the reference."""
    rng = np.random.default_rng(32)
    d, E, f, k = 16, 16, 8, 2
    moe = MoEConfig(num_experts=E, top_k=k, d_expert=f)
    p = _mk_params(rng, d, E, f)
    # one-hot tokens under the forced router: experts 0..5 routed (each
    # token's second choice is a tie, so take them from the ids), 6..15 not
    x = _onehot_tokens([0, 1, 2, 3, 4, 5, 0, 2], d)
    soe = np.full(E, -1)
    soe[[0, 2, 3, 7, 9]] = [3, 0, 5, 1, 2]          # 1, 4, 5 routed, missing
    soe_d = jnp.asarray(soe, jnp.int32)
    pool = _pool(rng, p, soe, 6)
    out, r = moe_mod.moe_slotbuf(p, pool, soe_d, x, moe, interpret=True)
    routed = set(np.asarray(r.expert_ids).reshape(-1).tolist())
    want_live = {e for e in routed if soe[e] >= 0}
    live = moe_mod.live_slots(soe_d, r.expert_ids)
    assert set(np.nonzero(np.asarray(live) >= 0)[0].tolist()) == want_live
    order, n = slot_gather.live_groups(live)
    assert int(n[0]) == len(want_live) > 0
    assert set(np.asarray(order)[:int(n[0])].tolist()) == want_live
    oracle, _ = ref.moe_slotbuf_einsum_ref(p, pool, soe_d, x, moe)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(oracle))


def test_capacity_of_t_rows_never_drops():
    """Every token routed to the same k experts: each expert gets all T
    tokens, which its T rows hold — no assignment drops, and every token's
    output equals the dense reference."""
    rng = np.random.default_rng(33)
    d, E, f, T, k = 16, 8, 8, 24, 3
    moe = MoEConfig(num_experts=E, top_k=k, d_expert=f)
    p = _mk_params(rng, d, E, f)
    router = np.zeros((d, E), np.float32)
    router[0, :k] = [3.0, 2.0, 1.0]                  # experts 0, 1, 2 win
    p["router"] = jnp.asarray(router)
    x = jnp.asarray(np.abs(rng.standard_normal((T, d))) + 0.5, jnp.float32)
    out, r = moe_mod.moe_slotbuf(p, _pool(rng, p, np.arange(E)[::-1], E),
                                 jnp.asarray(np.arange(E)[::-1], jnp.int32),
                                 x, moe, interpret=True)
    assert (np.sort(np.asarray(r.expert_ids), 1) == [0, 1, 2]).all()
    dense, _ = moe_mod.moe_reference(p, x, moe)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense),
                               rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# router-driven entry: `route` on device, then `moe_slotbuf(router_out=...)`,
# as the engine's `pre` and FFN dispatches run it
# ---------------------------------------------------------------------------


def _routed_case(rng, T, E, n_slots, k, norm, d=64, f=128,
                 dtype=jnp.bfloat16):
    """A random router over E experts, n_slots slots holding a random subset
    of the experts, and at least one ROUTED expert left non-resident (when
    more experts are routed than the pool holds, the rest miss too)."""
    moe = MoEConfig(num_experts=E, top_k=k, d_expert=f, router_norm_topk=norm)
    p = _mk_params(rng, d, E, f, dtype)
    p["router"] = jnp.asarray(rng.standard_normal((d, E)), jnp.float32) * 0.3
    x = jnp.asarray(rng.standard_normal((T, d)), dtype) * 0.5
    routed = np.unique(np.asarray(
        moe_mod.route(p["router"], x, k, norm).expert_ids))
    dead = {int(rng.choice(routed))}
    soe = np.full(E, -1)
    live = [e for e in rng.permutation(E) if e not in dead][:n_slots]
    soe[live] = rng.permutation(n_slots)[:len(live)]
    return moe, p, x, soe, _pool(rng, p, soe, n_slots)


def _softmax_topk(x, router, k, norm, bias=0.0):
    """float64 routing written out: softmax over biased logits, top-k."""
    lo = (np.asarray(x, np.float64) @ np.asarray(router, np.float64)
          + np.asarray(bias, np.float64))
    pr = np.exp(lo - lo.max(-1, keepdims=True))
    pr /= pr.sum(-1, keepdims=True)
    ids = np.argsort(-pr, axis=-1, kind="stable")[:, :k]
    gates = np.take_along_axis(pr, ids, -1)
    if norm:
        gates = gates / gates.sum(-1, keepdims=True)
    return ids, gates


def _check_routed_entry(moe, p, x, soe, pool, bias=None):
    """The served entry against the per-slot einsum oracle on the same
    routing, and the routing against float64 softmax-top-k."""
    soe_d = jnp.asarray(soe, jnp.int32)
    r = moe_mod.route(p["router"], x, moe.top_k, moe.router_norm_topk,
                      logit_bias=bias)
    out, r_used = moe_mod.moe_slotbuf(p, pool, soe_d, x, moe, router_out=r,
                                      interpret=True)
    assert r_used is r
    oracle, _ = ref.moe_slotbuf_einsum_ref(p, pool, soe_d, x, moe,
                                           router_out=r)
    # one bf16 rounding apart at most: the kernel's dots have another shape
    # than the oracle's, and can round the last bit of an output either way
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(oracle, np.float32),
                               rtol=2.0 ** -7, atol=1e-6)
    ids, gates = _softmax_topk(x, p["router"], moe.top_k,
                               moe.router_norm_topk,
                               0.0 if bias is None else bias)
    np.testing.assert_array_equal(np.sort(np.asarray(r.expert_ids), -1),
                                  np.sort(ids, -1))
    np.testing.assert_allclose(np.sort(np.asarray(r.gates), -1),
                               np.sort(gates, -1), rtol=1e-5, atol=1e-6)
    routed = np.asarray(r.expert_ids)
    assert (soe[routed] < 0).any() and (soe[routed] >= 0).any()
    return r, out


@pytest.mark.parametrize("T,E,n_slots,k", [(8, 8, 8, 2), (16, 8, 6, 2),
                                           (4, 16, 5, 4), (1, 8, 3, 8)])
@pytest.mark.parametrize("norm", [True, False])
def test_routed_entry_matches_einsum_oracle(T, E, n_slots, k, norm):
    """Random router, some routed experts not resident: `route` then
    `moe_slotbuf(router_out=...)` equals the per-slot einsum oracle on the
    same routing, and the routing is softmax-top-k of the router logits,
    renormalised or not."""
    rng = np.random.default_rng(T * E + k)
    _check_routed_entry(*_routed_case(rng, T, E, n_slots, k, norm))


@pytest.mark.parametrize("delta", [0.0, 1.5])
def test_routed_entry_with_residency_logit_bias(delta):
    """Cache-aware routing rides `route`'s `logit_bias`, built from the slot
    table as the engine's `pre` dispatch gets it: at delta 0 the bias is a
    bit-exact no-op; at delta 1.5 routing follows the biased logits and the
    entry still equals the oracle on that routing."""
    from repro.core.cache_aware import residency_logit_bias
    rng = np.random.default_rng(3)
    moe, p, x, soe, pool = _routed_case(rng, 8, 8, 5, 2, True)
    bias = jnp.asarray(residency_logit_bias(soe >= 0, delta))
    r, out = _check_routed_entry(moe, p, x, soe, pool, bias)
    if delta == 0.0:
        r0, out0 = _check_routed_entry(moe, p, x, soe, pool)
        np.testing.assert_array_equal(np.asarray(r.expert_ids),
                                      np.asarray(r0.expert_ids))
        np.testing.assert_array_equal(np.asarray(out), np.asarray(out0))


def test_routed_entry_off_the_tile():
    """d_model 48 and d_expert 72, off the 128-lane tile, 5 tokens: the
    kernel path needs no padding from its caller."""
    rng = np.random.default_rng(9)
    moe, p, x, soe, pool = _routed_case(rng, 5, 4, 3, 2, True, d=48, f=72,
                                        dtype=jnp.float32)
    soe_d = jnp.asarray(soe, jnp.int32)
    r = moe_mod.route(p["router"], x, 2, True)
    out, _ = moe_mod.moe_slotbuf(p, pool, soe_d, x, moe, router_out=r,
                                 interpret=True)
    oracle, _ = ref.moe_slotbuf_einsum_ref(p, pool, soe_d, x, moe,
                                           router_out=r)
    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                               rtol=1e-5, atol=1e-6)
