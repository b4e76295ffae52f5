"""moe_slotbuf unit tests (fast lane): sentinel-slot capacity isolation,
gather-dispatch parity with the grouped path, and the kernel path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import MoEConfig
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
    tokens. They must go to a dead sentinel slot instead."""
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
                                 moe, capacity=C)
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
    slot's capacity must write OUT of range — not onto (slot, capacity-1),
    where a duplicate-index set could zero the kept occupant of the last
    row."""
    d, E, f, C = 16, 4, 8, 4
    moe = MoEConfig(num_experts=E, top_k=1, d_expert=f)
    rng = np.random.default_rng(4)
    params = _mk_params(rng, d, E, f)
    sw = {kk: params[kk] for kk in ("w_gate", "w_up", "w_down")}
    ident = jnp.arange(E, dtype=jnp.int32)
    # 5 tokens onto expert 0 with capacity 4: the first 4 (stable sort) are
    # kept — INCLUDING the one at position capacity-1 — and the 5th drops
    x = _onehot_tokens([0] * 5, d)
    out, _ = moe_mod.moe_slotbuf(params, sw, ident, x, moe, capacity=C)
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
                                   moe, capacity=T * k)
    out_g, _ = moe_mod.moe_grouped(params, x, moe, capacity=T * k)
    np.testing.assert_array_equal(np.asarray(out_s, np.float32),
                                  np.asarray(out_g, np.float32))


def test_kernel_path_matches_einsum_path():
    """use_kernel=True (per-expert dispatch + Pallas slot indirection) must
    agree with the einsum oracle, including with non-resident experts."""
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
    out_e, _ = moe_mod.moe_slotbuf(params, sw, slot_of_expert, x, moe,
                                   capacity=T * k)
    out_k, _ = moe_mod.moe_slotbuf(params, sw, slot_of_expert, x, moe,
                                   capacity=T * k, use_kernel=True,
                                   interpret=True)
    np.testing.assert_allclose(np.asarray(out_k, np.float32),
                               np.asarray(out_e, np.float32),
                               rtol=3e-2, atol=3e-2)


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
    out_a, r = moe_mod.moe_slotbuf(params, sw, ident, x, moe, capacity=T * k)
    out_b, _ = moe_mod.moe_slotbuf(params, sw, ident, x, moe, capacity=T * k,
                                   router_out=r)
    np.testing.assert_array_equal(np.asarray(out_a), np.asarray(out_b))


@pytest.mark.parametrize("use_kernel", [False, True], ids=["einsum", "kernel"])
def test_output_independent_of_slot_placement(use_kernel):
    """Which slots hold a layer's experts must not change its output: each
    token's k expert outputs are summed in the router's order, not in slot
    order (in f32 a different order of the sum changes the last bits)."""
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
        out, _ = moe_mod.moe_slotbuf(p, slots, jnp.asarray(perm, jnp.int32),
                                     x, moe, capacity=T * 4,
                                     use_kernel=use_kernel)
        outs.append(np.asarray(out))
    for o in outs[1:]:
        np.testing.assert_array_equal(o, outs[0])
