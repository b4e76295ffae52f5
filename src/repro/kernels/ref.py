"""Pure-jnp oracles for every Pallas kernel (the correctness references)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def expert_ffn_ref(x: jnp.ndarray, w_gate: jnp.ndarray, w_up: jnp.ndarray,
                   w_down: jnp.ndarray) -> jnp.ndarray:
    """Grouped expert FFN. x: (E, C, D); weights (E, D, F)/(E, F, D).
    Returns (E, C, D) float32."""
    g = jnp.einsum("ecd,edf->ecf", x.astype(jnp.float32),
                   w_gate.astype(jnp.float32))
    u = jnp.einsum("ecd,edf->ecf", x.astype(jnp.float32),
                   w_up.astype(jnp.float32))
    h = jax.nn.silu(g) * u
    return jnp.einsum("ecf,efd->ecd", h, w_down.astype(jnp.float32))


def topk_gating_ref(logits: jnp.ndarray, k: int, norm: bool = True):
    """Fused softmax + top-k. logits: (T, E) -> (gates (T,k) f32, ids (T,k) i32)."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gates, ids = jax.lax.top_k(probs, k)
    if norm:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return gates, ids.astype(jnp.int32)


def slot_ffn_ref(x: jnp.ndarray, slot_of_group: jnp.ndarray,
                 s_gate: jnp.ndarray, s_up: jnp.ndarray,
                 s_down: jnp.ndarray) -> jnp.ndarray:
    """XLA reference of `slot_gather.slot_ffn`, under its numerics contract
    (`moe_gemm.ffn_block`; the down projection rounded once, at the end).

    x: (G, C, D) per-group dispatch buffer; slot_of_group: (G,) int32, -1
    for a dead group, whose rows come out zero; slot pool (S, D, F)/(S, F, D).
    """
    s = jnp.maximum(slot_of_group, 0)
    f32 = jnp.float32
    g = jnp.einsum("ecd,edf->ecf", x, s_gate[s],
                   preferred_element_type=f32).astype(x.dtype)
    u = jnp.einsum("ecd,edf->ecf", x, s_up[s],
                   preferred_element_type=f32).astype(x.dtype)
    y = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, s_down[s],
                   preferred_element_type=f32).astype(x.dtype)
    return jnp.where((slot_of_group >= 0)[:, None, None], y, 0)


def moe_slotbuf_einsum_ref(params, slot_weights, slot_of_expert, x, moe,
                           capacity=None, router_out=None):
    """Oracle for `models.moe.moe_slotbuf`: the same routing and combine,
    but tokens dispatched by SLOT and the FFN an einsum over every slot of
    the pool, capacity T*k rows each. Non-resident assignments go to a dead
    sentinel slot past the pool and contribute nothing."""
    from repro.models import moe as moe_mod
    from repro.models.layers import swiglu
    T, d = x.shape
    k = moe.top_k
    n_slots = slot_weights["w_gate"].shape[0]
    capacity = T * k if capacity is None else capacity
    r = router_out if router_out is not None else moe_mod.route(
        params["router"], x, k, moe.router_norm_topk)
    slot_raw = slot_of_expert[r.expert_ids]
    resident = slot_raw >= 0
    gates = r.gates * resident.astype(r.gates.dtype)
    slot_ids = jnp.where(resident, slot_raw, n_slots).astype(jnp.int32)
    buf, _, sid, keep, order, flat_slot = moe_mod._dispatch_gather(
        x, slot_ids, n_slots, capacity)
    g = jnp.einsum("scd,sdf->scf", buf, slot_weights["w_gate"])
    u = jnp.einsum("scd,sdf->scf", buf, slot_weights["w_up"])
    h = jax.nn.silu(g) * u
    y = jnp.einsum("scf,sfd->scd", h, slot_weights["w_down"])
    weight = gates.reshape(-1)[order] * keep.astype(jnp.float32)
    out = moe_mod._combine_gather(
        y.reshape(n_slots * capacity, d), flat_slot, order, weight, T, d,
        valid=keep & (sid < n_slots)).astype(x.dtype)
    if "shared" in params:
        s = params["shared"]
        out = out + swiglu(x, s["w_gate"], s["w_up"], s["w_down"])
    return out, r
