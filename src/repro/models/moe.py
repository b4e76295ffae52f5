"""Mixture-of-Experts layer: router, grouped expert compute, slot-buffer path.

Three compute formulations, all numerically equivalent (up to capacity drops):

- `moe_reference`   dense all-experts oracle (smoke tests / kernels ref)
- `moe_grouped`     sort + capacity-buffer + grouped einsum — the production
                    path; expert dim shards over the `model` mesh axis (EP)
- `moe_slotbuf`     ExpertFlow runtime path: the routed experts' weights are
                    streamed from a bounded device-resident slot pool via
                    an indirection table (the paper's GPU-memory cache,
                    TPU-adapted)
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.models.layers import swiglu, trunc_normal


class RouterOutput(NamedTuple):
    expert_ids: jnp.ndarray    # (T, k) int32
    gates: jnp.ndarray         # (T, k) float32, normalized if requested
    logits: jnp.ndarray        # (T, E) float32 (pre-gate signal for ExpertFlow)
    probs: jnp.ndarray         # (T, E) float32 softmax


def init_moe_params(key, d_model: int, moe, dtype=jnp.bfloat16):
    ks = jax.random.split(key, 5)
    E, f = moe.num_experts, moe.d_expert
    p = {
        "router": trunc_normal(ks[0], (d_model, E), d_model ** -0.5, jnp.float32),
        "w_gate": trunc_normal(ks[1], (E, d_model, f), d_model ** -0.5, dtype),
        "w_up": trunc_normal(ks[2], (E, d_model, f), d_model ** -0.5, dtype),
        "w_down": trunc_normal(ks[3], (E, f, d_model), f ** -0.5, dtype),
    }
    if moe.num_shared_experts:
        fs = (moe.d_shared or moe.d_expert) * moe.num_shared_experts
        ks2 = jax.random.split(ks[4], 3)
        p["shared"] = {
            "w_gate": trunc_normal(ks2[0], (d_model, fs), d_model ** -0.5, dtype),
            "w_up": trunc_normal(ks2[1], (d_model, fs), d_model ** -0.5, dtype),
            "w_down": trunc_normal(ks2[2], (fs, d_model), fs ** -0.5, dtype),
        }
    return p


def route(router_w: jnp.ndarray, x: jnp.ndarray, top_k: int,
          norm_topk: bool = True,
          logit_bias: Optional[jnp.ndarray] = None) -> RouterOutput:
    """Top-k softmax routing. x: (T, d) -> assignments over E experts.

    `logit_bias` ((E,) or (T, E) float32, additive) implements §3.4
    cache-aware routing: the engine passes 0 for resident experts and
    -strength for non-resident ones, so a non-resident expert loses its
    top-k slot only to a resident expert within `strength` logits of it.
    Because the bias is one-sided in [-strength, 0], the router
    distribution satisfies KL(p_orig || p_biased) <= strength nats (see
    `core.cache_aware.residency_logit_bias`). The returned logits/probs
    are the BIASED ones — downstream gate weights and pre-gate signals
    must agree with the assignments actually dispatched.
    """
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32),
                        router_w.astype(jnp.float32))
    if logit_bias is not None:
        logits = logits + logit_bias.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, expert_ids = jax.lax.top_k(probs, top_k)
    if norm_topk:
        gates = gates / jnp.maximum(jnp.sum(gates, axis=-1, keepdims=True), 1e-9)
    return RouterOutput(expert_ids.astype(jnp.int32), gates, logits, probs)


def load_balancing_loss(probs: jnp.ndarray, expert_ids: jnp.ndarray,
                        num_experts: int) -> jnp.ndarray:
    """Switch-style auxiliary loss (used when training MoE archs)."""
    T = probs.shape[0]
    counts = jnp.zeros((num_experts,), jnp.float32).at[expert_ids.reshape(-1)].add(1.0)
    frac_tokens = counts / jnp.maximum(counts.sum(), 1.0)
    frac_probs = jnp.mean(probs, axis=0)
    return num_experts * jnp.sum(frac_tokens * frac_probs)


# ---------------------------------------------------------------------------
# Reference (dense) formulation — oracle for tests
# ---------------------------------------------------------------------------

def moe_reference(params, x: jnp.ndarray, moe) -> jnp.ndarray:
    """Computes ALL experts for ALL tokens then combines. O(T*E*f) — smoke only."""
    T, d = x.shape
    r = route(params["router"], x, moe.top_k, moe.router_norm_topk)
    g = jnp.einsum("td,edf->tef", x, params["w_gate"])
    u = jnp.einsum("td,edf->tef", x, params["w_up"])
    h = jax.nn.silu(g) * u
    y_all = jnp.einsum("tef,efd->ted", h, params["w_down"])  # (T, E, d)
    comb = jnp.zeros((T, moe.num_experts), jnp.float32)
    t_idx = jnp.arange(T)[:, None]
    comb = comb.at[t_idx, r.expert_ids].add(r.gates)
    out = jnp.einsum("te,ted->td", comb.astype(x.dtype), y_all)
    if "shared" in params:
        s = params["shared"]
        out = out + swiglu(x, s["w_gate"], s["w_up"], s["w_down"])
    return out, r


# ---------------------------------------------------------------------------
# Explicit expert-parallel formulation (shard_map)
# ---------------------------------------------------------------------------

def _moe_shard_map(params, x, ids_g, gates_g, moe, capacity, mesh, fsdp):
    """Hand-scheduled EP MoE: experts sharded over `model`, groups over the
    batch axes. Collectives are EXACTLY: one weight all-gather over `data`
    per projection (FSDP storage) + one fp32 psum of the layer output over
    `model`. GSPMD's auto-partitioning of the dispatch gather/scatter was
    measured at 2.9-3.1 TB/device/step on qwen3-moe train_4k; this is
    ~0.1 TB."""
    from jax.sharding import PartitionSpec as P

    G, Tg, d = x.shape
    E, k = moe.num_experts, moe.top_k
    C = capacity
    daxes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    msize = mesh.shape["model"]
    E_loc = E // msize
    # wg/wu gather along axis 1 and wd along axis 2, but the gathered dim is
    # d_model in every case, so one legality check covers all three
    gather_w = _fsdp_gather_ok(mesh, fsdp, d)

    def local_fn(wg, wu, wd, x_blk, ids_blk, gates_blk):
        # blocks: wg/wu (E_loc, d/?, f), wd (E_loc, f, d/?),
        # x_blk (G_loc, Tg, d), ids/gates (G_loc, Tg, k)
        if gather_w:
            wg = jax.lax.all_gather(wg, "data", axis=1, tiled=True)
            wu = jax.lax.all_gather(wu, "data", axis=1, tiled=True)
            wd = jax.lax.all_gather(wd, "data", axis=2, tiled=True)
        G_loc = x_blk.shape[0]
        e0 = jax.lax.axis_index("model") * E_loc

        tok, eid, pos, keep, order = jax.vmap(
            lambda ids: compute_dispatch(ids, E, C))(ids_blk)
        pos_c = jnp.where(keep, pos, C - 1)
        local = keep & (eid >= e0) & (eid < e0 + E_loc)
        slot_local = jnp.where(local, (eid - e0) * C + pos_c, E_loc * C)

        # slot -> token map (drop non-local writes), then a LOCAL gather
        slot_tok = jnp.full((G_loc, E_loc * C), Tg, jnp.int32)
        slot_tok = slot_tok.at[jnp.arange(G_loc)[:, None], slot_local].set(
            tok.astype(jnp.int32), mode="drop")
        x_pad = jnp.concatenate(
            [x_blk, jnp.zeros((G_loc, 1, d), x_blk.dtype)], axis=1)
        buf = jnp.take_along_axis(x_pad, slot_tok[..., None], axis=1)
        buf = buf.reshape(G_loc, E_loc, C, d)
        g = jnp.einsum("gecd,edf->gecf", buf, wg)
        u = jnp.einsum("gecd,edf->gecf", buf, wu)
        h = jax.nn.silu(g) * u
        y = jnp.einsum("gecf,efd->gecd", h, wd).reshape(G_loc, E_loc * C, d)

        # combine local experts' contributions, then reduce over model
        y_pad = jnp.concatenate(
            [y, jnp.zeros((G_loc, 1, d), y.dtype)], axis=1)
        yg = jnp.take_along_axis(y_pad, slot_local[..., None], axis=1)
        flat_gates = jnp.take_along_axis(
            gates_blk.reshape(G_loc, Tg * k), order, axis=1)
        contrib = yg.astype(jnp.float32) * \
            (flat_gates * local.astype(jnp.float32))[..., None]
        out = jnp.zeros((G_loc, Tg, d), jnp.float32)
        out = out.at[jnp.arange(G_loc)[:, None], tok].add(contrib)
        # psum in bf16: halves the per-layer EP collective (each token gets
        # contributions from <= top_k shards, so bf16 summation is benign)
        return jax.lax.psum(out.astype(x_blk.dtype), "model")

    wspec_in = P("model", "data" if gather_w else None, None)
    wdspec_in = P("model", None, "data" if gather_w else None)
    bspec = P(daxes if daxes else None, None, None)
    return jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(wspec_in, wspec_in, wdspec_in, bspec, bspec, bspec),
        out_specs=bspec,
        check_vma=False,
    )(params["w_gate"], params["w_up"], params["w_down"], x, ids_g, gates_g)


def _dsize(mesh, axes) -> int:
    n = 1
    for a in axes:
        if a in mesh.axis_names:
            n *= mesh.shape[a]
    return n


def _fsdp_gather_ok(mesh, fsdp: bool, dim: int) -> bool:
    """FSDP weight all-gather is legal iff `dim` tiles evenly over `data`."""
    return (fsdp and "data" in mesh.axis_names
            and dim % _dsize(mesh, ("data",)) == 0)


def _can_shard_map(mesh, moe, G, Tg, d) -> bool:
    if mesh is None or "model" not in mesh.axis_names or Tg <= 1:
        return False
    daxes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dsz = _dsize(mesh, daxes)
    return (moe.num_experts % mesh.shape["model"] == 0
            and G % max(dsz, 1) == 0)


# ---------------------------------------------------------------------------
# Grouped (production) formulation
# ---------------------------------------------------------------------------

def compute_dispatch(expert_ids: jnp.ndarray, num_experts: int, capacity: int):
    """Static-shape dispatch plan from (T, k) assignments.

    Returns (sorted_token, sorted_expert, position_in_expert, keep_mask,
    inv_perm) — all (T*k,). Assignments beyond `capacity` per expert drop.
    """
    T, k = expert_ids.shape
    flat_e = expert_ids.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    sorted_tok = order // k
    # position within expert group = index - start_of_group
    ones = jnp.ones_like(sorted_e)
    counts = jnp.zeros((num_experts,), jnp.int32).at[sorted_e].add(ones)
    starts = jnp.cumsum(counts) - counts                     # exclusive cumsum
    pos = jnp.arange(T * k, dtype=jnp.int32) - starts[sorted_e]
    keep = pos < capacity
    return sorted_tok, sorted_e, pos, keep, order


def moe_grouped(params, x: jnp.ndarray, moe,
                capacity: Optional[int] = None,
                router_out: Optional[RouterOutput] = None):
    """Sort + capacity-buffer grouped MoE.

    x: (T, d) or (G, Tg, d). With a leading group dim the dispatch
    (argsort / gather / scatter) is vmapped per group, so under pjit the
    group dim shards over `data` and the expert dim over `model` with NO
    cross-group data movement — flattening tokens globally made the dispatch
    scatter unpartitionable (a measured 137 GB/device all-reduce per MoE
    layer on qwen3-moe train_4k).
    """
    from repro.distributed.sharding import constrain
    squeeze = x.ndim == 2
    if squeeze:
        x = x[None]
    G, Tg, d = x.shape
    E, k, f = moe.num_experts, moe.top_k, moe.d_expert
    if capacity is None:
        capacity = max(1, int(Tg * k / E * moe.capacity_factor))
    r = router_out if router_out is not None else route(
        params["router"], x.reshape(G * Tg, d), k, moe.router_norm_topk)
    ids_g = r.expert_ids.reshape(G, Tg, k)
    gates_g = r.gates.reshape(G, Tg, k)

    from repro.distributed.sharding import get_mesh
    mesh = get_mesh()
    if _can_shard_map(mesh, moe, G, Tg, d):
        from repro.distributed.sharding import _ACTIVE
        out = _moe_shard_map(params, x, ids_g, gates_g, moe, capacity,
                             mesh, fsdp=_ACTIVE["fsdp"])
        if "shared" in params:
            s = params["shared"]
            out = out + swiglu(x, s["w_gate"], s["w_up"], s["w_down"])
        out = constrain(out, ("data", None, None))
        if squeeze:
            out = out[0]
        return out, r

    tok, eid, pos, keep, order = jax.vmap(
        lambda ids: compute_dispatch(ids, E, capacity))(ids_g)
    pos_c = jnp.where(keep, pos, capacity - 1)          # (G, Tg*k)

    # dispatch: inverse-permutation GATHER. Instead of scattering (Tg*k, d)
    # payload rows into the expert buffer (whose transpose is a huge
    # cross-shard scatter), we scatter only the small int32 slot->token map
    # and build the buffer with take_along_axis. The index scatter is tiny
    # (E*C int32); the payload movement becomes a locally-shardable gather.
    slot = eid * capacity + pos_c                        # (G, Tg*k)
    sentinel = jnp.asarray(Tg, jnp.int32)                # pad row index
    slot_tok = jnp.full((G, E * capacity), sentinel, jnp.int32)
    # dropped assignments write OUT of range (mode="drop") so they cannot
    # clobber the kept token occupying (e, capacity-1) — cf. _moe_shard_map
    write_idx = jnp.where(keep, slot, E * capacity)
    slot_tok = slot_tok.at[jnp.arange(G)[:, None], write_idx].set(
        tok.astype(jnp.int32), mode="drop")
    # shard the (tiny) index map over (data, model) so the payload gather is
    # LOCAL per shard — each (data, model) shard reads only its experts' rows
    slot_tok = constrain(slot_tok.reshape(G, E, capacity),
                         ("data", "model", None)).reshape(G, E * capacity)
    x_pad = jnp.concatenate([x, jnp.zeros((G, 1, d), x.dtype)], axis=1)
    buf = jnp.take_along_axis(x_pad, slot_tok[..., None], axis=1)
    buf = buf.reshape(G, E, capacity, d)
    buf = constrain(buf, ("data", "model", None, None))
    g = jnp.einsum("gecd,edf->gecf", buf, params["w_gate"])
    u = jnp.einsum("gecd,edf->gecf", buf, params["w_up"])
    h = jax.nn.silu(g) * u
    y = jnp.einsum("gecf,efd->gecd", h, params["w_down"])  # (G, E, C, d)
    y = constrain(y, ("data", "model", None, None))

    # combine: batched gather back + scatter-add over tokens (fp32 accum so
    # dispatch order cannot perturb bf16 results — slot-buffer path matches)
    flat_gates = jnp.take_along_axis(gates_g.reshape(G, Tg * k), order,
                                     axis=1)
    yg = jnp.take_along_axis(y.reshape(G, E * capacity, d),
                             slot[..., None], axis=1)
    yg = constrain(yg, ("data", None, None))
    contrib = yg.astype(jnp.float32) * \
        (flat_gates * keep.astype(jnp.float32))[..., None]
    out = jnp.zeros((G, Tg, d), jnp.float32)
    out = out.at[jnp.arange(G)[:, None], tok].add(contrib)
    out = out.astype(x.dtype)
    if "shared" in params:
        s = params["shared"]
        out = out + swiglu(x, s["w_gate"], s["w_up"], s["w_down"])
    out = constrain(out, ("data", None, None))
    if squeeze:
        out = out[0]
    return out, r


# ---------------------------------------------------------------------------
# Slot-buffer (ExpertFlow runtime) formulation
# ---------------------------------------------------------------------------

def _dispatch_gather(x: jnp.ndarray, group_ids: jnp.ndarray, n_groups: int,
                     capacity: int):
    """Inverse-permutation gather dispatch (the `moe_grouped` scheme).

    Instead of scatter-ADDING (T*k, d) payload rows into the group buffer,
    scatter only the small int32 slot->token map and build the buffer with a
    single gather. group_ids may exceed n_groups - 1 (sentinel groups): those
    assignments land past the real buffer and are dropped by `mode="drop"`.

    Returns (buf (n_groups, capacity, d), tok, gid, keep, order, flat_slot)
    where gid is the sorted group id per assignment and flat_slot indexes
    rows of buf.reshape(n_groups*capacity, d), only valid where
    `keep & (gid < n_groups)`.
    """
    T, d = x.shape
    tok, gid, pos, keep, order = compute_dispatch(group_ids, n_groups + 1,
                                                  capacity)
    pos_c = jnp.where(keep, pos, capacity - 1)
    flat_slot = gid * capacity + pos_c                        # (T*k,)
    sentinel_tok = jnp.asarray(T, jnp.int32)
    slot_tok = jnp.full((n_groups * capacity,), sentinel_tok, jnp.int32)
    # dropped (over-capacity) assignments must write OUT of range, not onto
    # (group, capacity-1) — a duplicate-index set there could clobber the
    # kept occupant of the last row (cf. _moe_shard_map's slot_local)
    write_idx = jnp.where(keep, flat_slot, n_groups * capacity)
    slot_tok = slot_tok.at[write_idx].set(tok.astype(jnp.int32), mode="drop")
    x_pad = jnp.concatenate([x, jnp.zeros((1, d), x.dtype)], axis=0)
    buf = x_pad[slot_tok].reshape(n_groups, capacity, d)
    return buf, tok, gid, keep, order, flat_slot


def _combine_gather(y_flat: jnp.ndarray, flat_slot: jnp.ndarray,
                    order: jnp.ndarray, weight: jnp.ndarray, T: int, d: int,
                    valid: jnp.ndarray) -> jnp.ndarray:
    """Gather each assignment's FFN row back and fp32-sum it per token.

    y_flat: (rows, d); rows indexed by flat_slot where `valid`, anything else
    reads the appended zero pad row. `order` is the dispatch's sort
    permutation: the rows go back to (token, choice) order before the sum,
    so a token's k outputs add up in the router's order whatever groups
    (slots) they sat in — a sum in group order would change the f32 result
    with the slot placement.
    """
    rows = y_flat.shape[0]
    y_pad = jnp.concatenate(
        [y_flat, jnp.zeros((1, d), y_flat.dtype)], axis=0)
    idx = jnp.where(valid, flat_slot, rows)
    contrib = y_pad[idx].astype(jnp.float32) * weight[:, None]
    contrib = jnp.zeros_like(contrib).at[order].set(contrib)
    return contrib.reshape(T, -1, d).sum(axis=1)


# a slot-FFN group's rows are a multiple of the sublane tile (free on the
# TPU): a one-row group would be a vector-matrix product, whose f32 sum XLA
# orders differently, so a request served alone would not match its row of
# a batch bit for bit
ROW_TILE = 8


def live_slots(slot_of_expert: jnp.ndarray,
               expert_ids: jnp.ndarray) -> jnp.ndarray:
    """(E,) int32: the slot of each expert that has an assignment in
    `expert_ids` and is resident, -1 for every other expert — the groups
    whose weights the slot FFN streams."""
    routed = jnp.zeros(slot_of_expert.shape, jnp.bool_).at[
        expert_ids.reshape(-1)].set(True)
    return jnp.where(routed, slot_of_expert, -1).astype(jnp.int32)


def moe_slotbuf(params, slot_weights, slot_of_expert: jnp.ndarray,
                x: jnp.ndarray, moe,
                router_out: Optional[RouterOutput] = None,
                interpret: Optional[bool] = None):
    """MoE compute where expert weights live in a bounded slot pool.

    slot_weights: dict(w_gate (S, d, f), w_up (S, d, f), w_down (S, f, d));
    `slot_of_expert`: (E,) int32 slot of each of the layer's experts, -1 if
    not resident. Tokens are dispatched by EXPERT, E groups of T rows (T
    rounded up to ROW_TILE): top-k picks distinct experts per token, so no
    expert can get more than T assignments and nothing drops. The Pallas
    kernel (`kernels.slot_gather.slot_ffn`) then streams, through the slot
    indirection, only the weights of experts that have an assignment and are
    resident — the pool is never a compute dimension. On the CPU the
    kernel's XLA reference (`slot_ffn_ref`) stands in for it unless
    `interpret` asks for the kernel; it gathers the same experts' weights
    and rounds the same way. Assignments to non-resident experts get zero
    gates and read a zero row in the combine, so they contribute nothing;
    the runtime guarantees residency before dispatch, so in normal operation
    there are none.

    `router_out` skips re-routing when the caller already routed (the
    engine routes on device first to learn the needed-expert set).
    Router weights / shared experts stay permanently resident (small).
    """
    from repro.kernels import ops as kernel_ops
    T, d = x.shape
    E, k = moe.num_experts, moe.top_k
    r = router_out if router_out is not None else route(
        params["router"], x, k, moe.router_norm_topk)
    resident = slot_of_expert[r.expert_ids] >= 0                  # (T, k)
    C = -(-T // ROW_TILE) * ROW_TILE
    buf, _, _, _, order, flat_slot = _dispatch_gather(
        x, r.expert_ids, E, C)
    live_slot = live_slots(slot_of_expert, r.expert_ids)
    w = (slot_weights["w_gate"], slot_weights["w_up"], slot_weights["w_down"])
    if interpret is None and jax.default_backend() == "cpu":
        # the kernel's XLA reference, under its numerics contract: running
        # the kernel interpreted in every engine test cost the CPU test
        # suite 28% more time
        y = kernel_ops.slot_ffn_ref(buf, live_slot, *w)
    else:
        y = kernel_ops.slot_ffn(buf, live_slot, *w, interpret=interpret)
    live = resident.reshape(-1)[order]
    weight = r.gates.reshape(-1)[order] * live.astype(jnp.float32)
    out = _combine_gather(y.reshape(E * C, d), flat_slot, order, weight, T,
                          d, valid=live).astype(x.dtype)
    if "shared" in params:
        s = params["shared"]
        out = out + swiglu(x, s["w_gate"], s["w_up"], s["w_down"])
    return out, r
