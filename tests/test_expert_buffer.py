"""SlotTable invariants, the pinned host store, and batched swap_in_many
vs writing the experts in one at a time."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_smoke_config
from repro.core.expert_buffer import (HostExpertStore, SlotTable,
                                      _swap_in_many, make_buffer,
                                      swap_in_many)


# ---------------------------------------------------------------------------
# SlotTable invariants
# ---------------------------------------------------------------------------

def test_slot_table_assign_release_roundtrip():
    t = SlotTable(num_layers=2, num_experts=4, n_slots=3)
    s = t.assign(0, 2)
    assert t.lookup(0, 2) == s
    assert t.key_of_slot[s] == (0, 2)
    assert t.n_resident == 1
    released = t.release(0, 2)
    assert released == s
    assert t.lookup(0, 2) == -1
    assert t.key_of_slot[s] is None
    assert t.n_resident == 0
    # the slot is reusable after release
    s2 = t.assign(1, 0)
    assert 0 <= s2 < 3


def test_slot_table_free_list_never_double_assigns():
    t = SlotTable(num_layers=2, num_experts=8, n_slots=4)
    taken = [t.assign(0, e) for e in range(4)]
    assert sorted(taken) == [0, 1, 2, 3]       # each slot handed out once
    with pytest.raises(RuntimeError):
        t.assign(1, 0)                          # exhausted -> must refuse
    t.release(0, 1)
    s = t.assign(1, 5)
    assert s == taken[1]                        # freed slot is the one reused
    # releasing and re-assigning repeatedly never yields a duplicate
    seen = {t.lookup(0, 0), t.lookup(0, 2), t.lookup(0, 3), s}
    assert len(seen) == 4


def test_slot_table_layer_isolation():
    t = SlotTable(num_layers=3, num_experts=4, n_slots=6)
    t.assign(0, 1)
    t.assign(1, 1)
    m0, m1, m2 = (t.layer_slot_map(i) for i in range(3))
    assert m0[1] >= 0 and m1[1] >= 0 and m0[1] != m1[1]
    assert (m2 == -1).all()
    # the returned map is a COPY: mutating it cannot corrupt the table
    m0[:] = 99
    assert t.lookup(0, 1) != 99
    # releasing in one layer leaves the other layer's mapping intact
    t.release(0, 1)
    assert t.lookup(0, 1) == -1 and t.lookup(1, 1) >= 0


# ---------------------------------------------------------------------------
# swap_in_many == sequential swap_in (bitwise)
# ---------------------------------------------------------------------------

def _sources(kind, wg, wu, wd):
    """The experts of (N, d, f) / (N, f, d) stacks as `swap_in_many` gets
    them: the device stacks themselves, numpy stacks, or the pinned host
    store's per-expert arrays."""
    if kind == "device":
        return wg, wu, wd
    if kind == "numpy":
        return tuple(np.asarray(w) for w in (wg, wu, wd))
    store = HostExpertStore()
    store.add_layer(0, wg, wu, wd)
    return tuple(zip(*store.experts([(0, e) for e in range(wg.shape[0])])))


@pytest.mark.parametrize("source", ["device", "numpy", "pinned"])
@pytest.mark.parametrize("n_moved", [1, 3, 4, 7])
def test_swap_in_many_matches_sequential(n_moved, source):
    cfg = get_smoke_config("olmoe-1b-7b")
    d, f = cfg.d_model, cfg.moe.d_expert
    n_slots = 8
    rng = np.random.default_rng(n_moved)
    wg = jnp.asarray(rng.standard_normal((n_moved, d, f)), jnp.bfloat16)
    wu = jnp.asarray(rng.standard_normal((n_moved, d, f)), jnp.bfloat16)
    wd = jnp.asarray(rng.standard_normal((n_moved, f, d)), jnp.bfloat16)
    slots = rng.permutation(n_slots)[:n_moved]

    seq = make_buffer(cfg, n_slots)
    for i, s in enumerate(slots):
        seq = {k: seq[k].at[int(s)].set(w[i])
               for k, w in (("w_gate", wg), ("w_up", wu), ("w_down", wd))}
    batched = swap_in_many(make_buffer(cfg, n_slots), slots,
                           *_sources(source, wg, wu, wd))
    for k in ("w_gate", "w_up", "w_down"):
        assert batched[k].dtype == jnp.bfloat16
        assert batched[k].sharding.memory_kind == "device"
        np.testing.assert_array_equal(np.asarray(seq[k], np.float32),
                                      np.asarray(batched[k], np.float32))


def test_swap_in_many_overwrites_previous_occupant():
    cfg = get_smoke_config("olmoe-1b-7b")
    d, f = cfg.d_model, cfg.moe.d_expert
    rng = np.random.default_rng(0)
    old = jnp.asarray(rng.standard_normal((1, d, f)), jnp.bfloat16)
    new = jnp.asarray(rng.standard_normal((1, d, f)), jnp.bfloat16)
    old_d = jnp.asarray(rng.standard_normal((1, f, d)), jnp.bfloat16)
    new_d = jnp.asarray(rng.standard_normal((1, f, d)), jnp.bfloat16)
    buf = make_buffer(cfg, 2)
    buf = swap_in_many(buf, [1], old, old, old_d)
    buf = swap_in_many(buf, [1], new, new, new_d)
    np.testing.assert_array_equal(np.asarray(buf["w_gate"][1], np.float32),
                                  np.asarray(new[0], np.float32))


def _layer(rng, E=6, d=8, f=4):
    return (jnp.asarray(rng.standard_normal((E, d, f)), jnp.bfloat16),
            jnp.asarray(rng.standard_normal((E, d, f)), jnp.bfloat16),
            jnp.asarray(rng.standard_normal((E, f, d)), jnp.bfloat16))


def test_host_expert_store_gathers_contiguous_views():
    wg, wu, wd = _layer(np.random.default_rng(3))
    store = HostExpertStore()
    store.add_layer(0, wg, wu, wd)
    keys = [(0, 4), (0, 1)]
    got = store.experts(keys)
    for e, (g_wg, g_wu, g_wd), again in zip((4, 1), got, store.experts(keys)):
        # handed out by reference: the same stored arrays on every call
        assert all(a is b for a, b in zip((g_wg, g_wu, g_wd), again))
        np.testing.assert_array_equal(np.asarray(g_wg, np.float32),
                                      np.asarray(wg[e], np.float32))
        np.testing.assert_array_equal(np.asarray(g_wd, np.float32),
                                      np.asarray(wd[e], np.float32))


@pytest.mark.parametrize("source", ["device", "numpy"])
def test_host_store_holds_each_expert_in_pinned_host_memory(source):
    ws = _layer(np.random.default_rng(4))
    if source == "numpy":
        ws = tuple(np.asarray(w) for w in ws)
    store = HostExpertStore()
    store.add_layer(0, *ws)
    for e, got in enumerate(store.experts([(0, e) for e in range(6)])):
        for g, w in zip(got, ws):
            assert g.sharding.memory_kind == "pinned_host"
            assert g.shape == w.shape[1:] and g.dtype == jnp.bfloat16
    # the stacked layer (reference and export paths) is the source, bitwise
    for g, w in zip(store.layer(0), ws):
        assert isinstance(g, np.ndarray)
        np.testing.assert_array_equal(g.view(np.uint16),
                                      np.asarray(w).view(np.uint16))


def test_host_store_expert_lookup_compiles_and_dispatches_nothing():
    store = HostExpertStore()
    for li in range(2):
        store.add_layer(li, *_layer(np.random.default_rng(li)))
    keys = [(1, 5), (0, 0), (1, 2), (0, 5)]
    first = store.experts(keys)
    compiles = []

    def on_duration(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        with jax.transfer_guard("disallow"):
            got = store.experts(keys)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
    assert compiles == []
    # nothing computed: every array handed out is the one stored
    assert all(a is b for ga, fa in zip(got, first) for a, b in zip(ga, fa))


def test_swap_sizes_warmed_as_powers_of_two_cover_every_write_size():
    """The benchmark's warm-up writes 1, 2, 4 ... 32 experts once; after
    that no write of 1..32 experts may add a compiled shape."""
    rng = np.random.default_rng(5)
    E = 32
    buf = make_buffer(get_smoke_config("olmoe-1b-7b"), E)
    d, f = buf["w_gate"].shape[1:]
    store = HostExpertStore()
    store.add_layer(0, *_layer(rng, E, d, f))

    def write(n):
        slots = rng.permutation(E)[:n]
        return swap_in_many(buf, slots, *zip(*store.experts(
            [(0, int(e)) for e in rng.permutation(E)[:n]])))
    m = 1
    while m <= 32:
        buf = write(m)
        m *= 2
    warmed = _swap_in_many._cache_size()
    for n in range(1, 33):
        buf = write(n)
        assert _swap_in_many._cache_size() == warmed, n
