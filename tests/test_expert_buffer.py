"""SlotTable invariants + batched swap_in_many vs sequential swap_in."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_smoke_config
from repro.core.expert_buffer import (HostExpertStore, SlotTable, make_buffer,
                                      swap_in, swap_in_many)


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

@pytest.mark.parametrize("n_moved", [1, 3, 4, 7])
def test_swap_in_many_matches_sequential(n_moved):
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
        seq = swap_in(seq, int(s), wg[i], wu[i], wd[i])
    batched = swap_in_many(make_buffer(cfg, n_slots), slots, wg, wu, wd)
    for k in ("w_gate", "w_up", "w_down"):
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


def test_host_expert_store_gathers_contiguous_views():
    rng = np.random.default_rng(3)
    E, d, f = 6, 8, 4
    wg = jnp.asarray(rng.standard_normal((E, d, f)), jnp.bfloat16)
    wu = jnp.asarray(rng.standard_normal((E, d, f)), jnp.bfloat16)
    wd = jnp.asarray(rng.standard_normal((E, f, d)), jnp.bfloat16)
    store = HostExpertStore()
    store.add_layer(0, wg, wu, wd)
    for e, (g_wg, g_wu, g_wd) in zip((4, 1), store.experts([(0, 4), (0, 1)])):
        assert isinstance(g_wg, np.ndarray) and g_wg.flags["C_CONTIGUOUS"]
        np.testing.assert_array_equal(np.asarray(g_wg, np.float32),
                                      np.asarray(wg[e], np.float32))
        np.testing.assert_array_equal(np.asarray(g_wd, np.float32),
                                      np.asarray(wd[e], np.float32))
