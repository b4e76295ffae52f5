"""Pallas kernel validation: shape/dtype sweeps vs pure-jnp oracles
(interpret mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, slot_gather

SHAPES_FFN = [
    # (E, C, D, F, block_c, block_f)
    (2, 128, 64, 128, 128, 128),
    (4, 256, 64, 128, 128, 128),
    (4, 256, 128, 256, 128, 128),
    (8, 128, 32, 64, 64, 64),
    (1, 512, 256, 512, 128, 256),
]


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("shape", SHAPES_FFN)
def test_expert_ffn_matches_ref(shape, dtype):
    E, C, D, F, bc, bf = shape
    rng = np.random.default_rng(E * 1000 + C)
    x = jnp.asarray(rng.standard_normal((E, C, D)), dtype) * 0.5
    wg = jnp.asarray(rng.standard_normal((E, D, F)), dtype) * 0.1
    wu = jnp.asarray(rng.standard_normal((E, D, F)), dtype) * 0.1
    wd = jnp.asarray(rng.standard_normal((E, F, D)), dtype) * 0.1
    out = ops.expert_ffn(x, wg, wu, wd, block_c=bc, block_f=bf,
                         interpret=True)
    ref = ops.expert_ffn_ref(x, wg, wu, wd)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("T,E,k", [(64, 8, 2), (100, 16, 4), (256, 64, 8),
                                   (33, 128, 8), (7, 8, 8)])
@pytest.mark.parametrize("norm", [True, False])
def test_topk_gating_matches_ref(T, E, k, norm):
    rng = np.random.default_rng(T * E)
    logits = jnp.asarray(rng.standard_normal((T, E)), jnp.float32)
    g, i = ops.topk(logits, k, norm=norm, interpret=True)
    gr, ir = ops.topk_ref(logits, k, norm=norm)
    # sets must match; order may differ only on exact ties (none w/ floats)
    assert np.array_equal(np.asarray(i), np.asarray(ir))
    np.testing.assert_allclose(np.asarray(g), np.asarray(gr),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("S_extra", [0, 4])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_slot_ffn_matches_ref(S_extra, dtype):
    E, C, D, F = 4, 128, 64, 128
    S = E + S_extra
    rng = np.random.default_rng(S)
    x = jnp.asarray(rng.standard_normal((E, C, D)), dtype) * 0.5
    sg = jnp.asarray(rng.standard_normal((S, D, F)), dtype) * 0.1
    su = jnp.asarray(rng.standard_normal((S, D, F)), dtype) * 0.1
    sd = jnp.asarray(rng.standard_normal((S, F, D)), dtype) * 0.1
    soe = jnp.asarray(rng.permutation(S)[:E], jnp.int32)
    out = ops.slot_ffn(x, soe, sg, su, sd, interpret=True)
    ref = ops.slot_ffn_ref(x, soe, sg, su, sd)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=tol, atol=tol)


def test_slot_ffn_equals_expert_ffn_under_identity_mapping():
    E, C, D, F = 4, 128, 64, 128
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.standard_normal((E, C, D)), jnp.bfloat16)
    wg = jnp.asarray(rng.standard_normal((E, D, F)), jnp.bfloat16) * 0.1
    wu = jnp.asarray(rng.standard_normal((E, D, F)), jnp.bfloat16) * 0.1
    wd = jnp.asarray(rng.standard_normal((E, F, D)), jnp.bfloat16) * 0.1
    ident = jnp.arange(E, dtype=jnp.int32)
    a = ops.slot_ffn(x, ident, wg, wu, wd, block_f=128, interpret=True)
    b = ops.expert_ffn(x, wg, wu, wd, block_f=128, interpret=True)
    # one numerics contract (`moe_gemm.ffn_block`); slot_ffn rounds its f32
    # sum to the model dtype once, expert_ffn returns the f32 sum
    np.testing.assert_array_equal(np.asarray(a),
                                  np.asarray(b.astype(jnp.bfloat16)))


# slot tables exercising the scalar-prefetch indirection for real:
# non-identity permutations, partial occupancy (S > E, arbitrary slots), and
# repeated lookups (several experts reading the SAME slot)
SLOT_TABLES = [
    ("reversed", 4, [3, 2, 1, 0]),
    ("partial", 7, [5, 0, 6, 2]),
    ("repeated", 3, [2, 0, 2, 1]),
    ("all_same", 5, [3, 3, 3, 3]),
]


@pytest.mark.parametrize("name,S,table", SLOT_TABLES,
                         ids=[t[0] for t in SLOT_TABLES])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_slot_ffn_indirection_tables(name, S, table, dtype):
    """slot_ffn ≡ expert_ffn on pre-gathered weights ≡ einsum reference,
    under permuted / partial / repeated-lookup slot tables."""
    E, C, D, F = 4, 128, 64, 128
    rng = np.random.default_rng(S * 31 + len(name))
    x = jnp.asarray(rng.standard_normal((E, C, D)), dtype) * 0.5
    sg = jnp.asarray(rng.standard_normal((S, D, F)), dtype) * 0.1
    su = jnp.asarray(rng.standard_normal((S, D, F)), dtype) * 0.1
    sd = jnp.asarray(rng.standard_normal((S, F, D)), dtype) * 0.1
    soe = jnp.asarray(table, jnp.int32)
    out = ops.slot_ffn(x, soe, sg, su, sd, interpret=True)
    # the kernel's indirection must be EXACTLY a weight gather: same Pallas
    # arithmetic on pre-gathered weights gives bit-identical output
    via_gather = ops.expert_ffn(x, sg[soe], su[soe], sd[soe], interpret=True)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(via_gather.astype(dtype)))
    ref = ops.slot_ffn_ref(x, soe, sg, su, sd)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("C,F", [(96, 128), (200, 80), (40, 48)])
def test_slot_ffn_non_tile_aligned_shapes(C, F):
    """Capacities that do not divide the preferred 128 tile must still work
    (the block picker falls back to a divisor; arbitrary shapes are legal in
    interpret mode)."""
    E, D, S = 3, 32, 5
    rng = np.random.default_rng(C * F)
    x = jnp.asarray(rng.standard_normal((E, C, D)), jnp.float32) * 0.5
    sg = jnp.asarray(rng.standard_normal((S, D, F)), jnp.float32) * 0.1
    su = jnp.asarray(rng.standard_normal((S, D, F)), jnp.float32) * 0.1
    sd = jnp.asarray(rng.standard_normal((S, F, D)), jnp.float32) * 0.1
    soe = jnp.asarray(rng.permutation(S)[:E], jnp.int32)
    out = ops.slot_ffn(x, soe, sg, su, sd, interpret=True)
    ref = ops.slot_ffn_ref(x, soe, sg, su, sd)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("block_f", [32, 128], ids=["f_tiled", "f_whole"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_slot_ffn_skips_dead_groups(block_f, dtype):
    """Groups with slot -1 (unrouted, or not resident) are dead: compacted
    past the live count and never computed, so even NaN rows and a NaN
    slot 0 behind them cannot reach a live group, whose rows equal the XLA
    reference — bit for bit in one d_ff tile, to f32 summation order across
    tiles."""
    E, C, D, F, S = 6, 8, 64, 128, 7
    rng = np.random.default_rng(block_f)
    table = np.array([-1, 4, -1, 2, 6, -1])
    live = table >= 0
    x = np.asarray(rng.standard_normal((E, C, D)), np.float32) * 0.5
    x[~live] = np.nan
    sg, su, sd = (np.asarray(rng.standard_normal(s), np.float32) * 0.1
                  for s in ((S, D, F), (S, D, F), (S, F, D)))
    for w in (sg, su, sd):
        w[0] = np.nan                       # where a dead group would read
    args = [jnp.asarray(a, dtype) for a in (x, sg, su, sd)]
    soe = jnp.asarray(table, jnp.int32)
    order, n = slot_gather.live_groups(soe)
    assert int(n[0]) == live.sum()
    assert np.asarray(order)[:live.sum()].tolist() == [1, 3, 4]
    out = ops.slot_ffn(args[0], soe, *args[1:], block_f=block_f,
                       interpret=True)
    want = ops.slot_ffn_ref(args[0], soe, *args[1:])
    got, want = np.asarray(out, np.float32), np.asarray(want, np.float32)
    assert np.isfinite(got[live]).all()
    if block_f == F:
        np.testing.assert_array_equal(got[live], want[live])
    else:
        tol = 8e-3 if dtype == jnp.bfloat16 else 2e-6
        np.testing.assert_allclose(got[live], want[live], rtol=tol, atol=tol)


def test_interpret_mode_on_cpu_only(monkeypatch):
    """Mosaic on the TPU, the interpreter on the CPU, and an error on any
    other backend: the kernels never fall back to the interpreter there."""
    for backend, want in (("cpu", True), ("tpu", False)):
        monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
        assert ops._default_interpret() is want
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        ops._default_interpret()
