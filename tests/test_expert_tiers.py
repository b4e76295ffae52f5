"""Tiered expert store: shard format round-trips, host staging tier
semantics (budget/LRU/pins), per-expert host views, and
bit-exact engine serving through the disk->host->device chain."""
import json
import os

import ml_dtypes
import numpy as np
import pytest

from repro.core.expert_buffer import HostExpertStore
from repro.core.expert_tiers import (SHARD_MANIFEST, ExpertShardReader,
                                     HostTierModel, ShardError,
                                     TieredExpertStore, export_expert_shards)


def _store(rng, layers=2, experts=4, dtype=np.float32, d=6, f=10):
    st = HostExpertStore()
    for li in range(layers):
        wg = rng.standard_normal((experts, d, f)).astype(np.float32)
        wu = rng.standard_normal((experts, d, f)).astype(np.float32)
        wd = rng.standard_normal((experts, f, d)).astype(np.float32)
        st.add_layer(li, wg.astype(dtype), wu.astype(dtype), wd.astype(dtype))
    return st


def _bits(a):
    """Raw-storage view so exotic dtypes compare bitwise, NaNs included."""
    return np.asarray(a).view(np.uint8 if a.dtype.itemsize == 1
                              else np.uint16 if a.dtype.itemsize == 2
                              else np.uint32)


# --------------------------------------------------------------------------
# shard format round-trips
# --------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [np.float32, ml_dtypes.bfloat16,
                                   ml_dtypes.float8_e4m3fn])
def test_shard_roundtrip_bitwise(tmp_path, dtype):
    rng = np.random.default_rng(0)
    st = _store(rng, dtype=dtype)
    export_expert_shards(st, str(tmp_path / "sh"))
    rd = ExpertShardReader(str(tmp_path / "sh"))
    assert rd.layers() == [0, 1]
    for li in range(2):
        for e in range(4):
            got = rd.read_expert(li, e)
            for g, w in zip(got, st.experts([(li, e)])[0]):
                assert g.dtype == w.dtype
                np.testing.assert_array_equal(_bits(g), _bits(w))


def test_shard_noncontiguous_subset_and_cross_layer_gather(tmp_path):
    rng = np.random.default_rng(1)
    st = _store(rng, layers=3, experts=8)
    tiered = TieredExpertStore(
        export_expert_shards(st, str(tmp_path / "sh")))
    # non-contiguous, unordered subset within one layer
    subset = [6, 1, 3]
    sub_keys = [(1, e) for e in subset]
    for key in sub_keys:
        assert tiered.demand_host(key, 0.0) is not None
    for ge, we in zip(tiered.experts(sub_keys), st.experts(sub_keys)):
        for g, w in zip(ge, we):
            np.testing.assert_array_equal(g, w)
    # per-expert weights spanning layers in interleaved order
    keys = [(0, 5), (2, 0), (1, 6), (0, 2), (2, 7)]
    for key in keys:
        assert tiered.demand_host(key, 0.0) is not None
    for ge, we in zip(tiered.experts(keys), st.experts(keys)):
        for g, w in zip(ge, we):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_gather_before_residency_is_a_scheduling_bug(tmp_path):
    st = _store(np.random.default_rng(2))
    tiered = TieredExpertStore(export_expert_shards(st, str(tmp_path / "s")))
    with pytest.raises(RuntimeError, match="not staged"):
        tiered.experts([(0, 0)])


def test_truncated_and_corrupt_shards_raise_shard_error(tmp_path):
    st = _store(np.random.default_rng(3))
    sdir = export_expert_shards(st, str(tmp_path / "sh"))

    # truncated .bin -> ShardError at open
    import shutil
    t1 = str(tmp_path / "trunc")
    shutil.copytree(sdir, t1)
    binf = os.path.join(t1, "layer_00000.bin")
    with open(binf, "r+b") as f:
        f.truncate(os.path.getsize(binf) - 8)
    with pytest.raises(ShardError, match="truncated"):
        ExpertShardReader(t1)

    # manifest with inconsistent tensor byte counts -> ShardError
    t2 = str(tmp_path / "badman")
    shutil.copytree(sdir, t2)
    man = json.load(open(os.path.join(t2, SHARD_MANIFEST)))
    man["layers"][0]["tensors"][0]["nbytes"] += 4
    json.dump(man, open(os.path.join(t2, SHARD_MANIFEST), "w"))
    with pytest.raises(ShardError):
        ExpertShardReader(t2)

    # missing shard file -> ShardError
    t3 = str(tmp_path / "miss")
    shutil.copytree(sdir, t3)
    os.remove(os.path.join(t3, "layer_00001.bin"))
    with pytest.raises(ShardError, match="missing"):
        ExpertShardReader(t3)

    # unparsable manifest -> ShardError
    t4 = str(tmp_path / "nojson")
    shutil.copytree(sdir, t4)
    with open(os.path.join(t4, SHARD_MANIFEST), "w") as f:
        f.write("{not json")
    with pytest.raises(ShardError):
        ExpertShardReader(t4)


def test_truncation_after_open_fails_at_materialization(tmp_path):
    # The reader maps shard files lazily, so a file can shrink between
    # construction and first read (partial re-export, disk fault). A read
    # that lands inside the truncated tail must raise ShardError, not
    # silently return short/garbage bytes.
    st = _store(np.random.default_rng(4))
    sdir = export_expert_shards(st, str(tmp_path / "sh"))
    rd = ExpertShardReader(sdir)          # no reads yet: mmap still lazy
    binf = os.path.join(sdir, "layer_00001.bin")
    rec = rd.record_nbytes(1)
    # cut inside record k=2 (a mid-file record, not just the last one)
    with open(binf, "r+b") as f:
        f.truncate(2 * rec + rec // 2)
    with pytest.raises(ShardError, match="truncated"):
        rd.read_expert(1, 2)
    rd.read_expert(1, 0)                  # records before the cut still fine
    with pytest.raises(ShardError, match="truncated"):
        rd.read_expert(1, 3)


def test_manifest_checksums_stamped_and_optional(tmp_path):
    import zlib
    st = _store(np.random.default_rng(5))
    sdir = export_expert_shards(st, str(tmp_path / "sh"))
    rd = ExpertShardReader(sdir)
    assert rd.has_checksums()
    for li in rd.layers():
        for e in range(rd.num_experts(li)):
            want = rd.record_crc(li, e)
            got = zlib.crc32(rd.read_record_bytes(li, e).tobytes())
            assert got == want
    # pre-checksum manifests (no crc32 field) still load; verification
    # silently downgrades to off rather than refusing the store
    man_path = os.path.join(sdir, SHARD_MANIFEST)
    man = json.load(open(man_path))
    for rec in man["layers"]:
        del rec["crc32"]
    json.dump(man, open(man_path, "w"))
    rd2 = ExpertShardReader(sdir)
    assert not rd2.has_checksums()
    assert rd2.record_crc(0, 0) is None
    store = TieredExpertStore(sdir, verify="promote")
    assert store.verify == "off"


# --------------------------------------------------------------------------
# host staging tier: budget, LRU, pins
# --------------------------------------------------------------------------

def _tier(budget_experts, **kw):
    kw.setdefault("disk_bandwidth", 1e12)  # effectively instant promotions
    return HostTierModel(num_layers=2, num_experts=8, expert_nbytes=1000.0,
                         host_budget_bytes=budget_experts * 1000.0, **kw)


def test_budget_lru_eviction_order():
    m = _tier(2)
    for e in range(3):                      # third demand evicts LRU (0,0)
        assert m.demand((0, e), float(e)) is not None
    assert not m.host_resident((0, 0))
    assert m.host_resident((0, 1)) and m.host_resident((0, 2))
    assert m.evictions == 1 and m.host_bytes == 2000.0
    # touching (0,1) makes (0,2) the LRU victim for the next promotion
    assert m.demand((0, 1), 3.0) == (0.0, True)
    assert m.demand((0, 3), 4.0) is not None
    assert not m.host_resident((0, 2)) and m.host_resident((0, 1))


def test_pinned_expert_survives_eviction_churn():
    m = _tier(2)
    assert m.demand((0, 0), 0.0) is not None
    m.pin((0, 0))
    for e in range(1, 6):                   # churn through the other slot
        assert m.demand((0, e), float(e)) is not None
        assert m.host_resident((0, 0)), f"pinned entry evicted at e={e}"
    m.unpin((0, 0))
    assert m.demand((0, 6), 9.0) is not None
    assert not m.host_resident((0, 0))      # evictable again after unpin


def test_demand_overflows_budget_when_all_residents_pinned():
    """Forward progress beats the budget: a demand promotion into a fully
    pinned tier lands anyway (transient overflow), it never deadlocks."""
    m = _tier(1)
    assert m.demand((0, 0), 0.0) is not None
    m.pin((0, 0))
    assert m.demand((0, 1), 1.0) is not None
    assert m.host_resident((0, 0)) and m.host_resident((0, 1))
    assert m.host_bytes == 2000.0           # over budget, by design


def test_disk_prefetch_converts_misses_to_hits():
    m = _tier(8, disk_bandwidth=1e6, prefetch=True)
    m.note_layer_demand(2)
    for e in range(4):
        m.note_predicted([(0, e)])
        m.request((0, e), 0.0)
    m.advance(10.0)                         # promotions land
    for e in range(4):
        stall, hit = m.demand((0, e), 10.0)
        assert hit and stall == 0.0
    assert m.host_hits == 4 and m.host_misses == 0


# --------------------------------------------------------------------------
# per-expert pinned arrays: what swap_in_many sends, with no host copy
# --------------------------------------------------------------------------

def test_host_store_experts_are_uncopied_views_bit_exact():
    rng = np.random.default_rng(7)
    for dtype in (np.float32, ml_dtypes.bfloat16):
        st = _store(rng, layers=3, experts=8, dtype=dtype)
        keys = [(0, 3), (0, 5), (1, 1), (2, 7), (2, 0)]
        for (li, e), ws in zip(keys, st.experts(keys)):
            again = st.experts([(li, e)])[0]
            for w, w2, whole in zip(ws, again, st.layer(li)):
                np.testing.assert_array_equal(_bits(w), _bits(whole[e]))
                assert w is w2 and w.sharding.memory_kind == "pinned_host"


# --------------------------------------------------------------------------
# bit-exact engine serving through the tier under eviction churn
# --------------------------------------------------------------------------

def _greedy_tokens(sb, prompt, n_steps):
    import jax.numpy as jnp
    lo, st = sb.prefill(prompt)
    tok = jnp.argmax(lo, -1).astype(jnp.int32)
    toks = [int(tok[0])]
    for _ in range(n_steps):
        lo, st = sb.decode_step(tok, st)
        tok = jnp.argmax(lo, -1).astype(jnp.int32)
        toks.append(int(tok[0]))
    return toks


@pytest.mark.slow
@pytest.mark.parametrize("arch", ["olmoe-1b-7b", "deepseek-v2-lite"])
def test_engine_bit_exact_through_tier_at_half_budget(tmp_path, arch):
    """SlotBufferEngine on a TieredExpertStore with a host budget of ~50%
    of total expert bytes produces bit-exact greedy tokens vs the
    pre-staged HostExpertStore, under host-tier eviction churn (GQA and
    MLA architectures)."""
    from repro.configs.registry import get_smoke_config
    from repro.runtime.engine import Engine, SlotBufferEngine
    cfg = get_smoke_config(arch)
    eng = Engine(cfg, max_seq=48)
    kw = dict(n_slots_per_layer=2, step_size=1, max_seq=48)
    rng = np.random.default_rng(11)
    prompt = rng.integers(0, cfg.vocab_size, (1, 8)).astype(np.int32)

    ref = SlotBufferEngine(cfg, eng.params, eng.model, **kw)
    want = _greedy_tokens(ref, prompt, 6)

    sdir = export_expert_shards(ref.experts, str(tmp_path / arch))
    store = TieredExpertStore(
        sdir, host_budget_bytes=0.5 * TieredExpertStore(sdir).
        total_expert_bytes)
    sb = SlotBufferEngine(cfg, eng.params, eng.model, store=store, **kw)
    got = _greedy_tokens(sb, prompt, 6)
    assert got == want
    snap = store.snapshot()
    assert snap["evictions"] > 0, "no host-tier churn: budget too generous"
    assert sb.stats.host_hits + sb.stats.host_misses > 0
