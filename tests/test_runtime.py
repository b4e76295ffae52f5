"""Engine + slot-buffer + batching + checkpoint integration tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import Checkpointer, load_checkpoint, save_checkpoint
from repro.configs.registry import get_smoke_config
from repro.core import FeatureSpec, ForestPredictor
from repro.runtime.batching import ContinuousBatcher
from repro import compile_cache
from repro.models import Model
from repro.runtime import engine as engine_mod
from repro.runtime.engine import (ROUTED_EXPERT_KEYS, Engine,
                                  SlotBufferEngine, _all_specs,
                                  _layer_params, init_serving_params,
                                  split_params)
from repro.runtime.request import Request
from repro.models.transformer import layer_forward


@pytest.fixture(scope="module")
def engine():
    return Engine(get_smoke_config("qwen1.5-moe-a2.7b"), max_seq=96)


@pytest.mark.slow
def test_engine_generates_and_collects_traces(engine):
    toks = np.random.default_rng(0).integers(
        0, engine.cfg.vocab_size, (2, 12))
    out, trace, log = engine.generate(toks, n_steps=6)
    assert out.shape == (2, 6)
    assert len(trace.steps) == 6
    L = len(engine.moe_layer_ids)
    assert trace.num_moe_layers == L
    for st in trace.steps:
        assert len(st.assignments) == L
        assert st.hidden_pooled.shape == (L, engine.cfg.d_model)
    assert len(log.samples) == 6 * L


@pytest.mark.slow
def test_engine_trace_feeds_predictor(engine):
    toks = np.random.default_rng(1).integers(
        0, engine.cfg.vocab_size, (2, 12))
    _, trace, log = engine.generate(toks, n_steps=8)
    spec = FeatureSpec(engine.cfg.vocab_size, 8, trace.num_moe_layers,
                       trace.num_experts, include_pregate=True)
    pred = ForestPredictor(spec)
    mse = pred.fit(log)
    assert np.isfinite(mse) and mse < 0.5


def _eager_unrolled(model, params, cfg, toks):
    """Fully-resident eager reference (op-by-op, no jit)."""
    x = model.embed(params, toks)
    B, T = toks.shape
    positions = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
    for i, spec in enumerate(_all_specs(model)):
        x = layer_forward(_layer_params(model, params, i), cfg, spec, x,
                          positions)
    return x


@pytest.mark.slow
def test_slot_buffer_engine_exact_vs_reference():
    """The fused slot path must be BIT-exact versus the fully-resident model
    computed through the same jitted functions (identity slot table over the
    raw stacked weights) — the slot mechanism (indirection, batched swaps,
    prefetch) adds zero numerical difference. The eager unrolled model
    anchors it within bf16 jit-vs-eager rounding."""
    cfg = get_smoke_config("olmoe-1b-7b")
    eng = Engine(cfg, max_seq=64)
    toks = jnp.asarray(np.random.default_rng(2).integers(
        0, cfg.vocab_size, (2, 10)), jnp.int32)
    sb = SlotBufferEngine(cfg, eng.params, eng.model,
                          n_slots_per_layer=cfg.moe.num_experts)
    x_sb = sb.forward(toks)
    x_ref = sb.reference_forward(toks)
    assert float(jnp.max(jnp.abs(x_sb - x_ref))) == 0.0
    assert sb.stats.swap_experts > 0
    x_eager = _eager_unrolled(eng.model, eng.params, cfg, toks)
    np.testing.assert_allclose(
        np.asarray(x_sb, np.float32), np.asarray(x_eager, np.float32),
        rtol=5e-2, atol=5e-2)


@pytest.mark.slow
def test_slot_buffer_legacy_exact_vs_unrolled():
    """The pre-fused path keeps the original guarantee verbatim: eager
    slot-buffer execution is bit-exact versus the eager unrolled model."""
    cfg = get_smoke_config("olmoe-1b-7b")
    eng = Engine(cfg, max_seq=64)
    toks = jnp.asarray(np.random.default_rng(2).integers(
        0, cfg.vocab_size, (2, 10)), jnp.int32)
    sb = SlotBufferEngine(cfg, eng.params, eng.model,
                          n_slots_per_layer=cfg.moe.num_experts, fused=False)
    x_sb = sb.forward(toks)
    x = _eager_unrolled(eng.model, eng.params, cfg, toks)
    assert float(jnp.max(jnp.abs(x_sb - x))) == 0.0
    assert sb.stats.swap_experts > 0


@pytest.mark.slow
def test_slot_buffer_bit_exact_across_evictions():
    """Regression: with fewer slots than experts (forced swap-in/release
    churn), repeated forwards must stay bit-exact versus the fully-resident
    reference — eviction must never corrupt the indirection or weights."""
    cfg = get_smoke_config("olmoe-1b-7b")
    eng = Engine(cfg, max_seq=64)
    sb = SlotBufferEngine(cfg, eng.params, eng.model,
                          n_slots_per_layer=cfg.moe.num_experts // 2)
    rng = np.random.default_rng(11)
    for trial in range(3):
        toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, 6)),
                           jnp.int32)
        x_sb = sb.forward(toks)
        x = sb.reference_forward(toks)
        assert float(jnp.max(jnp.abs(x_sb - x))) == 0.0, \
            f"divergence on forward #{trial}"
    # the tight buffer must actually have churned
    assert sb.cache.stats.evictions > 0
    assert sb.table.n_resident <= sb.n_slots


@pytest.mark.slow
def test_slot_buffer_fused_batches_swaps_and_prefetches():
    """The hot path must issue BATCHED swaps (far fewer device swap calls
    than experts moved), pull only the small mask to host, and prefetch the
    next layer's experts ahead of demand."""
    cfg = get_smoke_config("olmoe-1b-7b")
    eng = Engine(cfg, max_seq=64)
    toks = jnp.asarray(np.random.default_rng(5).integers(
        0, cfg.vocab_size, (2, 12)), jnp.int32)
    sb = SlotBufferEngine(cfg, eng.params, eng.model,
                          n_slots_per_layer=cfg.moe.num_experts)
    sb.forward(toks)
    st = sb.stats
    n_moe = len(sb.moe_layer_ids)
    # at most one demand + one prefetch swap dispatch per MoE layer
    assert st.swap_calls <= 2 * n_moe
    assert st.swap_experts >= st.swap_calls  # batching actually batched
    assert st.prefetched > 0
    assert st.prefetch_hits > 0              # predictions actually landed
    assert st.host_syncs == n_moe            # one mask pull per MoE layer
    # transfers were accounted through the paper's link model
    assert sb.link.bytes_moved > 0


@pytest.mark.slow
def test_prefetch_never_self_evicts_into_duplicate_slots():
    """Regression: with one free slot and an empty low tier, prefetching
    two experts must NOT let the second insert evict the first — that would
    put two different payloads at the same slot index inside one batched
    swap (nondeterministic scatter) and silently desync table and buffer."""
    cfg = get_smoke_config("olmoe-1b-7b")
    eng = Engine(cfg, max_seq=64)
    E = cfg.moe.num_experts
    # capacity E+1 total: demand-fill layer 0 completely -> 1 free slot,
    # low tier empty (demand inserts go high)
    sb = SlotBufferEngine(cfg, eng.params, eng.model, n_slots_per_layer=1)
    sb.n_slots = E + 1
    sb.table = type(sb.table)(len(sb.moe_layer_ids), E, sb.n_slots)
    sb.cache.capacity = E + 1
    from repro.core.expert_buffer import make_buffer
    sb.buffer = make_buffer(cfg, sb.n_slots)
    sb.ensure_resident(0, list(range(E)))
    assert sb.cache.free_slots == 1 and not sb.cache.low
    issued = sb.prefetch_layer(1, [0, 1])
    assert issued == 1                        # second fill refused, not
    s0 = sb.table.lookup(1, 0)                # stacked onto the first
    assert s0 >= 0 and sb.table.lookup(1, 1) == -1
    # table and buffer agree: the issued expert's weights are in its slot
    wg_expected = sb.store.experts([(1, 0)])[0][0]
    np.testing.assert_array_equal(
        np.asarray(sb.buffer["w_gate"][s0], np.float32),
        np.asarray(wg_expected, np.float32))


@pytest.mark.slow
def test_slot_buffer_kernel_path_matches_einsum():
    """use_kernel=True routes the FFN through the Pallas slot-indirect
    kernel (interpret mode on CPU): bit-exact vs its own reference, and
    within bf16 tolerance of the einsum path."""
    cfg = get_smoke_config("olmoe-1b-7b")
    eng = Engine(cfg, max_seq=64)
    toks = jnp.asarray(np.random.default_rng(7).integers(
        0, cfg.vocab_size, (2, 8)), jnp.int32)
    sb_e = SlotBufferEngine(cfg, eng.params, eng.model,
                            n_slots_per_layer=cfg.moe.num_experts)
    sb_k = SlotBufferEngine(cfg, eng.params, eng.model,
                            n_slots_per_layer=cfg.moe.num_experts,
                            use_kernel=True)
    x_e = sb_e.forward(toks)
    x_k = sb_k.forward(toks)
    assert float(jnp.max(jnp.abs(x_k - sb_k.reference_forward(toks)))) == 0.0
    np.testing.assert_allclose(np.asarray(x_k, np.float32),
                               np.asarray(x_e, np.float32),
                               rtol=5e-2, atol=5e-2)
    # switching an engine to the kernel after it served on the einsum path
    # runs the kernel, not the einsum function cached for the same layer
    sb_e.use_kernel = True
    assert float(jnp.max(jnp.abs(sb_e.forward(toks) - x_k))) == 0.0


@pytest.mark.parametrize("arch", ["olmoe-1b-7b", "deepseek-v2-lite"],
                         ids=["gqa", "mla"])
def test_init_serving_params_equals_split_of_full_init(arch):
    """Built layer by layer, the routed experts land in host memory only,
    and every value equals the full stacked tree's (`Model.init`)."""
    cfg = get_smoke_config(arch)
    model = Model(cfg)
    key = jax.random.PRNGKey(3)
    got = init_serving_params(model, key)
    want = split_params(model, model.init(key))

    def same(a, b):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))

    n_moe = sum(s.is_moe for s in _all_specs(model))
    assert len(got.experts) == len(want.experts) == n_moe
    for li in range(n_moe):
        for a, b in zip(got.experts.layer(li), want.experts.layer(li)):
            assert isinstance(a, np.ndarray)
            same(a, b)
    jax.tree.map(same, (got.top, got.layers), (want.top, want.layers))
    assert not any(set(ROUTED_EXPERT_KEYS) & set(p.get("moe", {}))
                   for p in got.layers)


@pytest.mark.slow
def test_swap_writes_chunked_and_exact(monkeypatch):
    """A swap window wider than SWAP_CHUNK goes to the device in writes of
    at most SWAP_CHUNK experts, and the slot path stays bit-exact."""
    monkeypatch.setattr(engine_mod, "SWAP_CHUNK", 2)
    cfg = get_smoke_config("olmoe-1b-7b")
    eng = Engine(cfg, max_seq=64)
    toks = jnp.asarray(np.random.default_rng(5).integers(
        0, cfg.vocab_size, (2, 12)), jnp.int32)
    sb = SlotBufferEngine(cfg, eng.params, eng.model,
                          n_slots_per_layer=cfg.moe.num_experts)
    x = sb.forward(toks)
    assert float(jnp.max(jnp.abs(x - sb.reference_forward(toks)))) == 0.0
    st = sb.stats
    assert st.swap_calls >= st.swap_experts / 2
    assert st.swap_calls > 2 * len(sb.moe_layer_ids)   # windows were split


def test_bandwidth_estimate_sampled_from_completed_windows(monkeypatch):
    """C_s takes one swap window in BANDWIDTH_SAMPLE_EVERY, and each sample
    is timed until the window's writes have landed on the device."""
    cfg = get_smoke_config("olmoe-1b-7b")
    eng = Engine(cfg, max_seq=64)
    sb = SlotBufferEngine(cfg, eng.params, eng.model, n_slots_per_layer=3)
    ready, samples = [], []
    real_block = jax.block_until_ready
    monkeypatch.setattr(engine_mod.jax, "block_until_ready",
                        lambda x: ready.append(x) or real_block(x))
    monkeypatch.setattr(sb.controller, "update_bandwidth",
                        lambda b, s: samples.append((b, len(ready))))
    n = 2 * engine_mod.BANDWIDTH_SAMPLE_EVERY + 1
    for w in range(n):
        sb._dispatch_swap([w % 3], [(sb.moe_layer_ids[0], w % 4)])
    assert [b for b, _ in samples] == [sb._expert_nbytes] * 3
    # each sample drained the device before its window and after it
    assert [r for _, r in samples] == [2, 4, 6]


def test_compile_cache_env_dir_wins_else_checkout(monkeypatch, tmp_path):
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        jax.config.update("jax_compilation_cache_dir", prev)
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == prev   # untouched
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        got = compile_cache.enable_compile_cache()
        assert got == jax.config.jax_compilation_cache_dir
        assert got == str(compile_cache.CACHE_DIR)
        assert (compile_cache.CACHE_DIR.parent / "src" / "repro").is_dir()
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


@pytest.mark.slow
def test_slot_buffer_bounded_capacity_evicts_and_still_works():
    cfg = get_smoke_config("olmoe-1b-7b")
    eng = Engine(cfg, max_seq=64)
    # only half the experts fit per layer
    sb = SlotBufferEngine(cfg, eng.params, eng.model,
                          n_slots_per_layer=cfg.moe.num_experts // 2)
    toks = jnp.asarray(np.random.default_rng(3).integers(
        0, cfg.vocab_size, (1, 6)), jnp.int32)
    x1 = sb.forward(toks)
    swaps_first = sb.stats.swap_experts
    x2 = sb.forward(toks)
    assert jnp.isfinite(x1).all() and jnp.isfinite(x2).all()
    # deterministic routing -> second pass hits cached experts more
    assert sb.stats.swap_experts - swaps_first <= swaps_first


def test_continuous_batcher_slots_and_completion():
    b = ContinuousBatcher(max_batch=2)
    reqs = [Request(np.arange(4), max_new_tokens=2) for _ in range(3)]
    for r in reqs:
        b.submit(r)
    admitted = b.admit()
    assert len(admitted) == 2 and b.waiting
    finished = b.step({0: 7, 1: 8})
    assert not finished
    finished = b.step({0: 9, 1: 10})
    assert len(finished) == 2
    admitted = b.admit()
    assert len(admitted) == 1 and admitted[0].slot in (0, 1)
    b.step({admitted[0].slot: 1})
    b.step({admitted[0].slot: 2})
    assert not b.has_work
    assert b.stats.completed == 3


def test_continuous_batcher_arrival_gated_admission_and_release():
    b = ContinuousBatcher(max_batch=2)
    early = Request(np.arange(4), max_new_tokens=1)
    late = Request(np.arange(4), max_new_tokens=1)
    early.arrival_s, late.arrival_s = 0.0, 5.0
    b.submit(early)
    b.submit(late)
    # at t=1 only the arrived request is admitted
    admitted = b.admit(now=1.0)
    assert admitted == [early] and len(b.waiting) == 1
    # release frees the slot outside the step() path
    early.output.append(3)
    b.release(early)
    assert early.slot not in b.active and b.stats.completed == 1
    # double-release is a no-op
    b.release(early)
    assert b.stats.completed == 1
    admitted = b.admit(now=6.0)
    assert admitted == [late] and not b.waiting


def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "b": [jnp.ones((4,), jnp.bfloat16),
                  {"c": jnp.zeros((2, 2), jnp.int32)}]}
    save_checkpoint(str(tmp_path / "ck"), tree, step=7)
    restored, step = load_checkpoint(str(tmp_path / "ck"), tree)
    assert step == 7
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpointer_retention_and_restore(tmp_path):
    ck = Checkpointer(str(tmp_path), keep=2, every=1)
    state = {"w": jnp.zeros((3,))}
    for s in range(1, 5):
        state = {"w": state["w"] + 1}
        ck.maybe_save(s, state, blocking=True)
    dirs = sorted(p.name for p in tmp_path.iterdir())
    assert dirs == ["step_3", "step_4"]
    restored, step = ck.restore_latest(state)
    assert step == 4
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.full(3, 4.0))
