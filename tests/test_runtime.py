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
    """The FFN runs through the Pallas slot-indirect kernel over the routed,
    resident experts only (interpret mode on CPU), with a pool of half the
    experts so slots churn: bit-exact vs the engine's own fully-resident
    reference, and within bf16 tolerance of the eager model, whose MoE is
    the grouped einsum."""
    cfg = get_smoke_config("olmoe-1b-7b")
    eng = Engine(cfg, max_seq=64)
    toks = jnp.asarray(np.random.default_rng(7).integers(
        0, cfg.vocab_size, (2, 8)), jnp.int32)
    sb = SlotBufferEngine(cfg, eng.params, eng.model,
                          n_slots_per_layer=cfg.moe.num_experts // 2 + 1)
    x_k = sb.forward(toks)
    assert float(jnp.max(jnp.abs(x_k - sb.reference_forward(toks)))) == 0.0
    x_e = _eager_unrolled(eng.model, eng.params, cfg, toks)
    np.testing.assert_allclose(np.asarray(x_k, np.float32),
                               np.asarray(x_e, np.float32),
                               rtol=5e-2, atol=5e-2)
    L = len(sb.moe_layer_ids)
    assert sb.stats.ffn_calls == L
    assert 0 < sb.stats.ffn_experts <= L * cfg.moe.num_experts


def _out_shapes(jaxpr):
    """(primitive, shape) of every value computed in `jaxpr`, sub-jaxprs
    (jit, pallas kernels, loops) included; a jaxpr's inputs are not."""
    from jax.extend import core as jcore
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            yield eqn.primitive.name, tuple(getattr(v.aval, "shape", ()))
        for p in eqn.params.values():
            for sub in p if isinstance(p, (tuple, list)) else (p,):
                if isinstance(sub, jcore.ClosedJaxpr):
                    yield from _out_shapes(sub.jaxpr)
                elif isinstance(sub, jcore.Jaxpr):
                    yield from _out_shapes(sub)


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
@pytest.mark.parametrize("role,B,T", [("moe_ffn_decode", 3, 1),
                                      ("moe_ffn_chunk", 1, 32)],
                         ids=["decode", "chunk"])
def test_ffn_holds_nothing_pool_sized(role, B, T, backend, monkeypatch):
    """Guard: the decode and chunk FFNs compute nothing over the slot pool.
    No value in their traced program has the pool's n_slots as its leading
    dimension — only the pool operand itself does — so a compute over
    every slot cannot come back unnoticed. Traced as on the CPU (the
    kernel's XLA reference) and as on the TPU (the Mosaic kernel)."""
    from repro.models import moe as moe_mod
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    cfg = get_smoke_config("olmoe-1b-7b")
    eng = Engine(cfg, max_seq=64)
    sb = SlotBufferEngine(cfg, eng.params, eng.model, n_slots_per_layer=7)
    n_slots = sb.n_slots
    d, E, k = cfg.d_model, cfg.moe.num_experts, cfg.moe.top_k
    assert n_slots not in (d, E, E + 1, k, B * T, B * T * k, E * B * T,
                           cfg.moe.d_expert)
    i = sb.moe_layer_ids[0]
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((B, T, d)), eng.model.dtype)
    flat = x.reshape(B * T, d)
    r = moe_mod.route(sb._p[i]["moe"]["router"], flat, k)
    slot_map = jnp.asarray(rng.permutation(n_slots)[:E], jnp.int32)
    fn = sb._ffn_fn(sb.specs[i], role)
    jaxpr = jax.make_jaxpr(fn)(sb._p[i], sb.buffer, slot_map, x, flat, r)
    pool_sized = [(name, shape) for name, shape in _out_shapes(jaxpr.jaxpr)
                  if shape and shape[0] == n_slots]
    assert not pool_sized, pool_sized
    assert any(len(v.aval.shape) and v.aval.shape[0] == n_slots
               for v in jaxpr.jaxpr.invars)


def test_ffn_counters_count_routed_experts(monkeypatch):
    """`ffn_calls` counts the FFN dispatches and `ffn_experts` the experts
    they stream: under a hand-built routing, token t picks experts
    2t mod E and 2t+1 mod E in every layer."""
    from repro.models import moe as moe_mod

    def route(router_w, x, top_k, norm_topk=True, logit_bias=None):
        T, E = x.shape[0], router_w.shape[1]
        ids = (jnp.arange(T)[:, None] * top_k
               + jnp.arange(top_k)[None]) % E
        gates = jnp.full((T, top_k), 1.0 / top_k, jnp.float32)
        logits = jnp.zeros((T, E), jnp.float32)
        return moe_mod.RouterOutput(ids.astype(jnp.int32), gates, logits,
                                    jax.nn.softmax(logits, -1))

    monkeypatch.setattr(moe_mod, "route", route)
    cfg = get_smoke_config("olmoe-1b-7b")
    assert (cfg.moe.num_experts, cfg.moe.top_k) == (8, 2)
    eng = Engine(cfg, max_seq=64)
    sb = SlotBufferEngine(cfg, eng.params, eng.model,
                          n_slots_per_layer=cfg.moe.num_experts)
    L = len(sb.moe_layer_ids)
    toks = jnp.zeros((1, 3), jnp.int32)      # 3 tokens: experts 0..5
    sb.forward(toks)
    assert (sb.stats.ffn_calls, sb.stats.ffn_experts) == (L, 6 * L)
    sb.forward(jnp.zeros((2, 3), jnp.int32))  # 6 tokens: all 8 experts
    assert (sb.stats.ffn_calls, sb.stats.ffn_experts) == (2 * L, 14 * L)


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
