"""KV-cached incremental decode + adaptive prefetch horizon tests.

Covers the slot-path decode runtime (`SlotBufferEngine.prefill/decode_step/
generate`): bit-exactness versus the fully-resident oracle under eviction
churn (speculative replay included), greedy-token parity with `Engine`,
host-sync collapse as the horizon S grows, the StepSizeController feedback
signals wired into the real engine, and the `Engine.generate` decoded-token
trace fix."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import reduce_config
from repro.configs.registry import get_config, get_smoke_config
from repro.core.expert_buffer import HostExpertStore
from repro.core.expert_tiers import TieredExpertStore, export_expert_shards
from repro.core.prefetcher import Prefetcher, TransferLink
from repro.core.step_size import StepSizeConfig, StepSizeController
from repro.runtime.engine import Engine, SlotBufferEngine


# ---------------------------------------------------------------------------
# fast lane: new supporting pieces
# ---------------------------------------------------------------------------

def test_host_store_experts_match_per_layer_gather():
    rng = np.random.default_rng(0)
    store = HostExpertStore()
    for layer in range(3):
        store.add_layer(layer, rng.normal(size=(4, 6, 5)),
                        rng.normal(size=(4, 6, 5)), rng.normal(size=(4, 5, 6)))
    keys = [(0, 1), (0, 3), (2, 0), (1, 2), (1, 1)]
    got = store.experts(keys)
    assert len(got) == len(keys)
    for (wg, wu, wd), (layer, e) in zip(got, keys):
        assert wg.shape == (6, 5) and wd.shape == (5, 6)
        g1, u1, d1 = store.layer(layer)
        np.testing.assert_array_equal(wg, g1[e])
        np.testing.assert_array_equal(wu, u1[e])
        np.testing.assert_array_equal(wd, d1[e])


def test_prefetcher_unused_prefetch_accounting():
    link = TransferLink(bandwidth=100.0)
    pf = Prefetcher(link, expert_bytes=10.0)
    pf.prefetch_many([(0, 1), (0, 2), (1, 5)], now=0.0)
    pf.advance(10.0)                       # all transfers complete
    pf.demand((0, 1), 10.0)                # used via demand
    pf.note_use((0, 2))                    # used via cache hit
    pf.forget((0, 1))
    pf.forget((0, 2))
    assert pf.n_unused_prefetches == 0     # both were consumed
    pf.forget((1, 5))                      # evicted without any use
    assert pf.n_unused_prefetches == 1


def test_prefetcher_late_prefetch_counter():
    link = TransferLink(bandwidth=1.0)     # 10s per transfer
    pf = Prefetcher(link, expert_bytes=10.0)
    pf.prefetch((3, 0), now=0.0)
    pf.demand((3, 0), now=1.0)             # demanded before completion
    assert pf.n_late_prefetches == 1


def test_controller_horizon_clamps_to_remaining_layers():
    c = StepSizeController(s=4)
    assert c.horizon(10) == 4
    assert c.horizon(2) == 2
    assert c.horizon(0) == 0
    snap = c.snapshot()
    assert snap["s"] == 4 and "bandwidth_est" in snap


# ---------------------------------------------------------------------------
# slow lane: real-engine decode
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _setup(arch):
    cfg = reduce_config(get_config(arch), layers=4, d_model=64,
                        heads=4, kv_heads=4, d_ff=128, vocab=512, experts=8,
                        top_k=2, d_expert=32)
    eng = Engine(cfg, max_seq=64)
    prompt = np.random.default_rng(7).integers(
        0, cfg.vocab_size, (2, 12)).astype(np.int32)
    return cfg, eng, prompt


@pytest.fixture(scope="module")
def decode_setup():
    return _setup("olmoe-1b-7b")


def _slot_engine(cfg, eng, **kw):
    kw.setdefault("max_seq", 64)
    return SlotBufferEngine(cfg, eng.params, eng.model, **kw)


def _drive(sb, prompt, n_steps=10):
    logits, state = sb.prefill(prompt)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    for _ in range(n_steps):
        logits, state = sb.decode_step(tok, state)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
    return logits


@pytest.mark.slow
@pytest.mark.parametrize("arch", ["olmoe-1b-7b", "deepseek-v2-lite"],
                         ids=["gqa", "mla"])
def test_decode_bit_exact_vs_oracle_under_eviction_churn(arch):
    """Per-step decode logits must match the fully-resident oracle BITWISE
    with fewer slots than experts (forced churn) — speculative windows,
    demand swaps, and mispredict replays are numerically invisible. GQA
    attention and MLA (a dense first layer, shared experts) alike."""
    cfg, eng, prompt = _setup(arch)
    for spl, s in ((3, 2), (4, 1)):
        sb = _slot_engine(cfg, eng, n_slots_per_layer=spl, step_size=s)
        lo, st = sb.prefill(prompt)
        lr, sr = sb.reference_prefill(prompt)
        assert float(jnp.max(jnp.abs(lo - lr))) == 0.0
        tok = jnp.argmax(lo, -1).astype(jnp.int32)
        for step in range(8):
            lo, st = sb.decode_step(tok, st)
            lr, sr = sb.reference_decode_step(tok, sr)
            assert float(jnp.max(jnp.abs(lo - lr))) == 0.0, \
                f"divergence at decode step {step} (slots={spl}, S={s})"
            tok = jnp.argmax(lo, -1).astype(jnp.int32)
        assert sb.cache.stats.evictions > 0      # the buffer really churned
        assert int(st.cache_len) == prompt.shape[1] + 8


def _oracle_logits(sb, prompt, n_steps):
    """The fully-resident oracle's logits over its own greedy tokens."""
    lo, st = sb.reference_prefill(prompt)
    out = [np.asarray(lo)]
    toks = []
    for _ in range(n_steps):
        toks.append(jnp.argmax(lo, -1).astype(jnp.int32))
        lo, st = sb.reference_decode_step(toks[-1], st)
        out.append(np.asarray(lo))
    return out, toks


@pytest.mark.parametrize("expert", [0, 3])
def test_oracle_check_catches_a_dropped_expert(decode_setup, monkeypatch,
                                               expert):
    """Control for the bit-exact check above: an FFN whose slot map drops
    one expert in every MoE layer (its group streams nothing, so its
    outputs read zero) fails that check, fed the oracle's own tokens."""
    from repro.kernels import ops
    cfg, eng, prompt = decode_setup
    want, toks = _oracle_logits(
        _slot_engine(cfg, eng, n_slots_per_layer=3, step_size=2), prompt, 8)
    for name in ("slot_ffn", "slot_ffn_ref"):
        real = getattr(ops, name)

        def dropping(x, slot_of_group, *w, _real=real, **kw):
            return _real(x, slot_of_group.at[expert].set(-1), *w, **kw)
        monkeypatch.setattr(ops, name, dropping)
    sb = _slot_engine(cfg, eng, n_slots_per_layer=3, step_size=2)
    lo, st = sb.prefill(prompt)
    got = [np.asarray(lo)]
    for tok in toks:
        lo, st = sb.decode_step(tok, st)
        got.append(np.asarray(lo))
    off = [j for j, (g, w) in enumerate(zip(got, want))
           if not np.array_equal(g, w)]
    assert len(off) > 4, off


@pytest.mark.slow
def test_decode_replay_path_exercised_and_exact(decode_setup):
    """With a tight buffer the speculative window must actually mispredict
    (replays > 0) — and outputs stay exact through the rollback."""
    cfg, eng, prompt = decode_setup
    sb = _slot_engine(cfg, eng, n_slots_per_layer=3, step_size=2)
    sr_engine = _slot_engine(cfg, eng, n_slots_per_layer=3, step_size=2)
    lo, st = sb.prefill(prompt)
    lr, sr = sr_engine.reference_prefill(prompt)
    tok = jnp.argmax(lo, -1).astype(jnp.int32)
    for _ in range(10):
        lo, st = sb.decode_step(tok, st)
        lr, sr = sr_engine.reference_decode_step(tok, sr)
        assert float(jnp.max(jnp.abs(lo - lr))) == 0.0
        tok = jnp.argmax(lo, -1).astype(jnp.int32)
    assert sb.stats.replays > 0
    assert sb.stats.spec_layers > 0


@pytest.mark.slow
@pytest.mark.parametrize("arch", ["olmoe-1b-7b", "deepseek-v2-lite"],
                         ids=["gqa", "mla"])
@pytest.mark.parametrize("batched", [False, True],
                         ids=["single", "batched"])
@pytest.mark.parametrize("s", [1, 3])
def test_decode_step_pregates_inside_the_sync_layers_attention(arch,
                                                               batched, s):
    """With every expert resident nothing replays, and a decode step
    dispatches the embedding, each dense layer, one attention program and
    one FFN per MoE layer, and the logits: a sync layer's pre-gate rides
    its attention program and costs no dispatch of its own."""
    cfg, eng, prompt = _setup(arch)
    E = cfg.moe.num_experts
    sb = _slot_engine(cfg, eng, n_slots_per_layer=E, step_size=s)
    n_moe = len(sb.moe_layer_ids)
    n_dense = len(sb.specs) - n_moe
    if batched:
        state = sb.alloc_decode_state(prompt.shape[0])
        logits = jnp.concatenate([sb.prefill_into(state, b, prompt[b:b + 1])
                                  for b in range(prompt.shape[0])])
    else:
        logits, state = sb.prefill(prompt)
    for li in range(n_moe):
        sb.ensure_resident(li, range(E))
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    for _ in range(4):
        before = sb.stats.snapshot()
        logits, state = sb.decode_step(tok, state)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        after = sb.stats.snapshot()
        assert after["replays"] == before["replays"]
        assert after["jit_calls"] - before["jit_calls"] == \
            2 + n_dense + 2 * n_moe
        assert after["ffn_calls"] - before["ffn_calls"] == n_moe
    assert sb.stats.spec_layers > 0
    names = {f.__name__ for f in sb._fns.values()}
    assert any(n.startswith("pre_decode") and n.endswith("_pregate")
               for n in names), names


@pytest.mark.slow
def test_warm_serve_compiles_nothing_as_the_horizon_moves():
    """The compile contract behind a window with no compiles: once one
    request has been served at each fixed S in 1..s_max (as the benchmark's
    warm-up does), serving under the adaptive controller compiles nothing,
    whatever S it takes and through replays."""
    from repro.runtime.instrument import track_compiles
    from repro.runtime.request import Request
    from repro.runtime.serving import EngineServingConfig, ServingEngine
    cfg, eng, _ = _setup("olmoe-1b-7b")
    sb = _slot_engine(cfg, eng, n_slots_per_layer=3, pregate_margin=0)
    srv = ServingEngine(sb, EngineServingConfig(max_batch=3, prefill_chunk=8,
                                                admission_cap=False))
    rng = np.random.default_rng(5)

    def requests(n, first):
        return [Request(prompt=rng.integers(0, cfg.vocab_size, L)
                        .astype(np.int32), max_new_tokens=6,
                        request_id=first + i)
                for i, L in enumerate((12, 9, 12)[:n])]
    s_max = sb.controller.cfg.s_max
    assert s_max == len(sb.moe_layer_ids) - 1
    srv.serve(requests(3, 0))
    for s in range(1, s_max + 1):
        sb.fixed_s = s
        srv.serve(requests(1, 10 * s))
    sb.fixed_s = None
    real, seen = sb.decode_step, []

    def forced(tok, state):
        sb.controller.s = 1 + len(seen) % s_max
        seen.append(sb.controller.s)
        return real(tok, state)
    sb.decode_step = forced
    replays = sb.stats.replays
    with track_compiles(sb) as probe:
        srv.serve(requests(3, 100))
    assert set(seen) == set(range(1, s_max + 1))
    assert sb.stats.replays > replays
    assert probe.new_entries == 0 and probe.new_compiles == 0


@pytest.mark.slow
def test_generate_greedy_tokens_match_engine(decode_setup, tmp_path):
    """SlotBufferEngine.generate greedy continuation == Engine.generate on
    the same params, across slot-buffer sizes that force eviction churn;
    every swap is written from pinned host memory, and none is from a
    disk-backed store's numpy arrays, which serve the same tokens."""
    cfg, eng, prompt = decode_setup
    ref, _, _ = eng.generate(prompt, n_steps=6)
    E = cfg.moe.num_experts
    for spl in (E, E // 2):
        sb = _slot_engine(cfg, eng, n_slots_per_layer=spl)
        got = sb.generate(prompt, 6)
        np.testing.assert_array_equal(got, ref)
        assert sb.stats.swap_pinned_experts == sb.stats.swap_experts > 0
        if spl < E:
            assert sb.cache.stats.evictions > 0
        # and the slot path agrees with its own fully-resident oracle
        np.testing.assert_array_equal(sb.generate(prompt, 6, reference=True),
                                      ref)
    tiered = TieredExpertStore(
        export_expert_shards(sb.experts, str(tmp_path / "shards")))
    sb = _slot_engine(cfg, eng, n_slots_per_layer=E // 2, store=tiered)
    np.testing.assert_array_equal(sb.generate(prompt, 6), ref)
    assert sb.stats.swap_experts > 0 and sb.stats.swap_pinned_experts == 0


@pytest.mark.slow
def test_generate_greedy_matches_engine_on_shared_expert_arch():
    """Same parity on an arch with shared experts + first dense layer."""
    cfg = get_smoke_config("qwen1.5-moe-a2.7b")
    eng = Engine(cfg, max_seq=64)
    prompt = np.random.default_rng(4).integers(
        0, cfg.vocab_size, (2, 8)).astype(np.int32)
    ref, _, _ = eng.generate(prompt, n_steps=5)
    sb = SlotBufferEngine(cfg, eng.params, eng.model,
                          n_slots_per_layer=cfg.moe.num_experts // 2,
                          max_seq=64)
    np.testing.assert_array_equal(sb.generate(prompt, 5), ref)


@pytest.mark.slow
def test_decode_state_supports_branching(decode_setup):
    """decode_step must not mutate the caller's DecodeState: two
    continuations branched off one saved state stay independent, and
    replaying a branch from the same state reproduces it bitwise."""
    cfg, eng, prompt = decode_setup
    sb = _slot_engine(cfg, eng, n_slots_per_layer=8)
    logits, s0 = sb.prefill(prompt)
    tok_a = jnp.argmax(logits, -1).astype(jnp.int32)
    tok_b = (tok_a + 1) % cfg.vocab_size
    _ = sb.decode_step(tok_a, s0)
    l_b1, _ = sb.decode_step(tok_b, s0)     # branch off the SAME state
    l_b2, _ = sb.decode_step(tok_b, s0)
    assert s0.pos == prompt.shape[1]        # input state untouched
    assert float(jnp.max(jnp.abs(l_b1 - l_b2))) == 0.0


@pytest.mark.slow
def test_decode_step_guards_kv_ring_wraparound(decode_setup):
    """Decoding past max_seq must fail loudly instead of silently wrapping
    the KV ring buffer into an unintended sliding window."""
    cfg, eng, prompt = decode_setup         # prompt length 12
    sb = _slot_engine(cfg, eng, n_slots_per_layer=8, max_seq=14)
    logits, st = sb.prefill(prompt)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    logits, st = sb.decode_step(tok, st)    # cache fills to 13
    logits, st = sb.decode_step(tok, st)    # cache fills to 14 == max_seq
    with pytest.raises(AssertionError, match="max_seq"):
        sb.decode_step(tok, st)


@pytest.mark.slow
def test_host_syncs_collapse_as_horizon_grows(decode_setup):
    """One blocking mask pull per MoE layer at S=0; ~one per S layers once
    the speculative window opens. Roomy buffer => no replays, exact counts."""
    cfg, eng, prompt = decode_setup
    n_moe = 4
    expect = {0: 4.0, 1: 3.0, 2: 2.0}
    for s, want in expect.items():
        sb = _slot_engine(cfg, eng, n_slots_per_layer=8, step_size=s)
        logits, state = sb.prefill(prompt)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        sb.stats.reset()
        n = 6
        for _ in range(n):
            logits, state = sb.decode_step(tok, state)
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
        assert sb.stats.replays == 0
        assert sb.stats.host_syncs / n == want, f"S={s}"
        if s >= 2:
            assert sb.stats.host_syncs / n < n_moe


@pytest.mark.slow
def test_controller_s_rises_under_starved_link_bandwidth(decode_setup):
    """Stall feedback: with a starved TransferLink, prefetched experts land
    late (link-model lateness) — S must rise. An identical engine on a fast
    link sees no late transfers and holds S."""
    cfg, eng, prompt = decode_setup
    results = {}
    for name, bw in (("starved", 1.0), ("fast", 64e9)):
        ctrl = StepSizeController(
            cfg=StepSizeConfig(capacity_guard=False, stall_threshold=40,
                               overfetch_threshold=10 ** 9), s=2)
        sb = _slot_engine(cfg, eng, n_slots_per_layer=6, link_bandwidth=bw,
                          controller=ctrl)
        _drive(sb, prompt)
        results[name] = (ctrl.s, sb.stats.late_hits)
    assert results["fast"][1] == 0 and results["fast"][0] == 2
    assert results["starved"][1] > 0
    assert results["starved"][0] > 2


@pytest.mark.slow
def test_controller_s_falls_under_sustained_overfetch(decode_setup):
    """Overfetch feedback: prefetched-but-unused predictions (settled when
    the layer's actual routing is verified) must walk S down."""
    cfg, eng, prompt = decode_setup
    ctrl = StepSizeController(
        cfg=StepSizeConfig(stall_threshold=10 ** 9, overfetch_threshold=2),
        s=3)
    sb = _slot_engine(cfg, eng, n_slots_per_layer=6, controller=ctrl)
    _drive(sb, prompt)
    assert ctrl.s == ctrl.cfg.s_min
    assert ctrl.s_history and all(
        b < a for a, b in zip([3] + ctrl.s_history, ctrl.s_history))


@pytest.mark.slow
def test_capacity_guard_damps_thrash_driven_raises(decode_setup):
    """When unused-prefetch evidence is outstanding, stalls are capacity
    thrash: the §3.3.2 guard must consume overfetches instead of raising S,
    ending strictly below the unguarded run on the identical workload."""
    cfg, eng, prompt = decode_setup
    final = {}
    for guard in (True, False):
        ctrl = StepSizeController(
            cfg=StepSizeConfig(capacity_guard=guard,
                               overfetch_threshold=10 ** 9), s=2)
        sb = _slot_engine(cfg, eng, n_slots_per_layer=6, controller=ctrl)
        _drive(sb, prompt)
        final[guard] = (ctrl.s, sb.stats.demand_misses)
    assert final[True][1] == final[False][1]      # identical miss workload
    assert final[True][0] < final[False][0]       # guard suppressed raises


@pytest.mark.slow
def test_engine_generate_records_decoded_tokens():
    """Regression (satellite): each decode step's trace entry must include
    the tokens sampled so far, not the frozen prompt."""
    cfg = get_smoke_config("olmoe-1b-7b")
    eng = Engine(cfg, max_seq=64)
    B, T, n = 2, 6, 4
    prompt = np.random.default_rng(3).integers(
        0, cfg.vocab_size, (B, T)).astype(np.int32)
    out, trace, log = eng.generate(prompt, n_steps=n)
    lens = [len(st.token_ids) for st in trace.steps]
    assert lens == [B * T + B * k for k in range(n)]
    # the ids appended at step k are exactly the step-(k-1) samples
    for k in range(1, n):
        np.testing.assert_array_equal(
            trace.steps[k].token_ids[-B:], out[:, k - 1])
    # and with a context past the 64-id feature window, the TraceLog
    # window must SLIDE with decoding (tail, not the frozen prompt head)
    long_prompt = np.random.default_rng(5).integers(
        0, cfg.vocab_size, (B, 40)).astype(np.int32)      # 80 ids > 64
    out2, _, log2 = eng.generate(long_prompt, n_steps=3)
    n_moe = len(eng.moe_layer_ids)
    last_step_ids = log2.samples[-n_moe].token_ids        # step 2, layer 0
    assert len(last_step_ids) == 64
    np.testing.assert_array_equal(
        np.asarray(last_step_ids[-B:]), out2[:, 1])
