"""Chunked prefill: fixed-shape prompt ingestion interleaved with decode.

Contracts under test:
- chunked prefill logits (and subsequent decode) are BIT-exact versus the
  monolithic `prefill` path across chunk sizes, non-divisor prompt lengths,
  and eviction churn — on GQA (olmoe) and MLA + shared experts (deepseek);
- the chunked path's jit compile count is independent of prompt-length
  diversity (the probe in `runtime.instrument` measures it);
- the serving scheduler interleaves prefill chunks with batched decode, so
  a long prompt neither starves co-batched decoders nor perturbs their
  outputs, and TTFT decomposes into queue/prefill/first-step;
- `predict_working_set` buckets prompt lengths (flat compiles, same
  estimate).
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import reduce_config
from repro.configs.registry import get_config, get_smoke_config
from repro.runtime.engine import Engine, SlotBufferEngine
from repro.runtime.instrument import jit_cache_stats, track_compiles
from repro.runtime.request import Request
from repro.runtime.serving import EngineServingConfig, ServingEngine


# ---------------------------------------------------------------------------
# fast lane: instrument probe units
# ---------------------------------------------------------------------------

def test_jit_cache_stats_counts_compiled_specializations():
    import jax

    fns = {"a": jax.jit(lambda x: x + 1), "b": jax.jit(lambda x: x * 2),
           "plain": (lambda x: x)}          # non-jitted entries count 0
    fns["a"](jnp.ones(3))
    fns["a"](jnp.ones(4))                   # second shape -> second compile
    fns["b"](jnp.ones(3))
    stats = jit_cache_stats(fns)
    assert stats["entries"] == 3
    assert stats["compiles"] == 3


def test_track_compiles_reports_growth():
    import jax

    class FakeEngine:
        _fns = {}

    eng = FakeEngine()
    with track_compiles(eng) as probe:
        eng._fns["f"] = jax.jit(lambda x: x + 1)
        eng._fns["f"](jnp.ones(2))
    assert probe.new_entries == 1 and probe.new_compiles == 1
    with track_compiles(eng) as probe:
        eng._fns["f"](jnp.ones(2))          # warm call: no growth
    assert probe.new_entries == 0 and probe.new_compiles == 0


def test_request_metrics_ttft_attribution_identity():
    from repro.core.metrics import RequestMetrics
    m = RequestMetrics(request_id=0, arrival_s=1.0, admitted_s=1.5,
                       first_token_s=4.0, finish_s=6.0, n_tokens=3,
                       prefill_done_s=3.5)
    assert m.queue_delay_s == pytest.approx(0.5)
    assert m.prefill_s == pytest.approx(2.0)
    assert m.first_step_s == pytest.approx(0.5)
    assert m.ttft_s == pytest.approx(
        m.queue_delay_s + m.prefill_s + m.first_step_s)
    # unrecorded prefill completion (monolithic / simulator): prefill runs
    # to the first token and the identity still holds
    legacy = RequestMetrics(request_id=1, arrival_s=0.0, admitted_s=1.0,
                            first_token_s=3.0, finish_s=4.0, n_tokens=2)
    assert legacy.prefill_s == pytest.approx(2.0)
    assert legacy.first_step_s == 0.0
    assert legacy.ttft_s == pytest.approx(
        legacy.queue_delay_s + legacy.prefill_s + legacy.first_step_s)


# ---------------------------------------------------------------------------
# slow lane: real-engine chunked prefill
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def chunk_setup():
    cfg = reduce_config(get_config("olmoe-1b-7b"), layers=4, d_model=64,
                        heads=4, kv_heads=4, d_ff=128, vocab=512, experts=8,
                        top_k=2, d_expert=32)
    eng = Engine(cfg, max_seq=96)
    return cfg, eng


def _slot_engine(cfg, eng, **kw):
    kw.setdefault("max_seq", 96)
    return SlotBufferEngine(cfg, eng.params, eng.model, **kw)


@pytest.mark.slow
def test_chunked_prefill_bit_exact_vs_monolithic_under_churn(chunk_setup):
    """THE chunked-prefill contract: with a slot buffer smaller than the
    expert population (real eviction churn), chunked logits AND the decode
    steps that follow match the monolithic path bitwise — across chunk
    sizes, including non-divisor prompt lengths and chunk > prompt."""
    cfg, eng = chunk_setup
    rng = np.random.default_rng(7)
    churn = dict(n_slots_per_layer=3, step_size=2)
    for T, C in ((7, 4), (12, 5), (16, 8), (9, 32), (24, 8)):
        prompt = rng.integers(0, cfg.vocab_size, (1, T)).astype(np.int32)
        mono = _slot_engine(cfg, eng, **churn)
        chun = _slot_engine(cfg, eng, **churn)
        lo_m, st_m = mono.prefill(prompt)
        lo_c, st_c = chun.prefill_chunked(prompt, chunk_size=C)
        np.testing.assert_array_equal(
            np.asarray(lo_m), np.asarray(lo_c),
            err_msg=f"prefill logits diverged at T={T} C={C}")
        tok = jnp.argmax(lo_m, -1).astype(jnp.int32)
        for step in range(4):
            lm, st_m = mono.decode_step(tok, st_m)
            lc, st_c = chun.decode_step(tok, st_c)
            np.testing.assert_array_equal(
                np.asarray(lm), np.asarray(lc),
                err_msg=f"decode diverged at T={T} C={C} step={step}")
            tok = jnp.argmax(lm, -1).astype(jnp.int32)
        assert chun.cache.stats.evictions > 0    # the cache really churned


def _check_chunked_vs_monolithic_f32(layers):
    """Chunks of 32 and 16 vs whole-prompt prefill of a 64-token prompt, in
    f32 at olmoe-1b-7b's attention widths with 4 experts of d_ff 128."""
    import dataclasses
    base = get_config("olmoe-1b-7b")
    cfg = dataclasses.replace(
        base, num_layers=layers, vocab_size=512, dtype="float32",
        moe=dataclasses.replace(base.moe, num_experts=4, top_k=2,
                                d_expert=128))
    eng = Engine(cfg, max_seq=64)
    sb = SlotBufferEngine(cfg, eng.params, eng.model, n_slots_per_layer=4,
                          max_seq=64)
    prompt = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (1, 64)).astype(np.int32)
    mono = np.asarray(sb.prefill(prompt)[0], np.float64)
    for C in (32, 16):
        chunked = np.asarray(sb.prefill_chunked(prompt, chunk_size=C)[0],
                             np.float64)
        rel = np.sqrt(np.mean((chunked - mono) ** 2) / np.mean(mono ** 2))
        assert rel < 1e-5, (C, rel)


@pytest.mark.slow
def test_chunked_prefill_matches_monolithic_at_published_attention_width():
    """At olmoe-1b-7b's attention widths (d_model 2048, 16 heads of 128)
    bitwise equality no longer holds: XLA reduces a 16- or 32-row chunk's
    dots in another order than a 64-row prompt's. In f32 that reordering
    is all that differs — 4e-7..5e-7 relative RMS on the CPU at one layer —
    so 1e-5 holds the chunk path to it; a wrong mask or offset moves the
    logits by orders of magnitude more."""
    _check_chunked_vs_monolithic_f32(layers=1)


@pytest.mark.slow
def test_chunked_prefill_matches_monolithic_at_published_depth():
    """The same at olmoe-1b-7b's 16 layers: in f32 the reordering stays at
    1.0e-6..1.2e-6 relative RMS on the CPU, so the chunk path holds 1e-5
    through the whole depth. In bf16 it flips roundings that compound with
    depth instead (1.1e-2..7.6e-2 at 16 layers on the CPU, 1.7e-2 on a
    v5e), which is why `chip_smoke.py` prints the bf16 chunked gap and does
    not bound it."""
    _check_chunked_vs_monolithic_f32(layers=16)


@pytest.mark.slow
def test_chunked_prefill_bit_exact_on_mla_shared_expert_arch():
    """Same contract on MLA + shared experts + leading dense layer
    (deepseek-v2-lite smoke): the latent/pe-cache chunk path."""
    cfg = get_smoke_config("deepseek-v2-lite")
    eng = Engine(cfg, max_seq=48)
    rng = np.random.default_rng(2)
    kw = dict(n_slots_per_layer=cfg.moe.num_experts // 2, step_size=1,
              max_seq=48)
    for T, C in ((10, 4), (8, 3)):
        prompt = rng.integers(0, cfg.vocab_size, (1, T)).astype(np.int32)
        mono = SlotBufferEngine(cfg, eng.params, eng.model, **kw)
        chun = SlotBufferEngine(cfg, eng.params, eng.model, **kw)
        lo_m, st_m = mono.prefill(prompt)
        lo_c, st_c = chun.prefill_chunked(prompt, chunk_size=C)
        np.testing.assert_array_equal(np.asarray(lo_m), np.asarray(lo_c))
        tok = jnp.argmax(lo_m, -1).astype(jnp.int32)
        for _ in range(3):
            lm, st_m = mono.decode_step(tok, st_m)
            lc, st_c = chun.decode_step(tok, st_c)
            np.testing.assert_array_equal(np.asarray(lm), np.asarray(lc))
            tok = jnp.argmax(lm, -1).astype(jnp.int32)


@pytest.mark.slow
def test_chunked_prefill_compile_count_flat_across_lengths(chunk_setup):
    """After one warm prompt covering the longest KV-prefix bucket, four
    MORE distinct prompt lengths (divisor and non-divisor) compile NOTHING
    new on the chunked path — the jit cache is keyed on (chunk width, layer
    spec, log-bounded KV bucket) only. The monolithic path compiles per
    distinct length (the regression this PR removes)."""
    cfg, eng = chunk_setup
    rng = np.random.default_rng(11)
    # pin S: the adaptive controller may widen the pregate horizon, which
    # legitimately adds ONE fn per new S (bounded by s_max, not by lengths)
    chun = _slot_engine(cfg, eng, n_slots_per_layer=4, step_size=1)
    chun.prefill_chunked(
        rng.integers(0, cfg.vocab_size, (1, 33)).astype(np.int32),
        chunk_size=8)
    with track_compiles(chun) as probe:
        for T in (13, 17, 21, 29):
            chun.prefill_chunked(
                rng.integers(0, cfg.vocab_size, (1, T)).astype(np.int32),
                chunk_size=8)
    assert probe.new_compiles == 0 and probe.new_entries == 0

    mono = _slot_engine(cfg, eng, n_slots_per_layer=4, step_size=1)
    mono.prefill(rng.integers(0, cfg.vocab_size, (1, 33)).astype(np.int32))
    with track_compiles(mono) as probe:
        for T in (13, 17, 21, 29):
            mono.prefill(
                rng.integers(0, cfg.vocab_size, (1, T)).astype(np.int32))
    assert probe.new_compiles >= 4          # one-per-length: the baseline


@pytest.mark.slow
def test_long_prefill_not_starved_by_short_stream(chunk_setup):
    """Scheduler aging bound: a sustained stream of 1-token short requests
    (each a single chunk, retiring immediately, so a shorter cursor is
    nearly always in flight) cannot defer a long prompt's ingestion
    indefinitely — the starve limit forces the long cursor forward, so its
    prefill completes while shorts are still flowing, within its
    n_chunks * (limit + 1) iteration bound."""
    cfg, eng = chunk_setup
    rng = np.random.default_rng(21)
    long_req = Request(prompt=rng.integers(0, cfg.vocab_size, 32)
                       .astype(np.int32), max_new_tokens=2)
    shorts = [Request(prompt=rng.integers(0, cfg.vocab_size, 8)
                      .astype(np.int32), max_new_tokens=1, arrival_s=1e-3)
              for _ in range(32)]
    sb = _slot_engine(cfg, eng, n_slots_per_layer=4, step_size=1)
    srv = ServingEngine(sb, EngineServingConfig(max_batch=2,
                                                prefill_chunk=8))
    srv.serve([long_req] + shorts)
    assert len(long_req.output) == 2
    # without aging, SRF would hold the long cursor until the 32-short
    # stream drained; with it, the long prompt finishes ingesting while
    # shorts are still being served
    assert long_req.prefill_done_s < max(s.first_token_s for s in shorts)


@pytest.mark.slow
def test_serving_interleaves_decode_with_long_prefill(chunk_setup):
    """No decode starvation: while a long prompt ingests chunk-by-chunk, an
    already-decoding short request keeps emitting tokens — it FINISHES
    before the long prompt's prefill completes — and both requests' greedy
    outputs still match the single-request oracle. A later-admitted short
    prompt also overtakes the long cursor (shortest-remaining-first), so
    its TTFT is not head-of-line blocked."""
    cfg, eng = chunk_setup
    rng = np.random.default_rng(5)
    long_req = Request(prompt=rng.integers(0, cfg.vocab_size, 64)
                       .astype(np.int32), max_new_tokens=4)
    short_req = Request(prompt=rng.integers(0, cfg.vocab_size, 8)
                        .astype(np.int32), max_new_tokens=6)
    sb = _slot_engine(cfg, eng, n_slots_per_layer=4, step_size=1)
    srv = ServingEngine(sb, EngineServingConfig(max_batch=2,
                                                prefill_chunk=8))
    assert srv._chunked
    rep = srv.serve([long_req, short_req])
    # the short request decoded to completion BEFORE the long prompt was
    # even fully ingested: decode demonstrably interleaved with prefill
    assert short_req.finish_s < long_req.prefill_done_s
    # SRF: the short prompt's single chunk overtook the long cursor
    assert short_req.first_token_s < long_req.first_token_s
    ref = _slot_engine(cfg, eng, n_slots_per_layer=4, step_size=1)
    for r in (long_req, short_req):
        np.testing.assert_array_equal(
            np.asarray(r.output),
            ref.generate(r.prompt[None, :], r.max_new_tokens)[0])
    # TTFT attribution is coherent for every request
    for m in rep.requests:
        assert m.prefill_done_s >= 0
        assert m.prefill_s > 0 and m.first_step_s >= 0
        assert m.ttft_s == pytest.approx(
            m.queue_delay_s + m.prefill_s + m.first_step_s)


@pytest.mark.slow
def test_chunked_serving_matches_monolithic_serving_outputs(chunk_setup):
    """The scheduler change is output-invisible: the same request population
    served chunked and monolithic produces identical greedy tokens."""
    cfg, eng = chunk_setup
    outs = {}
    for chunk in (0, 8):
        rng = np.random.default_rng(9)
        reqs = [Request(prompt=rng.integers(0, cfg.vocab_size, L)
                        .astype(np.int32), max_new_tokens=4)
                for L in (20, 8, 33, 8)]
        sb = _slot_engine(cfg, eng, n_slots_per_layer=4, step_size=1)
        ServingEngine(sb, EngineServingConfig(
            max_batch=3, prefill_chunk=chunk)).serve(reqs)
        outs[chunk] = [list(r.output) for r in reqs]
    assert outs[0] == outs[8]


@pytest.mark.slow
def test_predict_working_set_buckets_prompt_lengths(chunk_setup):
    """Admission estimates pad prompts to length buckets: distinct lengths
    within one bucket share ONE compiled specialization, and padding does
    not perturb the estimate itself."""
    cfg, eng = chunk_setup
    rng = np.random.default_rng(13)
    sb = _slot_engine(cfg, eng, n_slots_per_layer=4)
    srv = ServingEngine(sb, EngineServingConfig(max_batch=2))
    prompt = rng.integers(0, cfg.vocab_size, 9).astype(np.int32)
    ws = srv.predict_working_set(Request(prompt=prompt))
    # oracle: the unbucketed computation in plain numpy — padding rows must
    # not perturb the distinct-expert counts
    x = np.asarray(eng.model.embed(eng.params, prompt[None, :])[0],
                   np.float32)
    want = np.mean([len({int(e) for e in
                         np.argsort(-(x @ r), axis=-1)[:, :cfg.moe.top_k]
                         .reshape(-1)})
                    for r in np.asarray(sb._router_stack, np.float32)])
    assert ws == pytest.approx(float(want))
    fn = sb._fns["predict_ws"]
    with track_compiles(sb) as probe:
        for L in (9, 10, 12, 15, 16):      # all bucket to 16
            srv.predict_working_set(
                Request(prompt=rng.integers(0, cfg.vocab_size, L)
                        .astype(np.int32)))
    assert probe.new_compiles == 0
    assert fn._cache_size() == 1
