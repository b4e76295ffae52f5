"""Batched serving on the real engine: batched decode matching the
single-request oracle under eviction churn and mid-stream
admissions/retirements, working-set admission-cap scheduling, the per-row
sampler, and the unified Request surface."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import reduce_config
from repro.configs.registry import get_config
from repro.core.metrics import ServingReport, request_metrics
from repro.core.step_size import StepSizeController
from repro.runtime.batching import ContinuousBatcher, WorkingSetAdmission
from repro.runtime.engine import Engine, SlotBufferEngine
from repro.runtime.request import Request
from repro.runtime.sampler import sample, sample_rows


# ---------------------------------------------------------------------------
# fast lane: Request / sampler / admission units
# ---------------------------------------------------------------------------

def test_request_eos_token_stops_generation():
    r = Request(prompt=np.arange(4), max_new_tokens=10, eos_token=7)
    r.output = [3, 5]
    assert not r.done
    r.output.append(7)
    assert r.done                      # eos beats max_new_tokens
    # eos only terminates as the LAST token
    r2 = Request(prompt=np.arange(4), max_new_tokens=3, eos_token=7)
    r2.output = [7, 1]
    assert not r2.done
    r2.output.append(2)
    assert r2.done                     # length limit still applies
    assert Request(prompt=np.arange(4), max_new_tokens=2).eos_token is None


def test_request_prompt_len_derivation():
    assert Request(prompt=np.arange(6)).prompt_len == 6
    assert Request(prompt=None, prompt_len=11).prompt_len == 11


def test_sample_vector_temperature_mixes_greedy_and_sampled():
    logits = jnp.asarray([[0.0, 0.0, 10.0, 0.0],
                          [0.0, 30.0, 0.0, 0.0]], jnp.float32)
    key = jax.random.PRNGKey(0)
    # scalar 0 = all greedy (unchanged contract)
    np.testing.assert_array_equal(np.asarray(sample(logits, key, 0.0)),
                                  [2, 1])
    # vector: row 0 greedy, row 1 sampled at a temperature so peaked the
    # draw is deterministic
    out = np.asarray(sample(logits, key, jnp.asarray([0.0, 0.01])))
    assert out[0] == 2 and out[1] == 1
    assert out.dtype == np.int32


def test_sample_rows_keys_are_per_request_not_per_batch():
    """A sampled row's token depends only on ITS key/logits — batch
    composition (what the neighbours are doing) cannot perturb it."""
    V = 16
    rng = np.random.default_rng(0)
    row = jnp.asarray(rng.normal(size=(V,)) * 3, jnp.float32)
    other = jnp.asarray(rng.normal(size=(V,)) * 3, jnp.float32)
    k_mine = jax.random.PRNGKey(42)
    k_other = jax.random.PRNGKey(7)
    temps = jnp.asarray([0.9, 0.9])
    a = np.asarray(sample_rows(jnp.stack([row, other]),
                               jnp.stack([k_mine, k_other]), temps))
    b = np.asarray(sample_rows(jnp.stack([row, row * -1.0]),
                               jnp.stack([k_mine, k_other]), temps))
    assert a[0] == b[0]                      # row 0 unaffected by row 1
    # and greedy rows in the same batch take argmax
    c = np.asarray(sample_rows(jnp.stack([row, other]),
                               jnp.stack([k_mine, k_other]),
                               jnp.asarray([0.0, 0.9])))
    assert c[0] == int(jnp.argmax(row))


def _admission(budget_slots, s=1, bw=0.0, default_ws=2.0, headroom=1.0):
    ctrl = StepSizeController(s=s)
    ctrl.bandwidth_est = bw
    ctrl.layer_time_est = 1.0
    return WorkingSetAdmission(controller=ctrl, slots_per_layer=budget_slots,
                               expert_bytes=1.0 if bw else 0.0,
                               default_ws=default_ws, headroom=headroom)


def test_admission_cap_respected():
    """Requests stop being admitted once the co-batched predicted working
    set would exceed the budget — even with free slots left."""
    adm = _admission(budget_slots=5, default_ws=2.0)
    b = ContinuousBatcher(max_batch=4, admission=adm)
    for _ in range(4):
        b.submit(Request(prompt=np.arange(4), max_new_tokens=2))
    admitted = b.admit()
    # budget 5, each request costs 2: two fit (4 <= 5), a third would be 6
    assert len(admitted) == 2
    assert len(b.waiting) == 2 and len(b.free_slots) == 2
    assert b.stats.admission_deferred == 1


def test_admission_uses_predicted_ws_and_controller_stream_budget():
    """predicted_ws overrides the default cost, and the budget grows with
    the controller's S/bandwidth estimates (the link can stream more of the
    working set within a deeper lookahead)."""
    tight = _admission(budget_slots=2, s=1, bw=0.0)
    b = ContinuousBatcher(max_batch=4, admission=tight)
    cheap = Request(prompt=np.arange(4), predicted_ws=1.0)
    pricey = Request(prompt=np.arange(4), predicted_ws=50.0)
    b.submit(cheap)
    b.submit(pricey)
    assert b.admit() == [cheap]        # 1 + 50 > 2: pricey deferred
    # same queue under a controller whose S=4 lookahead streams 48 more
    # experts per layer window: budget 2 + 48 covers both
    roomy = _admission(budget_slots=2, s=4, bw=12.0)
    b2 = ContinuousBatcher(max_batch=4, admission=roomy)
    c2 = Request(prompt=np.arange(4), predicted_ws=1.0)
    p2 = Request(prompt=np.arange(4), predicted_ws=40.0)
    b2.submit(c2)
    b2.submit(p2)
    assert len(b2.admit()) == 2


def test_admission_no_starvation_when_cap_exceeded():
    """A request whose working set alone exceeds the budget still runs: the
    queue head is always admitted into an empty batch, and head-of-line
    order drains the batch to empty for it."""
    adm = _admission(budget_slots=3, default_ws=2.0)
    b = ContinuousBatcher(max_batch=2, admission=adm)
    small = Request(prompt=np.arange(4), max_new_tokens=1, predicted_ws=2.0)
    huge = Request(prompt=np.arange(4), max_new_tokens=1, predicted_ws=99.0)
    b.submit(small)
    b.submit(huge)
    assert b.admit() == [small]        # huge deferred (2 + 99 > 3)
    assert b.stats.admission_deferred == 1
    b.step({small.slot: 0})            # small finishes, batch drains
    assert b.admit() == [huge]         # empty batch: admitted regardless
    b.step({huge.slot: 0})
    assert b.stats.completed == 2 and not b.has_work


def test_admission_preserves_fifo_order():
    """The cap is head-of-line: a blocked queue head is never overtaken by
    a cheaper request behind it (no reordering starvation)."""
    adm = _admission(budget_slots=3, default_ws=2.0)
    b = ContinuousBatcher(max_batch=3, admission=adm)
    first = Request(prompt=np.arange(4), predicted_ws=2.0)
    blocked = Request(prompt=np.arange(4), predicted_ws=9.0)
    cheap = Request(prompt=np.arange(4), predicted_ws=0.1)
    for r in (first, blocked, cheap):
        b.submit(r)
    assert b.admit() == [first]
    assert b.waiting[0] is blocked     # cheap did NOT jump the queue


# ---------------------------------------------------------------------------
# slow lane: real-engine batched serving
# ---------------------------------------------------------------------------

# A batched row and the same request decoded alone run the same math on
# differently shaped batches, and XLA may order a reduction differently for
# each shape: the rows then differ by float rounding (measured up to 2.4e-7
# on the CPU). Another request's row, or a dropped expert, moves logits by
# orders of magnitude more than this bound.
ROW_ATOL = 1e-5


@pytest.fixture(scope="module")
def serve_setup():
    cfg = reduce_config(get_config("olmoe-1b-7b"), layers=4, d_model=64,
                        heads=4, kv_heads=4, d_ff=128, vocab=512, experts=8,
                        top_k=2, d_expert=32)
    eng = Engine(cfg, max_seq=64)
    return cfg, eng


def _slot_engine(cfg, eng, **kw):
    kw.setdefault("max_seq", 64)
    return SlotBufferEngine(cfg, eng.params, eng.model, **kw)


def _single_request_logits(cfg, eng, prompt, n_steps, **kw):
    """Oracle: a dedicated single-request engine decoding `prompt` greedily;
    returns the prefill + per-step logits rows."""
    sb = _slot_engine(cfg, eng, **kw)
    logits, st = sb.prefill(prompt[None, :])
    rows = [np.asarray(logits)[0]]
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    for _ in range(n_steps):
        logits, st = sb.decode_step(tok, st)
        rows.append(np.asarray(logits)[0])
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
    return rows


@pytest.mark.slow
def test_batched_decode_bit_exact_vs_single_request_under_churn(serve_setup):
    """THE serving-correctness contract: with fewer slots than experts
    (forced eviction churn), a speculative horizon, mid-stream retirement
    and admission into a reused slot, every active row's logits match a
    single-request engine decoding the same prompt at every step (within
    the batch-shape rounding bound ROW_ATOL)."""
    cfg, eng = serve_setup
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab_size, L).astype(np.int32)
               for L in (8, 12, 8, 10)]
    churn = dict(n_slots_per_layer=3, step_size=2)
    sb = _slot_engine(cfg, eng, **churn)
    state = sb.alloc_decode_state(3)
    toks = np.zeros(3, np.int32)
    got = {}
    for slot in (0, 1):                         # admit requests 0, 1
        lo = sb.prefill_into(state, slot, prompts[slot][None, :])
        got[slot] = [np.asarray(lo)[0]]
        toks[slot] = int(jnp.argmax(lo, -1)[0])
    owner = {0: 0, 1: 1}                        # slot -> request
    for step in range(8):
        lo, state = sb.decode_step(jnp.asarray(toks), state)
        lo = np.asarray(lo)
        for slot in range(3):
            if state.active[slot]:
                got[owner[slot]].append(lo[slot])
                toks[slot] = int(np.argmax(lo[slot]))
        if step == 2:        # retire slot 1 mid-stream, admit request 2
            sb.retire_slot(state, 1)
            lo2 = sb.prefill_into(state, 1, prompts[2][None, :])
            owner[1] = 2
            got[2] = [np.asarray(lo2)[0]]
            toks[1] = int(jnp.argmax(lo2, -1)[0])
        if step == 4:        # grow the batch mid-stream: slot 2 joins
            lo3 = sb.prefill_into(state, 2, prompts[3][None, :])
            owner[2] = 3
            got[3] = [np.asarray(lo3)[0]]
            toks[2] = int(jnp.argmax(lo3, -1)[0])
    assert sb.cache.stats.evictions > 0         # the shared cache churned
    assert sb.stats.spec_layers > 0             # speculative window ran
    for rid, rows in got.items():
        want = _single_request_logits(cfg, eng, prompts[rid],
                                      len(rows) - 1, **churn)
        for k, (a, b) in enumerate(zip(rows, want)):
            np.testing.assert_allclose(
                a, b, rtol=0, atol=ROW_ATOL,
                err_msg=f"request {rid} diverged at step {k}")


@pytest.mark.slow
def test_batched_decode_bit_exact_with_replays(serve_setup):
    """Same contract on a buffer tight enough that the merged speculative
    window must mispredict: replays fire and rows stay exact."""
    cfg, eng = serve_setup
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, cfg.vocab_size, 12).astype(np.int32)
               for _ in range(3)]
    # margin 0: the pre-gate predicts exactly top-k from the PREVIOUS
    # layer's hidden state, so near-boundary routing flips mispredict
    churn = dict(n_slots_per_layer=3, step_size=2, pregate_margin=0)
    sb = _slot_engine(cfg, eng, **churn)
    state = sb.alloc_decode_state(3)
    toks = np.zeros(3, np.int32)
    got = {}
    for slot in range(3):
        lo = sb.prefill_into(state, slot, prompts[slot][None, :])
        got[slot] = [np.asarray(lo)[0]]
        toks[slot] = int(jnp.argmax(lo, -1)[0])
    for _ in range(8):
        lo, state = sb.decode_step(jnp.asarray(toks), state)
        lo = np.asarray(lo)
        for slot in range(3):
            got[slot].append(lo[slot])
            toks[slot] = int(np.argmax(lo[slot]))
    assert sb.stats.replays > 0
    for rid, rows in got.items():
        want = _single_request_logits(cfg, eng, prompts[rid],
                                      len(rows) - 1, **churn)
        for k, (a, b) in enumerate(zip(rows, want)):
            np.testing.assert_allclose(
                a, b, rtol=0, atol=ROW_ATOL,
                err_msg=f"request {rid} diverged at step {k}")


@pytest.mark.slow
def test_batched_decode_bit_exact_on_mla_shared_expert_arch():
    """Same per-row contract on an MLA architecture (deepseek-v2-lite smoke:
    latent KV cache with per-row positions, first dense layer, shared
    experts) — the vector-cache_len `mla_decode` path."""
    from repro.configs.registry import get_smoke_config
    cfg = get_smoke_config("deepseek-v2-lite")
    eng = Engine(cfg, max_seq=48)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, cfg.vocab_size, (1, 8)).astype(np.int32)
               for _ in range(2)]
    kw = dict(n_slots_per_layer=cfg.moe.num_experts // 2, step_size=1,
              max_seq=48)
    sb = SlotBufferEngine(cfg, eng.params, eng.model, **kw)
    state = sb.alloc_decode_state(2)
    toks = np.zeros(2, np.int32)
    rows = {0: [], 1: []}
    for slot, p in enumerate(prompts):
        lo = sb.prefill_into(state, slot, p)
        rows[slot].append(np.asarray(lo)[0])
        toks[slot] = int(jnp.argmax(lo, -1)[0])
    for _ in range(5):
        lo, state = sb.decode_step(jnp.asarray(toks), state)
        lo = np.asarray(lo)
        for slot in range(2):
            rows[slot].append(lo[slot])
            toks[slot] = int(np.argmax(lo[slot]))
    assert sb.cache.stats.evictions > 0
    for slot, p in enumerate(prompts):
        ref = SlotBufferEngine(cfg, eng.params, eng.model, **kw)
        lo, st = ref.prefill(p)
        tok = jnp.argmax(lo, -1).astype(jnp.int32)
        np.testing.assert_allclose(rows[slot][0], np.asarray(lo)[0],
                                   rtol=0, atol=ROW_ATOL)
        for k in range(5):
            lo, st = ref.decode_step(tok, st)
            tok = jnp.argmax(lo, -1).astype(jnp.int32)
            np.testing.assert_allclose(
                rows[slot][k + 1], np.asarray(lo)[0], rtol=0, atol=ROW_ATOL,
                err_msg=f"MLA row {slot} diverged at step {k}")


@pytest.mark.slow
def test_serving_engine_end_to_end_matches_generate(serve_setup):
    """ServingEngine greedy outputs == single-request generate per request,
    and the report is the SAME ServingReport type the simulator emits, with
    coherent SLO fields."""
    from repro.runtime.serving import EngineServingConfig, ServingEngine
    cfg, eng = serve_setup
    rng = np.random.default_rng(3)
    reqs = [Request(prompt=rng.integers(0, cfg.vocab_size, 8).astype(np.int32),
                    max_new_tokens=4 + (i % 3)) for i in range(5)]
    sb = _slot_engine(cfg, eng, n_slots_per_layer=4, step_size=1)
    srv = ServingEngine(sb, EngineServingConfig(max_batch=2))
    rep = srv.serve(reqs)
    assert isinstance(rep, ServingReport)
    assert len(rep.requests) == len(reqs)
    ref = _slot_engine(cfg, eng, n_slots_per_layer=4, step_size=1)
    for r in reqs:
        want = ref.generate(r.prompt[None, :], r.max_new_tokens)[0]
        np.testing.assert_array_equal(np.asarray(r.output), want)
    for m in rep.requests:
        assert m.finish_s >= m.first_token_s >= m.admitted_s >= 0.0
        assert m.ttft_s > 0 and m.e2e_s > 0
    assert rep.makespan_s > 0 and rep.throughput_tok_s > 0
    assert 0 < rep.mean_occupancy <= 1.0
    # max_batch=2 over 5 requests: the batcher really queued
    assert rep.queue_delay["p99"] > 0


@pytest.mark.slow
def test_serving_engine_eos_and_per_request_temperature(serve_setup):
    """eos_token retires a request early through the batched path, and a
    sampled request co-batched with greedy neighbours reproduces its
    single-request token stream (per-row keys + temperature)."""
    from repro.runtime.serving import EngineServingConfig, ServingEngine
    cfg, eng = serve_setup
    rng = np.random.default_rng(5)
    p_greedy = rng.integers(0, cfg.vocab_size, 8).astype(np.int32)
    p_hot = rng.integers(0, cfg.vocab_size, 8).astype(np.int32)
    sb = _slot_engine(cfg, eng, n_slots_per_layer=8)
    hot = Request(prompt=p_hot, max_new_tokens=6, temperature=0.7)
    greedy = Request(prompt=p_greedy, max_new_tokens=6)
    srv = ServingEngine(sb, EngineServingConfig(max_batch=2))
    srv.serve([greedy, hot])
    assert len(hot.output) == 6 and len(greedy.output) == 6
    # replicate the per-request key schedule on a single-request engine
    ref = _slot_engine(cfg, eng, n_slots_per_layer=8)
    key = jax.random.fold_in(srv.base_key, hot.request_id)
    logits, st = ref.prefill(p_hot[None, :])
    tok = sample(logits, key, hot.temperature)
    want = [int(np.asarray(tok)[0])]
    for step in range(1, 6):
        logits, st = ref.decode_step(tok, st)
        key = jax.random.fold_in(key, step)
        tok = sample(logits, key, hot.temperature)
        want.append(int(np.asarray(tok)[0]))
    assert hot.output == want
    # eos: the greedy request's second token, made an eos, stops it at 2
    eos = Request(prompt=p_greedy, max_new_tokens=6,
                  eos_token=greedy.output[1])
    sb2 = _slot_engine(cfg, eng, n_slots_per_layer=8)
    ServingEngine(sb2, EngineServingConfig(max_batch=2)).serve([eos])
    assert eos.output == greedy.output[:2]
    assert eos.done and len(eos.output) < eos.max_new_tokens


@pytest.mark.slow
def test_serving_engine_admission_cap_defers_but_completes(serve_setup):
    """With a deliberately tiny admission headroom the batcher defers
    co-scheduling (serializing the batch) yet every request completes with
    correct greedy output — the cap degrades batching, never correctness."""
    from repro.runtime.serving import EngineServingConfig, ServingEngine
    cfg, eng = serve_setup
    rng = np.random.default_rng(9)
    reqs = [Request(prompt=rng.integers(0, cfg.vocab_size, 8).astype(np.int32),
                    max_new_tokens=3) for _ in range(3)]
    sb = _slot_engine(cfg, eng, n_slots_per_layer=4, step_size=1,
                      link_bandwidth=1.0)   # starved link: tiny stream term
    sb.controller.bandwidth_est = 1.0
    sb.controller.layer_time_est = 1e-9
    srv = ServingEngine(sb, EngineServingConfig(
        max_batch=3, admission_headroom=1e-3))
    rep = srv.serve(reqs)
    assert srv.batcher.stats.admission_deferred > 0
    assert len(rep.requests) == 3
    ref = _slot_engine(cfg, eng, n_slots_per_layer=4, step_size=1)
    for r in reqs:
        np.testing.assert_array_equal(
            np.asarray(r.output),
            ref.generate(r.prompt[None, :], r.max_new_tokens)[0])
    assert rep.mean_occupancy <= 1.0 / 3 + 1e-9   # fully serialized


@pytest.mark.slow
def test_request_metrics_identical_shape_across_backends(serve_setup):
    """One `request_metrics` record serves both backends (the simulator
    path is covered in test_serving.py; here the engine path feeds it)."""
    from repro.runtime.serving import EngineServingConfig, ServingEngine
    cfg, eng = serve_setup
    rng = np.random.default_rng(13)
    req = Request(prompt=rng.integers(0, cfg.vocab_size, 8).astype(np.int32),
                  max_new_tokens=3)
    sb = _slot_engine(cfg, eng, n_slots_per_layer=8)
    ServingEngine(sb, EngineServingConfig(max_batch=1)).serve([req])
    m = request_metrics(req)
    assert m.n_tokens == 3 and m.prompt_len == 8
    assert m.tpot_s > 0 and m.ttft_s > 0
