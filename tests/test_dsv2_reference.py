"""DeepSeek-V2 (MLA, a dense first layer, routed and shared experts) served
through `ServingEngine.serve` -> `SlotBufferEngine`, against the plain f32
reference of `bench/models/deepseek_v2.py` on the same seeded weights.

The served path ingests prompts in chunks, decodes three requests of
different lengths in one batch, and holds a slot pool smaller than a
step's working set, so experts swap in and evict every few steps. The
logits after each prompt and at every decode step are compared with the
reference's teacher-forced pass over the prompt and the served tokens. A
reference that leaves out the shared experts, or one routed expert, fails
the same comparison."""
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from bench.lib import check
from bench.models import common, deepseek_v2
from bench.models.common import Reference, generate
from bench.program import deepseek_v2 as program
from repro.core.expert_buffer import HostExpertStore
from repro.models import Model
from repro.runtime.engine import ServingParams, SlotBufferEngine
from repro.runtime.request import Request
from repro.runtime.serving import EngineServingConfig, ServingEngine

SMOKE = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "bench", "tests", "data", "dsv2-smoke.json")
SEED = 2 ** 33 + 15
MAX_SEQ = 64
# Both sides compute in f32 (the smoke configuration's dtype). The served
# path reassociates the same sums: chunked flash attention against the
# reference's one softmax, decode's weight-absorbed latent products
# against the reference's expanded keys and values, and the expert
# dispatch by slot. That moved logits of magnitude ~1 by 2.1e-6 here;
# 1e-4 leaves room for other rounding orders, and a reference without
# expert 0 or without the shared experts lies 1.4 and 2.2 away.
ATOL = 1e-4


@pytest.fixture(scope="module")
def cfg():
    with open(SMOKE) as f:
        return json.load(f)


def _engine(cfg, slots_per_layer):
    pcfg = program.config(cfg)
    top = program.top(generate(deepseek_v2.top_spec(cfg), SEED, 0))
    layers, store = [], HostExpertStore()
    for i in range(cfg["num_hidden_layers"]):
        p, experts = program.layer(
            cfg, i, generate(deepseek_v2.layer_spec(cfg, i), SEED, i + 1))
        layers.append(p)
        if experts is not None:
            store.add_layer(len(store), *experts)
    return SlotBufferEngine(pcfg, ServingParams(top, layers, store),
                            Model(pcfg), n_slots_per_layer=slots_per_layer,
                            max_seq=MAX_SEQ, step_size=1, pregate_margin=0)


@pytest.fixture(scope="module")
def served(cfg):
    """Three requests of different prompt and output lengths, served at
    batch 3 with 8-token prefill chunks through a pool of 8 slots for
    2 MoE layers of 8 experts: one layer's working set always fits (one
    that did not would drop tokens), a step's two layers do not. Returns
    the served requests, every logit row the server sampled from (the
    prompt's last position first), and the engine's counters."""
    eng = _engine(cfg, slots_per_layer=4)
    srv = ServingEngine(eng, EngineServingConfig(
        max_batch=3, prefill_chunk=8, admission_cap=False, trace_logits=True))
    rng = np.random.default_rng(5)
    reqs = [Request(prompt=rng.integers(0, cfg["vocab_size"], L).astype(np.int32),
                    max_new_tokens=n, request_id=i, temperature=0.0)
            for i, (L, n) in enumerate(((21, 9), (8, 12), (14, 6)))]
    srv.serve(reqs)
    return reqs, srv.logits_trace, eng.stats


def _gap(cfg, reqs, trace, reference):
    """Largest |served logit - reference logit| over every sampled row."""
    tokens, lengths, rows, cols, _ = check.batch(reqs, len(reqs), MAX_SEQ)
    ref = np.asarray(reference.logits(SEED, tokens, lengths, rows, cols))
    got = np.concatenate([np.stack(trace[r.request_id]) for r in reqs])
    assert got.shape == ref.shape
    return float(np.max(np.abs(got - ref)))


def test_the_pool_swaps_and_evicts(served, cfg):
    reqs, trace, stats = served
    assert [len(r.output) for r in reqs] == [9, 12, 6]
    # experts were written again after the pool had first filled
    assert stats.demand_misses > 0 and stats.swap_experts > 8
    assert [len(trace[r.request_id]) for r in reqs] == [9, 12, 6]


def test_prefill_and_every_decode_step_match_the_reference(served, cfg):
    reqs, trace, _ = served
    assert _gap(cfg, reqs, trace, Reference(deepseek_v2, cfg)) <= ATOL


class _NoShared:
    """The reference module with each layer's shared experts left out."""

    def __getattr__(self, name):
        return getattr(deepseek_v2, name)

    @staticmethod
    def layer_spec(cfg, i):
        return {k: v for k, v in deepseek_v2.layer_spec(cfg, i).items()
                if not k.startswith("s_")}


def test_a_reference_without_the_shared_experts_fails(served, cfg):
    reqs, trace, _ = served
    assert _gap(cfg, reqs, trace, Reference(_NoShared(), cfg)) > 100 * ATOL


def test_a_reference_without_one_routed_expert_fails(served, cfg, monkeypatch):
    reqs, trace, _ = served
    orig = common.grouped_experts

    def without_expert_0(h, ids, gates, *a, **kw):
        return orig(h, ids, jnp.where(ids == 0, 0.0, gates), *a, **kw)
    monkeypatch.setattr(common, "grouped_experts", without_expert_0)
    assert _gap(cfg, reqs, trace, Reference(deepseek_v2, cfg)) > 100 * ATOL


def test_the_preset_routes_as_published():
    from repro.configs.registry import get_config, get_smoke_config
    assert get_config("deepseek-v2-lite").moe.router_norm_topk is False
    assert get_smoke_config("deepseek-v2-lite").moe.router_norm_topk is False
