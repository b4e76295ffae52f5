"""The program's own profiler spans and jitted-function names on the serving
path: every span is written into the trace's host plane, nested where the
work happens; every jitted function the engine registers has a name of its
own; and the spans change neither tokens nor compiles."""
import glob

import jax
import numpy as np
import pytest

from repro.configs.base import reduce_config
from repro.configs.registry import get_config, get_smoke_config
from repro.runtime.engine import Engine, SlotBufferEngine
from repro.runtime.instrument import named_jit, track_compiles
from repro.runtime.request import Request
from repro.runtime.serving import EngineServingConfig, ServingEngine

SPANS = ("serve.admit", "serve.sample", "serve.retire", "serve.sleep",
         "engine.decode_step", "engine.prefill_chunk", "engine.mask_pull",
         "engine.residency", "engine.replay", "engine.dispatch",
         "swap.write", "swap.drain", "swap.sample_wait")


@pytest.fixture(scope="module")
def model():
    cfg = reduce_config(get_config("olmoe-1b-7b"), layers=4, d_model=64,
                        heads=4, kv_heads=4, d_ff=128, vocab=512, experts=8,
                        top_k=2, d_expert=32)
    return cfg, Engine(cfg, max_seq=64)


def _requests(cfg, seed=11, arrival_s=0.0):
    """Three prompts that co-decode; with `arrival_s` > 0 the loop first
    sleeps while nothing is due."""
    rng = np.random.default_rng(seed)
    return [Request(prompt=rng.integers(0, cfg.vocab_size, L).astype(np.int32),
                    max_new_tokens=8, request_id=i, arrival_s=arrival_s)
            for i, L in enumerate((12, 9, 12))]


def _server(cfg, eng, **kw):
    # a pool of 3 of 8 experts a layer and a pre-gate of exactly top-k:
    # swaps, evictions and mispredicted windows (replays) every few steps
    sb = SlotBufferEngine(cfg, eng.params, eng.model, max_seq=64,
                          n_slots_per_layer=3, step_size=2, pregate_margin=0,
                          **kw)
    return sb, ServingEngine(sb, EngineServingConfig(
        max_batch=3, prefill_chunk=8, admission_cap=False))


def _traced(fn, path):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(path), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    from jax.profiler import ProfileData
    pb = glob.glob(str(path / "**" / "*.xplane.pb"), recursive=True)
    assert len(pb) == 1
    return ProfileData.from_file(pb[0])


def _host_spans(pd):
    """(name, start, end, args) of the program's spans."""
    out = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(ev.name.split("#")[0], ev.start_ns,
                         ev.start_ns + ev.duration_ns, dict(ev.stats))
                        for ev in line.events
                        if ev.name.startswith(("serve.", "engine.", "swap.",
                                               "bench."))]
    return out


def _within(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


@pytest.mark.slow
def test_serve_writes_every_span_nested_where_the_work_happens(model, tmp_path):
    cfg, eng = model
    sb, srv = _server(cfg, eng)
    reqs = _requests(cfg, arrival_s=0.05)
    spans = _host_spans(_traced(lambda: srv.serve(reqs), tmp_path))
    assert sb.stats.replays > 0
    names = {s[0] for s in spans}
    assert set(SPANS) <= names, set(SPANS) - names
    assert not any(n.startswith("bench.") for n in names)
    steps = [s for s in spans if s[0] == "engine.decode_step"]
    for child in ("engine.mask_pull", "swap.write", "engine.residency",
                  "engine.replay"):
        assert any(_within(c, p) for c in spans if c[0] == child for p in steps), child
    for child in ("swap.drain", "swap.sample_wait"):
        assert all(any(_within(c, w) for w in spans if w[0] == "swap.write")
                   for c in spans if c[0] == child), child
    # spans about a request carry its id, so one request's spans join up
    ids = {s[3].get("request_id") for s in spans
           if s[0] in ("serve.admit", "engine.prefill_chunk")}
    assert ids == {0, 1, 2}
    assert {s[3]["fn"] for s in spans if s[0] == "engine.dispatch"} >= {
        "moe_ffn_decode", "moe_ffn_chunk", "pre_decode_batched"}
    writes = [s[3] for s in spans if s[0] == "swap.write"]
    assert sum(w["experts"] for w in writes) == sb.stats.swap_experts
    assert sum(w["sampled"] for w in writes) == len(
        [s for s in spans if s[0] == "swap.sample_wait"])


@pytest.mark.slow
@pytest.mark.parametrize("arch,superkernel", [("olmoe-1b-7b", False),
                                               ("olmoe-1b-7b", True),
                                               ("deepseek-v2-lite", False)])
def test_every_registered_function_has_a_name_of_its_own(arch, superkernel):
    cfg = get_smoke_config(arch)
    eng = Engine(cfg, max_seq=48)
    sb = SlotBufferEngine(cfg, eng.params, eng.model, max_seq=48,
                          n_slots_per_layer=cfg.moe.num_experts // 2,
                          step_size=1, use_superkernel=superkernel)
    rng = np.random.default_rng(3)
    reqs = [Request(prompt=rng.integers(0, cfg.vocab_size, L).astype(np.int32),
                    max_new_tokens=3, request_id=i)
            for i, L in enumerate((8, 13))]
    ServingEngine(sb, EngineServingConfig(max_batch=2, prefill_chunk=8,
                                          admission_cap=True)).serve(reqs)
    sb.prefill(reqs[0].prompt[None, :])           # the whole-prompt path too
    names = [f.__name__ for f in sb._fns.values()]
    assert len(set(names)) == len(names), names
    assert not {"fn", "<lambda>"} & set(names)
    want = {"predict_ws", "embed", "logits"}
    want |= ({"decode_segment_s1_first_batched"} if superkernel
             else {"moe_ffn_decode", "embed_decode"})
    if arch == "olmoe-1b-7b":
        want |= {"moe_ffn_chunk", "moe_ffn", "embed_chunk", "logits_at"}
    assert want <= set(names), want - set(names)


SHARED_ROLES = ["embed", "embed_chunk", "embed_decode", "logits", "logits_at",
                "moe_ffn", "moe_ffn_chunk", "moe_ffn_decode", "pregate_s1",
                "pregate_s1_batched"]


@pytest.mark.parametrize("arch,attention_roles", [
    # GQA layers keep the names the benchmark's trace readers match
    ("olmoe-1b-7b", ["pre", "pre_decode_batched", "pre_prefill",
                     "pre_prefill_chunk_kv16", "pre_prefill_chunk_kv8",
                     "pre_pregate"]),
    # latent attention says so in every role that runs it, the dense first
    # layer's included; the MoE FFN (shared experts inside) is named as
    # for GQA
    ("deepseek-v2-lite", ["dense_decode_mla", "dense_mla",
                          "dense_prefill_chunk_mla_kv16",
                          "dense_prefill_chunk_mla_kv8", "dense_prefill_mla",
                          "pre_decode_mla_batched", "pre_mla",
                          "pre_mla_pregate", "pre_prefill_chunk_mla_kv16",
                          "pre_prefill_chunk_mla_kv8", "pre_prefill_mla"])])
def test_attention_roles_are_named_by_attention_kind(arch, attention_roles):
    cfg = get_smoke_config(arch)
    eng = Engine(cfg, max_seq=48)
    sb = SlotBufferEngine(cfg, eng.params, eng.model, max_seq=48,
                          n_slots_per_layer=cfg.moe.num_experts // 2,
                          step_size=1)
    rng = np.random.default_rng(3)
    reqs = [Request(prompt=rng.integers(0, cfg.vocab_size, L).astype(np.int32),
                    max_new_tokens=3, request_id=i)
            for i, L in enumerate((8, 13))]
    ServingEngine(sb, EngineServingConfig(max_batch=2, prefill_chunk=8,
                                          admission_cap=False)).serve(reqs)
    sb.prefill(reqs[0].prompt[None, :])          # whole-prompt roles
    sb.forward(reqs[0].prompt[None, :])
    names = sorted(f.__name__ for f in sb._fns.values())
    assert names == sorted(SHARED_ROLES + attention_roles)


def test_named_jit_compiles_under_its_name():
    f = named_jit("moe_ffn_decode", lambda x: x + 1)
    assert f.__name__ == "moe_ffn_decode"
    assert "jit_moe_ffn_decode" in f.lower(np.ones(2, np.float32)).as_text()


@pytest.mark.slow
def test_tokens_with_the_profiler_off_match_a_traced_serve(model, tmp_path):
    cfg, eng = model
    plain = _requests(cfg)
    _server(cfg, eng)[1].serve(plain)
    traced = _requests(cfg)
    srv = _server(cfg, eng)[1]
    _traced(lambda: srv.serve(traced), tmp_path)
    assert [r.output for r in traced] == [r.output for r in plain]
    assert all(len(r.output) == 8 for r in plain)


@pytest.mark.slow
def test_a_warm_serve_adds_no_compile(model):
    cfg, eng = model
    sb, srv = _server(cfg, eng)
    srv.serve(_requests(cfg, seed=1))
    with track_compiles(sb) as probe:
        srv.serve(_requests(cfg, seed=2))
    assert probe.new_entries == 0 and probe.new_compiles == 0
