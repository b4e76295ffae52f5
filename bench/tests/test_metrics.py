"""The end-to-end and per-layer readers, on synthetic timestamps."""
import os
from types import SimpleNamespace

import numpy as np
import pytest

from bench.lib.harness import ROOT, Ctx, load_module


def reader(name):
    return load_module(ROOT, "metrics", name + ".py")


def req(rid, arrival, first, admitted=None, prompt_len=16):
    return SimpleNamespace(request_id=rid, arrival_s=arrival,
                           first_token_s=first, prompt_len=prompt_len,
                           admitted_s=arrival if admitted is None else admitted,
                           output=[])


def make_ctx(requests, tokens, opened=10.0, closed=20.0, **kw):
    base = dict(cfg={}, model=None, peaks=None, setup_s=42.0, opened=opened,
                closed=closed, requests=requests, tokens=tokens, ctx_len=[],
                prompt_pos=[], spans=[], bw=[], at_open={}, at_close={})
    base.update(kw)
    return Ctx(**base)


@pytest.fixture
def stalled():
    """Two requests decoding every 0.1 s through [10, 20), except one stall
    of 2 s at t = 15 that both wait through; a third arrives before the
    window and gets its first token inside it; a fourth finishes before."""
    a = [10.05 + 0.1 * i for i in range(50)]                 # 10.05 .. 14.95
    b = [16.95 + 0.1 * i for i in range(30)]                 # 16.95 .. 19.85
    tok = {1: a + b, 2: [t + 0.01 for t in a + b],
           3: [19.5, 19.6, 20.4], 4: [1.0, 1.1]}
    reqs = [req(1, 5.0, 9.95), req(2, 5.0, 9.96), req(3, 12.0, 19.0, 18.0),
            req(4, 0.0, 0.9)]
    return make_ctx(reqs, tok)


def test_output_tok_s_counts_every_token_over_the_whole_window(stalled):
    # 80 + 80 decode tokens and request 3's first token and two more in
    # [10, 20); request 3's 20.4 and request 4's tokens fall outside
    assert reader("output_tok_s").read(stalled) == pytest.approx(163 / 10.0)


def test_itl_p95_pools_every_gap_so_the_stall_shows(stalled):
    gaps = []
    for r in stalled.requests:
        ts = [r.first_token_s] + stalled.tokens[r.request_id]
        gaps += [b - a for a, b in zip(ts, ts[1:]) if 10 <= b < 20]
    assert len(gaps) == 162
    got = reader("itl_p95_ms").read(stalled)
    assert got == pytest.approx(1e3 * np.percentile(gaps, 95))
    # two of 162 gaps are the 2 s stall: the 95th percentile is 0.1 s, and
    # a per-request mean (TPOT) would have hidden the stall entirely
    assert got == pytest.approx(100.0, rel=1e-6)
    many = make_ctx(stalled.requests,
                    {k: v for k, v in stalled.tokens.items() if k != 3})
    many.tokens[1] = [10.05 + 0.1 * i for i in range(50)] + \
        [17.0 + 2.0 * i for i in range(2)]
    assert reader("itl_p95_ms").read(many) > 100.0


def test_queue_wait_covers_requests_admitted_inside_the_window(stalled):
    # requests 1 and 2 admitted at 5.0 (before), 3 at 18.0 (inside): 6 s
    assert reader("queue_wait_p50_s").read(stalled) == pytest.approx(6.0)


def test_ttft_p50_covers_first_tokens_inside_the_window(stalled):
    # only request 3's first token (19.0, arrival 12.0) falls in [10, 20)
    assert reader("first_token_p50_s").read(stalled) == pytest.approx(7.0)
    more = make_ctx(stalled.requests + [req(5, 9.0, 11.0), req(6, 10.0, 13.0),
                                        req(7, 20.0, -1.0)], stalled.tokens)
    assert reader("first_token_p50_s").read(more) == pytest.approx(3.0)


def test_setup_s(stalled):
    assert reader("setup_s").read(stalled) == 42.0


def test_counter_readers():
    ctx = make_ctx([], {}, ctx_len=[30] * 10, prompt_pos=[(0, 32, False), (32, 8, True)],
                   at_open={"swap_experts": 100, "prefetch_hits": 5, "demand_misses": 10},
                   at_close={"swap_experts": 200, "prefetch_hits": 35, "demand_misses": 20},
                   bw=[(9.0, 1e9, 0.1), (12.0, 2e9, 0.5), (19.0, 1e9, 0.5)])
    assert reader("experts_swapped_per_token").read(ctx) == pytest.approx(100 / 50)
    assert reader("prefetch_hit_share").read(ctx) == pytest.approx(75.0)
    assert reader("h2d_gbps").read(ctx) == pytest.approx(3.0)
    assert reader("device_idle_share").read(ctx) is None
    assert reader("mfu").read(ctx) is None
    none = make_ctx([], {}, at_open={"prefetch_hits": 0, "demand_misses": 0},
                    at_close={"prefetch_hits": 0, "demand_misses": 0})
    assert reader("prefetch_hit_share").read(none) is None
    assert reader("h2d_gbps").read(none) is None


def test_spans_and_trace_readers():
    spans = [("decode_step", 11.0, 11.3), ("decode_step", 12.0, 12.5),
             ("decode_step", 13.0, 13.4), ("prefill_chunk", 14.0, 14.2),
             ("decode_step", 5.0, 9.0)]
    ctx = make_ctx([], {}, spans=spans,
                   trace={"busy_s": 6.0, "window_s": 10.0})
    assert reader("decode_step_p50_ms").read(ctx) == pytest.approx(400.0)
    assert reader("prefill_chunk_p50_ms").read(ctx) == pytest.approx(200.0)
    assert reader("device_idle_share").read(ctx) == pytest.approx(40.0)


def test_mfu_counts_needed_flops_over_window_and_peak():
    from bench.models import olmoe
    cfg = {"hidden_size": 8, "num_attention_heads": 2, "num_key_value_heads": 2,
           "num_experts": 4, "intermediate_size": 4, "num_experts_per_tok": 2,
           "num_hidden_layers": 1, "vocab_size": 10}
    ctx = make_ctx([], {}, cfg=cfg, model=olmoe, peaks={"bf16_flop_s": 1e4},
                   ctx_len=[3], prompt_pos=[(0, 2, True)])
    want = (olmoe.flops_per_token(cfg, 3, True) + olmoe.flops_per_token(cfg, 1, False)
            + olmoe.flops_per_token(cfg, 2, True))
    assert reader("mfu").read(ctx) == pytest.approx(100 * want / 10.0 / 1e4)
