"""Real-engine serving benchmark: batched continuous decode vs sequential.

Measures the tentpole claim of the unified serving surface — that driving
the real `SlotBufferEngine` with `ContinuousBatcher` at batch > 1 beats
serving the same requests one-at-a-time — on a reduced MoE model with a
slot buffer smaller than the expert population (real swap traffic):

1. aggregate tokens/s: batch-4 continuous serving vs sequential
   single-request `generate` and vs batch-1 serving (the scheduler's own
   overhead floor);
2. SLO shape: measured TTFT / TPOT p50 at batch 1 vs 4 — co-batching
   trades per-token latency for throughput, visibly but boundedly.

Writes BENCH_serving_engine.json and — in ``--smoke`` mode — asserts the
batch-4 aggregate tokens/s exceeds sequential serving so the CI fast lane
catches schedulers that stop batching.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir, "src"))

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.configs.base import reduce_config                    # noqa: E402
from repro.configs.registry import get_config                   # noqa: E402
from repro.runtime.engine import Engine, SlotBufferEngine       # noqa: E402
from repro.runtime.request import Request                       # noqa: E402
from repro.runtime.serving import (EngineServingConfig,         # noqa: E402
                                   ServingEngine)

DEFAULT = dict(layers=4, d_model=64, heads=4, kv_heads=4, d_ff=128,
               vocab=512, experts=8, top_k=2, d_expert=32,
               n_slots_per_layer=6, requests=8, prompt=16, max_new=16,
               repeats=3, sweep_batches=(4, 8, 16, 32))
SMOKE = dict(DEFAULT, requests=6, max_new=10, repeats=2,
             sweep_batches=(4, 8))


def _bench_config(p):
    return reduce_config(get_config("olmoe-1b-7b"), layers=p["layers"],
                         d_model=p["d_model"], heads=p["heads"],
                         kv_heads=p["kv_heads"], d_ff=p["d_ff"],
                         vocab=p["vocab"], experts=p["experts"],
                         top_k=p["top_k"], d_expert=p["d_expert"])


def _requests(p, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(prompt=rng.integers(0, p["vocab"], p["prompt"],
                                        dtype=np.int64).astype(np.int32),
                    max_new_tokens=p["max_new"]) for _ in range(p["requests"])]


def _max_seq(p):
    return p["prompt"] + p["max_new"] + 8


def _slot_engine(cfg, eng, p):
    return SlotBufferEngine(cfg, eng.params, eng.model,
                            n_slots_per_layer=p["n_slots_per_layer"],
                            max_seq=_max_seq(p))


def _total_tokens(reqs):
    return sum(len(r.output) for r in reqs)


def bench_sequential(cfg, eng, p):
    """One request at a time through single-request generate."""
    sb = _slot_engine(cfg, eng, p)
    warm = _requests(p, seed=1)
    for r in warm[:2]:
        sb.generate(r.prompt[None, :], r.max_new_tokens)
    best = 0.0
    for rep in range(p["repeats"]):
        reqs = _requests(p, seed=2 + rep)
        t0 = time.perf_counter()
        n = 0
        for r in reqs:
            out = sb.generate(r.prompt[None, :], r.max_new_tokens)
            n += out.shape[1]
        best = max(best, n / (time.perf_counter() - t0))
    return {"tok_s": best}


def bench_serving(cfg, eng, p, max_batch):
    """Continuous batching through ServingEngine at `max_batch` slots.

    Pinned to the monolithic prefill path (`prefill_chunk=0`): this bench's
    committed baseline measures batched-vs-sequential DECODE and predates
    chunked prefill; the chunked-vs-monolithic comparison lives in
    bench_prefill.py.

    `jit_calls_per_step`: warm jitted dispatches per decode step through the
    engine's Dispatcher funnel (prefill dispatches ride along in the
    numerator)."""
    sb = _slot_engine(cfg, eng, p)
    scfg = EngineServingConfig(max_batch=max_batch, prefill_chunk=0)
    # two warm-up serves, so the horizons the controller visits are
    # compiled before anything is timed
    ServingEngine(sb, scfg).serve(_requests(p, seed=1))
    ServingEngine(sb, scfg).serve(_requests(p, seed=2))
    best = None
    sb.stats.reset()
    for rep in range(p["repeats"]):
        reqs = _requests(p, seed=2 + rep)
        report = ServingEngine(sb, scfg).serve(reqs)
        assert _total_tokens(reqs) == p["requests"] * p["max_new"]
        if best is None or report.throughput_tok_s > best["tok_s"]:
            best = {"tok_s": report.throughput_tok_s,
                    "ttft_p50_s": report.ttft["p50"],
                    "tpot_p50_s": report.tpot["p50"],
                    "mean_occupancy": report.mean_occupancy}
    best["jit_calls_per_step"] = sb.stats.jit_calls / max(sb.stats.steps, 1)
    return best


def bench_batch_sweep(cfg, eng, p):
    """tokens/s + dispatches/step at batch 4/8/16/32, with enough queued
    requests to keep each batch full."""
    sweep = {}
    for b in p["sweep_batches"]:
        pb = dict(p, requests=max(p["requests"], 2 * b), repeats=1)
        sweep[f"b{b}"] = bench_serving(cfg, eng, pb, max_batch=b)
    return sweep


def verify_parity(cfg, eng, p):
    """Greedy outputs of batched serving == single-request generate
    (the logit-level contract lives in tests/test_serving_engine.py)."""
    sb = _slot_engine(cfg, eng, p)
    reqs = _requests(dict(p, requests=3, max_new=6), seed=9)
    ServingEngine(sb, EngineServingConfig(max_batch=3,
                                          prefill_chunk=0)).serve(reqs)
    ref = _slot_engine(cfg, eng, p)
    return all(
        np.array_equal(ref.generate(r.prompt[None, :], r.max_new_tokens)[0],
                       np.asarray(r.output)) for r in reqs)


def run_bench(p, out_path="BENCH_serving_engine.json", smoke=False,
              csv=None):
    cfg = _bench_config(p)
    eng = Engine(cfg, max_seq=_max_seq(p))
    parity = verify_parity(cfg, eng, p)
    seq = bench_sequential(cfg, eng, p)
    b1 = bench_serving(cfg, eng, p, max_batch=1)
    b4 = bench_serving(cfg, eng, p, max_batch=4)
    sweep = bench_batch_sweep(cfg, eng, p)
    result = {
        "config": {k: v for k, v in p.items()},
        "sequential_tok_s": seq["tok_s"],
        "serve_batch1": b1,
        "serve_batch4": b4,
        "batch_sweep": sweep,
        "speedup_b4_vs_sequential": b4["tok_s"] / seq["tok_s"],
        "speedup_b4_vs_b1": b4["tok_s"] / b1["tok_s"],
        "batched_matches_single_request_greedy": parity,
    }
    with open(out_path, "w") as f:
        json.dump(result, f, indent=2)
    for name, v in (("sequential", seq["tok_s"]), ("serve_b1", b1["tok_s"]),
                    ("serve_b4", b4["tok_s"])):
        line = f"serving_engine/{name}_tok_s: {v:.1f}"
        print(line)
        if csv is not None:
            csv.add(f"serving_engine/{name}", 0.0, f"{v:.1f}tok/s")
    print(f"serving_engine/speedup_b4_vs_sequential: "
          f"{result['speedup_b4_vs_sequential']:.2f}x "
          f"(ttft_p50 {b4['ttft_p50_s']*1e3:.1f}ms, "
          f"tpot_p50 {b4['tpot_p50_s']*1e3:.2f}ms)")
    for name, row in sweep.items():
        line = (f"serving_engine/sweep/{name}: {row['tok_s']:.1f}tok/s "
                f"@{row['jit_calls_per_step']:.1f}jit")
        print(line)
        if csv is not None:
            csv.add(f"serving_engine/sweep/{name}", 0.0, line.split(": ")[1])
    if smoke:
        assert parity, "batched serving diverged from single-request generate"
        assert result["speedup_b4_vs_sequential"] > 1.0, (
            "batch-4 continuous serving must beat sequential generate on "
            f"aggregate tokens/s, got {result['speedup_b4_vs_sequential']:.2f}x")
        print("SMOKE OK: batched serving beats sequential aggregate "
              "tokens/s")
    return result


def run(csv):
    """benchmarks.run entry point."""
    run_bench(dict(DEFAULT), csv=csv)


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small shapes + regression assertions (CI)")
    ap.add_argument("--out", default="BENCH_serving_engine.json")
    args = ap.parse_args()
    p = dict(SMOKE if args.smoke else DEFAULT)
    run_bench(p, out_path=args.out, smoke=args.smoke)


if __name__ == "__main__":
    main()
