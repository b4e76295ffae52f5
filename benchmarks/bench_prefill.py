"""Chunked-prefill benchmark: interleaved ingestion vs head-of-line prefill.

Measures the tentpole claims of chunked prefill on a mixed long+short
serving population on the real `SlotBufferEngine` (slot buffer smaller than
the expert population). Every timed repeat serves FRESH prompt lengths the
engine has never seen — the realistic serving regime, and exactly where the
monolithic path hurts: it compiles one jit specialization per distinct
prompt length, and that compile lands INSIDE the admitting iteration, so
every co-batched request head-of-line blocks behind it
(BENCH_serving_engine.json batch-1 TTFT p50 ~0.59s was dominated by these
recompiles). Chunked serving ingests every prompt as fixed-shape (1, C)
chunks — compile count independent of length diversity — interleaved one
chunk per iteration with batched decode (shortest-remaining-first).

1. TTFT shape: mixed-population TTFT p95 (and short-request p95) must
   improve vs the monolithic head-of-line baseline.
2. No decode-throughput regression: aggregate tokens/s of the chunked runs
   stays at least `TPUT_FLOOR` of the monolithic runs.
3. Compile-boundedness: a further population with yet more new prompt
   lengths compiles NOTHING on the chunked path, while the monolithic path
   keeps compiling per length.

Writes BENCH_prefill.json; ``--smoke`` asserts 1-3 for the CI fast lane.
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
from repro.runtime.instrument import track_compiles             # noqa: E402
from repro.runtime.request import Request                       # noqa: E402
from repro.runtime.serving import (EngineServingConfig,         # noqa: E402
                                   ServingEngine)

DEFAULT = dict(layers=4, d_model=64, heads=4, kv_heads=4, d_ff=128,
               vocab=512, experts=8, top_k=2, d_expert=32,
               n_slots_per_layer=6, long_prompt=64, short_prompt=8,
               n_short=5, max_new=8, max_batch=4, chunk=8, repeats=2)
SMOKE = dict(DEFAULT, n_short=4, max_new=6)

TPUT_FLOOR = 0.85      # chunked aggregate tok/s >= this fraction of mono

# warmup lengths: one per admission-predictor bucket (8/16/32/64), so the
# timed repeats isolate PREFILL-path compiles from the shared ws-fn ones
WARM_LENGTHS = (64, 33, 17, 9, 8)
# fresh-length pools for the timed repeats: never overlapping WARM_LENGTHS
# or each other across repeats (a length seen once is warm for monolithic)
LONG_POOL = (61, 59, 57, 55)
SHORT_POOL = (4, 5, 6, 7, 10, 11, 12, 13, 14, 15, 16)


def _bench_config(p):
    return reduce_config(get_config("olmoe-1b-7b"), layers=p["layers"],
                         d_model=p["d_model"], heads=p["heads"],
                         kv_heads=p["kv_heads"], d_ff=p["d_ff"],
                         vocab=p["vocab"], experts=p["experts"],
                         top_k=p["top_k"], d_expert=p["d_expert"])


def _max_seq(p):
    return p["long_prompt"] + p["max_new"] + 8


def _fresh_lengths(p, rep):
    """One unseen long + n_short unseen shorts for timed repeat `rep`."""
    lo = rep * p["n_short"]
    shorts = SHORT_POOL[lo:lo + p["n_short"]]
    assert len(shorts) == p["n_short"], "short-length pool exhausted"
    return [LONG_POOL[rep]] + list(shorts)


def _requests(p, lengths, seed=0):
    """One long prompt at t=0, shorts arriving just after it starts
    prefilling — the head-of-line pattern chunking exists to fix."""
    rng = np.random.default_rng(seed)
    return [Request(prompt=rng.integers(0, p["vocab"], L,
                                        dtype=np.int64).astype(np.int32),
                    max_new_tokens=p["max_new"],
                    arrival_s=0.0 if i == 0 else 1e-3)
            for i, L in enumerate(lengths)]


def _slot_engine(cfg, eng, p):
    return SlotBufferEngine(cfg, eng.params, eng.model,
                            n_slots_per_layer=p["n_slots_per_layer"],
                            step_size=1, max_seq=_max_seq(p))


def bench_serving(cfg, eng, p, chunk):
    """Serve fresh-length mixed populations; returns the mean-over-repeats
    SLO summary and the warm engine (for the compile probe)."""
    sb = _slot_engine(cfg, eng, p)
    scfg = EngineServingConfig(max_batch=p["max_batch"], prefill_chunk=chunk)
    ServingEngine(sb, scfg).serve(                             # warmup/jit
        _requests(p, list(WARM_LENGTHS), seed=1))
    agg = {"tok_s": [], "ttft_p95_s": [], "ttft_p50_s": [],
           "short_ttft_p95_s": [], "makespan_s": []}
    split = {"queue": [], "prefill": [], "first_step": []}
    for rep_i in range(p["repeats"]):
        reqs = _requests(p, _fresh_lengths(p, rep_i), seed=2 + rep_i)
        report = ServingEngine(sb, scfg).serve(reqs)
        assert all(len(r.output) == p["max_new"] for r in reqs)
        short_ttft = [m.ttft_s for m in report.requests
                      if m.prompt_len <= max(SHORT_POOL)]
        agg["tok_s"].append(report.throughput_tok_s)
        agg["ttft_p95_s"].append(report.ttft["p95"])
        agg["ttft_p50_s"].append(report.ttft["p50"])
        agg["short_ttft_p95_s"].append(float(np.percentile(short_ttft, 95)))
        agg["makespan_s"].append(report.makespan_s)
        for k, v in report.ttft_split.items():
            split[k].append(v)
    out = {k: float(np.mean(v)) for k, v in agg.items()}
    out["ttft_split"] = {k: float(np.mean(v)) for k, v in split.items()}
    return out, sb, scfg


def compile_growth(cfg, eng, p, sb, scfg):
    """Jit-cache growth when ANOTHER population of unseen prompt lengths
    hits the already-exercised engine. Lengths stay inside the admission
    predictor's warm buckets so the probe isolates PREFILL compiles."""
    lengths = [51, 39, 21, 28]          # unseen; buckets 64/64/32/32 warm
    with track_compiles(sb) as probe:
        ServingEngine(sb, scfg).serve(_requests(p, lengths, seed=7))
    return probe.new_compiles


def verify_parity(cfg, eng, p):
    """Chunked serving's greedy outputs == single-request generate (the
    logit-level contract lives in tests/test_prefill_chunked.py)."""
    sb = _slot_engine(cfg, eng, p)
    reqs = _requests(dict(p, max_new=5), [p["long_prompt"], 8, 8], seed=9)
    ServingEngine(sb, EngineServingConfig(
        max_batch=3, prefill_chunk=p["chunk"])).serve(reqs)
    ref = _slot_engine(cfg, eng, p)
    return all(
        np.array_equal(ref.generate(r.prompt[None, :], r.max_new_tokens)[0],
                       np.asarray(r.output)) for r in reqs)


def run_bench(p, out_path="BENCH_prefill.json", smoke=False, csv=None):
    cfg = _bench_config(p)
    eng = Engine(cfg, max_seq=_max_seq(p))
    parity = verify_parity(cfg, eng, p)
    mono, sb_m, scfg_m = bench_serving(cfg, eng, p, chunk=0)
    chun, sb_c, scfg_c = bench_serving(cfg, eng, p, chunk=p["chunk"])
    mono_compiles = compile_growth(cfg, eng, p, sb_m, scfg_m)
    chun_compiles = compile_growth(cfg, eng, p, sb_c, scfg_c)
    result = {
        "config": dict(p),
        "monolithic": mono,
        "chunked": chun,
        "ttft_p95_improvement": mono["ttft_p95_s"] / chun["ttft_p95_s"],
        "short_ttft_p95_improvement":
            mono["short_ttft_p95_s"] / chun["short_ttft_p95_s"],
        "tput_ratio_chunked_vs_mono": chun["tok_s"] / mono["tok_s"],
        "new_compiles_on_fresh_lengths":
            {"monolithic": mono_compiles, "chunked": chun_compiles},
        "chunked_matches_single_request_greedy": parity,
    }
    with open(out_path, "w") as f:
        json.dump(result, f, indent=2)
    for name, r in (("monolithic", mono), ("chunked", chun)):
        line = (f"prefill/{name}: ttft_p95={r['ttft_p95_s']*1e3:.1f}ms "
                f"short_ttft_p95={r['short_ttft_p95_s']*1e3:.1f}ms "
                f"tok_s={r['tok_s']:.1f}")
        print(line)
        if csv is not None:
            csv.add(f"prefill/{name}", 0.0,
                    f"ttft_p95={r['ttft_p95_s']*1e3:.1f}ms")
    print(f"prefill/ttft_p95_improvement: "
          f"{result['ttft_p95_improvement']:.2f}x "
          f"(short-only {result['short_ttft_p95_improvement']:.2f}x, "
          f"tput ratio {result['tput_ratio_chunked_vs_mono']:.2f})")
    print(f"prefill/new_compiles_on_fresh_lengths: "
          f"mono={mono_compiles} chunked={chun_compiles}")
    if smoke:
        assert parity, "chunked serving diverged from single-request greedy"
        assert result["ttft_p95_improvement"] > 1.0, (
            "chunked interleaving must improve mixed long+short TTFT p95 "
            f"vs monolithic head-of-line, got "
            f"{result['ttft_p95_improvement']:.2f}x")
        assert result["tput_ratio_chunked_vs_mono"] >= TPUT_FLOOR, (
            "chunked serving regressed aggregate decode throughput: "
            f"{result['tput_ratio_chunked_vs_mono']:.2f} < {TPUT_FLOOR}")
        assert chun_compiles == 0, (
            "chunked prefill compiled on fresh prompt lengths "
            f"({chun_compiles} new) — the jit cache must be keyed on chunk "
            "shape + layer spec only")
        assert mono_compiles > 0, (
            "monolithic baseline unexpectedly stopped compiling per length "
            "— the compile-boundedness comparison is vacuous")
        print("SMOKE OK: chunked prefill improves mixed TTFT p95 with flat "
              "compiles and no decode-throughput regression")
    return result


def run(csv):
    """benchmarks.run entry point."""
    run_bench(dict(DEFAULT), csv=csv)


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small shapes + regression assertions (CI)")
    ap.add_argument("--out", default="BENCH_prefill.json")
    args = ap.parse_args()
    p = dict(SMOKE if args.smoke else DEFAULT)
    run_bench(p, out_path=args.out, smoke=args.smoke)


if __name__ == "__main__":
    main()
