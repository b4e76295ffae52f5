"""Cache-aware routing benchmark: the stall/quality frontier (paper §3.4).

Measures the tentpole claim of the live routing perturbation — that biasing
non-resident experts' router logits down by a bounded delta reduces demand
misses (and the stalls they cause) at a provably bounded routing-quality
cost — on the real `SlotBufferEngine` under continuous-batching serving
with a contended slot buffer (3 slots for 8 experts):

1. miss frontier: demand misses / late hits / replays / swap traffic and
   throughput at delta in {0, 0.25, 0.5, 1.0}, plus an adaptive run where
   the shared `StepSizeController` ramps delta within [0, ceiling] from its
   stall/overfetch thresholds — across poisson / bursty / mixed workloads;
2. quality: greedy-token divergence and the LM-logit KL of the biased run
   vs the unperturbed run over same-context prefixes (tokens compared only
   while both runs have emitted identical outputs, so the logits are
   conditioned on the same sequence);
3. exactness: delta = 0 serving is bit-identical to an engine without the
   feature configured (the CA-gated jit traces must not perturb anything).

Writes BENCH_cache_aware.json and — in ``--smoke`` mode — asserts the
demand-miss reduction is > 0 on poisson AND bursty, quality stays within
the configured bounds, and delta = 0 logits are bit-exact, so the CI fast
lane catches regressions in the cache-aware routing loop.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir, "src"))

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.configs.base import reduce_config                    # noqa: E402
from repro.configs.registry import get_config                   # noqa: E402
from repro.data.workloads import make_workload, prompt_tokens   # noqa: E402
from repro.runtime.engine import Engine, SlotBufferEngine       # noqa: E402
from repro.runtime.request import Request                       # noqa: E402
from repro.runtime.serving import (EngineServingConfig,         # noqa: E402
                                   ServingEngine)

DEFAULT = dict(layers=4, d_model=64, heads=4, kv_heads=4, d_ff=128,
               vocab=512, experts=8, top_k=2, d_expert=32,
               n_slots_per_layer=5,           # contended: 5 slots, 8 experts
               requests=8, max_new=12, batch=4,
               deltas=(0.25, 0.5, 2.0),
               route_bias=1.0,                # the frontier point CI gates on
               # quality bounds for the gated delta (empirical, with margin;
               # the ROUTER-level KL is provably <= delta nats — these bound
               # the downstream LM-output drift at toy scale)
               max_greedy_divergence=0.9,
               max_mean_kl_nats=3.0)
SMOKE = dict(DEFAULT, requests=6, max_new=10, deltas=())

WORKLOADS = ("poisson", "bursty", "mixed")


def _bench_config(p):
    return reduce_config(get_config("olmoe-1b-7b"), layers=p["layers"],
                         d_model=p["d_model"], heads=p["heads"],
                         kv_heads=p["kv_heads"], d_ff=p["d_ff"],
                         vocab=p["vocab"], experts=p["experts"],
                         top_k=p["top_k"], d_expert=p["d_expert"])


def _pad_to_bucket(toks, bucket=16):
    T = len(toks)
    padded = ((T + bucket - 1) // bucket) * bucket
    if padded == T:
        return toks
    return np.concatenate([toks, np.zeros(padded - T, toks.dtype)])


def _requests(p, pattern, seed=0, zero_arrivals=False):
    """Workload-generated request population (arrival pattern + topic-
    anchored prompts). `zero_arrivals` collapses the arrival process so a
    run is deterministic (quality / exactness measurements)."""
    rng = np.random.default_rng(seed)
    specs = make_workload(pattern, p["requests"], seed=seed,
                          mean_decode=p["max_new"])
    reqs = []
    for s in specs:
        toks = _pad_to_bucket(prompt_tokens(s, p["vocab"], rng))
        reqs.append(Request(
            prompt=toks.astype(np.int32),
            max_new_tokens=max(2, min(s.decode_len, p["max_new"])),
            temperature=0.0,
            arrival_s=0.0 if zero_arrivals else s.arrival_s,
            request_id=s.request_id))
    return reqs


def _max_seq(p):
    # make_workload prompts are padded to 16-token buckets; long tail in
    # the mixed pattern reaches 64
    return 64 + p["max_new"] + 8


def _slot_engine(cfg, eng, p):
    return SlotBufferEngine(cfg, eng.params, eng.model,
                            n_slots_per_layer=p["n_slots_per_layer"],
                            max_seq=_max_seq(p))


def _serve(cfg, eng, p, reqs, route_bias=None, adaptive=False,
           trace=False, deterministic=False):
    """One serving run on a FRESH slot engine (cold cache each time).
    `adaptive` makes `route_bias` a ceiling the controller ramps within
    (`set_route_bias` seeds `StepSizeConfig.route_bias_max`)."""
    sb = _slot_engine(cfg, eng, p)
    scfg = EngineServingConfig(
        max_batch=p["batch"], prefill_chunk=0,
        admission_cap=not deterministic,
        route_bias=route_bias, route_bias_adaptive=adaptive or None,
        trace_logits=trace)
    srv = ServingEngine(sb, scfg)
    report = srv.serve(reqs)
    stats = sb.stats.snapshot()
    return {
        # decode-phase misses: the serving loop snapshots the miss counter
        # around each batched decode_step, so prefill misses (prefill is
        # intentionally unbiased) don't wash out the decode signal
        "decode_misses": sum(sm.n_misses for sm in report.run.steps),
        "demand_misses": stats["demand_misses"],
        "late_hits": stats["late_hits"],
        "replays": stats["replays"],
        "swap_experts": stats["swap_experts"],
        "stall_events": stats["demand_misses"],
        "throughput_tok_s": report.throughput_tok_s,
        "makespan_s": report.makespan_s,
        "route_bias_final": sb.controller.route_bias,
        "guard_hits": sb.controller.guard_hits,
    }, srv


def _frontier_point(cfg, eng, p, pattern, delta, adaptive=False, seed=3,
                    deterministic=False):
    stats, _ = _serve(cfg, eng, p,
                      _requests(p, pattern, seed=seed,
                                zero_arrivals=deterministic),
                      route_bias=delta if delta else None, adaptive=adaptive,
                      deterministic=deterministic)
    stats["delta"] = delta
    if adaptive:
        stats["adaptive"] = True
    return stats


def _quality(cfg, eng, p, pattern, delta, seed=5):
    """Greedy divergence + same-context LM-logit KL of the biased run vs
    unperturbed, on identical deterministic populations (arrivals zeroed,
    admission cap off, greedy decode)."""
    _, srv0 = _serve(cfg, eng, p,
                     _requests(p, pattern, seed=seed, zero_arrivals=True),
                     trace=True, deterministic=True)
    biased = _requests(p, pattern, seed=seed, zero_arrivals=True)
    _, srv1 = _serve(cfg, eng, p, biased, route_bias=delta,
                     trace=True, deterministic=True)
    ref = _requests(p, pattern, seed=seed, zero_arrivals=True)
    # greedy outputs re-derived from the traced logits (row t's argmax is
    # the token emitted at step t)
    n_tok = n_agree = 0
    kls = []
    for r in ref:
        rows0 = srv0.logits_trace.get(r.request_id, [])
        rows1 = srv1.logits_trace.get(r.request_id, [])
        o0 = [int(np.argmax(row)) for row in rows0]
        o1 = [int(np.argmax(row)) for row in rows1]
        n = min(len(o0), len(o1))
        lcp = 0
        while lcp < n and o0[lcp] == o1[lcp]:
            lcp += 1
        n_tok += n
        n_agree += lcp
        # rows 0..lcp are conditioned on identical context (row t depends on
        # outputs[:t]; outputs agree through lcp-1)
        for t in range(min(lcp + 1, n)):
            a, b = np.asarray(rows0[t], np.float64), \
                np.asarray(rows1[t], np.float64)
            pa = np.exp(a - a.max())
            pa /= pa.sum()
            lb = b - b.max() - np.log(np.exp(b - b.max()).sum())
            la = a - a.max() - np.log(np.exp(a - a.max()).sum())
            kls.append(float(np.sum(pa * (la - lb))))
    return {
        "delta": delta,
        "tokens_compared": n_tok,
        "greedy_divergence": 1.0 - (n_agree / n_tok if n_tok else 1.0),
        "mean_kl_nats": float(np.mean(kls)) if kls else 0.0,
        "max_kl_nats": float(np.max(kls)) if kls else 0.0,
        "router_kl_bound_nats": delta,
    }


def _exact_at_zero(cfg, eng, p, pattern, seed=7):
    """delta=0 serving must be bit-identical to an engine that never had
    the feature configured (route_bias=None)."""
    _, srv_off = _serve(cfg, eng, p,
                        _requests(p, pattern, seed=seed, zero_arrivals=True),
                        route_bias=None, trace=True, deterministic=True)
    _, srv_z = _serve(cfg, eng, p,
                      _requests(p, pattern, seed=seed, zero_arrivals=True),
                      route_bias=0.0, trace=True, deterministic=True)
    if set(srv_off.logits_trace) != set(srv_z.logits_trace):
        return False
    for rid, rows in srv_off.logits_trace.items():
        zrows = srv_z.logits_trace[rid]
        if len(rows) != len(zrows):
            return False
        for a, b in zip(rows, zrows):
            if not np.array_equal(a, b):
                return False
    return True


def run_bench(p, out_path="BENCH_cache_aware.json", smoke=False, csv=None):
    cfg = _bench_config(p)
    eng = Engine(cfg, max_seq=_max_seq(p))
    gated = p["route_bias"]
    deltas = [d for d in p["deltas"] if d != gated] + [gated]

    workloads = {}
    for pattern in WORKLOADS:
        base = _frontier_point(cfg, eng, p, pattern, 0.0)
        points = [base] + [_frontier_point(cfg, eng, p, pattern, d)
                           for d in sorted(deltas)]
        points.append(_frontier_point(cfg, eng, p, pattern, gated,
                                      adaptive=True))
        at = {pt["delta"]: pt for pt in points if not pt.get("adaptive")}
        # the CI gate compares DETERMINISTIC runs (arrivals zeroed, admission
        # cap off, greedy) so the assertion is exact, not wall-clock-shaped
        g0 = _frontier_point(cfg, eng, p, pattern, 0.0, deterministic=True)
        g1 = _frontier_point(cfg, eng, p, pattern, gated,
                             deterministic=True)
        workloads[pattern] = {
            "points": points,
            "gate": {"baseline": g0, "biased": g1},
            "miss_reduction_at_gated": (g0["decode_misses"]
                                        - g1["decode_misses"]),
            "stall_event_reduction_at_gated": (g0["stall_events"]
                                               - g1["stall_events"]),
        }
        line = (f"cache_aware/{pattern}: decode misses "
                f"{g0['decode_misses']} -> "
                f"{g1['decode_misses']} at delta={gated} "
                f"(total {g0['demand_misses']} -> "
                f"{g1['demand_misses']}, swaps {g0['swap_experts']} -> "
                f"{g1['swap_experts']})")
        print(line)
        if csv is not None:
            csv.add(f"cache_aware/{pattern}_miss_reduction", 0.0,
                    str(workloads[pattern]["miss_reduction_at_gated"]))

    quality = [_quality(cfg, eng, p, "mixed", d) for d in sorted(deltas)]
    q_gated = next(q for q in quality if q["delta"] == gated)
    exact = _exact_at_zero(cfg, eng, p, "mixed")
    print(f"cache_aware/quality@{gated}: "
          f"divergence={q_gated['greedy_divergence']:.3f} "
          f"mean_kl={q_gated['mean_kl_nats']:.4f} nats "
          f"({q_gated['tokens_compared']} tokens)")
    print(f"cache_aware/bit_exact_at_zero: {exact}")

    result = {
        "config": {k: (list(v) if isinstance(v, tuple) else v)
                   for k, v in p.items()},
        "workloads": workloads,
        "quality": quality,
        "bit_exact_at_zero": exact,
    }
    with open(out_path, "w") as f:
        json.dump(result, f, indent=2)

    if smoke:
        assert exact, "delta=0 serving diverged from the unconfigured engine"
        for pattern in ("poisson", "bursty"):
            red = workloads[pattern]["miss_reduction_at_gated"]
            assert red > 0, (
                f"cache-aware routing must reduce decode demand misses on "
                f"{pattern}, got reduction {red}")
        assert q_gated["greedy_divergence"] <= p["max_greedy_divergence"], (
            f"greedy divergence {q_gated['greedy_divergence']:.3f} exceeds "
            f"bound {p['max_greedy_divergence']}")
        assert q_gated["mean_kl_nats"] <= p["max_mean_kl_nats"], (
            f"mean LM KL {q_gated['mean_kl_nats']:.3f} nats exceeds bound "
            f"{p['max_mean_kl_nats']}")
        print("SMOKE OK: miss reduction > 0 on poisson+bursty, quality "
              "within bounds, bit-exact at delta=0")
    return result


def run(csv):
    """benchmarks.run entry point."""
    run_bench(dict(DEFAULT), csv=csv)


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small shapes + regression assertions (CI)")
    ap.add_argument("--out", default="BENCH_cache_aware.json")
    args = ap.parse_args()
    p = dict(SMOKE if args.smoke else DEFAULT)
    run_bench(p, out_path=args.out, smoke=args.smoke)


if __name__ == "__main__":
    main()
