"""Chaos benchmark: fault-injected serving + graceful-degradation gates.

Exercises the fault-injection layer (`core.faults`) end to end on BOTH
backends and asserts the degradation ladder holds — retry, then resident-
only degraded routing, then horizon collapse, then load shedding — with a
dead link never deadlocking a decode step:

1. engine scenarios: a seeded brownout plan (flaky + bandwidth collapse),
   flaky-only, injected stalls, and a TOTAL link outage, each served on the
   real `SlotBufferEngine` under continuous batching. Every non-shed
   request must finish its full token budget (zero hangs), brownout must
   report `n_retries > 0` and `n_degraded_steps > 0`;
2. exactness: an engine built with a *disabled* `FaultPlan` must emit
   bit-identical logits to an engine that never had the feature configured
   (trace-selection rule, as cache-aware routing's delta=0 guarantee), and
   a bandwidth-only brownout (no failures, uncontended slots) must not
   change emitted tokens — timing faults shape latency, not outputs;
3. shedding: `deadline_s=0` deterministically sheds the whole population
   (`n_shed == N`, nothing served uselessly late);
4. simulator mirror: the same `FaultPlan` semantics replayed in modeled
   time — health counters land in the SAME `ServingReport` summary keys as
   the engine's, a disabled plan is a no-op vs no plan, and tight
   deadlines shed late arrivals.

Writes BENCH_faults.json; ``--smoke`` asserts the gates for the CI fast
lane.
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
from repro.core.faults import FaultPlan                         # noqa: E402
from repro.data.workloads import make_workload, prompt_tokens   # noqa: E402
from repro.runtime.engine import Engine, SlotBufferEngine       # noqa: E402
from repro.runtime.request import Request                       # noqa: E402
from repro.runtime.serving import (EngineServingConfig,         # noqa: E402
                                   ServingEngine)
from repro.simulator.events import SimSpec, StepTrace           # noqa: E402
from repro.simulator.hardware import HardwareSpec               # noqa: E402
from repro.simulator.serving import (ServingConfig,             # noqa: E402
                                     ServingRequest,
                                     ServingWorkload,
                                     simulate_serving)

DEFAULT = dict(layers=4, d_model=64, heads=4, kv_heads=4, d_ff=128,
               vocab=512, experts=8, top_k=2, d_expert=32,
               n_slots_per_layer=5,           # contended: 5 slots, 8 experts
               requests=8, max_new=12, batch=4,
               retry_max=3)
SMOKE = dict(DEFAULT, requests=6, max_new=10)

HEALTH_KEYS = ("n_link_failures", "n_retries", "n_degraded_steps", "n_shed")


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


def _requests(p, seed=0):
    """Deterministic population: arrivals zeroed, greedy decode."""
    rng = np.random.default_rng(seed)
    specs = make_workload("poisson", p["requests"], seed=seed,
                          mean_decode=p["max_new"])
    reqs = []
    for s in specs:
        toks = _pad_to_bucket(prompt_tokens(s, p["vocab"], rng))
        reqs.append(Request(
            prompt=toks.astype(np.int32),
            max_new_tokens=max(2, min(s.decode_len, p["max_new"])),
            temperature=0.0, arrival_s=0.0, request_id=s.request_id))
    return reqs


def _max_seq(p):
    return 64 + p["max_new"] + 8


def _serve(cfg, eng, p, plan=None, slots=None, deadline_s=None, trace=False):
    """One serving run on a FRESH slot engine under `plan` (cold cache).
    Returns (scenario stats, the ServingEngine, the full summary dict)."""
    reqs = _requests(p)
    sb = SlotBufferEngine(cfg, eng.params, eng.model,
                          n_slots_per_layer=slots or p["n_slots_per_layer"],
                          max_seq=_max_seq(p),
                          faults=plan, retry_max=p["retry_max"],
                          retry_backoff_s=0.0)
    srv = ServingEngine(sb, EngineServingConfig(
        max_batch=p["batch"], prefill_chunk=0, admission_cap=False,
        deadline_s=deadline_s, trace_logits=trace))
    report = srv.serve(reqs)
    s = report.summary()
    served = [r for r in reqs if r.slot != -1 or len(r.output)]
    stats = {
        "n_requests": len(reqs),
        "n_served": len(served),
        "tokens_emitted": sum(len(r.output) for r in reqs),
        "tokens_expected_non_shed": sum(r.max_new_tokens for r in served),
        "all_non_shed_complete": all(
            len(r.output) == r.max_new_tokens for r in served),
        "throughput_tok_s": s["throughput_tok_s"],
        **{k: s[k] for k in HEALTH_KEYS},
    }
    return stats, srv, s


def _logits_equal(srv_a, srv_b):
    if set(srv_a.logits_trace) != set(srv_b.logits_trace):
        return False
    for rid, rows in srv_a.logits_trace.items():
        brows = srv_b.logits_trace[rid]
        if len(rows) != len(brows):
            return False
        for a, b in zip(rows, brows):
            if not np.array_equal(a, b):
                return False
    return True


# ------------------------------------------------------- simulator mirror
def _sim_steps(n_steps, rid, L, M, top_k):
    """Rotating routing so the contended sim cache keeps missing."""
    steps = []
    for si in range(n_steps):
        assigns = [np.array([[(rid + si + li + j) % M]
                             for j in range(top_k)])
                   for li in range(L)]
        steps.append(StepTrace(si, np.arange(4), assigns,
                               np.zeros((L, 4), np.float32)))
    return steps


def _sim_serve(p, plan=None, deadline_s=None, max_batch=None,
               arrival_gap_s=0.0):
    L, M, top_k = 2, p["experts"], 2
    reqs = []
    for rid in range(p["requests"]):
        reqs.append(ServingRequest(
            prompt_len=16, max_new_tokens=p["max_new"],
            steps=_sim_steps(p["max_new"], rid, L, M, top_k),
            arrival_s=rid * arrival_gap_s, request_id=rid))
    wl = ServingWorkload(L, M, top_k,
                         [np.zeros((4, M), np.float32) for _ in range(L)],
                         reqs, name="faults")
    # slow host link so transfers are on the critical path and brownout
    # bandwidth derates visibly stretch them
    hw = HardwareSpec("faultlane", host_bw=1e8, flops=1e15, hbm_bw=1e12,
                      mem_cap=1e9)
    spec = SimSpec(expert_bytes=1e5, layer_time_s=1e-3,
                   capacity_experts=6)   # contended: 6 slots, L*M=16 keys
    from repro.core.coordinator import ablation
    pol = ablation("faults", prefetch=True, adaptive_s=False,
                   two_level_lru=False, cache_aware=False,
                   blocking_swap_out=False, protect_early_layers=False)
    cfg = ServingConfig(max_batch=max_batch or p["batch"], prefill_chunk=16,
                        admission_cap=False, fault_plan=plan,
                        retry_max=p["retry_max"], deadline_s=deadline_s)
    rep = simulate_serving(wl, spec, hw, pol, cfg=cfg)
    s = rep.summary()
    served = {m.request_id for m in rep.requests}
    return {
        "n_requests": len(reqs),
        "n_served": len(served),
        "all_non_shed_complete": all(
            m.n_tokens == p["max_new"] for m in rep.requests),
        "makespan_s": s["makespan_s"],
        "stall_s": s["stall_s"],
        **{k: s[k] for k in HEALTH_KEYS},
    }, s


def run_bench(p, out_path="BENCH_faults.json", smoke=False, csv=None):
    cfg = _bench_config(p)
    eng = Engine(cfg, max_seq=_max_seq(p))

    # --- engine scenarios -------------------------------------------------
    engine = {}
    healthy, srv_healthy, eng_summary = _serve(cfg, eng, p, plan=None,
                                               trace=True)
    engine["healthy"] = healthy
    disabled, srv_disabled, _ = _serve(cfg, eng, p, plan=FaultPlan(),
                                       trace=True)
    engine["disabled_plan"] = disabled
    exact_disabled = _logits_equal(srv_healthy, srv_disabled)

    for name, plan in (("brownout", FaultPlan.brownout_preset(seed=0)),
                       ("flaky", FaultPlan.flaky(seed=0)),
                       ("stall", FaultPlan.stall(seed=0)),
                       ("outage", FaultPlan.total_outage())):
        engine[name], _, _ = _serve(cfg, eng, p, plan=plan)
        st = engine[name]
        print(f"faults/engine/{name}: complete={st['all_non_shed_complete']} "
              f"failures={st['n_link_failures']} retries={st['n_retries']} "
              f"degraded_steps={st['n_degraded_steps']} shed={st['n_shed']}")

    # timing-only faults must not change outputs: uncontended slots (every
    # demanded expert fits) + bandwidth collapse, vs the same healthy shape
    full0, srv_f0, _ = _serve(cfg, eng, p, plan=None, slots=p["experts"],
                              trace=True)
    bw_plan = FaultPlan(seed=0, bandwidth_factor=0.05)
    full1, srv_f1, _ = _serve(cfg, eng, p, plan=bw_plan, slots=p["experts"],
                              trace=True)
    timing_parity = _logits_equal(srv_f0, srv_f1)
    engine["bandwidth_only"] = full1

    shed, _, _ = _serve(cfg, eng, p, plan=FaultPlan.brownout_preset(seed=0),
                        deadline_s=0.0)
    engine["shed_all"] = shed
    print(f"faults/engine/shed_all: shed={shed['n_shed']}/"
          f"{shed['n_requests']}")
    print(f"faults/engine/exactness: disabled_plan={exact_disabled} "
          f"bandwidth_only={timing_parity}")

    # --- simulator mirror -------------------------------------------------
    sim = {}
    sim["none"], sum_none = _sim_serve(p, plan=None)
    sim["disabled_plan"], sum_disabled = _sim_serve(p, plan=FaultPlan())
    sim["brownout"], sum_brownout = _sim_serve(
        p, plan=FaultPlan.brownout_preset(seed=0))
    sim["shed"], _ = _sim_serve(p, plan=FaultPlan.brownout_preset(seed=0),
                                deadline_s=4e-3, max_batch=1,
                                arrival_gap_s=1e-4)
    sim_noop = sum_none == sum_disabled
    # acceptance: both backends report health in the SAME summary shape
    keys_match = set(sum_brownout) == set(eng_summary)
    for name in ("none", "brownout", "shed"):
        st = sim[name]
        print(f"faults/sim/{name}: complete={st['all_non_shed_complete']} "
              f"failures={st['n_link_failures']} retries={st['n_retries']} "
              f"degraded_steps={st['n_degraded_steps']} shed={st['n_shed']}")

    result = {
        "config": {k: (list(v) if isinstance(v, tuple) else v)
                   for k, v in p.items()},
        "engine": engine,
        "sim": sim,
        "exact_disabled_plan": exact_disabled,
        "timing_fault_output_parity": timing_parity,
        "sim_disabled_plan_noop": sim_noop,
        "summary_keys_match": keys_match,
    }
    with open(out_path, "w") as f:
        json.dump(result, f, indent=2)

    if csv is not None:
        csv.add("faults/brownout_retries", 0.0,
                str(engine["brownout"]["n_retries"]))
        csv.add("faults/brownout_degraded_steps", 0.0,
                str(engine["brownout"]["n_degraded_steps"]))

    if smoke:
        assert exact_disabled, \
            "disabled FaultPlan diverged from the unconfigured engine"
        assert timing_parity, \
            "bandwidth-only faults changed emitted logits"
        b = engine["brownout"]
        assert b["all_non_shed_complete"] and b["n_shed"] == 0, \
            f"brownout dropped requests: {b}"
        assert b["n_retries"] > 0, f"brownout fired no retries: {b}"
        assert b["n_degraded_steps"] > 0, \
            f"brownout never degraded: {b}"
        o = engine["outage"]
        assert o["all_non_shed_complete"], \
            f"total outage deadlocked/truncated decode: {o}"
        assert o["n_degraded_steps"] > 0, f"outage never degraded: {o}"
        assert shed["n_shed"] == shed["n_requests"], \
            f"deadline_s=0 must shed everything: {shed}"
        assert sim_noop, "sim: disabled plan perturbed the report"
        sb = sim["brownout"]
        assert sb["all_non_shed_complete"], f"sim brownout dropped: {sb}"
        assert sb["n_retries"] > 0 and sb["n_link_failures"] > 0, \
            f"sim brownout fired no retries: {sb}"
        assert sb["n_degraded_steps"] > 0, f"sim never degraded: {sb}"
        assert sim["shed"]["n_shed"] > 0, \
            f"sim tight deadline shed nothing: {sim['shed']}"
        assert keys_match, "engine/sim ServingReport summary keys diverged"
        print("SMOKE OK: brownout completes with retries+degraded steps on "
              "both backends, outage cannot deadlock, disabled plan "
              "bit-exact, deadline shedding deterministic")
    return result


def run(csv):
    """benchmarks.run entry point."""
    run_bench(dict(DEFAULT), csv=csv)


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small shapes + regression assertions (CI)")
    ap.add_argument("--out", default="BENCH_faults.json")
    args = ap.parse_args()
    p = dict(SMOKE if args.smoke else DEFAULT)
    run_bench(p, out_path=args.out, smoke=args.smoke)


if __name__ == "__main__":
    main()
