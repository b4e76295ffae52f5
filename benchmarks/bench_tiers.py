"""Tiered expert store benchmark: disk->host->device streaming gates.

Exercises `core.expert_tiers` end to end on BOTH backends and asserts the
streaming contract holds:

1. exactness: a `SlotBufferEngine` whose experts stream through a
   `TieredExpertStore` with a host budget of ~50% of total expert bytes —
   i.e. under real host LRU eviction churn — emits bit-identical greedy
   tokens to the same engine on the pre-staged `HostExpertStore`, on a
   GQA (olmoe) and an MLA (deepseek-v2-lite) architecture. (The gate uses
   single-row greedy decode: when a layer's demanded set exceeds the
   device slot count, WHICH overflow tokens drop legitimately depends on
   residency history, so batched capacity-overflow serving is compared on
   health counters, not logits);
2. conversion: with the long-horizon disk prefetcher on, the majority of
   the would-be host demand misses (measured by the same run with
   `prefetch=False`) become host hits, and the exposed disk stall
   fraction drops;
3. degradation: a dead disk link (`disk_dead` plan) never deadlocks a
   decode step — every non-shed request finishes its token budget while
   the engine reports degraded steps;
4. simulator mirror: a layer-sweep workload whose per-layer hot set
   exceeds the host budget shows the same conversion behavior in modeled
   time, and both backends report tier health through the SAME
   `ServingReport` summary keys.

Writes BENCH_tiers.json; ``--smoke`` asserts the gates for the CI fast
lane.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir, "src"))

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.configs.base import reduce_config                    # noqa: E402
from repro.configs.registry import get_config                   # noqa: E402
from repro.core.expert_tiers import (TieredExpertStore,         # noqa: E402
                                     export_expert_shards)
from repro.core.faults import FaultPlan                         # noqa: E402
from repro.data.workloads import make_workload, prompt_tokens   # noqa: E402
from repro.runtime.engine import (Engine, SlotBufferEngine,     # noqa: E402
                                  split_params)
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
               n_slots_per_layer=2,         # tight device tier: churn
               host_budget_frac=0.5,        # host tier holds HALF the model
               disk_bandwidth=1e6,          # bytes per engine link-clock unit
               requests=6, max_new=12, batch=4,
               retry_max=3)
SMOKE = dict(DEFAULT, requests=5, max_new=10)

TIER_KEYS = ("n_host_hits", "n_host_misses", "disk_stall_s")


def _bench_config(p, arch="olmoe-1b-7b"):
    return reduce_config(get_config(arch), layers=p["layers"],
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


def _make_store(eng, p, sdir, prefetch=True):
    if not os.path.exists(os.path.join(sdir, "manifest.json")):
        export_expert_shards(split_params(eng.model, eng.params).experts,
                             sdir)
    probe = TieredExpertStore(sdir)
    return TieredExpertStore(
        sdir,
        host_budget_bytes=p["host_budget_frac"] * probe.total_expert_bytes,
        disk_bandwidth=p["disk_bandwidth"], prefetch=prefetch)


def _serve(cfg, eng, p, store=None, plan=None, trace=False):
    """One cold-cache serving run; returns (stats, ServingEngine, summary)."""
    reqs = _requests(p)
    sb = SlotBufferEngine(cfg, eng.params, eng.model,
                          n_slots_per_layer=p["n_slots_per_layer"],
                          max_seq=_max_seq(p), store=store,
                          faults=plan, retry_max=p["retry_max"],
                          retry_backoff_s=0.0)
    srv = ServingEngine(sb, EngineServingConfig(
        max_batch=p["batch"], prefill_chunk=0, admission_cap=False,
        trace_logits=trace))
    report = srv.serve(reqs)
    s = report.summary()
    served = [r for r in reqs if r.slot != -1 or len(r.output)]
    stats = {
        "n_requests": len(reqs),
        "n_served": len(served),
        "all_non_shed_complete": all(
            len(r.output) == r.max_new_tokens for r in served),
        "n_degraded_steps": s["n_degraded_steps"],
        **{k: s[k] for k in TIER_KEYS},
    }
    if store is not None:
        stats["tier"] = store.snapshot()
    return stats, srv, s


def _greedy_tokens(sb, prompt, n_steps):
    import jax.numpy as jnp
    lo, st = sb.prefill(prompt)
    tok = jnp.argmax(lo, -1).astype(jnp.int32)
    toks = [int(tok[0])]
    for _ in range(n_steps):
        lo, st = sb.decode_step(tok, st)
        tok = jnp.argmax(lo, -1).astype(jnp.int32)
        toks.append(int(tok[0]))
    return toks


def _exactness_leg(cfg, eng, p, sdir, n_steps=8):
    """Bit-exact greedy decode through the tier at 50% host budget vs the
    pre-staged store; returns (exact, store_snapshot)."""
    rng = np.random.default_rng(11)
    prompt = rng.integers(0, cfg.vocab_size, (1, 8)).astype(np.int32)
    kw = dict(n_slots_per_layer=2, step_size=1, max_seq=48)
    ref = SlotBufferEngine(cfg, eng.params, eng.model, **kw)
    want = _greedy_tokens(ref, prompt, n_steps)
    store = _make_store(eng, p, sdir)
    sb = SlotBufferEngine(cfg, eng.params, eng.model, store=store, **kw)
    got = _greedy_tokens(sb, prompt, n_steps)
    return got == want, store.snapshot()


def _conversion(miss_np, miss_p):
    """Fraction of the no-prefetch run's host misses the prefetcher
    converted into hits."""
    if miss_np <= 0:
        return 0.0
    return max(0.0, (miss_np - miss_p) / miss_np)


# ------------------------------------------------------- simulator mirror
def _sweep_steps(n_steps, rid, L, M, hot):
    """Layer-sweep workload: layer li re-demands the SAME `hot` experts
    {li*hot..li*hot+hot-1 mod M} every step. Total hot keys L*hot exceed
    the host budget, so a demand-only LRU thrashes cyclically, while the
    popularity-driven streamer stages the next layers' sets ahead of the
    sweep — the streaming-wins regime."""
    steps = []
    for si in range(n_steps):
        assigns = [np.array([[(li * hot + j) % M] for j in range(hot)])
                   for li in range(L)]
        steps.append(StepTrace(si, np.arange(4), assigns,
                               np.zeros((L, 4), np.float32)))
    return steps


def _sim_serve(p, prefetch=True, host_budget_frac=None, plan=None):
    # hot=5 of M=8 per layer: 20 hot keys cyclically swept against a
    # 16-entry host budget — the classic sequential-scan regime where a
    # demand-only LRU evicts every entry just before its reuse, while the
    # streamer's ~3 in-flight waves of 5 keys fit the budget
    L, M, hot = 4, p["experts"], 5
    reqs = []
    for rid in range(p["requests"]):
        reqs.append(ServingRequest(
            prompt_len=16, max_new_tokens=p["max_new"],
            steps=_sweep_steps(p["max_new"], rid, L, M, hot),
            arrival_s=0.0, request_id=rid))
    wl = ServingWorkload(L, M, 2,
                         [np.zeros((4, M), np.float32) for _ in range(L)],
                         reqs, name="tiers")
    hw = HardwareSpec("tierlane", host_bw=1e8, flops=1e15, hbm_bw=1e12,
                      mem_cap=1e9)
    spec = SimSpec(expert_bytes=1e5, layer_time_s=1e-3,
                   capacity_experts=4)
    from repro.core.coordinator import ablation
    # oracle predictor: this lane measures the TIER (staging, eviction,
    # promotion timing), not prediction quality — the workload's synthetic
    # gate scores carry no signal for the pregate path
    pol = ablation("tiers", prefetch=True, adaptive_s=False,
                   two_level_lru=False, cache_aware=False,
                   blocking_swap_out=False, protect_early_layers=False,
                   predictor="oracle")
    cfg = ServingConfig(
        max_batch=p["batch"], prefill_chunk=16, admission_cap=False,
        fault_plan=plan, retry_max=p["retry_max"],
        host_budget_frac=(host_budget_frac
                          if host_budget_frac is not None
                          else p["host_budget_frac"]),
        disk_bandwidth=4e9,          # modeled B/s: ~40 experts/layer-time
        disk_prefetch=prefetch)
    rep = simulate_serving(wl, spec, hw, pol, cfg=cfg)
    s = rep.summary()
    return {
        "n_requests": len(reqs),
        "all_complete": all(m.n_tokens == p["max_new"]
                            for m in rep.requests),
        "stall_s": s["stall_s"],
        "n_degraded_steps": s["n_degraded_steps"],
        **{k: s[k] for k in TIER_KEYS},
    }, s


def run_bench(p, out_path="BENCH_tiers.json", smoke=False, csv=None):
    cfg = _bench_config(p)
    eng = Engine(cfg, max_seq=_max_seq(p))
    tmp = tempfile.mkdtemp(prefix="bench_tiers_")

    # --- engine: bit-exact greedy decode under host eviction churn --------
    engine = {}
    exact, snap_gqa = _exactness_leg(cfg, eng, p, os.path.join(tmp, "gqa"))
    churn = snap_gqa["evictions"] > 0
    from repro.configs.registry import get_smoke_config
    cfg_m = get_smoke_config("deepseek-v2-lite")
    eng_m = Engine(cfg_m, max_seq=48)
    exact_mla, snap_mla = _exactness_leg(cfg_m, eng_m, p,
                                         os.path.join(tmp, "mla"))
    engine["exact_gqa_tier"] = snap_gqa
    engine["exact_mla_tier"] = snap_mla
    print(f"tiers/engine/exact: gqa={exact} mla={exact_mla} "
          f"churn_evictions={snap_gqa['evictions']:.0f}")

    # --- engine: serving conversion + degradation -------------------------
    base, _, eng_summary = _serve(cfg, eng, p)
    engine["prestaged"] = base

    sdir = os.path.join(tmp, "olmoe")
    tiered, _, _ = _serve(cfg, eng, p, store=_make_store(eng, p, sdir))
    engine["tiered"] = tiered

    nopf, _, _ = _serve(cfg, eng, p,
                        store=_make_store(eng, p, sdir, prefetch=False))
    engine["tiered_noprefetch"] = nopf
    conv = _conversion(nopf["n_host_misses"], tiered["n_host_misses"])
    print(f"tiers/engine: misses {nopf['n_host_misses']}->"
          f"{tiered['n_host_misses']} (conversion={conv:.2f}) "
          f"stall {nopf['disk_stall_s']:.2f}->"
          f"{tiered['disk_stall_s']:.2f} link-units")

    # dead disk link: degrade, never deadlock
    dead, _, _ = _serve(cfg, eng, p,
                        store=_make_store(eng, p, os.path.join(tmp, "dead")),
                        plan=FaultPlan.disk_dead())
    engine["disk_dead"] = dead
    print(f"tiers/engine/disk_dead: complete={dead['all_non_shed_complete']} "
          f"degraded_steps={dead['n_degraded_steps']}")

    # --- simulator mirror -------------------------------------------------
    sim = {}
    sim["prefetch"], sum_pf = _sim_serve(p, prefetch=True)
    sim["noprefetch"], _ = _sim_serve(p, prefetch=False)
    sim_conv = _conversion(sim["noprefetch"]["n_host_misses"],
                           sim["prefetch"]["n_host_misses"])
    keys_match = set(sum_pf) == set(eng_summary)
    print(f"tiers/sim: misses {sim['noprefetch']['n_host_misses']}->"
          f"{sim['prefetch']['n_host_misses']} (conversion={sim_conv:.2f}) "
          f"stall {sim['noprefetch']['stall_s']*1e3:.2f}->"
          f"{sim['prefetch']['stall_s']*1e3:.2f}ms keys_match={keys_match}")

    result = {
        "config": {k: (list(v) if isinstance(v, tuple) else v)
                   for k, v in p.items()},
        "engine": engine,
        "sim": sim,
        "bit_exact_gqa": exact,
        "bit_exact_mla": exact_mla,
        "host_churn": churn,
        "engine_conversion": conv,
        "sim_conversion": sim_conv,
        "summary_keys_match": keys_match,
    }
    with open(out_path, "w") as f:
        json.dump(result, f, indent=2)

    if csv is not None:
        csv.add("tiers/engine_conversion", 0.0, f"{conv:.3f}")
        csv.add("tiers/sim_conversion", 0.0, f"{sim_conv:.3f}")
        csv.add("tiers/engine_host_misses", 0.0,
                str(tiered["n_host_misses"]))

    if smoke:
        assert exact, \
            "tiered store diverged from pre-staged host store (GQA)"
        assert exact_mla, \
            "tiered store diverged from pre-staged host store (MLA)"
        assert churn, "no host eviction churn: budget not binding"
        assert (base["n_host_misses"] == 0 and base["n_host_hits"] == 0
                and base["disk_stall_s"] == 0), \
            f"pre-staged store reported tier activity: {base}"
        assert nopf["n_host_misses"] > 0, \
            "no-prefetch run saw no host misses: workload not streaming"
        assert conv >= 0.5, \
            f"disk prefetch converted only {conv:.0%} of host misses"
        assert (tiered["disk_stall_s"]
                <= 0.5 * max(nopf["disk_stall_s"], 1e-12)), \
            "prefetch did not cut the exposed disk stall in half"
        assert dead["all_non_shed_complete"], \
            f"dead disk link deadlocked/truncated decode: {dead}"
        assert dead["n_degraded_steps"] > 0, \
            f"dead disk link never degraded: {dead}"
        assert sim["noprefetch"]["n_host_misses"] > 0
        assert sim_conv >= 0.5, \
            f"sim: disk prefetch converted only {sim_conv:.0%}"
        assert sim["prefetch"]["all_complete"]
        assert keys_match, "engine/sim ServingReport summary keys diverged"
        print("SMOKE OK: tiered store bit-exact on GQA+MLA under churn, "
              "disk prefetch converts the majority of host misses on both "
              "backends, dead disk degrades without deadlock")
    return result


def run(csv):
    """benchmarks.run entry point."""
    run_bench(dict(DEFAULT), csv=csv)


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small shapes + regression assertions (CI)")
    ap.add_argument("--out", default="BENCH_tiers.json")
    args = ap.parse_args()
    p = dict(SMOKE if args.smoke else DEFAULT)
    run_bench(p, out_path=args.out, smoke=args.smoke)


if __name__ == "__main__":
    main()
