"""End-to-end expert integrity benchmark: checksummed tiers under chaos.

Exercises the `core.integrity` verification/quarantine/re-fetch machinery
on BOTH backends and asserts the integrity contract:

1. containment: under the seeded `corrupt_flaky` plan (transient link
   corruption + host-copy rot) with `verify=scrub`, single-row greedy
   decode through the tier is BIT-EXACT against an unfaulted oracle —
   every corrupt promotion is caught by its CRC and transparently
   re-fetched, so corrupt weight bytes never reach an FFN dispatch —
   and the run reports `n_corrupt_detected > 0` with zero quarantines;
2. serving resilience: the same plan under batched serving completes
   every non-shed request while detecting and healing corruption
   (`n_requarantined > 0`);
3. permanent damage: the `corrupt_disk` plan (deterministic per-record
   disk corruption — re-reads stay corrupt) exhausts the bounded
   re-fetch, permanently quarantines the damaged experts, and serving
   still completes every request via degraded resident-only routing —
   corruption degrades, it never deadlocks and never reaches logits;
4. zero-cost when off: `verify=off` on a clean store is bit-exact vs the
   pre-integrity engine, and `verify=scrub` on a clean store detects
   nothing and changes nothing;
5. simulator mirror: the modeled tier detects/heals the same chaos scopes
   and both backends report health through the SAME `ServingReport` keys.

Writes BENCH_integrity.json; ``--smoke`` asserts the gates for CI.
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
               n_slots_per_layer=2,
               host_budget_frac=0.5,        # eviction churn -> re-promotions
               disk_bandwidth=1e6,
               requests=6, max_new=12, batch=4,
               retry_max=3, scrub_budget=2, refetch_max=3,
               flaky_seed=3, disk_seed=0)
SMOKE = dict(DEFAULT, requests=5, max_new=10)

HEALTH_KEYS = ("n_corrupt_detected", "n_requarantined", "n_scrubbed",
               "n_quarantined_experts")


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


def _make_store(eng, p, sdir, verify="off", refetch_max=None):
    if not os.path.exists(os.path.join(sdir, "manifest.json")):
        export_expert_shards(split_params(eng.model, eng.params).experts,
                             sdir)
    probe = TieredExpertStore(sdir)
    return TieredExpertStore(
        sdir,
        host_budget_bytes=p["host_budget_frac"] * probe.total_expert_bytes,
        disk_bandwidth=p["disk_bandwidth"],
        verify=verify, scrub_budget=p["scrub_budget"],
        refetch_max=(p["refetch_max"] if refetch_max is None
                     else refetch_max))


def _serve(cfg, eng, p, store=None, plan=None):
    """One cold-cache serving run; returns (stats, summary)."""
    reqs = _requests(p)
    sb = SlotBufferEngine(cfg, eng.params, eng.model,
                          n_slots_per_layer=p["n_slots_per_layer"],
                          max_seq=_max_seq(p), store=store,
                          faults=plan, retry_max=p["retry_max"],
                          retry_backoff_s=0.0)
    srv = ServingEngine(sb, EngineServingConfig(
        max_batch=p["batch"], prefill_chunk=0, admission_cap=False))
    report = srv.serve(reqs)
    s = report.summary()
    served = [r for r in reqs if r.slot != -1 or len(r.output)]
    stats = {
        "n_requests": len(reqs),
        "n_served": len(served),
        "all_non_shed_complete": all(
            len(r.output) == r.max_new_tokens for r in served),
        "n_degraded_steps": s["n_degraded_steps"],
        **{k: s[k] for k in HEALTH_KEYS},
    }
    return stats, s


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


def _exactness_leg(cfg, eng, p, sdir, verify, plan=None, n_steps=10):
    """Single-row greedy decode through a (possibly chaos-injected,
    possibly verifying) tier vs the unfaulted no-store oracle; returns
    (exact, guard_counters). Transient corruption heals with probability
    1 given enough attempts, so this leg deepens the bounded re-fetch
    (refetch_max=8 -> quarantine odds ~0.3^8 per episode) to keep the
    oracle comparison meaningful: zero quarantines, bit-exact or bust."""
    rng = np.random.default_rng(11)
    prompt = rng.integers(0, cfg.vocab_size, (1, 8)).astype(np.int32)
    kw = dict(n_slots_per_layer=2, step_size=1, max_seq=48)
    ref = SlotBufferEngine(cfg, eng.params, eng.model, **kw)
    want = _greedy_tokens(ref, prompt, n_steps)
    store = _make_store(eng, p, sdir, verify=verify, refetch_max=8)
    sb = SlotBufferEngine(cfg, eng.params, eng.model, store=store,
                          faults=plan, retry_max=p["retry_max"],
                          retry_backoff_s=0.0, **kw)
    got = _greedy_tokens(sb, prompt, n_steps)
    return got == want, dict(store.model.guard.counters(),
                             n_quarantined_experts=store.model.guard
                             .n_quarantined_experts)


# ------------------------------------------------------- simulator mirror
def _sweep_steps(n_steps, L, M, hot):
    steps = []
    for si in range(n_steps):
        assigns = [np.array([[(li * hot + j) % M] for j in range(hot)])
                   for li in range(L)]
        steps.append(StepTrace(si, np.arange(4), assigns,
                               np.zeros((L, 4), np.float32)))
    return steps


def _sim_serve(p, plan=None, verify="off"):
    L, M, hot = 4, p["experts"], 5
    reqs = []
    for rid in range(p["requests"]):
        reqs.append(ServingRequest(
            prompt_len=16, max_new_tokens=p["max_new"],
            steps=_sweep_steps(p["max_new"], L, M, hot),
            arrival_s=0.0, request_id=rid))
    wl = ServingWorkload(L, M, 2,
                         [np.zeros((4, M), np.float32) for _ in range(L)],
                         reqs, name="integrity")
    hw = HardwareSpec("integlane", host_bw=1e8, flops=1e15, hbm_bw=1e12,
                      mem_cap=1e9)
    spec = SimSpec(expert_bytes=1e5, layer_time_s=1e-3, capacity_experts=4)
    from repro.core.coordinator import ablation
    pol = ablation("integrity", prefetch=True, adaptive_s=False,
                   two_level_lru=False, cache_aware=False,
                   blocking_swap_out=False, protect_early_layers=False,
                   predictor="oracle")
    cfg = ServingConfig(
        max_batch=p["batch"], prefill_chunk=16, admission_cap=False,
        fault_plan=plan, retry_max=p["retry_max"],
        host_budget_frac=p["host_budget_frac"], disk_bandwidth=4e9,
        disk_prefetch=True, verify=verify,
        scrub_budget=p["scrub_budget"], refetch_max=p["refetch_max"])
    rep = simulate_serving(wl, spec, hw, pol, cfg=cfg)
    s = rep.summary()
    return {
        "n_requests": len(reqs),
        "all_complete": all(m.n_tokens == p["max_new"]
                            for m in rep.requests),
        "n_degraded_steps": s["n_degraded_steps"],
        **{k: s[k] for k in HEALTH_KEYS},
    }, s


def run_bench(p, out_path="BENCH_integrity.json", smoke=False, csv=None):
    cfg = _bench_config(p)
    eng = Engine(cfg, max_seq=_max_seq(p))
    tmp = tempfile.mkdtemp(prefix="bench_integrity_")
    sdir = os.path.join(tmp, "olmoe")
    flaky = FaultPlan.corrupt_flaky(seed=p["flaky_seed"])
    diskp = FaultPlan.corrupt_disk(seed=p["disk_seed"])
    engine = {}

    # --- containment: corrupt_flaky + scrub is bit-exact vs oracle --------
    exact_flaky, g_flaky = _exactness_leg(cfg, eng, p, sdir, "scrub",
                                          plan=flaky)
    engine["flaky_exact"] = dict(g_flaky, exact=exact_flaky)
    print(f"integrity/engine/flaky_exact: exact={exact_flaky} "
          f"detected={g_flaky['n_corrupt_detected']} "
          f"healed={g_flaky['n_requarantined']} "
          f"quarantined={g_flaky['n_quarantined_experts']}")

    # --- zero-cost when off + silent when clean ---------------------------
    exact_off, g_off = _exactness_leg(cfg, eng, p, sdir, "off")
    exact_clean, g_clean = _exactness_leg(cfg, eng, p, sdir, "scrub")
    engine["verify_off_clean"] = dict(g_off, exact=exact_off)
    engine["verify_scrub_clean"] = dict(g_clean, exact=exact_clean)
    print(f"integrity/engine/clean: off_exact={exact_off} "
          f"scrub_exact={exact_clean} "
          f"scrub_detected={g_clean['n_corrupt_detected']}")

    # --- serving resilience: flaky heals, disk damage degrades ------------
    sflaky, eng_summary = _serve(cfg, eng, p,
                                 store=_make_store(eng, p, sdir, "scrub"),
                                 plan=flaky)
    engine["serve_flaky"] = sflaky
    print(f"integrity/engine/serve_flaky: "
          f"complete={sflaky['all_non_shed_complete']} "
          f"detected={sflaky['n_corrupt_detected']} "
          f"healed={sflaky['n_requarantined']} "
          f"scrubbed={sflaky['n_scrubbed']}")

    sdisk, _ = _serve(cfg, eng, p,
                      store=_make_store(eng, p, sdir, "promote"),
                      plan=diskp)
    engine["serve_corrupt_disk"] = sdisk
    print(f"integrity/engine/serve_corrupt_disk: "
          f"complete={sdisk['all_non_shed_complete']} "
          f"quarantined={sdisk['n_quarantined_experts']} "
          f"degraded_steps={sdisk['n_degraded_steps']}")

    # --- simulator mirror -------------------------------------------------
    sim = {}
    sim["flaky"], sum_flaky = _sim_serve(p, plan=flaky, verify="scrub")
    sim["corrupt_disk"], _ = _sim_serve(p, plan=diskp, verify="promote")
    sim["clean"], _ = _sim_serve(p, verify="scrub")
    keys_match = set(sum_flaky) == set(eng_summary)
    print(f"integrity/sim: flaky detected={sim['flaky']['n_corrupt_detected']}"
          f" healed={sim['flaky']['n_requarantined']} "
          f"disk_quarantined={sim['corrupt_disk']['n_quarantined_experts']} "
          f"clean_detected={sim['clean']['n_corrupt_detected']} "
          f"keys_match={keys_match}")

    result = {
        "config": {k: (list(v) if isinstance(v, tuple) else v)
                   for k, v in p.items()},
        "engine": engine,
        "sim": sim,
        "bit_exact_under_flaky_corruption": exact_flaky,
        "bit_exact_verify_off": exact_off,
        "bit_exact_scrub_clean": exact_clean,
        "summary_keys_match": keys_match,
    }
    with open(out_path, "w") as f:
        json.dump(result, f, indent=2)

    if csv is not None:
        csv.add("integrity/engine_flaky_detected", 0.0,
                str(sflaky["n_corrupt_detected"]))
        csv.add("integrity/engine_flaky_healed", 0.0,
                str(sflaky["n_requarantined"]))
        csv.add("integrity/engine_disk_quarantined", 0.0,
                str(sdisk["n_quarantined_experts"]))

    if smoke:
        assert g_flaky["n_quarantined_experts"] == 0, \
            f"flaky seed {p['flaky_seed']} quarantined an expert — the " \
            f"exactness oracle only holds with zero quarantines: {g_flaky}"
        assert exact_flaky, \
            "corrupt bytes reached logits: flaky decode diverged from oracle"
        assert g_flaky["n_corrupt_detected"] > 0, \
            "corrupt_flaky plan injected nothing — chaos scope not wired"
        assert exact_off and exact_clean, \
            "clean store diverged (verification must be a no-op when clean)"
        assert (g_clean["n_corrupt_detected"] == 0
                and g_clean["n_requarantined"] == 0
                and g_clean["n_quarantined_experts"] == 0), \
            f"clean store reported corruption: {g_clean}"
        assert sflaky["all_non_shed_complete"], \
            f"flaky corruption truncated a request: {sflaky}"
        assert sflaky["n_corrupt_detected"] > 0 \
            and sflaky["n_requarantined"] > 0, \
            f"serving saw no corruption under corrupt_flaky: {sflaky}"
        assert sdisk["all_non_shed_complete"], \
            f"disk corruption deadlocked/truncated serving: {sdisk}"
        assert sdisk["n_quarantined_experts"] > 0, \
            f"corrupt_disk quarantined nothing: {sdisk}"
        assert sim["flaky"]["all_complete"] \
            and sim["flaky"]["n_corrupt_detected"] > 0 \
            and sim["flaky"]["n_requarantined"] > 0, \
            f"sim flaky lane: {sim['flaky']}"
        assert sim["corrupt_disk"]["all_complete"] \
            and sim["corrupt_disk"]["n_quarantined_experts"] > 0, \
            f"sim corrupt_disk lane: {sim['corrupt_disk']}"
        assert sim["clean"]["n_corrupt_detected"] == 0, \
            f"sim clean lane reported corruption: {sim['clean']}"
        assert keys_match, "engine/sim ServingReport summary keys diverged"
        print("SMOKE OK: corruption detected+healed on both backends, "
              "flaky decode bit-exact vs oracle, disk damage quarantines "
              "and degrades without deadlock, clean stores stay silent")
    return result


def run(csv):
    """benchmarks.run entry point."""
    run_bench(dict(DEFAULT), csv=csv)


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small shapes + regression assertions (CI)")
    ap.add_argument("--out", default="BENCH_integrity.json")
    args = ap.parse_args()
    p = dict(SMOKE if args.smoke else DEFAULT)
    run_bench(p, out_path=args.out, smoke=args.smoke)


if __name__ == "__main__":
    main()
