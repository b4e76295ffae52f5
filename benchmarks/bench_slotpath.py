"""Slot-path runtime benchmark: pre-fused per-expert dispatch loop vs the
fused batched-swap + gather-dispatch + prefetch-overlap pipeline.

Runs decode-shaped steps (fresh small token batches) through a reduced MoE
model with a slot buffer smaller than the expert population, so every step
produces real swap traffic. Per decode step and per path it reports:

- tokens/s (wall clock, post-warmup)
- device dispatches  = eager primitive binds + engine-issued jit/swap calls
- swap device calls vs experts moved (batching factor)
- host syncs (blocking device->host pulls)

Writes BENCH_slotpath.json (the repo's slot-path perf trajectory record) and
— in ``--smoke`` mode — asserts the fused path's dispatch reduction so the
CI fast lane catches any regression back to per-expert dispatching.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir, "src"))

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.configs.base import reduce_config            # noqa: E402
from repro.configs.registry import get_config           # noqa: E402
from repro.models import Model                          # noqa: E402
from repro.runtime.engine import SlotBufferEngine       # noqa: E402
from repro.runtime.instrument import count_dispatches   # noqa: E402

DEFAULT = dict(layers=4, d_model=64, heads=4, kv_heads=4, d_ff=128,
               vocab=512, experts=8, top_k=2, d_expert=32,
               n_slots_per_layer=6, batch=4, seq=8, steps=8, warmup=2)
SMOKE = dict(DEFAULT, layers=2, batch=2, seq=4, steps=3, warmup=1)


def _bench_config(p):
    return reduce_config(get_config("olmoe-1b-7b"), layers=p["layers"],
                         d_model=p["d_model"], heads=p["heads"],
                         kv_heads=p["kv_heads"], d_ff=p["d_ff"],
                         vocab=p["vocab"], experts=p["experts"],
                         top_k=p["top_k"], d_expert=p["d_expert"])


def _token_stream(p, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, p["vocab"], (p["batch"], p["seq"]),
                         dtype=np.int32)
            for _ in range(p["steps"] + p["warmup"])]


def _measure(sb: SlotBufferEngine, batches, p):
    for toks in batches[:p["warmup"]]:
        sb.forward(toks).block_until_ready()
    sb.stats.reset()
    measured = batches[p["warmup"]:]
    with count_dispatches() as c:
        t0 = time.perf_counter()
        for toks in measured:
            sb.forward(toks).block_until_ready()
        wall_s = time.perf_counter() - t0
    st = sb.stats
    steps = len(measured)
    tokens = steps * p["batch"] * p["seq"]
    dispatches = c.eager + st.jit_calls + st.swap_calls
    return {
        "tokens_per_s": tokens / wall_s,
        "wall_s_per_step": wall_s / steps,
        "device_dispatches_per_step": dispatches / steps,
        "eager_dispatches_per_step": c.eager / steps,
        "jit_calls_per_step": st.jit_calls / steps,
        "swap_calls_per_step": st.swap_calls / steps,
        "swap_experts_per_step": st.swap_experts / steps,
        "host_syncs_per_step": st.host_syncs / steps,
        "prefetched_per_step": st.prefetched / steps,
        "prefetch_hits_per_step": st.prefetch_hits / steps,
        "demand_misses_per_step": st.demand_misses / steps,
    }


def bench(p) -> dict:
    cfg = _bench_config(p)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batches = _token_stream(p)
    legacy = SlotBufferEngine(cfg, params, model,
                              n_slots_per_layer=p["n_slots_per_layer"],
                              fused=False)
    fused = SlotBufferEngine(cfg, params, model,
                             n_slots_per_layer=p["n_slots_per_layer"],
                             fused=True, prefetch=True)
    res_legacy = _measure(legacy, batches, p)
    res_fused = _measure(fused, batches, p)
    ratios = {
        "device_dispatch_reduction":
            res_legacy["device_dispatches_per_step"]
            / max(res_fused["device_dispatches_per_step"], 1e-9),
        "tokens_per_s_speedup":
            res_fused["tokens_per_s"] / max(res_legacy["tokens_per_s"], 1e-9),
        "swap_call_reduction":
            res_legacy["swap_calls_per_step"]
            / max(res_fused["swap_calls_per_step"], 1e-9),
    }
    return {"config": p, "legacy": res_legacy, "fused": res_fused,
            "ratios": ratios}


def run(csv) -> None:
    """benchmarks/run.py entry: smoke-scale sweep, CSV rows only."""
    report = bench(SMOKE)
    for path in ("legacy", "fused"):
        r = report[path]
        csv.add(f"slotpath/{path}/step", r["wall_s_per_step"] * 1e6,
                f"{r['tokens_per_s']:.1f}tok/s,"
                f"{r['device_dispatches_per_step']:.1f}dispatches,"
                f"{r['swap_calls_per_step']:.1f}swapcalls")
    rt = report["ratios"]
    csv.add("slotpath/ratios", 0.0,
            f"{rt['device_dispatch_reduction']:.1f}x_dispatch,"
            f"{rt['tokens_per_s_speedup']:.2f}x_tokens_per_s")


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny config + regression assertions (CI fast lane)")
    ap.add_argument("--out", default=None,
                    help="write the JSON report to this path")
    args = ap.parse_args()
    p = SMOKE if args.smoke else DEFAULT
    report = bench(p)
    print(json.dumps(report, indent=2))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=2)
            f.write("\n")
    if args.smoke:
        rt = report["ratios"]
        assert rt["device_dispatch_reduction"] >= 5.0, (
            "fused slot path regressed towards per-op dispatching: "
            f"only {rt['device_dispatch_reduction']:.1f}x fewer dispatches")
        assert report["fused"]["swap_experts_per_step"] >= \
            report["fused"]["swap_calls_per_step"], "swap batching regressed"
        assert report["fused"]["host_syncs_per_step"] <= \
            report["legacy"]["host_syncs_per_step"] + 1e-9, \
            "fused path pulls more host syncs than the legacy path"
        print(f"# smoke OK: {rt['device_dispatch_reduction']:.1f}x fewer "
              f"dispatches, {rt['tokens_per_s_speedup']:.2f}x tokens/s")


if __name__ == "__main__":
    main()
