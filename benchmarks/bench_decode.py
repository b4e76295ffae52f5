"""Incremental-decode benchmark: KV-cached `decode_step` vs re-running the
full slot-path `forward()` over the whole growing sequence every token.

Two claims are measured on a reduced MoE model with a slot buffer smaller
than the expert population (so both paths produce real swap traffic):

1. decode tokens/s far above per-step full `forward()` — the O(1)-attention
   decode step vs the O(T^2) re-forward;
2. host syncs per decode step DROP as the prefetch horizon S grows — the
   speculative window executes S MoE layers per blocking (S+1, E) mask pull,
   verified (and replayed on mispredict) at the next sync, so outputs stay
   bit-exact versus the fully-resident oracle.

Writes BENCH_decode.json and — in ``--smoke`` mode — asserts the decode
speedup (>=2x tokens/s) and the sync collapse (host_syncs/step strictly
below the MoE layer count at S=2) so the CI fast lane catches regressions.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir, "src"))

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.configs.base import reduce_config            # noqa: E402
from repro.configs.registry import get_config           # noqa: E402
from repro.models import Model                          # noqa: E402
from repro.runtime.engine import SlotBufferEngine       # noqa: E402

DEFAULT = dict(layers=4, d_model=64, heads=4, kv_heads=4, d_ff=128,
               vocab=512, experts=8, top_k=2, d_expert=32,
               n_slots_per_layer=6, batch=2, prompt=96, steps=16, warmup=3,
               repeats=3, horizons=(0, 1, 2, 4))
SMOKE = dict(DEFAULT, steps=8, warmup=2, repeats=3, horizons=(0, 2))


def _bench_config(p):
    return reduce_config(get_config("olmoe-1b-7b"), layers=p["layers"],
                         d_model=p["d_model"], heads=p["heads"],
                         kv_heads=p["kv_heads"], d_ff=p["d_ff"],
                         vocab=p["vocab"], experts=p["experts"],
                         top_k=p["top_k"], d_expert=p["d_expert"])


def _prompt(p, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, p["vocab"], (p["batch"], p["prompt"]),
                        dtype=np.int32)


def _max_seq(p):
    return p["prompt"] + p["warmup"] + p["repeats"] * p["steps"] + 8


def _engine(cfg, model, params, p, step_size=None):
    return SlotBufferEngine(cfg, params, model,
                            n_slots_per_layer=p["n_slots_per_layer"],
                            max_seq=_max_seq(p), step_size=step_size)


def bench_full_forward(cfg, model, params, p) -> dict:
    """Baseline: every new token re-runs the whole-sequence slot-path
    forward (O(T^2) attention, no KV cache). One full greedy pass warms the
    jit cache for every sequence length; the best of `repeats` subsequent
    passes is reported (the machine-noise floor)."""
    sb = _engine(cfg, model, params, p)
    prompt = jnp.asarray(_prompt(p))
    lf = sb._logits_fn()

    def run():
        seq = prompt
        for _ in range(p["steps"]):
            x = sb.forward(seq)
            tok = jnp.argmax(lf(sb.params, x), -1).astype(jnp.int32)
            seq = jnp.concatenate([seq, tok[:, None]], axis=1)
        return seq

    run()                                     # compile all lengths
    sb.stats.reset()
    wall = None
    for _ in range(p["repeats"]):
        t0 = time.perf_counter()
        run()
        dt = time.perf_counter() - t0
        wall = dt if wall is None else min(wall, dt)
    st = sb.stats
    steps = p["steps"] * p["repeats"]         # stats span ALL repeats
    tokens = p["steps"] * p["batch"]
    return {
        "tokens_per_s": tokens / wall,
        "wall_s_per_step": wall / p["steps"],
        "host_syncs_per_step": st.host_syncs / steps,
        "jit_calls_per_step": st.jit_calls / steps,
        "swap_experts_per_step": st.swap_experts / steps,
    }


def bench_decode(cfg, model, params, p, step_size) -> dict:
    """prefill() once, then `repeats` measured windows of KV-cached
    decode_step()s (best window reported; counters span all windows)."""
    sb = _engine(cfg, model, params, p, step_size=step_size)
    logits, state = sb.prefill(_prompt(p))
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    for _ in range(p["warmup"]):
        logits, state = sb.decode_step(tok, state)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
    sb.stats.reset()
    wall = None
    for _ in range(p["repeats"]):
        t0 = time.perf_counter()
        for _ in range(p["steps"]):
            logits, state = sb.decode_step(tok, state)
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
        jax.block_until_ready(logits)
        dt = time.perf_counter() - t0
        wall = dt if wall is None else min(wall, dt)
    st = sb.stats
    steps = p["steps"] * p["repeats"]
    tokens = p["steps"] * p["batch"]
    out = {
        "tokens_per_s": tokens / wall,
        "wall_s_per_step": wall / p["steps"],
        "host_syncs_per_step": st.host_syncs / steps,
        "jit_calls_per_step": st.jit_calls / steps,
        "swap_experts_per_step": st.swap_experts / steps,
        "prefetched_per_step": st.prefetched / steps,
        "prefetch_hits_per_step": st.prefetch_hits / steps,
        "demand_misses_per_step": st.demand_misses / steps,
        "spec_layers_per_step": st.spec_layers / steps,
        "replays_per_step": st.replays / steps,
    }
    if step_size is None:
        out["controller"] = {k: v for k, v in sb.controller.snapshot().items()
                             if k in ("s", "s_history")}
    return out


def check_oracle_bitexact(cfg, model, params, p) -> bool:
    """Eviction-churn config (slots << experts): per-step decode logits must
    match the fully-resident oracle bitwise, replays included."""
    churn = dict(p, n_slots_per_layer=max(2, p["experts"] // 3))
    sb = _engine(cfg, model, params, churn, step_size=2)
    prompt = _prompt(p)
    lo, st = sb.prefill(prompt)
    lr, sr = sb.reference_prefill(prompt)
    if float(jnp.max(jnp.abs(lo - lr))) != 0.0:
        return False
    tok = jnp.argmax(lo, -1).astype(jnp.int32)
    for _ in range(min(p["steps"], 8)):
        lo, st = sb.decode_step(tok, st)
        lr, sr = sb.reference_decode_step(tok, sr)
        if float(jnp.max(jnp.abs(lo - lr))) != 0.0:
            return False
        tok = jnp.argmax(lo, -1).astype(jnp.int32)
    return True


def bench(p) -> dict:
    cfg = _bench_config(p)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    full = bench_full_forward(cfg, model, params, p)
    decode = {}
    for s in p["horizons"]:
        decode[f"S={s}"] = bench_decode(cfg, model, params, p, step_size=s)
    decode["adaptive"] = bench_decode(cfg, model, params, p, step_size=None)
    best = max(v["tokens_per_s"] for v in decode.values())
    s_ref = f"S={p['horizons'][-1]}"
    report = {
        "config": {k: v for k, v in p.items() if k != "horizons"},
        "n_moe_layers": p["layers"],
        "full_forward": full,
        "decode": decode,
        "ratios": {
            "decode_speedup_vs_full_forward":
                best / max(full["tokens_per_s"], 1e-9),
            "host_sync_reduction_vs_per_layer":
                p["layers"] / max(decode[s_ref]["host_syncs_per_step"], 1e-9),
        },
        "oracle_bitexact_under_churn":
            check_oracle_bitexact(cfg, model, params, p),
    }
    return report


def run(csv) -> None:
    """benchmarks/run.py entry: smoke-scale sweep, CSV rows only."""
    report = bench(SMOKE)
    f = report["full_forward"]
    csv.add("decode/full_forward/step", f["wall_s_per_step"] * 1e6,
            f"{f['tokens_per_s']:.1f}tok/s,{f['host_syncs_per_step']:.1f}syncs")
    for name, r in report["decode"].items():
        csv.add(f"decode/{name}/step", r["wall_s_per_step"] * 1e6,
                f"{r['tokens_per_s']:.1f}tok/s,"
                f"{r['host_syncs_per_step']:.2f}syncs,"
                f"{r['replays_per_step']:.2f}replays")
    rt = report["ratios"]
    csv.add("decode/ratios", 0.0,
            f"{rt['decode_speedup_vs_full_forward']:.2f}x_tokens_per_s,"
            f"{rt['host_sync_reduction_vs_per_layer']:.1f}x_fewer_syncs,"
            f"bitexact={report['oracle_bitexact_under_churn']}")


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
    assert report["oracle_bitexact_under_churn"], \
        "slot-path decode diverged from the fully-resident oracle"
    if args.smoke:
        n_moe = report["n_moe_layers"]
        s2 = report["decode"]["S=2"]
        speedup = report["ratios"]["decode_speedup_vs_full_forward"]
        if speedup < 2.0:
            # wall-clock gate on a shared CI runner: re-measure once (warm
            # jit caches, so this is cheap) before declaring a regression
            report = bench(p)
            speedup = report["ratios"]["decode_speedup_vs_full_forward"]
            s2 = report["decode"]["S=2"]
        assert speedup >= 2.0, (
            "KV-cached decode no longer beats full-forward re-run: "
            f"only {speedup:.2f}x tokens/s")
        assert s2["host_syncs_per_step"] < n_moe, (
            "speculative horizon no longer collapses host syncs: "
            f"{s2['host_syncs_per_step']:.2f}/step vs {n_moe} MoE layers")
        print(f"# smoke OK: {speedup:.2f}x tokens/s over full forward, "
              f"{s2['host_syncs_per_step']:.2f} host syncs/step "
              f"({n_moe} MoE layers)")


if __name__ == "__main__":
    main()
