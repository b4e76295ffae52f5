#!/usr/bin/env python3
"""Bring-up check on one TPU chip: olmoe-1b-7b served end to end at its
published widths and depth, with its routed experts held in host memory.

    python3 chip_smoke.py

One process, in this order:

1. builds the model from a seed, layer by layer: every routed expert goes
   straight to a host store, the device keeps the rest plus a slot pool of
   60% of each layer's experts;
2. serves a poisson stream of 8 requests (prompts of 64 or 512 tokens, 32
   new tokens each) at batch 4 through `ServingEngine`, its MoE FFN the
   Mosaic `slot_ffn` kernel over each layer's routed experts;
3. checks the slot path's logits after whole-prompt prefill of 64 random
   tokens and two decode steps against the fully-resident reference,
   which stages each layer's 64 experts on the device only while that
   layer runs — and checks that a reference missing one expert fails the
   same tolerance.

Earlier lines report device kind, compile seconds, TTFT, tokens/s, swaps
and host syncs per step, experts streamed per FFN call, and peak device
memory. They are a bring-up record, not benchmark numbers. The last line
is one JSON object,
`{"ok": ..., "device": {"platform", "kind", "count"}}`; the exit code is 0
only if every phase passed on a TPU.
"""
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "olmoe-1b-7b"
N_REQUESTS, BATCH, MAX_NEW = 8, 4, 32
SHORT_PROMPT, LONG_PROMPT = 64, 512
CAPACITY_FRAC = 0.6
CHECK_PROMPT = 64          # tokens of the logit-check prompt
SEED = 0
# Relative RMS logit error allowed between the slot path and the reference.
# Both run the same jitted functions, and the combine sums each token's
# expert outputs in router order whatever slots hold them, so the two agree
# bit for bit (0.0 on a v5e and on the CPU). A combine that sums in slot
# order moves the logits by 7.1e-3..7.8e-3 on a v5e once the pool has
# churned, and a reference missing expert 0 in every layer by 2.2e-2: both
# fail this bound.
REL_TOL = 1e-3


def _rel_rms(a, b) -> float:
    import numpy as np
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.sqrt(np.mean((a - b) ** 2) / np.mean(b ** 2)))


class _MissingExpert:
    """A host store whose expert `e` is zero in every layer: the reference
    then runs as a slot path would that never loaded that expert."""

    def __init__(self, store, e: int):
        self.store, self.e = store, e

    def layer(self, li):
        ws = tuple(w.copy() for w in self.store.layer(li))
        for w in ws:
            w[self.e] = 0
        return ws


def _compile_seconds():
    """Running total of XLA backend compile time in this process."""
    import jax
    total = [0.0]

    def on_event(event, duration_secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            total[0] += duration_secs
    jax.monitoring.register_event_duration_secs_listener(on_event)
    return total


def _memory(dev) -> dict:
    stats = dev.memory_stats() or {}
    return {k: stats.get(k, 0) for k in ("bytes_in_use", "peak_bytes_in_use",
                                         "bytes_limit")}


def _peak_gb(dev, *pending) -> float:
    """Peak device memory so far, once `pending` arrays are computed."""
    import jax
    jax.block_until_ready(pending)
    return _memory(dev)["peak_bytes_in_use"] / 1e9


def serve_and_check(cfg, *, max_new=MAX_NEW, short_prompt=SHORT_PROMPT,
                    long_prompt=LONG_PROMPT, check_prompt=CHECK_PROMPT
                    ) -> None:
    """Every phase above for `cfg`; raises on the first failure."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.data.workloads import make_workload
    from repro.launch.serve import build_requests, slots_per_layer
    from repro.models import Model
    from repro.runtime.engine import (SlotBufferEngine, init_serving_params)
    from repro.runtime.serving import EngineServingConfig, ServingEngine

    dev = jax.devices()[0]
    compile_s = _compile_seconds()
    m = cfg.moe
    print(f"model {cfg.name}: layers={cfg.num_layers} d_model={cfg.d_model} "
          f"experts={m.num_experts} top_k={m.top_k} d_expert={m.d_expert}")

    # -- 1. build: routed experts to host, the rest to the device ----------
    t0 = time.perf_counter()
    model = Model(cfg)
    params = init_serving_params(model, jax.random.PRNGKey(SEED))
    routed = sum(w.nbytes for li in range(len(params.experts))
                 for w in params.experts.layer(li))
    dev_params = sum(x.nbytes for x in jax.tree.leaves(
        (params.top, params.layers)))
    e_shape = (m.num_experts, cfg.d_model, m.d_expert)
    assert not any(x.shape[-3:] == e_shape for x in jax.tree.leaves(
        params.layers)), "a routed expert tensor is on the device"
    specs = make_workload("mixed", N_REQUESTS, seed=SEED,
                          short_prompt=short_prompt, long_prompt=long_prompt,
                          mean_decode=max_new)
    requests = build_requests(cfg, specs, np.random.default_rng(SEED),
                              max_new)
    for r in requests:
        r.max_new_tokens = max_new
    max_seq = max(r.prompt_len for r in requests) + max_new + 8
    slots = slots_per_layer(cfg, CAPACITY_FRAC)
    sb = SlotBufferEngine(cfg, params, model, n_slots_per_layer=slots,
                          max_seq=max_seq)
    del params
    pool = sum(x.nbytes for x in jax.tree.leaves(sb.buffer))
    print(f"build: {time.perf_counter() - t0:.1f}s; routed experts "
          f"{routed / 1e9:.2f} GB in host memory, none on the device; "
          f"device params {dev_params / 1e9:.2f} GB; slot pool "
          f"{slots}/layer = {pool / 1e9:.2f} GB; memory {_memory(dev)}")

    # -- 2. serve the request stream --------------------------------------
    srv = ServingEngine(sb, EngineServingConfig(max_batch=BATCH))
    c0, t0 = compile_s[0], time.perf_counter()
    rep = srv.serve(requests)
    wall = time.perf_counter() - t0
    s = rep.summary()
    for r in requests:
        assert len(r.output) == max_new, (r.request_id, len(r.output))
        assert all(0 <= t < cfg.vocab_size for t in r.output)
    st = sb.stats
    print(f"serve: {len(requests)} requests (prompts "
          f"{sorted(r.prompt_len for r in requests)}) x {max_new} tokens, "
          f"batch {BATCH}, all complete in {wall:.1f}s, of which compile "
          f"{compile_s[0] - c0:.1f}s; ttft_p50="
          f"{s['ttft_p50_s']:.3f}s ttft_p99={s['ttft_p99_s']:.3f}s "
          f"tokens/s={s['throughput_tok_s']:.1f}; per step: swaps "
          f"{st.swap_experts / max(st.steps, 1):.1f} experts in "
          f"{st.swap_calls / max(st.steps, 1):.2f} writes, host syncs "
          f"{st.host_syncs / max(st.steps, 1):.1f} ({st.steps} steps); "
          f"experts streamed per FFN call "
          f"{st.ffn_experts / max(st.ffn_calls, 1):.1f} of {m.num_experts} "
          f"({st.ffn_calls} calls, pool {sb.n_slots} slots); "
          f"{st.swap_experts * sb._expert_nbytes / 1e9:.1f} GB swapped in; "
          f"host->device rate estimate C_s "
          f"{sb.controller.bandwidth_est / 1e9:.2f} GB/s; "
          f"peak memory so far {_peak_gb(dev):.2f} GB")

    # -- 3. logits: slot path vs the fully-resident reference --------------
    # uniform tokens: the served prompts are topic-anchored, so they can
    # leave expert 0 unrouted, and then the missing-expert control is void
    prompt = jnp.asarray(np.random.default_rng(SEED).integers(
        0, cfg.vocab_size, (1, check_prompt)), jnp.int32)
    want, ref_state = sb.reference_prefill(prompt)
    peaks = {"reference": _peak_gb(dev, want)}
    # printed, not held to REL_TOL: XLA reduces a chunk's dots in another
    # order than the whole prompt's, and bf16 rounding compounds that with
    # depth (1.7e-2 in bf16 at 16 layers on a v5e, 1.1e-2..7.6e-2 on the
    # CPU). The chunk path itself is held to 1e-5 in f32 at these attention
    # widths and this depth, where only the order differs (1.2e-6 on the
    # CPU): test_chunked_prefill_matches_monolithic_at_published_depth
    chunked = _rel_rms(sb.prefill_chunked(prompt)[0], want)
    peaks["chunked"] = _peak_gb(dev)
    got, state = sb.prefill(prompt)
    peaks["whole-prompt"] = _peak_gb(dev, got)
    errs = [_rel_rms(got, want)]
    for _ in range(2):
        tok = jnp.argmax(got, -1).astype(jnp.int32)
        got, state = sb.decode_step(tok, state)
        want, ref_state = sb.reference_decode_step(tok, ref_state)
        errs.append(_rel_rms(got, want))
    real = sb.experts
    sb.experts = _MissingExpert(real, 0)
    try:
        dropped, _ = sb.reference_prefill(prompt)
    finally:
        sb.experts = real
    drop_err = _rel_rms(dropped, sb.reference_prefill(prompt)[0])
    for lo in (got, want, dropped):
        assert np.isfinite(np.asarray(lo)).all()
    print(f"logits vs reference (relative RMS): prefill {errs[0]:.2e}, "
          f"decode {errs[1]:.2e}, {errs[2]:.2e}; tolerance {REL_TOL:.0e}; "
          f"reference missing expert 0: {drop_err:.2e}; chunked prefill "
          f"{chunked:.2e}; peak memory after each prefill (GB): "
          + ", ".join(f"{k} {v:.2f}" for k, v in peaks.items()))
    assert max(errs) <= REL_TOL, f"slot path off the reference: {errs}"
    assert drop_err > REL_TOL, "a missing expert passes the tolerance"

    mem = _memory(dev)
    print(f"peak device memory {mem['peak_bytes_in_use'] / 1e9:.2f} GB of "
          f"{mem['bytes_limit'] / 1e9:.2f} GB; routed experts "
          f"{routed / 1e9:.2f} GB; total compile {compile_s[0]:.1f}s")
    if mem["bytes_limit"]:
        assert mem["peak_bytes_in_use"] < mem["bytes_limit"]


def main() -> int:
    ok, device = False, None
    try:
        import jax
        d = jax.devices()[0]
        device = {"platform": d.platform, "kind": d.device_kind,
                  "count": len(jax.devices())}
        print(f"device: {device}")
        if d.platform != "tpu":
            raise RuntimeError(f"no TPU: JAX runs on {d.platform!r}; this "
                               "check runs on the chip only")
        from repro.compile_cache import enable_compile_cache
        from repro.configs.registry import get_config
        cache = enable_compile_cache()
        n = len(os.listdir(cache)) if os.path.isdir(cache) else 0
        # compile seconds below are cold only where the cache starts empty
        print(f"compile cache: {cache}, {n} entries at start")
        serve_and_check(get_config(ARCH))
        ok = True
    except Exception:
        traceback.print_exc()
    print(json.dumps({"ok": ok, "device": device}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
