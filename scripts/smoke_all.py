"""Dev harness: forward + prefill + decode every smoke config, then a
fault lane — brownout-plan serving through the simulator mirror must
complete every request with retries firing (graceful degradation) — and
a tier lane — serving with a budgeted host staging tier must complete
every request while reporting disk->host promotion health."""
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import ARCH_IDS, get_smoke_config
from repro.models import Model

B, T = 2, 16


def run(arch: str) -> None:
    cfg = get_smoke_config(arch)
    model = Model(cfg)
    key = jax.random.PRNGKey(0)
    params = model.init(key)
    n = sum(x.size for x in jax.tree.leaves(params))
    tokens = jax.random.randint(key, (B, T), 0, cfg.vocab_size)
    enc_out = None
    if cfg.is_encoder_decoder:
        frames = jax.random.normal(key, (B, 24, cfg.d_model), jnp.bfloat16)
        enc_out = model.encode(params, frames)
    if cfg.uses_input_embeds:
        embeds = jax.random.normal(key, (B, T, cfg.d_model), jnp.bfloat16) * 0.02
        h = model.forward(params, embeds=embeds, enc_out=enc_out)
        logits_p, cache = model.prefill(params, embeds=embeds, max_seq=T + 8,
                                        enc_out=enc_out)
    else:
        h = model.forward(params, tokens, enc_out=enc_out)
        logits_p, cache = model.prefill(params, tokens, max_seq=T + 8,
                                        enc_out=enc_out)
    assert h.shape == (B, T, cfg.d_model), h.shape
    logits_f = model.logits(params, h[:, -1])
    assert jnp.isfinite(logits_f).all(), "forward logits NaN"
    assert jnp.isfinite(logits_p).all(), "prefill logits NaN"
    # prefill last-token logits must match forward last-token logits
    diff = jnp.max(jnp.abs(logits_f - logits_p))
    # decode one token, compare against forward of extended sequence
    nxt = jnp.argmax(logits_p, -1).astype(jnp.int32)
    logits_d, cache = model.decode_step(params, nxt, cache)
    assert jnp.isfinite(logits_d).all(), "decode logits NaN"
    if not cfg.uses_input_embeds:
        ext = jnp.concatenate([tokens, nxt[:, None]], axis=1)
        h2 = model.forward(params, ext, enc_out=enc_out)
        logits_ref = model.logits(params, h2[:, -1])
        ddiff = jnp.max(jnp.abs(logits_d - logits_ref))
    else:
        ddiff = -1.0
    print(f"{arch:24s} params={n/1e6:7.2f}M prefill_diff={diff:.4f} "
          f"decode_diff={float(ddiff):.4f}")


def run_fault_lane() -> None:
    """Brownout-plan serving on the simulator: every request must finish
    its token budget (no hangs) and retries must fire."""
    from repro.core.coordinator import ablation
    from repro.core.faults import FaultPlan
    from repro.simulator.events import SimSpec, StepTrace
    from repro.simulator.hardware import HardwareSpec
    from repro.simulator.serving import (ServingConfig, ServingRequest,
                                         ServingWorkload, simulate_serving)
    L, M, top_k, n_new = 2, 8, 2, 10
    reqs = []
    for rid in range(6):
        steps = []
        for si in range(n_new):
            assigns = [np.array([[(rid + si + li + j) % M]
                                 for j in range(top_k)])
                       for li in range(L)]
            steps.append(StepTrace(si, np.arange(4), assigns,
                                   np.zeros((L, 4), np.float32)))
        reqs.append(ServingRequest(prompt_len=16, max_new_tokens=n_new,
                                   steps=steps, request_id=rid))
    wl = ServingWorkload(L, M, top_k,
                         [np.zeros((4, M), np.float32) for _ in range(L)],
                         reqs, name="faults")
    hw = HardwareSpec("faultlane", host_bw=1e8, flops=1e15, hbm_bw=1e12,
                      mem_cap=1e9)
    spec = SimSpec(expert_bytes=1e5, layer_time_s=1e-3, capacity_experts=6)
    pol = ablation("faults", prefetch=True, adaptive_s=False,
                   two_level_lru=False, cache_aware=False,
                   blocking_swap_out=False, protect_early_layers=False)
    rep = simulate_serving(wl, spec, hw, pol, cfg=ServingConfig(
        max_batch=4, prefill_chunk=16, admission_cap=False,
        fault_plan=FaultPlan.brownout_preset(seed=0), retry_max=3))
    assert all(m.n_tokens == n_new for m in rep.requests), "request truncated"
    assert rep.n_retries > 0, "brownout plan fired no retries"
    print(f"fault lane: {len(rep.requests)} requests complete under "
          f"brownout (failures={rep.n_link_failures} "
          f"retries={rep.n_retries} degraded_steps={rep.n_degraded_steps})")


def run_tiers_lane() -> None:
    """Serving through the budgeted host staging tier: every request must
    finish, host-tier activity must show up, and the new tier health
    fields must be present in the ServingReport summary."""
    from repro.core.coordinator import ablation
    from repro.simulator.events import SimSpec, StepTrace
    from repro.simulator.hardware import HardwareSpec
    from repro.simulator.serving import (ServingConfig, ServingRequest,
                                         ServingWorkload, simulate_serving)
    L, M, top_k, n_new = 2, 8, 2, 10
    reqs = []
    for rid in range(6):
        steps = []
        for si in range(n_new):
            assigns = [np.array([[(rid + si + li + j) % M]
                                 for j in range(top_k)])
                       for li in range(L)]
            steps.append(StepTrace(si, np.arange(4), assigns,
                                   np.zeros((L, 4), np.float32)))
        reqs.append(ServingRequest(prompt_len=16, max_new_tokens=n_new,
                                   steps=steps, request_id=rid))
    wl = ServingWorkload(L, M, top_k,
                         [np.zeros((4, M), np.float32) for _ in range(L)],
                         reqs, name="tiers")
    hw = HardwareSpec("tierlane", host_bw=1e8, flops=1e15, hbm_bw=1e12,
                      mem_cap=1e9)
    spec = SimSpec(expert_bytes=1e5, layer_time_s=1e-3, capacity_experts=6)
    pol = ablation("tiers", prefetch=True, adaptive_s=False,
                   two_level_lru=False, cache_aware=False,
                   blocking_swap_out=False, protect_early_layers=False)
    rep = simulate_serving(wl, spec, hw, pol, cfg=ServingConfig(
        max_batch=4, prefill_chunk=16, admission_cap=False,
        host_budget_frac=0.5, disk_bandwidth=1e9, disk_prefetch=True))
    s = rep.summary()
    assert all(m.n_tokens == n_new for m in rep.requests), "request truncated"
    for k in ("n_host_hits", "n_host_misses", "disk_stall_s"):
        assert k in s, f"ServingReport summary missing tier field {k}"
    assert s["n_host_hits"] + s["n_host_misses"] > 0, "host tier never hit"
    print(f"tiers lane: {len(rep.requests)} requests complete through the "
          f"host staging tier (host_hits={s['n_host_hits']} "
          f"host_misses={s['n_host_misses']} "
          f"disk_stall={s['disk_stall_s'] * 1e3:.3f}ms)")


def run_integrity_lane() -> None:
    """Serving under seeded corruption chaos with verification on: every
    request must finish, corruption must be detected AND healed, and the
    integrity health fields must be present in the ServingReport summary."""
    from repro.core.coordinator import ablation
    from repro.core.faults import FaultPlan
    from repro.simulator.events import SimSpec, StepTrace
    from repro.simulator.hardware import HardwareSpec
    from repro.simulator.serving import (ServingConfig, ServingRequest,
                                         ServingWorkload, simulate_serving)
    L, M, top_k, n_new = 2, 8, 2, 10
    reqs = []
    for rid in range(6):
        steps = []
        for si in range(n_new):
            assigns = [np.array([[(rid + si + li + j) % M]
                                 for j in range(top_k)])
                       for li in range(L)]
            steps.append(StepTrace(si, np.arange(4), assigns,
                                   np.zeros((L, 4), np.float32)))
        reqs.append(ServingRequest(prompt_len=16, max_new_tokens=n_new,
                                   steps=steps, request_id=rid))
    wl = ServingWorkload(L, M, top_k,
                         [np.zeros((4, M), np.float32) for _ in range(L)],
                         reqs, name="integrity")
    hw = HardwareSpec("integlane", host_bw=1e8, flops=1e15, hbm_bw=1e12,
                      mem_cap=1e9)
    spec = SimSpec(expert_bytes=1e5, layer_time_s=1e-3, capacity_experts=6)
    pol = ablation("integrity", prefetch=True, adaptive_s=False,
                   two_level_lru=False, cache_aware=False,
                   blocking_swap_out=False, protect_early_layers=False)
    rep = simulate_serving(wl, spec, hw, pol, cfg=ServingConfig(
        max_batch=4, prefill_chunk=16, admission_cap=False,
        host_budget_frac=0.5, disk_bandwidth=1e9, disk_prefetch=True,
        fault_plan=FaultPlan.corrupt_flaky(seed=0), retry_max=3,
        verify="scrub", scrub_budget=2, refetch_max=3))
    s = rep.summary()
    assert all(m.n_tokens == n_new for m in rep.requests), "request truncated"
    for k in ("n_corrupt_detected", "n_requarantined", "n_scrubbed",
              "n_quarantined_experts"):
        assert k in s, f"ServingReport summary missing health field {k}"
    assert s["n_corrupt_detected"] > 0, "corrupt_flaky plan injected nothing"
    assert s["n_requarantined"] > 0, "no corrupt promotion ever healed"
    print(f"integrity lane: {len(rep.requests)} requests complete under "
          f"corruption chaos (detected={s['n_corrupt_detected']} "
          f"healed={s['n_requarantined']} scrubbed={s['n_scrubbed']} "
          f"quarantined={s['n_quarantined_experts']})")


if __name__ == "__main__":
    import traceback
    archs = sys.argv[1:] or ARCH_IDS
    failed = []
    for a in archs:
        try:
            run(a)
        except Exception as e:
            print(f"{a:24s} FAILED: {type(e).__name__}: {e}")
            traceback.print_exc()
            failed.append(a)
    run_fault_lane()
    run_tiers_lane()
    run_integrity_lane()
    if failed:
        sys.exit(f"{len(failed)} architecture(s) failed: {', '.join(failed)}")
