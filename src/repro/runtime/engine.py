"""Inference engine: real JAX execution with routing-trace collection.

The engine runs reduced-config MoE models on the host device, capturing per
MoE layer: the router's per-token expert assignments, pre-gate logits, and
pooled hidden states. These *real* routing traces drive (a) predictor
training (`core.trace`/`core.predictor`) and (b) the latency simulator
(`simulator.events`), which replays them under baseline/ExpertFlow policies
with platform timing constants.

It also provides `SlotBufferEngine`: the MoE forward computed through the
bounded device slot buffer (`core.expert_buffer` + `models.moe.moe_slotbuf`)
with the host-side TwoLevelLRU controlling swaps. Its one path jits
per-layer compute once, routes on device (pulling only a small expert mask
to host), batches every layer's swap-ins into one donated device write, and
issues predicted next-layer swap-ins BEFORE dispatching the current layer's
FFN so JAX async dispatch overlaps transfer with compute — while staying
bit-exact versus the fully-resident model computed through the same jitted
functions whenever the runtime keeps the working set resident.

`prefill`/`decode_step`/`generate` add KV-cached incremental decode: O(1)
attention per step, an adaptive multi-layer prefetch horizon S (pre-gating
the next S routers in one dispatch, ONE (S+1, E) mask pull per sync, and
speculative execution of the S-layer window with verify-and-replay), with a
`core.step_size.StepSizeController` closing the paper's stall/overfetch
feedback loop from real runtime signals.
"""
from __future__ import annotations

import dataclasses
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.cache import TwoLevelLRU
from repro.core.cache_aware import residency_logit_bias
from repro.core.expert_buffer import (HostExpertStore, SlotTable, is_pinned,
                                      make_buffer, swap_in_many)
from repro.core.faults import FaultInjector, FaultPlan, StepWatchdog
from repro.core.prefetcher import Prefetcher, TransferLink
from repro.core.step_size import StepSizeController
from repro.core.trace import TraceLog
from repro.models import moe as moe_mod
from repro.models.layers import rms_norm
from repro.models.transformer import (LayerSpec, Model, init_layer,
                                      init_layer_cache, layer_decode,
                                      layer_forward, layer_prefill,
                                      layer_prefill_chunk, split_ffn_params)
from repro.runtime.instrument import Dispatcher, named_jit, span
from repro.runtime.sampler import sample
from repro.simulator.events import RoutingTrace, StepTrace


def _all_specs(model: Model) -> List[LayerSpec]:
    specs = list(model.prefix)
    for _ in range(model.num_units):
        specs.extend(model.unit)
    specs.extend(model.tail)
    return specs


def _layer_params(model: Model, params, i: int):
    """Per-layer params for absolute depth i (unstacks unit params)."""
    np_ = len(model.prefix)
    nu = len(model.unit)
    if i < np_:
        return params["prefix"][i]
    j = i - np_
    if j < model.num_units * nu:
        u, k = divmod(j, nu)
        return jax.tree.map(lambda x: x[u], params["unit"][k])
    return params["tail"][j - model.num_units * nu]


ROUTED_EXPERT_KEYS = ("w_gate", "w_up", "w_down")


class ServingParams(NamedTuple):
    """Parameters as the slot-path runtime holds them: everything but the
    routed experts on the device, the routed experts in host memory only.
    The device then keeps no expert outside the bounded slot pool."""
    top: Dict[str, Any]              # embed / final_norm / lm_head
    layers: List[Dict[str, Any]]     # per absolute layer, no routed experts
    experts: HostExpertStore         # every MoE layer's routed experts


def _strip_routed(p):
    """A layer's params without its routed expert weights."""
    if "moe" not in p:
        return p
    moe = {k: v for k, v in p["moe"].items() if k not in ROUTED_EXPERT_KEYS}
    return {**p, "moe": moe}


def split_params(model: Model, params) -> ServingParams:
    """`ServingParams` from a full stacked tree (`Model.init`)."""
    top = {k: params[k] for k in ("embed", "final_norm", "lm_head")
           if k in params}
    layers, store = [], HostExpertStore()
    for i, s in enumerate(_all_specs(model)):
        p = _layer_params(model, params, i)
        if s.is_moe:
            store.add_layer(len(store), *(p["moe"][k]
                                          for k in ROUTED_EXPERT_KEYS))
        layers.append(_strip_routed(p))
    return ServingParams(top, layers, store)


def init_serving_params(model: Model, key) -> ServingParams:
    """`ServingParams` built layer by layer, equal to
    `split_params(model, model.init(key))` but with at most one layer's
    routed experts on the device at a time: each is generated there and
    copied into the pinned host store before the next."""
    cfg, dtype = model.cfg, model.dtype
    fns: Dict[Any, Any] = {}

    def layer_fn(spec: LayerSpec, routed: bool):
        ck = (LayerSpec(spec.kind, spec.window, spec.is_moe, 0), routed)
        if ck not in fns:
            def fn(k):
                p = init_layer(k, cfg, ck[0], dtype)
                if routed:
                    return tuple(p["moe"][n] for n in ROUTED_EXPERT_KEYS)
                return _strip_routed(p)
            fns[ck] = jax.jit(fn)
        return fns[ck]

    layers, store = [], HostExpertStore()
    for i, spec in enumerate(_all_specs(model)):
        k = model.layer_key(key, i)
        layers.append(layer_fn(spec, False)(k))
        if spec.is_moe:
            store.add_layer(len(store), *layer_fn(spec, True)(k))
    return ServingParams(jax.jit(model.init_top)(key), layers, store)


class Engine:
    """Single-model inference engine with trace collection."""

    def __init__(self, cfg: ModelConfig, key: Optional[jax.Array] = None,
                 max_seq: int = 512):
        assert cfg.moe is not None, "Engine requires an MoE config"
        self.cfg = cfg
        self.model = Model(cfg)
        self.max_seq = max_seq
        key = key if key is not None else jax.random.PRNGKey(0)
        self.params = self.model.init(key)
        self.specs = _all_specs(self.model)
        self.moe_layer_ids = [i for i, s in enumerate(self.specs) if s.is_moe]
        self._prefill = jax.jit(self._prefill_collect,
                                static_argnames=("max_seq",))
        self._decode = jax.jit(self._decode_collect)

    # -- router weights for pre-gating ----------------------------------------
    def routers(self) -> List[np.ndarray]:
        out = []
        for i in self.moe_layer_ids:
            p = _layer_params(self.model, self.params, i)
            out.append(np.asarray(p["moe"]["router"], np.float32))
        return out

    # -- jitted bodies ---------------------------------------------------------
    def _prefill_collect(self, params, tokens, max_seq: int):
        cfg = self.cfg
        model = self.model
        x = model.embed(params, tokens)
        B, T = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
        from repro.models.transformer import layer_prefill

        routers, hiddens, caches = [], [], []
        for i, spec in enumerate(self.specs):
            p = _layer_params(model, params, i)
            sink: list = []
            x, c = layer_prefill(p, cfg, spec, x, positions, max_seq,
                                 router_sink=sink)
            caches.append(c)
            if spec.is_moe:
                r = sink[0]
                routers.append((r.expert_ids, r.probs))
                hiddens.append(jnp.mean(x.astype(jnp.float32), axis=(0, 1)))
        logits = model.logits(params, x[:, -1])
        return logits, caches, routers, hiddens

    def _decode_collect(self, params, token, caches, cache_len):
        cfg = self.cfg
        model = self.model
        pos = jnp.broadcast_to(jnp.asarray(cache_len).reshape(-1, 1),
                               (token.shape[0], 1))
        x = model.embed(params, token[:, None], positions=pos)
        routers, hiddens = [], []
        new_caches = []
        for i, spec in enumerate(self.specs):
            p = _layer_params(model, params, i)
            sink: list = []
            x, c = layer_decode_collect(p, cfg, spec, x, caches[i], cache_len,
                                        sink)
            new_caches.append(c)
            if spec.is_moe:
                r = sink[0]
                routers.append((r.expert_ids, r.probs))
                hiddens.append(jnp.mean(x.astype(jnp.float32), axis=(0, 1)))
        logits = model.logits(params, x[:, 0])
        return logits, new_caches, routers, hiddens

    # -- public API ---------------------------------------------------------
    def generate(self, tokens: np.ndarray, n_steps: int,
                 temperature: float = 0.0, collect: bool = True,
                 fixed_s_for_log: int = 2,
                 key: Optional[jax.Array] = None
                 ) -> Tuple[np.ndarray, RoutingTrace, TraceLog]:
        """tokens: (B, T). Returns (generated (B, n_steps), trace, log)."""
        cfg = self.cfg
        m = cfg.moe
        tokens = jnp.asarray(tokens, jnp.int32)
        B, T = tokens.shape
        key = key if key is not None else jax.random.PRNGKey(17)
        logits, caches, routers, hiddens = self._prefill(
            self.params, tokens, max_seq=self.max_seq)

        trace = RoutingTrace(model=cfg.name,
                             num_moe_layers=len(self.moe_layer_ids),
                             num_experts=m.num_experts, top_k=m.top_k,
                             routers=self.routers())
        log = TraceLog()
        token_list = np.asarray(tokens).reshape(-1)
        embeds = np.asarray(
            self.model.embed(self.params, tokens).astype(jnp.float32)
        ).reshape(B * T, -1)

        def record_step(step_idx, routers_out, hiddens_out, embeddings=None):
            assigns = [np.asarray(r[0]) for r in routers_out]
            probs = [np.asarray(r[1]) for r in routers_out]
            hp = np.stack([np.asarray(h) for h in hiddens_out])
            trace.steps.append(StepTrace(step_idx, token_list, assigns, hp,
                                         embeddings))
            if collect:
                for li, a in enumerate(assigns):
                    actual = sorted({int(e) for e in a.reshape(-1)})
                    # LAST 64 ids: the window must slide with decoding, or
                    # prompts >= 64 ids keep the features frozen at the
                    # prompt prefix forever
                    log.add(token_ids=tuple(int(t)
                                            for t in token_list[-64:]),
                            layer_idx=li,
                            predicted_experts=(),
                            actual_experts=tuple(actual),
                            step_size=fixed_s_for_log,
                            request_id=step_idx,
                            pregate_probs=tuple(
                                float(p) for p in probs[li].mean(0)[:64]))

        record_step(0, routers, hiddens, embeds)
        out = []
        cache_len = jnp.asarray(T, jnp.int32)
        tok = sample(logits, key, temperature)
        out.append(np.asarray(tok))
        # decoded tokens extend the recorded context: each step's TraceLog /
        # StepTrace entry must see the ids the model actually conditioned on,
        # not the frozen prompt (predictor features drift otherwise)
        token_list = np.concatenate([token_list,
                                     np.asarray(tok).reshape(-1)])
        for step in range(1, n_steps):
            logits, caches, routers, hiddens = self._decode(
                self.params, tok, caches, cache_len)
            cache_len = cache_len + 1
            record_step(step, routers, hiddens)
            key = jax.random.fold_in(key, step)
            tok = sample(logits, key, temperature)
            out.append(np.asarray(tok))
            token_list = np.concatenate([token_list,
                                         np.asarray(tok).reshape(-1)])
        return np.stack(out, axis=1), trace, log


def layer_decode_collect(p, cfg, spec, x, cache, cache_len, sink):
    """layer_decode variant that captures the MoE router output."""
    if not spec.is_moe:
        return layer_decode(p, cfg, spec, x, cache, cache_len)
    # replicate layer_decode but keep the RouterOutput
    from repro.models.transformer import _zc
    B = x.shape[0]
    x, new_cache = _attn_only_decode(p, cfg, spec, x, cache, cache_len)
    h2 = rms_norm(x, p["ffn_norm"], cfg.norm_eps, zero_centered=_zc(cfg))
    flat = h2.reshape(B, -1)
    out, r = moe_mod.moe_grouped(p["moe"], flat, cfg.moe,
                                 capacity=B * cfg.moe.top_k)
    sink.append(r)
    ff = out.reshape(B, 1, -1)
    if "post_ffn_norm" in p:
        ff = rms_norm(ff, p["post_ffn_norm"], cfg.norm_eps, zero_centered=_zc(cfg))
    return x + ff, new_cache


def _attn_only_decode(p, cfg, spec, x, cache, cache_len):
    """The attention/mixing part of layer_decode (FFN stripped)."""
    stripped, spec_no_ffn = split_ffn_params(p, spec)
    return layer_decode(stripped, cfg, spec_no_ffn, x, cache, cache_len)


def _route_ffn_entry(p, cfg, x, active=None, rbias=None):
    """Shared FFN-entry block of the jitted pre fns: ffn-norm the attention
    output, flatten, route on device, build the (E,) needed mask.
    Returns (flat, RouterOutput, needed).

    `active` (continuous batching): (B,) bool — the needed mask is the UNION
    over active rows only, so idle slots' garbage rows cannot demand swaps.
    All rows still flow through the FFN; inactive rows' outputs are ignored
    by the caller (and their non-resident experts fall to the dead sentinel
    slot inside `moe_slotbuf`).

    `rbias` (§3.4 cache-aware routing): optional (E,) additive router-logit
    bias (0 for resident experts, -strength otherwise; see
    `core.cache_aware.residency_logit_bias`). Passing None traces the exact
    pre-bias graph, so engines with the perturbation disabled stay bit-exact
    with builds that predate it."""
    from repro.models.transformer import _zc
    h2 = rms_norm(x, p["ffn_norm"], cfg.norm_eps, zero_centered=_zc(cfg))
    flat = h2.reshape(-1, x.shape[-1])
    r = moe_mod.route(p["moe"]["router"], flat, cfg.moe.top_k,
                      cfg.moe.router_norm_topk, logit_bias=rbias)
    E = cfg.moe.num_experts
    needed = jnp.zeros((E,), jnp.bool_)
    ids = r.expert_ids
    if active is not None:
        # inactive rows scatter out of range and drop from the union
        ids = jnp.where(active[:, None], ids, E)
    return flat, r, needed.at[ids.reshape(-1)].set(True, mode="drop")


# ---------------------------------------------------------------------------
# Slot-buffer execution (device-side cache integration)
# ---------------------------------------------------------------------------

@dataclass
class SlotPathStats:
    """Per-engine counters for the slot-path benchmark."""
    swap_calls: int = 0        # device swap dispatches (batched or per-expert)
    swap_experts: int = 0      # experts actually transferred
    swap_pinned_experts: int = 0  # of those, written from pinned host memory
    prefetched: int = 0        # experts transferred ahead of demand
    prefetch_hits: int = 0     # prefetched experts later demanded
    late_hits: int = 0         # prefetch hits the link model says arrived late
    demand_misses: int = 0     # experts swapped in on demand at layer entry
    host_syncs: int = 0        # blocking device->host pulls
    jit_calls: int = 0         # engine-issued jitted computation dispatches
    steps: int = 0             # forward() / decode_step invocations
    spec_layers: int = 0       # MoE layers executed speculatively (no sync)
    replays: int = 0           # speculative windows rolled back on mispredict
    link_failures: int = 0     # injected transfer failures observed
    retries: int = 0           # demand swap-in retry attempts
    degraded_steps: int = 0    # decode steps in degraded mode (resident-only
                               # routing engaged or watchdog tripped)
    host_hits: int = 0         # demanded experts already staged in host tier
    host_misses: int = 0       # demanded experts promoted disk->host first
    disk_stall_s: float = 0.0  # exposed disk-link stall (link-clock units)
    ffn_calls: int = 0         # slot-pool FFN dispatches (one per MoE layer)
    ffn_experts: int = 0       # experts whose weights those FFNs streamed

    def snapshot(self) -> Dict[str, int]:
        return dataclasses.asdict(self)

    def reset(self) -> None:
        for f in dataclasses.fields(self):
            setattr(self, f.name, 0)


# experts per donated slot-pool write: bounds its device transfer buffers
# (see `_dispatch_swap`)
SWAP_CHUNK = 32

# one swap window in this many is timed to completion for the controller's
# host->device bandwidth estimate C_s (see `_dispatch_swap`)
BANDWIDTH_SAMPLE_EVERY = 8

# chunked prefill: fixed prompt-chunk width C. Every chunk dispatch is a
# padded (1, C) shape, so the jit cache is keyed on (C, layer spec) only —
# compile count stays flat no matter how many distinct prompt lengths a
# serving mix carries.
DEFAULT_PREFILL_CHUNK = 32


@dataclass
class PrefillCursor:
    """Resumable chunked-prefill state for ONE prompt.

    Built by `SlotBufferEngine.start_prefill`; each `prefill_chunk` call
    ingests the next `chunk`-wide padded slice of `tokens` into the
    per-layer single-row `caches` (KV written at absolute positions
    `offset..offset+t`). The serving scheduler advances cursors one chunk
    per iteration, interleaved with batched decode, so a long prompt never
    head-of-line blocks co-batched decoders. When the cursor completes,
    `logits` holds the prompt's last-token logits (1, V) for sampling the
    first output token.
    """
    tokens: np.ndarray           # (T,) int32 full prompt
    chunk: int                   # fixed chunk width C
    caches: List[Any]            # per-layer batch-1 caches, filled so far
    offset: int = 0              # tokens already ingested
    logits: Optional[jnp.ndarray] = None   # set when done
    skipped: int = 0             # scheduler aging: consecutive iterations
                                 # another cursor was advanced instead
    request_id: int = -1         # the request it ingests, for its spans

    @property
    def done(self) -> bool:
        return self.offset >= len(self.tokens)

    @property
    def remaining(self) -> int:
        return len(self.tokens) - self.offset


@dataclass
class DecodeState:
    """KV/recurrent caches + position for incremental slot-path decode.

    Two shapes of state share this class:
    - single-stream (`prefill`): `cache_len` is a scalar int32 and `pos` an
      int — every batch row decodes in lockstep at one position;
    - batched serving (`alloc_decode_state` + `prefill_into`): `cache_len`
      is a (B,) int32 vector, `pos` its (B,) host mirror, and `active` a
      (B,) host bool mask of occupied slots. Rows advance independently;
      inactive rows still flow through compute (static shapes) but are
      masked out of routing demand, sampling, and the max_seq guard.
    """
    caches: List[Any]            # one populated cache entry per absolute layer
    cache_len: jnp.ndarray       # () or (B,) int32: tokens already cached
    pos: Any = 0                 # host mirror of cache_len (max_seq guard
                                 # without a device sync); int or (B,) array
    active: Optional[np.ndarray] = None   # (B,) bool; None = single-stream

    @property
    def batched(self) -> bool:
        return self.active is not None


class SlotBufferEngine:
    """MoE forward through the bounded expert slot buffer.

    Host side: TwoLevelLRU + SlotTable decide residency; device side: slots
    updated via batched donated scatters (`swap_in_many`), MoE computed with
    `moe_slotbuf`. One path serves every call — `forward`, the whole-prompt
    and chunked prefills, and `decode_step`:

    - per-layer compute is jitted ONCE per layer shape (no per-layer
      retrace) — one `pre` dispatch (attention + norm + on-device routing)
      and one `ffn` dispatch per MoE layer;
    - routing stays on device; only a (2, E) bool needed/predicted mask is
      pulled to host per MoE layer;
    - ALL missing experts of a layer swap in through batched donated
      writes (one per power of two in their count) that copy them from the
      pinned host store (`HostExpertStore`) themselves;
    - predicted next-layer experts (pre-gating the next router on the
      current hidden state) are issued BEFORE the current layer's FFN is
      dispatched, so JAX async dispatch overlaps the transfer with compute;
      speculative fills only ever take free slots or evict the cold
      (low-reuse) tier — demand residency is never displaced by a guess.
      Issued transfers are also accounted through the paper's
      `core.prefetcher` link model (virtual time = MoE layer index).

    Residency is guaranteed before each FFN dispatch (or, for a layer
    dispatched speculatively in decode, verified after it and replayed), so
    outputs are bit-exact versus the fully-resident model computed through
    the SAME jitted functions (`reference_forward`, `reference_prefill`,
    `reference_decode_step`).
    """

    def __init__(self, cfg: ModelConfig, params, model: Model,
                 n_slots_per_layer: int, *,
                 link_bandwidth: float = 64e9, max_seq: int = 256,
                 step_size: Optional[int] = None,
                 controller: Optional[StepSizeController] = None,
                 pregate_margin: int = 2, route_bias: float = 0.0,
                 route_bias_adaptive: bool = False,
                 faults: Optional[FaultPlan] = None,
                 retry_max: int = 3, retry_backoff_s: float = 1e-3,
                 degraded_route_bias: float = 4.0,
                 degraded_recover_streak: int = 8,
                 watchdog: Optional[StepWatchdog] = None,
                 store: Optional[Any] = None):
        assert cfg.moe is not None
        if not isinstance(params, ServingParams):
            params = split_params(model, params)
        self.cfg = cfg
        self.model = model
        # device-resident non-layer params; per-layer params live in _p and
        # the routed experts only in the host store
        self.params = params.top
        self.experts = params.experts
        self.max_seq = max_seq
        self.specs = _all_specs(model)
        self.moe_layer_ids = [i for i, s in enumerate(self.specs) if s.is_moe]
        L, E = len(self.moe_layer_ids), cfg.moe.num_experts
        self.n_slots = n_slots_per_layer * L
        self.table = SlotTable(L, E, self.n_slots)
        self.cache = TwoLevelLRU(self.n_slots)
        # the pool holds experts in the model's dtype: a bf16 pool under an
        # f32 model would round every expert it swaps in
        self.buffer = make_buffer(cfg, self.n_slots, model.dtype)
        self.stats = SlotPathStats()
        # every warm jitted dispatch funnels through this counter so
        # jit_calls accounting cannot drift from the calls actually made
        self._dispatch = Dispatcher(self.stats)
        # per-absolute-layer params (routed experts stripped)
        self._p = params.layers
        # expert weight source: the pinned host store by default,
        # or a caller-supplied TieredExpertStore (core.expert_tiers) whose
        # host residency the demand/prefetch paths must guarantee first
        if store is None:
            self.store = self.experts
            self.tiers = None
        else:
            self.store = store
            self.tiers = store if hasattr(store, "demand_host") else None
            if self.tiers is not None:
                tm = self.tiers.model
                assert (tm.L, tm.E) == (L, E), (
                    f"shard store shape ({tm.L},{tm.E}) != model ({L},{E})")
        # transfer accounting through the paper's link/prefetcher model
        # (virtual time: one unit per MoE layer dispatch)
        self.link = TransferLink(bandwidth=link_bandwidth)
        self._expert_nbytes = float(cfg.expert_bytes())
        self.prefetcher = Prefetcher(self.link, self._expert_nbytes,
                                     cancel_on_forget=True)
        self._clock = 0.0
        self._prefetch_pending: set = set()
        # speculative-window bookkeeping: layers whose FFN has dispatched
        # but whose actual routing is not yet verified, and prefetched keys
        # evicted mid-window (key -> link-model readiness at eviction) whose
        # used/unused classification must wait for verification
        self._window_layers: set = set()
        self._evicted_spec: Dict[Tuple[int, int], bool] = {}
        self._fns: Dict[Any, Any] = {}     # jitted per-layer fns, keyed by spec
        self._ident_map = jnp.arange(E, dtype=jnp.int32)
        # adaptive prefetch horizon (paper §3.2): fixed_s pins S for
        # benchmarks/ablation; otherwise the controller's stall/overfetch
        # feedback moves it at runtime
        self.fixed_s = step_size
        if controller is None:
            controller = StepSizeController()
            controller.bandwidth_est = link_bandwidth
            # lookahead beyond the remaining sweep buys nothing: clamp the
            # default controller to the model's own depth
            controller.cfg = dataclasses.replace(
                controller.cfg, s_max=min(controller.cfg.s_max, max(1, L - 1)))
        self.controller = controller
        # pre-gate over-selection: predict top-(k + margin) per token so
        # near-boundary experts (the §3.2.1 cumulative-probability tail)
        # prefetch too instead of forcing a replay when routing lands on them
        self.pregate_margin = pregate_margin
        # swap windows dispatched so far (one in BANDWIDTH_SAMPLE_EVERY is
        # timed to completion; see `_dispatch_swap`)
        self._swap_windows = 0
        # all MoE routers stacked (L, d, E) so the pre-gate fn can take any
        # lookahead window as ONE device slice
        self._router_stack = jnp.stack(
            [self._p[i]["moe"]["router"] for i in self.moe_layer_ids])
        # §3.4 cache-aware routing: bounded residency perturbation of the
        # decode routers (see `set_route_bias`). 0 disables it entirely —
        # the jitted fns are then called exactly as without the feature, so
        # disabled-engine logits are bit-exact with pre-feature builds.
        self.route_bias = 0.0
        self.route_bias_adaptive = False
        if route_bias:
            self.set_route_bias(route_bias, adaptive=route_bias_adaptive)
        # chaos / graceful degradation (core.faults): deterministic injected
        # transfer failures with bounded retry-with-backoff, a resident-only
        # degraded-routing mode (residency bias at a capped delta, so a dead
        # link can never deadlock a decode step), and a step watchdog that
        # collapses the speculative horizon S->0 under wall-time blowout.
        # faults=None (or a disabled plan) leaves every hot path — and the
        # selected jit traces — byte-identical to a pre-feature engine.
        self.faults: Optional[FaultInjector] = None
        if faults is not None and faults.enabled:
            self.faults = FaultInjector(faults)
            # brownout/jitter/stalls shape the VIRTUAL link timing: late
            # prefetches and demand stalls then feed the controller's
            # bandwidth/stall signals exactly like a genuinely slow link
            self.faults.attach_link(self.link)
            if watchdog is None:
                watchdog = StepWatchdog()
        self.watchdog = watchdog
        self.retry_max = int(retry_max)
        self.retry_backoff_s = float(retry_backoff_s)
        self.degraded_route_bias = float(degraded_route_bias)
        self.degraded_recover_streak = int(degraded_recover_streak)
        self._degraded = False
        self._fault_ok_streak = 0
        # tiered store: share the adaptive controller (its layer-time /
        # stall signals size the disk horizon S_disk) and the fault plan's
        # disk scope (independent draws from the device link's)
        if self.tiers is not None:
            if self.tiers.model.controller is None:
                self.tiers.model.controller = self.controller
            if self.faults is not None:
                self.tiers.set_faults(self.faults, retry_max=self.retry_max)

    # -- jitted per-layer functions (compiled once per layer shape) ---------
    @staticmethod
    def _spec_key(spec: LayerSpec) -> LayerSpec:
        # layer_idx does not affect compute; canonicalize so repeated layers
        # share one trace
        return LayerSpec(spec.kind, spec.window, spec.is_moe, 0)

    def _spec_tag(self, spec: LayerSpec, attention: bool = True) -> str:
        """Name suffix of a layer shape in a jitted function's name: empty
        for global GQA attention, `_mla` for latent attention, else the
        mixer kind and its window. Roles that run no attention (the MoE
        FFN) pass `attention=False` and name latent layers as GQA ones."""
        kind = spec.kind
        if kind == "attn" and attention and self.cfg.attention == "mla":
            kind = "mla"
        if kind == "attn" and spec.window == 0:
            return ""
        return f"_{kind}" + (f"_w{spec.window}" if spec.window else "")

    def _embed_fn(self):
        if "embed" not in self._fns:
            model = self.model

            def fn(params, tokens):
                x = model.embed(params, tokens)
                B, T = tokens.shape
                positions = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
                return x, positions
            self._fns["embed"] = named_jit("embed", fn)
        return self._fns["embed"]

    def _dense_fn(self, spec: LayerSpec):
        key = ("dense", self._spec_key(spec))
        if key not in self._fns:
            cfg, cspec = self.cfg, self._spec_key(spec)
            self._fns[key] = named_jit(
                "dense" + self._spec_tag(spec),
                lambda p, x, pos: layer_forward(p, cfg, cspec, x, pos))
        return self._fns[key]

    def _pre_fn(self, spec: LayerSpec, has_next: bool):
        """Attention + norm + on-device routing (+ next-layer pre-gate)."""
        key = ("pre", self._spec_key(spec), has_next)
        if key not in self._fns:
            cfg = self.cfg
            cspec = self._spec_key(spec)
            E, k = cfg.moe.num_experts, cfg.moe.top_k

            def fn(p, x, positions, next_router):
                stripped, spec_nf = split_ffn_params(p, cspec)
                x = layer_forward(stripped, cfg, spec_nf, x, positions)
                flat, r, needed = _route_ffn_entry(p, cfg, x)
                masks = jnp.zeros((2, E), jnp.bool_).at[0].set(needed)
                if has_next:
                    rn = moe_mod.route(next_router, flat, k,
                                       cfg.moe.router_norm_topk)
                    masks = masks.at[1, rn.expert_ids.reshape(-1)].set(True)
                return x, flat, r, masks
            self._fns[key] = named_jit(
                "pre" + self._spec_tag(spec) + ("_pregate" if has_next else ""),
                fn)
        return self._fns[key]

    def _ffn_fn(self, spec: LayerSpec, role: str):
        """The slot-pool MoE FFN. `role` names the path it serves
        (`moe_ffn` whole prompts, `moe_ffn_chunk` prefill chunks,
        `moe_ffn_decode` decode steps): one registry entry, compiled and
        traced under that name, per path."""
        key = ("ffn", self._spec_key(spec), role)
        if key not in self._fns:
            cfg = self.cfg
            from repro.models.transformer import _zc

            def fn(p, slot_weights, slot_map, x, flat, r):
                B, T, d = x.shape
                out, _ = moe_mod.moe_slotbuf(
                    p["moe"], slot_weights, slot_map, flat, cfg.moe,
                    router_out=r)
                ff = out.reshape(B, T, d)
                if "post_ffn_norm" in p:
                    ff = rms_norm(ff, p["post_ffn_norm"], cfg.norm_eps,
                                  zero_centered=_zc(cfg))
                return x + ff
            self._fns[key] = named_jit(
                role + self._spec_tag(spec, attention=False), fn)
        return self._fns[key]

    def _ffn(self, spec: LayerSpec, role: str, p, slot_map, x, flat, r,
             n_experts: int):
        """Dispatch the layer's FFN against the pool, counting the call and
        the `n_experts` resident experts it streams (host-side: the needed
        set of a sync layer, the prediction a speculative one runs on)."""
        self.stats.ffn_calls += 1
        self.stats.ffn_experts += n_experts
        return self._dispatch(self._ffn_fn(spec, role), p, self.buffer,
                              slot_map, x, flat, r)

    def _next_router(self, li: int):
        """Router weights of MoE layer li (device array), or None."""
        if li >= len(self.moe_layer_ids):
            return None
        return self._p[self.moe_layer_ids[li]]["moe"]["router"]

    # -- jitted decode-path functions ---------------------------------------
    def _embed_decode_fn(self):
        if "embed_decode" not in self._fns:
            model = self.model

            def fn(params, tok, cache_len):
                pos = jnp.broadcast_to(jnp.asarray(cache_len).reshape(-1, 1),
                                       (tok.shape[0], 1))
                return model.embed(params, tok[:, None], positions=pos)
            self._fns["embed_decode"] = named_jit("embed_decode", fn)
        return self._fns["embed_decode"]

    def _logits_fn(self):
        if "logits" not in self._fns:
            model = self.model
            self._fns["logits"] = named_jit(
                "logits", lambda params, x: model.logits(params, x[:, -1]))
        return self._fns["logits"]

    def _dense_prefill_fn(self, spec: LayerSpec):
        key = ("dense_prefill", self._spec_key(spec))
        if key not in self._fns:
            cfg, cspec, max_seq = self.cfg, self._spec_key(spec), self.max_seq
            self._fns[key] = named_jit(
                "dense_prefill" + self._spec_tag(spec),
                lambda p, x, pos: layer_prefill(p, cfg, cspec, x, pos,
                                                max_seq))
        return self._fns[key]

    def _dense_decode_fn(self, spec: LayerSpec):
        key = ("dense_decode", self._spec_key(spec))
        if key not in self._fns:
            cfg, cspec = self.cfg, self._spec_key(spec)
            self._fns[key] = named_jit(
                "dense_decode" + self._spec_tag(spec),
                lambda p, x, c, n: layer_decode(p, cfg, cspec, x, c, n))
        return self._fns[key]

    def _pre_prefill_fn(self, spec: LayerSpec):
        """Prefill pre half of a MoE layer: attention + KV-cache population +
        norm + on-device routing. One dispatch; no host pulls."""
        key = ("pre_prefill", self._spec_key(spec))
        if key not in self._fns:
            cfg, cspec, max_seq = self.cfg, self._spec_key(spec), self.max_seq

            def fn(p, x, positions):
                stripped, spec_nf = split_ffn_params(p, cspec)
                x, cache = layer_prefill(stripped, cfg, spec_nf, x, positions,
                                         max_seq)
                flat, r, needed = _route_ffn_entry(p, cfg, x)
                return x, flat, r, needed, cache
            self._fns[key] = named_jit("pre_prefill" + self._spec_tag(spec),
                                       fn)
        return self._fns[key]

    def _embed_chunk_fn(self):
        """Embed one padded (1, C) prompt chunk starting at `offset`.
        Returns (x, positions (1, C) absolute, valid (C,) bool row mask)."""
        if "embed_chunk" not in self._fns:
            model = self.model

            def fn(params, tokens, offset, n_valid):
                B, C = tokens.shape
                positions = jnp.broadcast_to(
                    offset + jnp.arange(C)[None, :], (B, C))
                x = model.embed(params, tokens, positions=positions)
                return x, positions, jnp.arange(C) < n_valid
            self._fns["embed_chunk"] = named_jit("embed_chunk", fn)
        return self._fns["embed_chunk"]

    @staticmethod
    def _kv_bucket(end: int, max_seq: int) -> int:
        """Static KV-prefix length covering `end` ingested positions: the
        next power of two (floor 8), clamped to max_seq. Chunk attention
        (and MLA latent expansion) runs over this prefix instead of the
        whole max_seq cache, so per-chunk cost tracks what's actually been
        ingested — at a log2(max_seq)-bounded number of specializations,
        still independent of prompt-length diversity."""
        b = 8
        while b < end:
            b <<= 1
        return min(b, max_seq)

    def _dense_prefill_chunk_fn(self, spec: LayerSpec, bucket: int):
        key = ("dense_prefill_chunk", self._spec_key(spec), bucket)
        if key not in self._fns:
            cfg, cspec = self.cfg, self._spec_key(spec)
            self._fns[key] = named_jit(
                f"dense_prefill_chunk{self._spec_tag(spec)}_kv{bucket}",
                lambda p, x, pos, c, clen, nv: layer_prefill_chunk(
                    p, cfg, cspec, x, pos, c, clen, nv, kv_bucket=bucket))
        return self._fns[key]

    def _pre_prefill_chunk_fn(self, spec: LayerSpec, bucket: int):
        """Chunk-prefill pre half of a MoE layer: chunk attention resuming at
        cache_len + KV scatter + norm + on-device routing. Padding rows are
        masked out of the needed-mask union (`active`), so a padded chunk
        can never demand — or evict residency for — experts no real token
        routed to."""
        key = ("pre_prefill_chunk", self._spec_key(spec), bucket)
        if key not in self._fns:
            cfg, cspec = self.cfg, self._spec_key(spec)

            def fn(p, x, positions, cache, cache_len, n_valid):
                stripped, spec_nf = split_ffn_params(p, cspec)
                x, new_cache = layer_prefill_chunk(
                    stripped, cfg, spec_nf, x, positions, cache, cache_len,
                    n_valid, kv_bucket=bucket)
                active = jnp.arange(x.shape[1]) < n_valid
                flat, r, needed = _route_ffn_entry(p, cfg, x, active)
                return x, flat, r, needed, new_cache
            self._fns[key] = named_jit(
                f"pre_prefill_chunk{self._spec_tag(spec)}_kv{bucket}", fn)
        return self._fns[key]

    def _logits_at_fn(self):
        """Last-token logits at a DYNAMIC row index (the final chunk's last
        valid row lands mid-buffer, not at -1)."""
        if "logits_at" not in self._fns:
            model = self.model
            self._fns["logits_at"] = named_jit(
                "logits_at",
                lambda params, x, idx: model.logits(params, x[:, idx]))
        return self._fns["logits_at"]

    def _pre_decode_fn(self, spec: LayerSpec, batched: bool = False):
        """Decode pre half: O(1) attention against the KV cache + cache
        update + norm + on-device routing. One dispatch; no host pulls.

        `batched` (continuous batching): the fn additionally takes an
        `active` (B,) bool mask — cache_len is then per-row and the needed
        mask is the union over active rows only — so one call still serves
        the whole co-batched decode iteration.

        `rbias` (cache-aware serving): optional (E,) residency logit bias
        for this layer's router. jit re-traces on argument structure, so
        calls with rbias=None compile the EXACT pre-bias graph — engines
        with the perturbation off are bit-exact by construction."""
        key = ("pre_decode", self._spec_key(spec), batched)
        if key not in self._fns:
            cfg, cspec = self.cfg, self._spec_key(spec)

            def fn(p, x, cache, cache_len, active=None, rbias=None):
                stripped, spec_nf = split_ffn_params(p, cspec)
                x, new_cache = layer_decode(stripped, cfg, spec_nf, x, cache,
                                            cache_len)
                flat, r, needed = _route_ffn_entry(p, cfg, x, active, rbias)
                return x, flat, r, needed, new_cache
            self._fns[key] = named_jit(
                "pre_decode" + self._spec_tag(spec)
                + ("_batched" if batched else ""), fn)
        return self._fns[key]

    def _pregate_fn(self, n_next: int, batched: bool = False):
        """Pre-gate the next `n_next` routers on the current hidden state in
        ONE dispatch, returning a single (n_next + 1, E) bool mask: row 0 is
        the layer's actual needed set, rows 1.. the speculative horizon.

        `batched`: idle batch slots are masked out of the union (their rows
        scatter out of range, mode="drop"), so one host sync still covers
        the whole co-batched decode iteration without garbage rows inflating
        the predicted working set.

        `rbias` (cache-aware serving): optional (n_next, E) per-target-layer
        residency bias so predictions agree with the biased routing those
        layers will run; None traces the exact pre-bias graph."""
        key = ("pregate", n_next, batched)
        if key not in self._fns:
            cfg = self.cfg
            E = cfg.moe.num_experts
            k_pred = min(E, cfg.moe.top_k + self.pregate_margin)

            def fn(flat, needed, routers, active=None, rbias=None):
                rows = [needed[None]]
                for j in range(n_next):
                    rn = moe_mod.route(routers[j], flat, k_pred,
                                       cfg.moe.router_norm_topk,
                                       logit_bias=None if rbias is None
                                       else rbias[j])
                    ids = rn.expert_ids
                    if active is not None:
                        ids = jnp.where(active[:, None], ids, E)
                    m = jnp.zeros((E,), jnp.bool_)
                    m = m.at[ids.reshape(-1)].set(True, mode="drop")
                    rows.append(m[None])
                return jnp.concatenate(rows, axis=0)
            self._fns[key] = named_jit(
                f"pregate_s{n_next}" + ("_batched" if batched else ""), fn)
        return self._fns[key]

    # -- cache-aware routing (§3.4) ------------------------------------------
    def set_route_bias(self, strength: float, adaptive: bool = False) -> None:
        """Enable/adjust the bounded residency perturbation of decode
        routing: non-resident experts' router logits drop by up to
        `strength` before top-k, so a non-resident expert loses its slot
        only to a resident expert within `strength` logits — and router
        KL vs unperturbed is provably <= strength nats
        (`core.cache_aware.residency_logit_bias`).

        `adaptive=True` makes `strength` a CEILING: the shared
        `StepSizeController` ramps its `route_bias` within [0, strength]
        from the same stall/overfetch thresholds that move S, so the
        perturbation only pays its quality cost while residency is actually
        churning. Strength 0 disables the feature (bit-exact logits)."""
        self.route_bias = float(strength)
        self.route_bias_adaptive = bool(adaptive)
        if adaptive and self.route_bias > 0.0 \
                and self.controller.cfg.route_bias_max <= 0.0:
            self.controller.cfg = dataclasses.replace(
                self.controller.cfg, route_bias_max=self.route_bias)

    def _route_bias_strength(self) -> float:
        """Current perturbation strength delta (router-logit units)."""
        if self.route_bias_adaptive:
            base = float(min(self.controller.route_bias, self.route_bias))
        else:
            base = self.route_bias
        if self._degraded:
            # resident-only degraded routing: with the link effectively
            # dead, stop steering tokens at non-resident experts — but the
            # perturbation stays a bounded delta (router KL <=
            # degraded_route_bias nats per layer), never a hard mask
            return max(base, self.degraded_route_bias)
        return base

    def _residency_bias(self, li: int) -> jnp.ndarray:
        """(E,) device bias for MoE layer li from the HOST slot table — the
        same state every residency decision already reads, so this adds no
        device->host sync. In-flight assigned transfers count as resident
        (their slots are assigned): they land before the FFN dispatch, so
        routing to them costs nothing."""
        mask = self.table.layer_slot_map(li) >= 0
        return jnp.asarray(
            residency_logit_bias(mask, self._route_bias_strength()))

    def _pregate_bias(self, li: int, s: int) -> jnp.ndarray:
        """(s, E) bias stack for the pre-gated horizon (layers li+1..li+s),
        each row from its own layer's residency, so speculative predictions
        agree with the biased routing those layers will actually run."""
        strength = self._route_bias_strength()
        rows = np.stack([self.table.layer_slot_map(li + 1 + j) >= 0
                         for j in range(s)])
        return jnp.asarray(residency_logit_bias(rows, strength))

    # -- adaptive horizon ----------------------------------------------------
    def _s_eff(self) -> int:
        return self.fixed_s if self.fixed_s is not None else self.controller.s

    def _horizon(self, li: int) -> int:
        """Lookahead from MoE layer li, clamped to the remaining sweep."""
        if self.watchdog is not None and self.watchdog.tripped:
            # step deadline blown: collapse speculation to S=0 (sync every
            # MoE layer) until the watchdog's hysteresis re-expands it
            return 0
        if self.faults is not None \
                and self.faults.predictor_blackout(self._clock):
            return 0       # predictor signal dark: nothing to speculate on
        remaining = len(self.moe_layer_ids) - (li + 1)
        if self.fixed_s is not None:
            return max(0, min(self.fixed_s, remaining))
        return self.controller.horizon(remaining)

    def _router_slice(self, li: int, s: int) -> jnp.ndarray:
        """(s, d, E) device slice of the routers for MoE layers li+1..li+s."""
        return self._router_stack[li + 1: li + 1 + s]

    def _sync_masks_dev(self, li: int, s: int, flat, needed_dev,
                        active_dev=None, rbias=None):
        """Device-side (s+1, E) sync mask block: row 0 the layer's actual
        needed set, rows 1.. the pre-gated horizon. At s == 0 the pregate
        dispatch is pure overhead — the needed mask alone suffices.
        `active_dev`: (B,) bool for batched serving (idle rows masked).
        `rbias`: optional (s, E) cache-aware bias for the horizon routers
        (None keeps the exact pre-bias traces)."""
        if s == 0:
            return needed_dev[None]
        if rbias is not None:
            return self._dispatch(
                self._pregate_fn(s, batched=active_dev is not None),
                flat, needed_dev, self._router_slice(li, s), active_dev,
                rbias)
        if active_dev is not None:
            return self._dispatch(self._pregate_fn(s, batched=True),
                                  flat, needed_dev,
                                  self._router_slice(li, s), active_dev)
        return self._dispatch(self._pregate_fn(s), flat, needed_dev,
                              self._router_slice(li, s))

    @staticmethod
    def _decode_sync_rows(li: int, s: int, rows: np.ndarray):
        """Pulled (s+1, E) sync block -> (needed expert ids, predicted sets
        keyed by MoE layer)."""
        needed = np.nonzero(rows[0])[0]
        predicted = {li + 1 + j: {int(e) for e in np.nonzero(rows[1 + j])[0]}
                     for j in range(s)}
        return needed, predicted

    # -- fault handling ------------------------------------------------------
    def _fault_transfer_ok(self, key: Tuple[int, int], *,
                           demand: bool) -> bool:
        """Decide (deterministically, from the FaultPlan) whether a swap-in
        for `key` goes through. Demand transfers get bounded
        retry-with-backoff; exhausting the retries enters degraded mode.
        Speculative fills are best-effort: one attempt, no retry, and no
        degraded-mode transition (a failed prefetch costs nothing — the
        expert is simply re-demanded later). Always True without faults."""
        fi = self.faults
        if fi is None:
            return True
        if not fi.transfer_fails(key, self._clock):
            if demand:
                self._note_transfer_ok()
            return True
        self.stats.link_failures += 1
        if not demand:
            return False
        for attempt in range(self.retry_max):
            self.stats.retries += 1
            if self.retry_backoff_s > 0.0:
                time.sleep(self.retry_backoff_s * (2.0 ** attempt))
            if not fi.transfer_fails(key, self._clock):
                self._note_transfer_ok()
                return True
            self.stats.link_failures += 1
        self._enter_degraded()
        return False

    def _note_transfer_ok(self) -> None:
        self._fault_ok_streak += 1
        if self._degraded \
                and self._fault_ok_streak >= self.degraded_recover_streak:
            # hysteresis: N consecutive clean demand transfers before
            # leaving degraded routing (at route_bias 0 this also returns
            # decode to the exact pre-bias jit traces — bit-exact recovery)
            self._degraded = False

    def _enter_degraded(self) -> None:
        self._fault_ok_streak = 0
        self._degraded = True

    def _fault_step_end(self, step_s: float) -> None:
        """Watchdog + degraded-step accounting at the end of one decode
        step. Inert when neither faults nor a watchdog are configured."""
        if self.watchdog is not None:
            self.watchdog.observe(step_s)
        if self._degraded or (self.watchdog is not None
                              and self.watchdog.tripped):
            self.stats.degraded_steps += 1

    # -- host tier (core.expert_tiers) --------------------------------------
    def _tier_demand(self, key: Tuple[int, int]) -> bool:
        """Guarantee host-tier residency for a demanded expert (always True
        on a pre-staged store). A host miss blocks on the disk link and
        records a stall just like a device miss; returns False only when
        injected disk faults defeat every retry — the caller then drops
        the expert's tokens and degrades (never deadlocks)."""
        if self.tiers is None:
            return True
        r = self.tiers.demand_host(key, self._clock)
        if r is None:
            self.stats.host_misses += 1
            self._enter_degraded()
            return False
        stall, was_hit = r
        if was_hit:
            self.stats.host_hits += 1
        else:
            self.stats.host_misses += 1
            self.stats.disk_stall_s += stall
        return True

    def _tier_ready(self, key: Tuple[int, int]) -> bool:
        """Speculative fills only proceed for host-resident experts; a
        host-absent key queues a disk->host promotion instead of blocking
        the window."""
        if self.tiers is None:
            return True
        if self.tiers.host_resident(key):
            return True
        self.tiers.request_host(key, self._clock)
        return False

    def _advance_clock(self) -> None:
        """One virtual link-clock tick per MoE-layer dispatch: the device
        prefetcher lands arrivals; with a tiered store the disk link lands
        promotions, the popularity-driven S_disk prefetcher issues the
        next disk window, and the integrity scrubber (when configured)
        spends its idle-paced budget re-verifying host-resident copies."""
        self._clock += 1.0
        self.prefetcher.advance(self._clock)
        if self.tiers is not None:
            self.tiers.advance(self._clock)
            n_moe = max(len(self.moe_layer_ids), 1)
            self.tiers.auto_prefetch(self._clock, int(self._clock) % n_moe)
            if hasattr(self.tiers, "scrub_tick"):
                self.tiers.scrub_tick(self._clock)

    def integrity_counters(self) -> Dict[str, float]:
        """The tier's integrity-guard health counters (zeros without a
        tiered store) — `ServingEngine` mirrors these into the
        `ServingReport` exactly like the link/tier counters."""
        if self.tiers is None:
            return dict(n_corrupt_detected=0, n_requarantined=0,
                        n_scrubbed=0, n_quarantined_experts=0)
        return self.tiers.model.guard.counters()

    # -- residency ----------------------------------------------------------
    def ensure_resident(self, li: int, experts, *,
                        speculative: bool = False) -> int:
        """Swap in ALL missing experts for MoE layer li in one batched
        donated device write. Returns #experts swapped.

        The full needed set is pinned while inserting so a later insert can
        never evict an earlier-needed expert of the same layer; if the cache
        is smaller than the working set the overflow experts simply stay
        non-resident (their tokens drop via the sentinel slot) instead of
        silently corrupting residents.

        `speculative=True` (the decode window demanding its PREDICTED set):
        prediction accounting — prefetch hits, late-transfer stalls,
        overfetches — is deferred to `_settle_prediction` when the layer's
        ACTUAL routing is verified; touching a predicted key here must not
        declare the prediction correct."""
        keys = [(li, int(e)) for e in experts]
        if self.tiers is not None and not speculative:
            # host-tier demand-size EWMA: the n_e term of S_disk
            self.tiers.note_layer_demand(len(keys))
        for key in keys:
            self.cache.pin(key)
        missing: List[int] = []
        slots: List[int] = []
        try:
            for key in keys:
                if self.cache.touch(key):
                    if self.tiers is not None and not speculative:
                        self.tiers.note_access(key)
                    if not speculative and key in self._prefetch_pending:
                        self._prefetch_pending.discard(key)
                        self._settle_hit(
                            key, self.prefetcher.is_ready(key, self._clock))
                    continue
                if not speculative:
                    self.stats.demand_misses += 1
                    self.controller.record_stall()
                    if not self._fault_transfer_ok(key, demand=True):
                        # retries exhausted: the expert stays non-resident
                        # this step — its tokens drop via the dead sentinel
                        # slot (exactly the capacity-overflow semantics
                        # below) and degraded routing engages. A dead link
                        # can never deadlock a decode step.
                        continue
                    if not self._tier_demand(key):
                        # the disk link defeated the promotion: the expert
                        # cannot be staged — degrade exactly like an
                        # exhausted device demand above
                        continue
                    self.prefetcher.demand(key, self._clock)
                else:
                    if not self._fault_transfer_ok(key, demand=False):
                        continue
                    if not self._tier_ready(key):
                        # speculative fills never block on the disk: skip
                        # the host-absent key (a promotion is queued; the
                        # next window or a demand picks it up)
                        continue
                try:
                    victim = self.cache.insert(key)
                except RuntimeError:     # every resident expert is needed NOW
                    continue
                if speculative:
                    # a predicted expert the prefetch window couldn't fit:
                    # fill it now, but book it as speculation — verification
                    # settles it as a hit or an overfetch, never as a
                    # demand-miss stall (no token is known to need it yet)
                    self.stats.prefetched += 1
                    self.prefetcher.prefetch(key, self._clock)
                    self._prefetch_pending.add(key)
                if victim is not None:
                    self._evict(victim)
                slots.append(self.table.assign(li, key[1]))
                if self.tiers is not None:
                    # slot residency pins the host copy (in-flight/resident
                    # experts can never be dropped from the host tier)
                    self.tiers.pin(key)
                missing.append(key[1])
        finally:
            for key in keys:
                self.cache.unpin(key)
        if missing:
            self._dispatch_swap(slots, [(li, e) for e in missing])
            self.stats.swap_experts += len(missing)
        return len(missing)

    def _settle_hit(self, key: Tuple[int, int], ready: bool, *,
                    forgotten: bool = False) -> None:
        """A prefetched expert was consumed. `ready`: whether the link model
        had delivered its bytes when the consuming dispatch happened — if
        not, that's a stall in the paper's timing (§3.2.2): deeper lookahead
        would have bought the transfer time. `forgotten`: the key was
        already evicted — marking it used now would poison the NEXT
        eviction's unused-prefetch verdict."""
        self.stats.prefetch_hits += 1
        if not forgotten:
            self.prefetcher.note_use(key)
        if not ready:
            self.stats.late_hits += 1
            self.controller.record_stall()

    def _evict(self, victim: Tuple[int, int]) -> None:
        """Release a victim's slot; an evicted never-demanded prefetch is the
        controller's overfetch signal (§3.2.2) — unless the victim's layer is
        mid-speculative-window: its FFN already dispatched against the
        then-resident slot, so whether the prefetch was USED is only known at
        verification. Park the link-readiness snapshot for
        `_settle_prediction` instead of guessing."""
        self.table.release(*victim)
        if self.tiers is not None:
            self.tiers.unpin(victim)
        deferred = False
        if victim in self._prefetch_pending:
            self._prefetch_pending.discard(victim)
            if victim[0] in self._window_layers:
                self._evicted_spec[victim] = self.prefetcher.is_ready(
                    victim, self._clock)
                deferred = True
            else:
                self.controller.record_overfetch()
        self.prefetcher.forget(victim, count_unused=not deferred)

    def _dispatch_swap(self, slots: List[int],
                       keys: List[Tuple[int, int]]) -> None:
        """Batched donated device writes of `keys` into `slots`, at most
        SWAP_CHUNK experts each: that bounds the device transfer buffers (a
        whole prefetch window of olmoe-1b-7b experts would be gigabytes).

        From the pinned host store each write is a program that copies its
        experts to the device itself (`swap_in_many`), so a window's host
        time is a dispatch time, not a transfer rate (an unpinned numpy
        source, e.g. a `TieredExpertStore`'s, is still staged on this
        thread first). One window in
        BANDWIDTH_SAMPLE_EVERY feeds the controller's C_s instead: it
        starts once earlier writes to the pool have landed (untimed) and is
        timed until its last write has landed. Compute still reading the
        pool delays that write too, so a sample errs slow, never fast.
        The window is a `swap.write` span (arg `pinned`: the experts
        written from pinned host memory); a sampled one holds the
        `swap.drain` and `swap.sample_wait` spans in which the host waits
        and dispatches nothing."""
        sample = self._swap_windows % BANDWIDTH_SAMPLE_EVERY == 0
        self._swap_windows += 1
        nbytes = len(slots) * self._expert_nbytes
        ws = self.store.experts(keys)
        pinned = sum(all(map(is_pinned, w)) for w in ws)
        self.stats.swap_pinned_experts += pinned
        with span("swap.write", experts=len(keys), bytes=int(nbytes),
                  sampled=int(sample), pinned=pinned):
            if sample:
                with span("swap.drain"):
                    jax.block_until_ready(self.buffer)
            t0 = time.perf_counter()
            for i in range(0, len(keys), SWAP_CHUNK):
                self.buffer = swap_in_many(
                    self.buffer, slots[i:i + SWAP_CHUNK],
                    *zip(*ws[i:i + SWAP_CHUNK]))
                self.stats.swap_calls += 1
            if sample:
                with span("swap.sample_wait"):
                    jax.block_until_ready(self.buffer)
                self.controller.update_bandwidth(nbytes,
                                                 time.perf_counter() - t0)

    def prefetch_layer(self, li: int, experts) -> int:
        """Speculatively swap in predicted experts for ONE future layer
        (single-layer window; see `prefetch_window`)."""
        return self.prefetch_window([(li, experts)])

    def prefetch_window(self, plan) -> int:
        """Fan speculative swap-ins across a multi-layer horizon in ONE
        batched donated device write.

        `plan`: [(layer, experts)] ordered nearest layer first, so fills for
        the layer needed soonest take slots (and link slots) first. Issued
        BEFORE the current layer's FFN dispatch so the batched transfer
        overlaps multiple layers of compute. Guesses only take free slots or
        evict the cold low-reuse tier — never the high tier holding demand
        residency. Returns #experts issued."""
        slots: List[int] = []
        issued_keys: List[Tuple[int, int]] = []
        if self.tiers is not None:
            # predictor output feeds the disk tier's popularity stats even
            # for keys the device window cannot take this round
            self.tiers.note_predicted(
                [(li, int(e)) for li, experts in plan for e in experts])
        try:
            for li, experts in plan:
                stop = False
                for e in experts:
                    key = (li, int(e))
                    if key in self.cache:
                        continue
                    if not self._fault_transfer_ok(key, demand=False):
                        continue     # failed speculative fill: skip the key
                    if not self._tier_ready(key):
                        continue     # host-absent: promotion queued instead
                    if self.cache.free_slots <= 0 and not any(
                            k not in self.cache.pinned
                            for k in self.cache.low):
                        # no free slot and no evictable COLD victim: stopping
                        # here (a) never displaces high-tier demand residency
                        # for a guess and (b) never evicts this batch's own
                        # pinned fills, which would stack two payloads onto
                        # one slot inside a single batched swap
                        stop = True
                        break
                    victim = self.cache.insert(key, high=False)
                    if victim is not None:
                        self._evict(victim)
                    # pin so a later insert in THIS batch cannot evict it
                    self.cache.pin(key)
                    issued_keys.append(key)
                    slots.append(self.table.assign(li, int(e)))
                    if self.tiers is not None:
                        self.tiers.pin(key)
                    self._prefetch_pending.add(key)
                if stop:
                    break
            self.prefetcher.prefetch_many(issued_keys, self._clock)
        finally:
            for key in issued_keys:
                self.cache.unpin(key)
        if issued_keys:
            self._dispatch_swap(slots, issued_keys)
            self.stats.swap_experts += len(issued_keys)
            self.stats.prefetched += len(issued_keys)
        return len(issued_keys)

    # -- forward ------------------------------------------------------------
    def forward(self, tokens: jnp.ndarray) -> jnp.ndarray:
        """Full forward with slot-buffer MoE. tokens: (B, T) -> (B, T, d)."""
        self.stats.steps += 1
        tokens = jnp.asarray(tokens, jnp.int32)
        x, positions = self._dispatch(self._embed_fn(), self.params,
                                      tokens)
        li = 0
        for i, spec in enumerate(self.specs):
            p = self._p[i]
            if not spec.is_moe:
                x = self._dispatch(self._dense_fn(spec), p, x,
                                   positions)
                continue
            nxt = self._next_router(li + 1)
            want_pred = nxt is not None
            x, flat, r, masks = self._dispatch(
                self._pre_fn(spec, want_pred), p, x, positions, nxt)
            # ONE small host pull: (2, E) needed/predicted bool masks
            with span("engine.mask_pull", layer=li, rows=2):
                masks_h = np.asarray(masks)
            self.stats.host_syncs += 1
            self._advance_clock()
            needed = np.nonzero(masks_h[0])[0]
            predicted = np.nonzero(masks_h[1])[0] if want_pred else []
            # paper §3.3.1: tiers track the sweep — experts needed now or
            # predicted next stay high, everything else (including idle
            # residents of the current/next layer) demotes to the
            # evict-first low tier (which is what speculative fills may take)
            self.cache.retier(
                [(li, int(e)) for e in needed]
                + [(li + 1, int(e)) for e in predicted],
                recent_layers=(), current_layer=li)
            self.ensure_resident(li, needed)
            if want_pred:
                # issue next-layer swap-ins BEFORE this layer's FFN dispatch
                self.prefetch_layer(li + 1, predicted)
            slot_map = jnp.asarray(self.table.layer_slot_map(li))
            x = self._ffn(spec, "moe_ffn", p, slot_map, x, flat, r,
                          len(needed))
            li += 1
        # next step's sweep restarts at layer 0: shield the first layer's
        # residents from the step-boundary prefetches (paper §3.3.1)
        self.cache.protect_early_layers(1)
        return x

    def _staged_experts(self, li: int, after) -> Dict[str, jnp.ndarray]:
        """All of MoE layer li's experts, put on the device for the one
        reference FFN dispatch that reads them (identity slot table).
        Staged only once `after` — the FFN's input, so every earlier
        layer — has run: dispatch would otherwise run ahead and hold
        several layers' experts on the device at once."""
        jax.block_until_ready(after)
        return dict(zip(ROUTED_EXPERT_KEYS,
                        (jnp.asarray(w) for w in self.experts.layer(li))))

    def reference_forward(self, tokens: jnp.ndarray) -> jnp.ndarray:
        """Fully-resident oracle through the SAME jitted functions: each MoE
        layer's experts are staged whole from the host store with the
        identity slot table — no buffer, no swaps, no cache. The slot path
        must match this bitwise whenever the working set stays resident."""
        tokens = jnp.asarray(tokens, jnp.int32)
        x, positions = self._embed_fn()(self.params, tokens)
        li = 0
        for i, spec in enumerate(self.specs):
            p = self._p[i]
            if not spec.is_moe:
                x = self._dense_fn(spec)(p, x, positions)
                continue
            # mirror forward()'s exact pre-fn variants so both paths run the
            # IDENTICAL compiled computations up to the slot indirection
            nxt = self._next_router(li + 1)
            x, flat, r, _ = self._pre_fn(spec, nxt is not None)(
                p, x, positions, nxt)
            x = self._ffn_fn(spec, "moe_ffn")(
                p, self._staged_experts(li, x), self._ident_map, x, flat, r)
            li += 1
        return x

    # -- incremental decode (KV-cached) -------------------------------------
    def _settle_prediction(self, li: int, needed: set,
                           ready_at_dispatch: Optional[Dict] = None) -> None:
        """Actual routing for layer li is now known: every still-outstanding
        prefetch for it settles as a hit (used — with a late-transfer stall
        if the link model says the bytes weren't there yet) or as an
        overfetch (§3.2.2). Runs at sync layers (before `ensure_resident`)
        and at speculative-window verification; the latter passes the
        readiness snapshot taken when the layer's FFN DISPATCHED — judging
        lateness at verification time would grant deep windows S extra
        virtual layers of grace and mute the stall signal."""
        for k in [k for k in self._prefetch_pending if k[0] == li]:
            self._prefetch_pending.discard(k)
            if k[1] in needed:
                ready = (ready_at_dispatch.get(k, False)
                         if ready_at_dispatch is not None
                         else self.prefetcher.is_ready(k, self._clock))
                self._settle_hit(k, ready)
            else:
                self.controller.record_overfetch()
        # prefetches evicted mid-window: classified with the readiness the
        # link model reported when their slot was still live
        for k in [k for k in self._evicted_spec if k[0] == li]:
            was_ready = self._evicted_spec.pop(k)
            if k[1] in needed:
                self._settle_hit(k, was_ready, forgotten=True)
            else:
                self.prefetcher.note_unused(k)
                self.controller.record_overfetch()

    def _sync_moe_layer(self, li: int, needed: np.ndarray,
                        predicted: Dict[int, set]) -> None:
        """Host-side residency work at a sync layer: tier maintenance, demand
        swap-ins for the actual needed set, and the speculative multi-layer
        prefetch fan-out — all issued BEFORE the FFN dispatch."""
        self._settle_prediction(li, {int(e) for e in needed})
        self.cache.retier(
            [(li, int(e)) for e in needed]
            + [(lj, int(e)) for lj, es in predicted.items() for e in es],
            recent_layers=(), current_layer=li)
        self.ensure_resident(li, needed)
        if predicted:
            self.prefetch_window(
                [(lj, sorted(es)) for lj, es in sorted(predicted.items())])

    def _prefill_moe_sync(self, li: int, flat, needed_dev,
                          active_dev=None) -> jnp.ndarray:
        """The prefill paths' shared per-MoE-layer sync sequence: pull the
        (S+1, E) mask block, advance the link clock, settle/tier/ensure
        residency and fan out the speculative window. Monolithic `prefill`
        and `prefill_chunk` MUST run this identically — any accounting or
        residency change that touched only one would silently diverge the
        two ingestion paths the bit-exactness contract pins together.
        Returns the layer's slot map for the FFN dispatch and the number of
        experts it needs."""
        s = self._horizon(li)
        masks = self._sync_masks_dev(li, s, flat, needed_dev, active_dev)
        with span("engine.mask_pull", layer=li, rows=s + 1):
            masks_h = np.asarray(masks)      # ONE (S+1, E) blocking pull
        self.stats.host_syncs += 1
        self._advance_clock()
        with span("engine.residency", layer=li):
            needed, predicted = self._decode_sync_rows(li, s, masks_h)
            self._sync_moe_layer(li, needed, predicted)
        return jnp.asarray(self.table.layer_slot_map(li)), len(needed)

    def prefill(self, tokens) -> Tuple[jnp.ndarray, DecodeState]:
        """Run the prompt through the slot path, populating per-layer KV /
        recurrent caches. Returns (last-token logits (B, V), DecodeState).

        Same per-layer-shape jitted structure as `forward` (pre = attention
        + cache population + on-device routing; ffn = `moe_slotbuf`), plus
        the adaptive horizon: each sync pulls ONE (S+1, E) mask and fans
        speculative swap-ins across layers l+1..l+S in one batched write."""
        tokens = jnp.asarray(tokens, jnp.int32)
        B, T = tokens.shape
        assert T <= self.max_seq, f"prompt {T} exceeds max_seq {self.max_seq}"
        self.stats.steps += 1
        x, positions = self._dispatch(self._embed_fn(), self.params,
                                      tokens)
        caches: List[Any] = []
        li = 0
        for i, spec in enumerate(self.specs):
            p = self._p[i]
            if not spec.is_moe:
                x, c = self._dispatch(self._dense_prefill_fn(spec), p,
                                      x, positions)
                caches.append(c)
                continue
            x, flat, r, needed_dev, c = self._dispatch(
                self._pre_prefill_fn(spec), p, x, positions)
            caches.append(c)
            slot_map, n = self._prefill_moe_sync(li, flat, needed_dev)
            x = self._ffn(spec, "moe_ffn", p, slot_map, x, flat, r, n)
            li += 1
        self.cache.protect_early_layers(
            max(1, min(self._s_eff(), len(self.moe_layer_ids))))
        logits = self._dispatch(self._logits_fn(), self.params, x)
        return logits, DecodeState(caches, jnp.asarray(T, jnp.int32),
                           pos=int(T))

    # -- chunked prefill (fixed-shape prompt ingestion) ----------------------
    @property
    def chunked_prefill_supported(self) -> bool:
        """Chunked ingestion addresses caches by absolute position: it needs
        every layer to be a global-attention layer (recurrent/xLSTM mixers
        carry sequential state; sliding windows ring-wrap the cache)."""
        return all(s.kind == "attn" and s.window == 0 for s in self.specs)

    def start_prefill(self, tokens,
                      chunk_size: int = DEFAULT_PREFILL_CHUNK
                      ) -> PrefillCursor:
        """Open a resumable chunked prefill for one prompt. tokens: (T,) or
        (1, T) int32. Drive it with `prefill_chunk` (one fixed-shape chunk
        per call); consume the result via `finish_prefill_into` (batched
        serving) or let `prefill_chunked` run it to completion."""
        assert self.chunked_prefill_supported, (
            "chunked prefill needs global-attention layers throughout; use "
            "the monolithic `prefill` for this architecture")
        toks = np.asarray(tokens, np.int32)
        assert toks.ndim == 1 or toks.shape[0] == 1, (
            "start_prefill ingests ONE prompt ((T,) or (1, T)); flattening "
            f"a {toks.shape} batch would silently concatenate prompts")
        toks = toks.reshape(-1)
        T = toks.size
        assert 1 <= T <= self.max_seq, (
            f"prompt {T} exceeds max_seq {self.max_seq}")
        assert chunk_size >= 1
        caches = [init_layer_cache(self.cfg, spec, 1, self.max_seq,
                                   self.model.dtype)
                  for spec in self.specs]
        return PrefillCursor(tokens=toks,
                             chunk=int(min(chunk_size, self.max_seq)),
                             caches=caches)

    def prefill_chunk(self, cursor: PrefillCursor) -> bool:
        """Ingest ONE padded (1, C) chunk of the cursor's prompt through the
        slot path, writing KV at absolute positions offset..offset+t and
        attending over everything ingested so far. Returns `cursor.done`.

        Every dispatch here is shaped (1, C) regardless of prompt length or
        position, so the jit cache is keyed on (chunk width, layer spec,
        KV-prefix bucket) only — the bucket set is log2(max_seq)-bounded,
        so serving a mix of prompt lengths compiles nothing new once its
        longest prefix has been seen. Each chunk runs the same
        adaptive-horizon residency
        machinery as `prefill` (one (S+1, E) sync per MoE layer, batched
        speculative swap-ins), with padding rows masked out of routing
        demand, so chunked logits stay bit-exact versus the monolithic
        path even under eviction churn."""
        assert not cursor.done, "cursor already consumed its prompt"
        o, C = cursor.offset, cursor.chunk
        t = min(C, len(cursor.tokens) - o)
        with span("engine.prefill_chunk", offset=o, tokens=t,
                  request_id=cursor.request_id):
            bucket = self._kv_bucket(o + C, self.max_seq)
            buf = np.zeros((1, C), np.int32)
            buf[0, :t] = cursor.tokens[o:o + t]
            self.stats.steps += 1
            x, positions, valid = self._dispatch(
                self._embed_chunk_fn(), self.params, jnp.asarray(buf), o, t)
            li = 0
            for i, spec in enumerate(self.specs):
                p = self._p[i]
                if not spec.is_moe:
                    x, cursor.caches[i] = self._dispatch(
                        self._dense_prefill_chunk_fn(spec, bucket), p, x,
                        positions, cursor.caches[i], o, t)
                    continue
                x, flat, r, needed_dev, cursor.caches[i] = self._dispatch(
                    self._pre_prefill_chunk_fn(spec, bucket), p, x, positions,
                    cursor.caches[i], o, t)
                slot_map, n = self._prefill_moe_sync(li, flat, needed_dev,
                                                     valid)
                x = self._ffn(spec, "moe_ffn_chunk", p, slot_map, x, flat, r,
                              n)
                li += 1
            self.cache.protect_early_layers(
                max(1, min(self._s_eff(), len(self.moe_layer_ids))))
            cursor.offset = o + t
            if cursor.done:
                cursor.logits = self._dispatch(self._logits_at_fn(),
                                               self.params, x, t - 1)
        return cursor.done

    def _run_prefill_cursor(self, tokens, chunk_size: int) -> PrefillCursor:
        """Open a cursor and drive it to completion (the non-interleaved
        convenience drive shared by `prefill_chunked`/`prefill_into`)."""
        cursor = self.start_prefill(tokens, chunk_size)
        while not self.prefill_chunk(cursor):
            pass
        return cursor

    def prefill_chunked(self, tokens,
                        chunk_size: int = DEFAULT_PREFILL_CHUNK
                        ) -> Tuple[jnp.ndarray, DecodeState]:
        """Chunked counterpart of `prefill`: same (logits, DecodeState)
        contract, built one fixed-shape chunk at a time."""
        cursor = self._run_prefill_cursor(tokens, chunk_size)
        T = len(cursor.tokens)
        return cursor.logits, DecodeState(
            cursor.caches, jnp.asarray(T, jnp.int32), pos=int(T))

    def _commit_prefill_row(self, state: DecodeState, slot: int,
                            caches, T: int) -> None:
        """Write one completed prompt's per-layer batch-1 caches into batch
        row `slot` and mark it live — the ONE row-commit sequence behind
        both the monolithic and chunked admission paths (a bookkeeping
        change applied to only one would diverge them)."""
        for i in range(len(self.specs)):
            state.caches[i] = jax.tree.map(
                lambda full, new: full.at[slot].set(new[0].astype(full.dtype)),
                state.caches[i], caches[i])
        state.cache_len = state.cache_len.at[slot].set(T)
        state.pos[slot] = T
        state.active[slot] = True

    def finish_prefill_into(self, state: DecodeState, slot: int,
                            cursor: PrefillCursor) -> jnp.ndarray:
        """Commit a completed cursor into batch row `slot` of a batched
        DecodeState (the chunked analogue of `prefill_into`'s tail).
        Returns the prompt's last-token logits (1, V)."""
        assert state.batched and cursor.done
        assert not state.active[slot], f"slot {slot} is still occupied"
        self._commit_prefill_row(state, slot, cursor.caches,
                                 int(len(cursor.tokens)))
        return cursor.logits

    # -- batched serving state (continuous batching over one engine) --------
    def alloc_decode_state(self, batch: int) -> DecodeState:
        """Empty batched DecodeState with `batch` request slots: zeroed
        per-layer caches, per-row cache positions, all slots idle. Requests
        enter via `prefill_into` and leave via `retire_slot`; the decode
        batch shape stays static so the jitted step never retraces."""
        caches = [init_layer_cache(self.cfg, spec, batch, self.max_seq,
                                   self.model.dtype)
                  for spec in self.specs]
        return DecodeState(caches, jnp.zeros((batch,), jnp.int32),
                           pos=np.zeros(batch, np.int64),
                           active=np.zeros(batch, bool))

    def prefill_into(self, state: DecodeState, slot: int, tokens,
                     chunk_size: Optional[int] = None) -> jnp.ndarray:
        """Admit a request: run its prompt through the slot path (seeding
        shared-cache residency) and write the resulting KV/recurrent caches
        into batch row `slot` of `state` IN PLACE. Returns the prompt's
        last-token logits (1, V) for sampling the first output token.

        tokens: (1, T) int32. The prefill itself is single-row (prompts of
        different lengths can't share one dispatch); only decode iterations
        are batched — the paper's continuous-batching regime.

        `chunk_size`: ingest through the fixed-shape chunked path (bounded
        recompiles; bit-exact vs monolithic) instead of one whole-prompt
        dispatch. Schedulers that want to interleave chunks with decode
        drive `start_prefill`/`prefill_chunk`/`finish_prefill_into`
        directly; this convenience form runs the cursor to completion."""
        assert state.batched, "prefill_into requires an alloc_decode_state"
        assert not state.active[slot], f"slot {slot} is still occupied"
        tokens = jnp.asarray(tokens, jnp.int32)
        assert tokens.ndim == 2 and tokens.shape[0] == 1
        if chunk_size:
            cursor = self._run_prefill_cursor(tokens, chunk_size)
            return self.finish_prefill_into(state, slot, cursor)
        logits, st1 = self.prefill(tokens)
        self._commit_prefill_row(state, slot, st1.caches, st1.pos)
        return logits

    def retire_slot(self, state: DecodeState, slot: int) -> None:
        """Free a finished request's batch row. The cache row's stale
        contents are inert: inactive rows are masked out of routing demand
        and overwritten wholesale by the next `prefill_into`."""
        assert state.batched
        state.active[slot] = False

    def decode_step(self, tok, state: DecodeState
                    ) -> Tuple[jnp.ndarray, DecodeState]:
        """One KV-cached decode step: O(1) attention per layer, MoE through
        the slot buffer, and S-layer speculative execution between host
        syncs. tok: (B,) int32. Returns (logits (B, V), state).

        A *sync* MoE layer pulls one (S+1, E) mask (actual routing + the
        pre-gated next-S prediction) and fans speculative swap-ins across
        layers l+1..l+S. The next S MoE layers then execute WITHOUT any
        device->host pull: their FFNs dispatch against the predicted
        residency, while their actual needed masks accumulate on device.
        The next sync pulls those masks together with its own (still one
        blocking pull) and verifies needed ⊆ resident-at-dispatch for every
        speculative layer; a misprediction rolls x and the caches back to
        the first wrong layer and replays it as a sync layer (the stall
        path). Outputs are therefore ALWAYS bit-exact versus
        `reference_decode_step` through the same jitted functions — the
        horizon only moves how often the host blocks. (With
        `set_route_bias(delta > 0)` routing itself is perturbed within the
        delta bound, so outputs intentionally diverge from the unperturbed
        oracle; at delta = 0 the pre-bias traces are used and exactness
        holds unchanged.)

        Batched serving states (`state.batched`, built by
        `alloc_decode_state`/`prefill_into`) run the SAME control flow: each
        row sits at its own cache position, the per-layer routing/pre-gate
        masks are the union over active rows (idle slots masked on device),
        and one (S+1, E) sync still covers the whole batch. Per-row outputs
        stay bit-exact versus a single-request engine decoding the same
        prompt, because every row's compute is independent of its
        neighbours and residency is guaranteed (or replayed) before each
        FFN dispatch."""
        rows = (int(np.count_nonzero(state.active)) if state.batched
                else int(np.shape(tok)[0]))
        with span("engine.decode_step", step=self.stats.steps, rows=rows,
                  S=self._s_eff()):
            return self._decode_step_layers(tok, state)

    def _decode_step_layers(self, tok, state: DecodeState
                            ) -> Tuple[jnp.ndarray, DecodeState]:
        """The body of `decode_step`, inside its span: one `pre` and one
        FFN dispatch per MoE layer."""
        # cache-aware routing is gated on the CEILING, not the live strength:
        # an adaptive engine at strength 0 keeps using the biased traces
        # (with a zero bias) so ramping costs no recompiles mid-serve.
        # Degraded mode (link faults) engages the same biased traces at the
        # capped degraded delta — one recompile the first time, none after.
        ca = self.route_bias > 0.0 or self._degraded
        batched = state.batched
        if batched:
            act = np.asarray(state.active, bool)
            if act.any():
                assert int(np.asarray(state.pos)[act].max()) < self.max_seq, (
                    f"decode past max_seq={self.max_seq} would silently wrap "
                    "the KV ring buffer; raise max_seq at engine "
                    "construction or retire the request")
            active_dev = jnp.asarray(act)
        else:
            assert state.pos < self.max_seq, (
                f"decode past max_seq={self.max_seq} would silently wrap the "
                "KV ring buffer; raise max_seq at engine construction")
            active_dev = None
        t0 = time.perf_counter()
        self.stats.steps += 1
        tok = jnp.asarray(tok, jnp.int32)
        # fresh state: the input DecodeState stays valid (branching several
        # continuations off one saved state must not share cache writes)
        caches, clen = list(state.caches), state.cache_len
        x = self._dispatch(self._embed_decode_fn(), self.params, tok,
                           clen)

        predicted: Dict[int, set] = {}   # li -> predicted expert set
        # pending: (li, abs_i, needed_dev, slot_snap, ready_snap) per
        # speculatively-dispatched MoE layer — slot_snap/ready_snap capture
        # residency and link readiness AT FFN DISPATCH for verification
        pending: List[tuple] = []
        ckpt: Dict[int, tuple] = {}      # abs_i -> (x_in, old_cache)
        self._window_layers.clear()
        self._evicted_spec.clear()

        def replay_from(fail_idx: int) -> Tuple[int, int, jnp.ndarray]:
            """Roll back to the first mis-speculated layer (§3.4 stall)."""
            plj, pabs = pending[fail_idx][0], pending[fail_idx][1]
            with span("engine.replay", layer=plj):
                self.stats.replays += 1
                for k, (_, old_c) in ckpt.items():
                    if k >= pabs:
                        caches[k] = old_c
                x_r = ckpt[pabs][0]
                # mid-window evictions parked for rolled-back layers: their
                # consuming dispatch is being discarded, so the transfer WAS
                # wasted — settle as overfetch now, or a re-prefetch after
                # replay would double-settle the stale entry as a hit
                for k in [k for k in self._evicted_spec if k[0] >= plj]:
                    del self._evicted_spec[k]
                    self.prefetcher.note_unused(k)
                    self.controller.record_overfetch()
                predicted.clear()
                pending.clear()
                ckpt.clear()
                self._window_layers.clear()
            return pabs, plj, x_r

        def verify(masks_h: np.ndarray) -> int:
            """First pending index whose actual routing escaped the residency
            it was dispatched with, or -1. Masks of layers past the first
            failure are stale (their inputs get replayed) — stop there."""
            for idx, (plj, _, _, snap, rsnap) in enumerate(pending):
                needed = np.nonzero(masks_h[idx])[0]
                self._settle_prediction(plj, {int(e) for e in needed},
                                        ready_at_dispatch=rsnap)
                if any(snap[int(e)] < 0 for e in needed):
                    return idx
            return -1

        def pull_and_verify(extra) -> Tuple[np.ndarray, int]:
            """ONE blocking pull of the window's accumulated needed masks
            (+ optional sync-layer rows), then verification. On success the
            window commits (pending/ckpt clear); returns (sync_rows, -1).
            On mispredict returns (stale rows, fail index). The masks are
            copied to the host together and joined there: joining them on
            the device would compile once per pair of row counts."""
            mats = [p[2] for p in pending]
            if extra is not None:
                mats.append(extra)
            rows = len(pending) + (0 if extra is None else extra.shape[0])
            with span("engine.mask_pull", layer=li, rows=rows):
                masks_h = np.concatenate(
                    [np.atleast_2d(m) for m in jax.device_get(mats)], 0)
            self.stats.host_syncs += 1
            npend = len(pending)
            fail = verify(masks_h[:npend])
            if fail < 0:
                pending.clear()
                ckpt.clear()
                self._window_layers.clear()
            return masks_h[npend:], fail

        i, li = 0, 0
        n_specs = len(self.specs)
        while True:
            if i == n_specs:
                if pending:
                    _, fail = pull_and_verify(None)
                    if fail >= 0:
                        i, li, x = replay_from(fail)
                        continue
                break
            spec = self.specs[i]
            p = self._p[i]
            if not spec.is_moe:
                if pending:
                    ckpt[i] = (x, caches[i])
                x, caches[i] = self._dispatch(
                    self._dense_decode_fn(spec), p, x, caches[i], clen)
                i += 1
                continue
            x_in, old_c = x, caches[i]
            if ca:
                # cache-aware routing: this layer's residency bias rides the
                # pre dispatch (host mask push only — no extra syncs)
                x2, flat, r, needed_dev, c2 = self._dispatch(
                    self._pre_decode_fn(spec, batched=batched),
                    p, x_in, old_c, clen, active_dev,
                    self._residency_bias(li))
            elif batched:
                x2, flat, r, needed_dev, c2 = self._dispatch(
                    self._pre_decode_fn(spec, batched=True),
                    p, x_in, old_c, clen, active_dev)
            else:
                x2, flat, r, needed_dev, c2 = self._dispatch(
                    self._pre_decode_fn(spec), p, x_in, old_c, clen)
            self._advance_clock()
            if li in predicted:
                # ---- speculative layer: no host pull ----------------------
                ckpt[i] = (x_in, old_c)
                caches[i] = c2
                with span("engine.residency", layer=li):
                    self.ensure_resident(li, sorted(predicted[li]),
                                         speculative=True)
                    snap = self.table.layer_slot_map(li)
                    ready_snap = {k: self.prefetcher.is_ready(k, self._clock)
                                  for k in self._prefetch_pending
                                  if k[0] == li}
                pending.append((li, i, needed_dev, snap, ready_snap))
                self._window_layers.add(li)
                x = self._ffn(spec, "moe_ffn_decode", p, jnp.asarray(snap),
                              x2, flat, r,
                              int(sum(snap[e] >= 0 for e in predicted[li])))
                self.stats.spec_layers += 1
                i += 1
                li += 1
                continue
            # ---- sync layer: ONE blocking pull for verify + routing + S ---
            s = self._horizon(li)
            masks = self._sync_masks_dev(
                li, s, flat, needed_dev, active_dev,
                self._pregate_bias(li, s) if ca and s > 0 else None)
            sync, fail = pull_and_verify(masks)
            if fail >= 0:
                i, li, x = replay_from(fail)
                continue
            with span("engine.residency", layer=li):
                needed, pred = self._decode_sync_rows(li, s, sync)
                predicted.clear()
                predicted.update(pred)
                self._sync_moe_layer(li, needed, predicted)
            caches[i] = c2
            slot_map = jnp.asarray(self.table.layer_slot_map(li))
            x = self._ffn(spec, "moe_ffn_decode", p, slot_map, x2, flat, r,
                          len(needed))
            i += 1
            li += 1

        self.cache.protect_early_layers(
            max(1, min(self._s_eff(), len(self.moe_layer_ids))))
        logits = self._dispatch(self._logits_fn(), self.params, x)
        step_s = time.perf_counter() - t0
        self.controller.update_layer_time(step_s / max(len(self.specs), 1))
        self._fault_step_end(step_s)
        if batched:
            # only occupied slots advance; idle rows hold position so a
            # later prefill_into overwrites a stable garbage row
            return logits, DecodeState(
                caches, clen + active_dev.astype(jnp.int32),
                pos=np.where(act, np.asarray(state.pos) + 1,
                             np.asarray(state.pos)),
                active=act.copy())
        return logits, DecodeState(caches, clen + 1, pos=state.pos + 1)

    # -- fully-resident decode oracle ---------------------------------------
    def reference_prefill(self, tokens) -> Tuple[jnp.ndarray, DecodeState]:
        """Prefill through the SAME jitted functions with the identity slot
        table over each layer's whole staged expert set — no buffer, no
        swaps."""
        tokens = jnp.asarray(tokens, jnp.int32)
        B, T = tokens.shape
        x, positions = self._embed_fn()(self.params, tokens)
        caches: List[Any] = []
        li = 0
        for i, spec in enumerate(self.specs):
            p = self._p[i]
            if not spec.is_moe:
                x, c = self._dense_prefill_fn(spec)(p, x, positions)
                caches.append(c)
                continue
            x, flat, r, _, c = self._pre_prefill_fn(spec)(p, x, positions)
            caches.append(c)
            x = self._ffn_fn(spec, "moe_ffn")(
                p, self._staged_experts(li, x), self._ident_map, x, flat, r)
            li += 1
        logits = self._logits_fn()(self.params, x)
        return logits, DecodeState(caches, jnp.asarray(T, jnp.int32),
                           pos=int(T))

    def reference_decode_step(self, tok, state: DecodeState
                              ) -> Tuple[jnp.ndarray, DecodeState]:
        """One decode step of the fully-resident oracle. The slot path must
        match this bitwise — under eviction churn, replay included.

        Single-stream states only: the batched serving path's oracle is a
        single-request engine decoding the same prompt (see
        tests/test_serving_engine.py)."""
        assert not state.batched, (
            "reference_decode_step is the single-stream oracle; compare "
            "batched rows against a single-request engine instead")
        assert state.pos < self.max_seq, (
            f"decode past max_seq={self.max_seq} would silently wrap the KV "
            "ring buffer; raise max_seq at engine construction")
        tok = jnp.asarray(tok, jnp.int32)
        caches, clen = list(state.caches), state.cache_len
        x = self._embed_decode_fn()(self.params, tok, clen)
        li = 0
        for i, spec in enumerate(self.specs):
            p = self._p[i]
            if not spec.is_moe:
                x, caches[i] = self._dense_decode_fn(spec)(p, x, caches[i],
                                                           clen)
                continue
            x2, flat, r, _, c2 = self._pre_decode_fn(spec)(p, x, caches[i],
                                                           clen)
            caches[i] = c2
            x = self._ffn_fn(spec, "moe_ffn_decode")(
                p, self._staged_experts(li, x2), self._ident_map, x2, flat, r)
            li += 1
        logits = self._logits_fn()(self.params, x)
        return logits, DecodeState(caches, clen + 1, pos=state.pos + 1)

    def generate(self, tokens, n_steps: int, temperature: float = 0.0,
                 key: Optional[jax.Array] = None,
                 reference: bool = False) -> np.ndarray:
        """Prefill + n_steps incremental decode steps through the slot path.
        tokens: (B, T). Returns generated ids (B, n_steps). Greedy by
        default; sampling follows `Engine.generate`'s key schedule so the
        two runtimes are comparable token-for-token."""
        key = key if key is not None else jax.random.PRNGKey(17)
        do_prefill = self.reference_prefill if reference else self.prefill
        do_step = self.reference_decode_step if reference else self.decode_step
        logits, state = do_prefill(tokens)
        tok = sample(logits, key, temperature)
        out = [np.asarray(tok)]
        for step in range(1, n_steps):
            logits, state = do_step(tok, state)
            key = jax.random.fold_in(key, step)
            tok = sample(logits, key, temperature)
            out.append(np.asarray(tok))
        return np.stack(out, axis=1)
