"""Tiered expert store: disk -> host -> device expert streaming.

The paper's premise is that the expert set no longer fits device memory;
at DeepSeek/Qwen3-235B scale it does not fit *host* RAM either. This
module adds the third tier beneath the slot buffer:

- **On-disk expert shards** — one binary file per MoE layer holding
  back-to-back per-expert records ``w_gate | w_up | w_down`` (raw bytes,
  exotic dtypes stored via the checkpointer's raw-view convention, see
  `checkpoint.serde`), plus a ``manifest.json`` describing shapes/dtypes.
  `export_expert_shards` writes a directory atomically (temp dir +
  ``os.replace``); `ExpertShardReader` memory-maps each layer file and
  materializes single experts on request, validating sizes up front so a
  truncated or corrupt shard raises `ShardError` instead of serving
  garbage weights.

- **`HostTierModel`** — the byte-budgeted host staging tier. Pure
  bookkeeping (numpy only), shared verbatim by the live engine and the
  event simulator so both backends run identical accounting and emit the
  same `ServingReport` health fields. Holds an LRU of host-resident
  experts with refcount pins (an expert assigned to a device slot or
  in-flight to the device can never be dropped from host), a disk->host
  promotion queue on its own `TransferLink` (bandwidth/latency hooks, so
  `FaultPlan`'s disk scope composes), and a long-horizon popularity-driven
  disk prefetcher: the disk horizon ``S_disk`` is derived from the
  `StepSizeController`'s layer-time estimate and the disk bandwidth —
  independently of, and clamped above, the device horizon S.

- **`TieredExpertStore`** — drop-in superset of
  `core.expert_buffer.HostExpertStore`: same ``experts`` contract
  (per-expert host arrays), so ``swap_in_many`` and the
  device prefetch window are untouched. Residency in the host tier must
  be guaranteed first via ``demand_host`` (blocking, records a stall just
  like a device miss) or the speculative ``request_host`` path.

Degradation policy mirrors the device link (PR-8): a *demand* promotion
always delivers unless the injected disk fault defeats every retry — in
which case the caller drops the expert's tokens and degrades, exactly
like an exhausted device demand. A dead disk link therefore degrades,
never deadlocks. Demand promotions may transiently overflow the byte
budget when every resident expert is pinned (correctness over budget);
speculative promotions are dropped instead.
"""
from __future__ import annotations

import json
import os
import pathlib
import shutil
import tempfile
import zlib
from collections import OrderedDict
from typing import (Any, Callable, Dict, Iterable, List, Mapping, Optional,
                    Tuple)

import numpy as np

from repro.checkpoint.serde import decode_raw, encode_raw, storage_dtype
from repro.core.integrity import IntegrityGuard
from repro.core.prefetcher import Prefetcher, TransferLink

Key = Tuple[int, int]                       # (moe_layer_index, expert_id)

SHARD_MANIFEST = "manifest.json"
SHARD_VERSION = 1
TENSOR_NAMES = ("w_gate", "w_up", "w_down")


class ShardError(ValueError):
    """An expert shard directory is missing, truncated, or corrupt."""


# ---------------------------------------------------------------- writer
def _layer_map(params: Any) -> Tuple[List[int], Callable[[int], Any]]:
    """Accept a `HostExpertStore` or a {layer: (wg, wu, wd)} mapping:
    (layer ids, one layer's (wg, wu, wd) by id). A store's layer is
    stacked only when it is asked for."""
    if hasattr(params, "layer_ids"):
        return params.layer_ids(), params.layer
    if not isinstance(params, Mapping) or not params:
        raise ValueError(
            "export_expert_shards wants a HostExpertStore or a non-empty "
            "{moe_layer_index: (w_gate, w_up, w_down)} mapping")
    return sorted(params), params.__getitem__


def export_expert_shards(params: Any, out_dir: str) -> str:
    """Write per-layer expert shard files + manifest to `out_dir`.

    Atomic: everything lands in a temp directory first, then one
    ``os.replace``. Returns the final directory path."""
    layer_ids, layer_weights = _layer_map(params)
    out = pathlib.Path(out_dir)
    out.parent.mkdir(parents=True, exist_ok=True)
    tmp = pathlib.Path(tempfile.mkdtemp(dir=out.parent,
                                        prefix=".tmp_shards_"))
    manifest: Dict[str, Any] = {"version": SHARD_VERSION, "layers": []}
    for layer in layer_ids:
        ws = [np.ascontiguousarray(np.asarray(w))
              for w in layer_weights(layer)]
        if len(ws) != len(TENSOR_NAMES):
            raise ValueError(f"layer {layer}: expected {TENSOR_NAMES}")
        n_experts = ws[0].shape[0]
        if any(w.shape[0] != n_experts for w in ws):
            raise ValueError(f"layer {layer}: mismatched expert counts")
        raws = [encode_raw(w) for w in ws]
        tensors = [{"name": name, "shape": list(w.shape[1:]),
                    "dtype": str(w.dtype), "nbytes": int(raw[0].nbytes)}
                   for name, w, raw in zip(TENSOR_NAMES, ws, raws)]
        record_nbytes = sum(t["nbytes"] for t in tensors)
        fname = f"layer_{int(layer):05d}.bin"
        crcs = []
        with open(tmp / fname, "wb") as f:
            for e in range(n_experts):
                crc = 0
                for raw in raws:
                    b = raw[e].tobytes()
                    crc = zlib.crc32(b, crc)
                    f.write(b)
                crcs.append(crc)
        manifest["layers"].append({
            "layer": int(layer), "file": fname,
            "num_experts": int(n_experts),
            "record_nbytes": int(record_nbytes),
            "crc32": crcs,
            "tensors": tensors})
    (tmp / SHARD_MANIFEST).write_text(json.dumps(manifest))
    if out.exists():
        shutil.rmtree(out)
    os.replace(tmp, out)
    return str(out)


# ---------------------------------------------------------------- reader
class ExpertShardReader:
    """Memory-mapped reader over an exported shard directory.

    Validates the manifest against the actual file sizes up front
    (`ShardError` on any mismatch) so a truncated download can never be
    served as weights. `read_expert` returns fresh host copies — the
    caller owns plain RAM, never mmap-backed views."""

    def __init__(self, store_dir: str):
        self.path = pathlib.Path(store_dir)
        man = self.path / SHARD_MANIFEST
        if not man.is_file():
            raise ShardError(f"no {SHARD_MANIFEST} in {store_dir!r} — "
                             "not an expert shard directory")
        try:
            manifest = json.loads(man.read_text())
        except (json.JSONDecodeError, UnicodeDecodeError) as e:
            raise ShardError(f"corrupt shard manifest {man}: {e}") from e
        if manifest.get("version") != SHARD_VERSION:
            raise ShardError(f"shard version {manifest.get('version')!r} "
                             f"unsupported (want {SHARD_VERSION})")
        self._layers: Dict[int, Dict[str, Any]] = {}
        self._mmaps: Dict[int, np.memmap] = {}
        for rec in manifest.get("layers", []):
            f = self.path / rec["file"]
            if not f.is_file():
                raise ShardError(f"shard file missing: {f}")
            off = 0
            for t in rec["tensors"]:
                want = (int(np.prod(t["shape"], dtype=np.int64))
                        * storage_dtype(t["dtype"]).itemsize)
                if want != t["nbytes"]:
                    raise ShardError(
                        f"{f}: tensor {t['name']} claims {t['nbytes']}B "
                        f"but shape/dtype imply {want}B")
                off += want
            if off != rec["record_nbytes"]:
                raise ShardError(f"{f}: record size {rec['record_nbytes']} "
                                 f"!= sum of tensors {off}")
            expect = rec["record_nbytes"] * rec["num_experts"]
            actual = f.stat().st_size
            if actual != expect:
                raise ShardError(f"{f} is {actual} bytes, expected {expect} "
                                 "— truncated or corrupt shard")
            crcs = rec.get("crc32")
            if crcs is not None and len(crcs) != rec["num_experts"]:
                raise ShardError(
                    f"{f}: manifest lists {len(crcs)} checksums for "
                    f"{rec['num_experts']} experts")
            self._layers[int(rec["layer"])] = rec

    def layers(self) -> List[int]:
        return sorted(self._layers)

    def num_experts(self, layer: int) -> int:
        return int(self._layers[layer]["num_experts"])

    def record_nbytes(self, layer: int) -> int:
        return int(self._layers[layer]["record_nbytes"])

    def has_checksums(self) -> bool:
        """True when every layer record carries per-expert CRC-32s
        (pre-integrity manifests load fine, with verification off)."""
        return all(rec.get("crc32") is not None
                   for rec in self._layers.values())

    def record_crc(self, layer: int, expert: int) -> Optional[int]:
        crcs = self._layers[layer].get("crc32")
        return None if crcs is None else int(crcs[expert])

    def _mmap(self, layer: int) -> np.memmap:
        if layer not in self._mmaps:
            rec = self._layers[layer]
            self._mmaps[layer] = np.memmap(self.path / rec["file"],
                                           dtype=np.uint8, mode="r")
        return self._mmaps[layer]

    def _record_span(self, layer: int, expert: int) -> Tuple[np.memmap, int]:
        """Bounds-checked (mmap, record_offset) for one expert record.

        The whole-file size is validated at construction, but the mmap is
        lazy: a file truncated *after* the reader opened maps short. Check
        the record's byte span against the actual mapping at every
        materialization so a mid-record truncation raises `ShardError`
        instead of serving a short read."""
        rec = self._layers.get(layer)
        if rec is None:
            raise ShardError(f"layer {layer} not present in shard store "
                             f"(have {self.layers()})")
        if not 0 <= expert < rec["num_experts"]:
            raise ShardError(f"expert {expert} out of range "
                             f"[0, {rec['num_experts']}) for layer {layer}")
        mm = self._mmap(layer)
        off = expert * rec["record_nbytes"]
        end = off + rec["record_nbytes"]
        if end > mm.size:
            raise ShardError(
                f"{self.path / rec['file']}: record {expert} spans bytes "
                f"[{off}, {end}) but only {mm.size} are mapped — shard "
                "truncated after open")
        return mm, off

    def read_record_bytes(self, layer: int, expert: int) -> np.ndarray:
        """One expert's raw record as a fresh uint8 copy (the integrity
        layer checksums / decodes this, never the mmap itself)."""
        mm, off = self._record_span(layer, expert)
        n = self._layers[layer]["record_nbytes"]
        return np.array(mm[off:off + n], dtype=np.uint8)

    def decode_record(self, layer: int,
                      raw: np.ndarray) -> Tuple[np.ndarray, ...]:
        """Decode a raw uint8 record (from `read_record_bytes`) into the
        per-tensor host arrays `read_expert` would return."""
        rec = self._layers[layer]
        buf = np.ascontiguousarray(raw, dtype=np.uint8)
        if buf.size != rec["record_nbytes"]:
            raise ShardError(f"record buffer is {buf.size}B, expected "
                             f"{rec['record_nbytes']}B")
        off, out = 0, []
        for t in rec["tensors"]:
            flat = np.frombuffer(buf, dtype=storage_dtype(t["dtype"]),
                                 count=int(np.prod(t["shape"],
                                                   dtype=np.int64)),
                                 offset=off)
            out.append(np.array(decode_raw(flat,
                                           t["dtype"]).reshape(t["shape"])))
            off += t["nbytes"]
        return tuple(out)

    def read_expert(self, layer: int, expert: int) -> Tuple[np.ndarray, ...]:
        mm, off = self._record_span(layer, expert)
        rec = self._layers[layer]
        out = []
        for t in rec["tensors"]:
            raw = np.frombuffer(mm, dtype=storage_dtype(t["dtype"]),
                                count=int(np.prod(t["shape"], dtype=np.int64)),
                                offset=off)
            arr = decode_raw(raw, t["dtype"]).reshape(t["shape"])
            out.append(np.array(arr))         # own RAM, drop the mmap ref
            off += t["nbytes"]
        return tuple(out)


# ------------------------------------------------------------ tier model
class HostTierModel:
    """Byte-budgeted host staging tier + disk->host promotion accounting.

    Bookkeeping only — `TieredExpertStore` composes it with a shard
    reader that moves the actual bytes on the same events
    (`on_insert`/`on_evict`), and `simulator.events.SimCore` drives it
    bare. Times are in the owning backend's link clock (engine: one unit
    per MoE layer; simulator: modeled seconds).

    Pin semantics: ``pin(key)`` is a refcount taken when an expert is
    assigned to a device slot (and released on slot eviction). Pinned
    entries are never LRU victims; a demand promotion into a fully-pinned
    tier transiently overflows the budget rather than failing."""

    def __init__(self, num_layers: int, num_experts: int,
                 expert_nbytes: float, host_budget_bytes: float, *,
                 disk_bandwidth: float = 2e9,
                 controller: Optional[Any] = None,
                 disk_horizon_max: int = 64,
                 prefetch: bool = True):
        self.L = int(num_layers)
        self.E = int(num_experts)
        self.expert_nbytes = float(expert_nbytes)
        self.host_budget_bytes = float(host_budget_bytes)
        self.disk_bandwidth = float(disk_bandwidth)
        self.controller = controller
        self.disk_horizon_max = int(disk_horizon_max)
        self.prefetch_on = bool(prefetch)
        self.link = TransferLink(bandwidth=self.disk_bandwidth)
        self.pf = Prefetcher(self.link, self.expert_nbytes,
                             cancel_on_forget=True)
        self.retry_max = 0
        self.retry_backoff_s = 0.0
        # host residency: insertion-ordered (oldest first = LRU victim)
        self._resident: "OrderedDict[Key, None]" = OrderedDict()
        self._pins: Dict[Key, int] = {}
        self.host_bytes = 0.0
        # popularity EWMA per (layer, expert): fed by actual routing
        # (note_access / demand) and by predictor output (note_predicted),
        # decayed once per auto_prefetch tick so stale mass fades
        self.popularity = np.zeros((self.L, self.E), np.float64)
        self.pop_decay = 0.98
        self._mean_demand = 1.0          # EWMA distinct experts per layer
        self._n_layer_obs = 0
        # bytes-moved callbacks: TieredExpertStore loads/drops real copies
        self.on_insert: Optional[Callable[[Key], None]] = None
        self.on_evict: Optional[Callable[[Key], None]] = None
        # health counters (mirrored into ServingReport by both backends)
        self.host_hits = 0
        self.host_misses = 0
        self.disk_stall_s = 0.0
        self.promotions = 0
        self.evictions = 0
        self.disk_late_hits = 0          # demanded while already in-flight
        self.n_demand_failures = 0       # promotions defeated by disk faults
        self.dropped_arrivals = 0        # speculative landings with no room
        # integrity: verify/quarantine/re-fetch state (off by default —
        # zero-cost, pre-feature behavior). The verify hooks are backend
        # specific: the real store checksums real bytes, the simulator
        # draws the same outcomes from the fault injector.
        self.guard = IntegrityGuard()
        self.verify_fn: Optional[Callable[[Key], bool]] = None
        self.scrub_fn: Optional[Callable[[Key], bool]] = None
        self._scrub_cursor = 0
        self._scrub_miss_mark = 0

    # ------------------------------------------------------------ faults
    def set_faults(self, injector: Any, retry_max: int = 3,
                   retry_backoff_s: float = 0.0) -> None:
        """Attach the disk scope of a `FaultInjector` (via `disk_view`) to
        the promotion link + retry policy."""
        view = injector.disk_view() if hasattr(injector, "disk_view") \
            else injector
        view.attach_link(self.link)
        self.pf.injector = view
        self.retry_max = int(retry_max)
        self.retry_backoff_s = float(retry_backoff_s)

    # --------------------------------------------------------- integrity
    def configure_integrity(self, mode: str, *, scrub_budget: int = 2,
                            refetch_max: int = 3,
                            verify_fn: Optional[Callable[[Key], bool]] = None,
                            scrub_fn: Optional[Callable[[Key], bool]] = None,
                            ) -> None:
        """Enable promotion verification (and, in ``scrub`` mode, the
        budgeted background scrubber). `verify_fn(key)` checks a freshly
        promoted copy, `scrub_fn(key)` re-checks a host-resident one;
        both return True when the copy is clean."""
        self.guard = IntegrityGuard(mode, scrub_budget=scrub_budget,
                                    refetch_max=refetch_max)
        if verify_fn is not None:
            self.verify_fn = verify_fn
        if scrub_fn is not None:
            self.scrub_fn = scrub_fn

    def _verify(self, key: Key) -> bool:
        return True if self.verify_fn is None else bool(self.verify_fn(key))

    def _verified_delivery(self, key: Key, t_done: float) -> Optional[float]:
        """Verify a completed demand promotion; on corruption, discard
        the copy and re-fetch from disk (bounded by the guard's
        ``refetch_max``). Returns the delivery time of the first clean
        copy, or None once the key is permanently quarantined — the
        caller degrades exactly like an exhausted faulted demand."""
        g = self.guard
        t = t_done
        while not self._verify(key):
            n = g.record_corrupt(key)
            self.pf.forget(key, count_unused=False)
            if n > g.refetch_max:
                g.quarantine(key)
                return None
            t2 = self.pf.demand(key, t, max_retries=self.retry_max,
                                backoff_s=self.retry_backoff_s)
            if t2 is None:               # disk faults ate the re-fetch too
                g.quarantine(key)
                return None
            t = t2
        g.record_clean(key)
        return t

    def scrub_tick(self, now: float) -> int:
        """Budgeted background re-verification of host-resident copies.

        Paced off the controller's stall signal: a tick is skipped
        whenever the tier serviced demand misses (or the shared
        `StepSizeController` has stalls pending) since the last one —
        scrubbing is idle-time work and must never add pressure to a
        pipeline that is already behind. Visits unpinned residents
        round-robin, ``scrub_budget`` verifications per tick, pinning
        each copy only for the duration of its check (pins never leak).
        A corrupt copy is evicted and transparently re-promoted from
        disk; the re-promotion re-verifies on arrival like any other."""
        g = self.guard
        if not g.scrub_enabled or self.scrub_fn is None:
            return 0
        busy = self.host_misses > self._scrub_miss_mark
        self._scrub_miss_mark = self.host_misses
        c = self.controller
        if busy or (c is not None and getattr(c, "stall_counter", 0) > 0):
            return 0
        victims = [k for k in self._resident if self._pins.get(k, 0) == 0]
        if not victims:
            return 0
        self._scrub_cursor %= len(victims)
        scrubbed = 0
        for i in range(min(g.scrub_budget, len(victims))):
            key = victims[(self._scrub_cursor + i) % len(victims)]
            self.pin(key)
            try:
                ok = bool(self.scrub_fn(key))
            finally:
                self.unpin(key)
            g.n_scrubbed += 1
            scrubbed += 1
            if not ok:
                n = g.record_corrupt(key)
                self._evict_one(key)     # drop the rotten copy
                if n > g.refetch_max:
                    g.quarantine(key)
                else:
                    self.pf.prefetch(key, now)   # self-heal: re-promote
        self._scrub_cursor = (self._scrub_cursor + scrubbed) \
            % max(1, len(victims))
        return scrubbed

    # --------------------------------------------------------- residency
    def host_resident(self, key: Key) -> bool:
        return key in self._resident

    def free_bytes(self) -> float:
        return max(0.0, self.host_budget_bytes - self.host_bytes)

    def pin(self, key: Key) -> None:
        self._pins[key] = self._pins.get(key, 0) + 1

    def unpin(self, key: Key) -> None:
        n = self._pins.get(key, 0)
        if n <= 1:
            self._pins.pop(key, None)
        else:
            self._pins[key] = n - 1

    def pinned(self, key: Key) -> bool:
        return self._pins.get(key, 0) > 0

    def _evict_one(self, victim: Key) -> None:
        del self._resident[victim]
        self.host_bytes -= self.expert_nbytes
        self.evictions += 1
        self.pf.forget(victim, count_unused=False)
        if self.on_evict is not None:
            self.on_evict(victim)

    def _land(self, key: Key, demand: bool) -> bool:
        """Book a completed promotion as host-resident, evicting LRU
        unpinned entries to stay inside the budget. Returns False (and
        drops the arrival) only for speculative landings into a
        fully-pinned tier."""
        if key in self._resident:
            self._resident.move_to_end(key)
            return True
        while self.host_bytes + self.expert_nbytes > self.host_budget_bytes:
            victim = next((k for k in self._resident
                           if self._pins.get(k, 0) == 0), None)
            if victim is None:
                if demand:
                    break            # correctness over budget (all pinned)
                self.dropped_arrivals += 1
                self.pf.forget(key, count_unused=False)
                return False
            self._evict_one(victim)
        self._resident[key] = None
        self.host_bytes += self.expert_nbytes
        self.promotions += 1
        if self.on_insert is not None:
            self.on_insert(key)
        return True

    # ----------------------------------------------------------- demand
    def demand(self, key: Key, now: float) -> Optional[Tuple[float, bool]]:
        """Blocking host-residency guarantee for a demanded expert.

        Returns ``(exposed_stall, was_hit)``, or None when injected disk
        faults defeat every retry — the caller degrades (drops the
        expert's tokens) exactly like an exhausted device demand. A host
        miss records a controller stall just like a device miss."""
        # settle promotions that already completed by `now` first: a
        # speculative promotion issued one layer ago must count as the hit
        # it is, not as an in-flight miss
        self.advance(now)
        if self.guard.is_quarantined(key):
            # the disk record itself is bad: no promotion is attempted,
            # no hit is counted — the caller degrades (dead sentinel)
            self.guard.n_quarantine_denials += 1
            return None
        self.note_use(key)
        if key in self._resident:
            self.host_hits += 1
            self._resident.move_to_end(key)
            return 0.0, True
        self.host_misses += 1
        if self.controller is not None:
            self.controller.record_stall()
        if key in self.pf.issued:
            self.disk_late_hits += 1
        t_done = self.pf.demand(key, now, max_retries=self.retry_max,
                                backoff_s=self.retry_backoff_s)
        if t_done is None:
            self.n_demand_failures += 1
            return None
        if self.guard.enabled:
            t_done = self._verified_delivery(key, t_done)
            if t_done is None:
                self.n_demand_failures += 1
                return None
        self._land(key, demand=True)
        stall = max(0.0, t_done - now)
        self.disk_stall_s += stall
        return stall, False

    def request(self, key: Key, now: float) -> bool:
        """Queue a speculative disk->host promotion (device prefetch
        window hitting a host-absent key). Never blocks; refused when the
        tier plus in-flight work already covers the budget. Deliberately
        NOT subject to the popularity floor: these requests carry the
        device predictor's forward-looking signal, and a newly-hot expert
        has no popularity history yet — exactly the case the prefetch
        window exists for."""
        if not self.prefetch_on:
            return False
        if self.guard.is_quarantined(key):
            return False
        if key in self._resident or key in self.pf.issued:
            return False
        if self._issue_slots() < 1:
            return False
        self.pf.prefetch(key, now)
        return True

    def advance(self, now: float) -> List[Key]:
        """Land completed promotions up to `now`; returns keys that
        became host-resident. With integrity enabled every speculative
        arrival is verified first: a corrupt copy is discarded and
        re-requested (bounded), a copy that keeps arriving corrupt is
        quarantined — corruption never lands."""
        landed = []
        g = self.guard
        for key in self.pf.advance(now):
            if g.enabled:
                if g.is_quarantined(key):
                    self.pf.forget(key, count_unused=False)
                    continue
                if not self._verify(key):
                    n = g.record_corrupt(key)
                    self.pf.forget(key, count_unused=False)
                    if n > g.refetch_max:
                        g.quarantine(key)
                    else:
                        self.pf.prefetch(key, now)   # self-heal re-fetch
                    continue
                g.record_clean(key)
            if self._land(key, demand=False):
                landed.append(key)
        return landed

    # ------------------------------------------------------- popularity
    def note_use(self, key: Key) -> None:
        li, e = key
        if 0 <= li < self.L and 0 <= e < self.E:
            self.popularity[li, e] += 1.0

    def note_access(self, key: Key) -> None:
        """An expert was actually routed to, whichever tier served it:
        popularity bump + host-LRU touch."""
        if key in self._resident:
            self._resident.move_to_end(key)
        self.note_use(key)

    def note_predicted(self, keys: Iterable[Key]) -> None:
        """Fold predictor output (forest/pregate top-k) into popularity at
        half the weight of an observed use."""
        for li, e in keys:
            if 0 <= li < self.L and 0 <= e < self.E:
                self.popularity[li, e] += 0.5

    def note_layer_demand(self, n: int) -> None:
        """EWMA of distinct experts demanded per layer visit — the n_e
        term of the horizon formula, and the per-layer prefetch quota."""
        if self._n_layer_obs == 0:
            self._mean_demand = float(n)
        else:
            self._mean_demand = 0.8 * self._mean_demand + 0.2 * float(n)
        self._n_layer_obs += 1

    # -------------------------------------------------------- prefetcher
    def disk_horizon(self) -> int:
        """S_disk = n_e * E_bytes / (C_disk * T_layer) — the §3.3 horizon
        with the *disk* link's bandwidth — clamped above the device
        horizon S and below `disk_horizon_max`."""
        c = self.controller
        s_dev = int(getattr(c, "s", 1)) if c is not None else 1
        layer_t = getattr(c, "layer_time_est", 0.0) if c is not None else 0.0
        if layer_t <= 0.0:
            layer_t = 1e-3
        ne = max(self._mean_demand, 1.0)
        s = ne * self.expert_nbytes / max(self.disk_bandwidth * layer_t,
                                          1e-12)
        return int(np.clip(np.ceil(s), s_dev + 1, self.disk_horizon_max))

    def _stage_floor(self) -> float:
        """Thrash guard for speculative promotions: when every landing
        must evict (tier projected full counting in-flight work), a
        candidate must be at least as popular as the coldest unpinned
        resident — a weak prediction never displaces a known-hot entry
        just because the link had issue slots free."""
        full = (self.host_bytes
                + (len(self.pf.issued) + 1) * self.expert_nbytes
                > self.host_budget_bytes)
        if not full:
            return -np.inf
        unpinned = [k for k in self._resident
                    if self._pins.get(k, 0) == 0]
        if not unpinned:
            return -np.inf
        return min(self.popularity[k] for k in unpinned)

    def _issue_slots(self) -> int:
        """How many promotions may be outstanding: the evictable capacity
        (budget minus pinned residents) less what is already in flight.
        Issuing over a *full* tier is deliberate — landings evict LRU
        unpinned entries, which is what streaming means."""
        pinned = sum(1 for k in self._resident if self._pins.get(k, 0) > 0)
        cap = int(self.host_budget_bytes / self.expert_nbytes) - pinned
        return max(0, cap - len(self.pf.issued))

    def auto_prefetch(self, now: float, current_layer: int) -> int:
        """Issue popularity-ranked disk->host promotions for the next
        `disk_horizon()` layers. Returns the number issued."""
        if not self.prefetch_on or self.L == 0:
            return 0
        # settle what already completed so the issue-slot accounting sees
        # the real in-flight set, not promotions that landed layers ago
        self.advance(now)
        self.popularity *= self.pop_decay
        slots = self._issue_slots()
        if slots < 1:
            return 0
        pop_floor = self._stage_floor()
        quota = max(1, int(np.ceil(self._mean_demand)))
        # staging deeper than the evictable capacity can HOLD only makes
        # wave d+1's landings evict wave d's not-yet-used stagings: clamp
        # the horizon to the number of whole per-layer quotas that fit
        pinned = sum(1 for k in self._resident if self._pins.get(k, 0) > 0)
        evictable = int(self.host_budget_bytes / self.expert_nbytes) - pinned
        s_disk = min(self.disk_horizon(), max(1, evictable // quota))
        issued = 0
        for d in range(1, s_disk + 1):
            li = (current_layer + d) % self.L
            order = np.argsort(-self.popularity[li], kind="stable")
            n_li = 0
            for e in order:
                if issued >= slots or n_li >= quota:
                    break
                if self.popularity[li, e] <= 0.0:
                    break          # nothing known-popular left here
                if self.popularity[li, e] < pop_floor:
                    break          # colder than every eviction victim
                key = (li, int(e))
                if key in self._resident or key in self.pf.issued:
                    continue
                if self.guard.is_quarantined(key):
                    continue             # permanently dead on disk
                self.pf.prefetch(key, now)
                issued += 1
                n_li += 1
            if issued >= slots:
                break
        return issued

    # ----------------------------------------------------------- stats
    @property
    def n_disk_failures(self) -> int:
        return self.pf.n_failed + self.link.n_failed

    @property
    def n_disk_retries(self) -> int:
        return self.pf.n_retries

    def snapshot(self) -> Dict[str, float]:
        out = dict(host_hits=self.host_hits,
                   host_misses=self.host_misses,
                   disk_stall_s=self.disk_stall_s,
                   promotions=self.promotions,
                   evictions=self.evictions,
                   disk_prefetches=self.pf.n_prefetches,
                   disk_late_hits=self.disk_late_hits,
                   n_disk_failures=self.n_disk_failures,
                   n_disk_retries=self.n_disk_retries,
                   n_demand_failures=self.n_demand_failures,
                   dropped_arrivals=self.dropped_arrivals,
                   host_bytes=self.host_bytes)
        out.update(self.guard.counters())
        return out


# ------------------------------------------------------------ full store
class TieredExpertStore:
    """Disk-backed drop-in superset of `HostExpertStore`.

    ``experts`` keeps the `HostExpertStore` contract (per-expert host
    arrays) but may only be called for host-resident experts — residency
    is the engine's job via ``demand_host``/``request_host``, exactly as device-slot residency
    is guaranteed by ``ensure_resident`` before each FFN dispatch."""

    def __init__(self, store_dir: str, *,
                 host_budget_bytes: Optional[float] = None,
                 disk_bandwidth: float = 2e9,
                 controller: Optional[Any] = None,
                 disk_horizon_max: int = 64,
                 prefetch: bool = True,
                 verify: str = "off",
                 scrub_budget: int = 2,
                 refetch_max: int = 3):
        self.reader = ExpertShardReader(store_dir)
        layer_ids = self.reader.layers()
        if not layer_ids:
            raise ShardError(f"empty shard store at {store_dir!r}")
        if layer_ids != list(range(len(layer_ids))):
            raise ShardError("MoE layer ids in shard store must be dense "
                             f"0..L-1, got {layer_ids}")
        recs = {self.reader.record_nbytes(li) for li in layer_ids}
        counts = {self.reader.num_experts(li) for li in layer_ids}
        if len(recs) != 1 or len(counts) != 1:
            raise ShardError("heterogeneous per-layer expert shapes are "
                             "not supported by the host tier")
        self.expert_nbytes = float(recs.pop())
        num_experts = counts.pop()
        self.total_expert_bytes = \
            self.expert_nbytes * num_experts * len(layer_ids)
        if host_budget_bytes is None:
            host_budget_bytes = self.total_expert_bytes
        self.model = HostTierModel(
            len(layer_ids), num_experts, self.expert_nbytes,
            host_budget_bytes, disk_bandwidth=disk_bandwidth,
            controller=controller, disk_horizon_max=disk_horizon_max,
            prefetch=prefetch)
        self.model.on_insert = self._load
        self.model.on_evict = self._drop
        self._host: Dict[Key, Tuple[np.ndarray, ...]] = {}
        # integrity: verified-but-not-yet-landed copies, and the chaos
        # source (the injector's disk view) that flips bytes before the
        # CRC check so detection exercises the REAL verification path
        self._staged: Dict[Key, Tuple[np.ndarray, ...]] = {}
        self._chaos: Optional[Any] = None
        if verify != "off" and not self.reader.has_checksums():
            verify = "off"               # pre-integrity manifest
        self.verify = verify
        if verify != "off":
            self.model.configure_integrity(
                verify, scrub_budget=scrub_budget, refetch_max=refetch_max,
                verify_fn=self._verify_promotion, scrub_fn=self._scrub_host)

    # tier events -> actual bytes
    def _load(self, key: Key) -> None:
        if key not in self._host:
            staged = self._staged.pop(key, None)
            self._host[key] = staged if staged is not None \
                else self.reader.read_expert(*key)

    def _drop(self, key: Key) -> None:
        self._host.pop(key, None)
        self._staged.pop(key, None)

    # ------------------------------------------------------- integrity
    @staticmethod
    def _flip_byte(raw: np.ndarray, key: Key, attempt: int = 0) -> None:
        """Deterministic single-byte corruption (chaos injection): any
        flip defeats CRC-32, so the position only needs to be stable."""
        li, e = key
        pos = (li * 1315423911 + e * 2654435761 + attempt * 97) % raw.size
        raw[pos] ^= 0x01

    def _verify_promotion(self, key: Key) -> bool:
        """Load + checksum a freshly promoted record. The chaos source
        may flip real bytes first (on-media rot per key, in-transit rot
        per attempt); the CRC catches every flip. A clean record is
        decoded and staged so landing never re-reads the disk."""
        li, e = key
        want = self.reader.record_crc(li, e)
        if want is None:
            return True
        raw = self.reader.read_record_bytes(li, e)
        ch = self._chaos
        if ch is not None:
            if getattr(ch, "disk_record_corrupt", lambda k: False)(key):
                self._flip_byte(raw, key)
            if getattr(ch, "promotion_corrupt", lambda k: False)(key):
                self._flip_byte(raw, key, attempt=1)
        if zlib.crc32(raw.tobytes()) != want:
            self._staged.pop(key, None)
            return False
        self._staged[key] = self.reader.decode_record(li, raw)
        return True

    def _scrub_host(self, key: Key) -> bool:
        """Re-checksum a host-resident copy in place (background scrub).
        The chaos source models in-RAM rot by flipping a real byte of
        the resident array, which the CRC then detects."""
        li, e = key
        want = self.reader.record_crc(li, e)
        ws = self._host.get(key)
        if want is None or ws is None:
            return True
        ch = self._chaos
        if ch is not None and \
                getattr(ch, "host_copy_corrupt", lambda k: False)(key):
            buf = encode_raw(ws[0]).reshape(-1).view(np.uint8)
            self._flip_byte(buf, key)
        crc = 0
        for w in ws:
            crc = zlib.crc32(encode_raw(np.ascontiguousarray(w)).tobytes(),
                             crc)
        return crc == want

    # ------------------------------------------------- tier delegation
    def host_resident(self, key: Key) -> bool:
        return self.model.host_resident(key)

    def demand_host(self, key: Key, now: float):
        return self.model.demand(key, now)

    def request_host(self, key: Key, now: float) -> bool:
        return self.model.request(key, now)

    def advance(self, now: float) -> List[Key]:
        landed = self.model.advance(now)
        # staged copies whose arrival was dropped (tier fully pinned)
        # were forgotten by the model; release the bytes too
        if self._staged:
            self._staged.clear()
        return landed

    def auto_prefetch(self, now: float, current_layer: int) -> int:
        return self.model.auto_prefetch(now, current_layer)

    def scrub_tick(self, now: float) -> int:
        return self.model.scrub_tick(now)

    @property
    def guard(self) -> IntegrityGuard:
        return self.model.guard

    def note_predicted(self, keys: Iterable[Key]) -> None:
        self.model.note_predicted(keys)

    def note_access(self, key: Key) -> None:
        self.model.note_access(key)

    def note_layer_demand(self, n: int) -> None:
        self.model.note_layer_demand(n)

    def pin(self, key: Key) -> None:
        self.model.pin(key)

    def unpin(self, key: Key) -> None:
        self.model.unpin(key)

    def set_faults(self, injector: Any, retry_max: int = 3,
                   retry_backoff_s: float = 0.0) -> None:
        self.model.set_faults(injector, retry_max=retry_max,
                              retry_backoff_s=retry_backoff_s)
        # the corrupt scope flips real bytes inside the verify hooks
        self._chaos = injector.disk_view() \
            if hasattr(injector, "disk_view") else injector

    def snapshot(self) -> Dict[str, float]:
        return self.model.snapshot()

    # ------------------------------------- HostExpertStore contract
    def _expert(self, key: Key) -> Tuple[np.ndarray, ...]:
        w = self._host.get(key)
        if w is None:
            raise RuntimeError(
                f"expert {key} is not staged in the host tier — "
                "demand_host/request_host must guarantee residency before "
                "reading it (this is a scheduling bug, not a data error)")
        return w

    def experts(self, keys: List[Key]) -> List[Tuple[np.ndarray, ...]]:
        return [self._expert((li, int(e))) for li, e in keys]
