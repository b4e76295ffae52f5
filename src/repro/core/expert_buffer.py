"""Device-resident expert slot buffer (the TPU adaptation of the paper's
GPU expert cache).

A bounded number of *slots* hold expert FFN weights in device memory; an
indirection table maps (layer, expert) -> slot. The host-side controller
(`TwoLevelLRU` + prefetcher) owns the replacement policy; the device side is
purely functional: `swap_in_many` writes ALL of a layer's missing experts in
a few jitted donated scatters. `HostExpertStore` keeps every routed expert
in pinned host memory as a device-addressable array, and the scatter
program copies its experts from there itself, so the host->HBM copy is an
asynchronous DMA inside the program, not a copy on the calling thread.
The MoE layer computes through
`repro.models.moe.moe_slotbuf` using the indirection.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import SingleDeviceSharding

from repro.configs.base import ModelConfig

PINNED = "pinned_host"


def make_buffer(cfg: ModelConfig, n_slots: int, dtype=jnp.bfloat16):
    """The slot pool, committed to the first device as every write leaves
    it: its jitted users then see one placement throughout."""
    m = cfg.moe
    assert m is not None, "slot buffer only applies to MoE configs"
    d, f = cfg.d_model, m.d_expert
    device = jax.devices()[0]
    slots = {
        "w_gate": jnp.zeros((n_slots, d, f), dtype, device=device),
        "w_up": jnp.zeros((n_slots, d, f), dtype, device=device),
        "w_down": jnp.zeros((n_slots, f, d), dtype, device=device),
    }
    return slots


@functools.partial(jax.jit, donate_argnums=(0,))
def _swap_in_many(slots: Dict[str, jnp.ndarray], slot_idx: jnp.ndarray,
                  w_gate, w_up, w_down):
    """Scatter N experts into `slot_idx`. Each weight is moved to device
    memory inside the program: from pinned host memory that is a DMA the
    executable issues and waits on itself, so the dispatch returns at once;
    a weight already on the device moves nowhere."""
    def stack(ws):
        return jnp.stack([jax.device_put(w, jax.memory.Space.Device)
                          for w in ws])
    return {
        "w_gate": slots["w_gate"].at[slot_idx].set(stack(w_gate)),
        "w_up": slots["w_up"].at[slot_idx].set(stack(w_up)),
        "w_down": slots["w_down"].at[slot_idx].set(stack(w_down)),
    }


def _pow2_parts(n: int) -> List[int]:
    """n as a sum of distinct powers of two, largest first: 7 -> 4, 2, 1."""
    return [1 << b for b in reversed(range(n.bit_length())) if n >> b & 1]


def is_pinned(w) -> bool:
    """Whether `w` is an array held in pinned host memory."""
    sharding = getattr(w, "sharding", None)
    return getattr(sharding, "memory_kind", None) == PINNED


def swap_in_many(slots: Dict[str, jnp.ndarray], slot_idx,
                 w_gate, w_up, w_down) -> Dict[str, jnp.ndarray]:
    """Write N experts' weights in at most log2(N)+1 donated dispatches.

    slot_idx: (N,) distinct slots; w_*: the N experts' weights, each a
    sequence of N arrays — the host store's
    pinned per-expert arrays (see `HostExpertStore.experts`), numpy arrays,
    or a stacked (N, d, f) / (N, f, d) array. Pinned arrays go to the
    program as they are, and the program copies them to the device (see
    `_swap_in_many`), so the calling thread neither copies nor waits on the
    transfer. (A numpy source is still read through a staging copy on the
    calling thread when the program is dispatched.) N is split into
    powers of two (7 -> 4 + 2 + 1), one dispatch each, which bounds the jit
    cache at O(log n_slots) entries with no padding entry transferred.
    """
    idx = np.asarray(slot_idx, np.int32)
    n = idx.shape[0]
    assert n > 0, "swap_in_many needs at least one expert"
    ws = [list(w) for w in (w_gate, w_up, w_down)]
    assert all(len(w) == n for w in ws), "one weight per slot"
    start = 0
    for size in _pow2_parts(n):
        part = slice(start, start + size)
        slots = _swap_in_many(slots, jnp.asarray(idx[part]),
                              *(tuple(w[part]) for w in ws))
        start += size
    return slots


class HostExpertStore:
    """Every MoE layer's routed experts, one pinned host array per (layer,
    expert, weight), which the first device (the slot pool's) reads by DMA.

    Built once, before the engine; `experts` hands out those arrays by
    reference — the swap path neither slices nor copies on the host."""

    def __init__(self):
        self._pinned = SingleDeviceSharding(jax.devices()[0],
                                            memory_kind=PINNED)
        self._layers: Dict[int, List[Tuple[jax.Array, ...]]] = {}

    def add_layer(self, layer: int, w_gate, w_up, w_down) -> None:
        """Take one layer's (E, d, f), (E, d, f), (E, f, d) experts, device
        or numpy arrays: sliced on their own side, each expert put to pinned
        host memory. Waits for the copies, so a device source's slices are
        freed before the caller builds the next layer."""
        per_weight = [jax.device_put(list(w), self._pinned)
                      for w in (w_gate, w_up, w_down)]
        jax.block_until_ready(per_weight)
        self._layers[layer] = list(zip(*per_weight))

    def __len__(self) -> int:
        return len(self._layers)

    def layer_ids(self) -> List[int]:
        return sorted(self._layers)

    def layer(self, layer: int) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """All of one layer's experts (w_gate, w_up, w_down), stacked into
        fresh numpy arrays: a host copy, for paths off the serving loop."""
        return tuple(np.stack([np.asarray(w) for w in ws])
                     for ws in zip(*self._layers[layer]))

    def experts(self, keys) -> List[Tuple[jax.Array, jax.Array, jax.Array]]:
        """(w_gate, w_up, w_down) of each (layer, expert) key, in any layer
        order: the stored pinned arrays themselves, with no computation.
        This is what lets the multi-layer prefetch horizon fan speculative
        fills across layers l+1..l+S into one batched device swap
        (`swap_in_many`)."""
        return [self._layers[li][e] for li, e in keys]


class SlotTable:
    """Host-side mirror: (layer, expert) <-> slot assignments."""

    def __init__(self, num_layers: int, num_experts: int, n_slots: int):
        self.L, self.E, self.n_slots = num_layers, num_experts, n_slots
        self.slot_of = -np.ones((num_layers, num_experts), np.int32)
        self.key_of_slot: list = [None] * n_slots
        self.free: list = list(range(n_slots))

    def lookup(self, layer: int, expert: int) -> int:
        return int(self.slot_of[layer, expert])

    def assign(self, layer: int, expert: int) -> int:
        """Grab a free slot for (layer, expert). Caller must have evicted."""
        if not self.free:
            raise RuntimeError("no free slots; evict first")
        s = self.free.pop()
        old = self.key_of_slot[s]
        assert old is None
        self.key_of_slot[s] = (layer, expert)
        self.slot_of[layer, expert] = s
        return s

    def release(self, layer: int, expert: int) -> int:
        s = int(self.slot_of[layer, expert])
        assert s >= 0, "releasing non-resident expert"
        self.slot_of[layer, expert] = -1
        self.key_of_slot[s] = None
        self.free.append(s)
        return s

    def layer_slot_map(self, layer: int) -> np.ndarray:
        """(E,) int32 slot ids for one layer (-1 = not resident)."""
        return self.slot_of[layer].copy()

    @property
    def n_resident(self) -> int:
        return int((self.slot_of >= 0).sum())
