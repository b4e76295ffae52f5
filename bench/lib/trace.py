"""From a profiler trace to the device's busy time, its idle gaps and its
heaviest operations.

A trace is the `.xplane.pb` that `jax.profiler` writes. Each TPU is a plane
named `/device:TPU:<n>`; its line `XLA Ops` holds one event per operation
that ran on it (other lines, such as `XLA Modules` or `Steps`, hold events
that span operations and are not counted twice). The host's plane holds the
harness's own spans, named `bench.<what>`, on the same clock. Busy time is
the length of the union of operation intervals inside the window; each idle
gap is named by the innermost harness span that covers its middle.
"""
from __future__ import annotations

import bisect
from collections import defaultdict
from typing import Dict, List, Sequence, Tuple

Interval = Tuple[float, float]

OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."


def union(intervals: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """Sorted disjoint union of the intervals, clipped to [lo, hi]."""
    out: List[list] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The stretches of [lo, hi] that `busy` (sorted, disjoint) leaves free."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def _events(line):
    for ev in line.events:
        yield ev.name, float(ev.start_ns), float(ev.start_ns + ev.duration_ns)


def device_ops(pd) -> Dict[str, List[Tuple[str, float, float]]]:
    """Per TPU plane: (op name, start ns, end ns) of every operation."""
    out = {}
    for plane in pd.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        for line in plane.lines:
            if line.name == OPS_LINE:
                out[plane.name] = list(_events(line))
    return out


def host_spans(pd) -> List[Tuple[str, float, float]]:
    """The harness's own spans on the host's planes."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out.extend(e for e in _events(line) if e[0].startswith(SPAN_PREFIX))
    return out


def op_label(name: str) -> str:
    """An operation's name and result shape, without its operands:
    `%fusion.9 = bf16[864,64,2048]{...} fusion(...)` -> `%fusion.9 = bf16[864,64,2048]`."""
    return name.split("{")[0].split(" fusion(")[0].strip()


def label_at(spans: Sequence[Tuple[str, float, float]],
             starts: Sequence[float], t: float, depth: int = 64) -> str:
    """Innermost span covering t (the latest to start), or `none`. `spans`
    are sorted by start and `starts` are their starts; spans nest only a
    few deep, so the look goes back `depth` spans at most."""
    i = bisect.bisect_right(starts, t)
    for sp in reversed(spans[max(0, i - depth):i]):
        if t < sp[2]:
            return sp[0][len(SPAN_PREFIX):]
    return "none"


def reduce(pd, window: str = SPAN_PREFIX + "window", top: int = 10) -> Dict:
    """busy_s and window_s (averaged over the TPUs that ran anything), the
    operations that took the most device time, and idle time by what the
    host was doing. The window is the host span named `window`."""
    spans = host_spans(pd)
    win = [s for s in spans if s[0] == window]
    if not win:
        raise ValueError(f"no span {window!r} in the trace")
    _, lo, hi = win[0]
    inner = sorted((s for s in spans if s[0] != window), key=lambda s: s[1])
    starts = [s[1] for s in inner]
    planes = {k: v for k, v in device_ops(pd).items() if v}
    if not planes:
        raise ValueError("no TPU operation in the trace")
    busy_ns, op_ns, idle_ns = 0.0, defaultdict(float), defaultdict(float)
    for ops in planes.values():
        u = union([(s, e) for _, s, e in ops], lo, hi)
        busy_ns += sum(e - s for s, e in u)
        for name, s, e in ops:
            s, e = max(s, lo), min(e, hi)
            if e > s:
                op_ns[op_label(name)] += e - s
        for s, e in gaps(u, lo, hi):
            idle_ns[label_at(inner, starts, (s + e) / 2)] += e - s
    n = len(planes)
    by = lambda d: sorted(([k, v / n / 1e9] for k, v in d.items()),
                          key=lambda kv: -kv[1])[:top]
    return {"busy_s": busy_ns / n / 1e9, "window_s": (hi - lo) / 1e9,
            "device_ops": by(op_ns), "idle_gaps": by(idle_ns)}
