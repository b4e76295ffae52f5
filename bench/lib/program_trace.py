"""From a profiler trace to what the program says about itself: its own
spans, the device time of its named jitted functions, and the chip's idle
gaps labelled by the program's span.

The program opens host spans named `serve.*` (serving loop), `engine.*`
(engine) and `swap.*` (slot-pool writes), on the host's plane and on the
same clock as the device's operations; a span's arguments may be encoded in
its name after a `#`. Its jitted functions are named by their role, so each
TPU plane's `XLA Modules` line reads `jit_<role>(<hash>)`, for example
`jit_moe_ffn_decode(1234)`. This module adds to `bench.lib.trace` and
changes none of its numbers. A trace of a program that has no such spans or
names gives empty results here, never an error.

    python3 -m bench.lib.program_trace <file.xplane.pb>

prints the reduction of one trace as JSON.
"""
from __future__ import annotations

import functools
import glob
import json
import os
import re
import sys
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

from bench.lib import trace

Span = Tuple[str, float, float]

PREFIXES = ("serve.", "engine.", "swap.")
MODULES_LINE = "XLA Modules"
NONE = "none"


def program_spans(pd) -> List[Span]:
    """The program's spans (name without its arguments, start ns, end ns) on
    the host's planes, sorted by start, the outer first of two that start
    together."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                name = ev.name.split("#", 1)[0]
                if name.startswith(PREFIXES):
                    s = float(ev.start_ns)
                    out.append((name, s, s + float(ev.duration_ns)))
    return sorted(out, key=lambda sp: (sp[1], -sp[2]))


def module_name(name: str) -> str:
    """`jit_moe_ffn_decode(1234)` -> `jit_moe_ffn_decode`."""
    return re.sub(r"\(\d+\)$", "", name)


def _busy_planes(pd) -> list:
    """The TPU planes on which any operation ran, as `trace.reduce` counts
    them."""
    busy = {k for k, v in trace.device_ops(pd).items() if v}
    return [p for p in pd.planes if p.name in busy]


def module_seconds(pd, lo: float, hi: float) -> Dict[str, float]:
    """Device seconds of each `XLA Modules` name inside [lo, hi], averaged
    over the TPU planes that ran anything."""
    planes = _busy_planes(pd)
    ns = defaultdict(float)
    for plane in planes:
        for line in plane.lines:
            if line.name != MODULES_LINE:
                continue
            for ev in line.events:
                s = max(float(ev.start_ns), lo)
                e = min(float(ev.start_ns + ev.duration_ns), hi)
                if e > s:
                    ns[module_name(ev.name)] += e - s
    return {k: v / len(planes) / 1e9 for k, v in ns.items()}


def innermost(spans: Sequence[Span], times: Sequence[float]) -> List[str]:
    """For each of the ascending `times`, the name of the innermost span
    covering it (the latest to start of those open), or `none`. `spans`
    sorted as `program_spans` gives them."""
    out, stack, i = [], [], 0
    for t in times:
        while i < len(spans) and spans[i][1] <= t:
            stack.append(spans[i])
            i += 1
        # a span that has ended before t has ended before every later time
        while stack and stack[-1][2] <= t:
            stack.pop()
        out.append(stack[-1][0] if stack else NONE)
    return out


def program_idle_gaps(pd, lo: float, hi: float,
                      spans: Optional[Sequence[Span]] = None) -> List[list]:
    """Idle seconds inside [lo, hi], averaged over the TPU planes that ran
    anything, by the innermost program span covering each gap's middle."""
    spans = program_spans(pd) if spans is None else spans
    planes = {k: v for k, v in trace.device_ops(pd).items() if v}
    idle = defaultdict(float)
    for ops in planes.values():
        busy = trace.union([(s, e) for _, s, e in ops], lo, hi)
        free = trace.gaps(busy, lo, hi)
        for (s, e), label in zip(free, innermost(spans, [(s + e) / 2 for s, e in free])):
            idle[label] += e - s
    n = max(len(planes), 1)
    return sorted(([k, v / n / 1e9] for k, v in idle.items()), key=lambda kv: -kv[1])


def self_seconds(spans: Sequence[Span], name: str, minus: Sequence[str],
                 lo: float, hi: float) -> List[float]:
    """For each span `name` that starts inside [lo, hi): its length less
    what the spans named in `minus` that it holds cover of it."""
    starts = [sp[1] for sp in spans]
    out = []
    for k, (n, s, e) in enumerate(spans):
        if n != name or not lo <= s < hi:
            continue
        held = []
        j = k + 1
        while j < len(spans) and starts[j] < e:
            if spans[j][0] in minus:
                held.append((spans[j][1], min(spans[j][2], e)))
            j += 1
        out.append((e - s - sum(b - a for a, b in trace.union(held, s, e))) / 1e9)
    return out


def covered_seconds(spans: Sequence[Span], names: Sequence[str],
                    lo: float, hi: float) -> float:
    """Seconds of [lo, hi] that the spans named in `names` cover."""
    return sum(e - s for s, e in trace.union(
        [(s, e) for n, s, e in spans if n in names], lo, hi)) / 1e9


def reduce(pd, window: str = trace.SPAN_PREFIX + "window") -> Optional[Dict]:
    """The program's view of the window (the host span `window`), or None
    where the trace has no window or no TPU operation: `lo`, `hi` (ns),
    `program_spans`, `modules` (device seconds by name) and
    `program_idle_gaps`."""
    win = [s for s in trace.host_spans(pd) if s[0] == window]
    if not win or not _busy_planes(pd):
        return None
    _, lo, hi = win[0]
    spans = program_spans(pd)
    return {"lo": lo, "hi": hi, "program_spans": spans,
            "modules": module_seconds(pd, lo, hi),
            "program_idle_gaps": program_idle_gaps(pd, lo, hi, spans)}


@functools.lru_cache(maxsize=1)
def _reduce_file(path: str, mtime_ns: int) -> Optional[Dict]:
    from jax.profiler import ProfileData
    return reduce(ProfileData.from_file(path))


def load(ctx, metrics_dir: str) -> Optional[Dict]:
    """The reduction of the trace that this traced run wrote under the
    checkout of `metrics_dir` (`bench/metrics`), or None in a run without
    one. Read once for all the metrics of a run."""
    if ctx.trace is None:
        return None
    root = os.path.dirname(os.path.dirname(os.path.abspath(metrics_dir)))
    paths = glob.glob(os.path.join(root, ".bench_cache", "trace", "**",
                                   "*.xplane.pb"), recursive=True)
    if not paths:
        return None
    return _reduce_file(paths[0], os.stat(paths[0]).st_mtime_ns)


def count(r: Dict, name: str) -> int:
    """Spans `name` that start inside the window."""
    return sum(1 for n, s, _ in r["program_spans"] if n == name and r["lo"] <= s < r["hi"])


def module_ms_per_span(r: Optional[Dict], module: str, name: str) -> Optional[float]:
    """Device milliseconds of the modules named `module` (or `module_<variant>`)
    in the window, per span `name` that starts in it; None where either is
    absent."""
    if r is None:
        return None
    n = count(r, name)
    secs = sum(v for k, v in r["modules"].items()
               if k == module or k.startswith(module + "_"))
    return 1e3 * secs / n if n and secs else None


def main(argv=None) -> int:
    from jax.profiler import ProfileData
    path = (argv or sys.argv[1:])[0]
    r = reduce(ProfileData.from_file(path))
    if r is None:
        print("no window or no TPU operation in the trace", file=sys.stderr)
        return 1
    lo, hi = r["lo"], r["hi"]
    spans = defaultdict(lambda: [0, 0.0])
    for n, s, e in r["program_spans"]:
        if lo <= s < hi:
            spans[n][0] += 1
            spans[n][1] += (e - s) / 1e9
    print(json.dumps({
        "window_s": (hi - lo) / 1e9,
        "program_idle_gaps": r["program_idle_gaps"],
        "program_spans": {k: v for k, v in sorted(spans.items())},
        "modules": sorted(([k, v] for k, v in r["modules"].items()),
                          key=lambda kv: -kv[1])}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
