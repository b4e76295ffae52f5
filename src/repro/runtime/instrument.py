"""Device-dispatch accounting, compile probes and profiler spans for the
slot-path runtime.

`span(name, **args)` opens a `jax.profiler.TraceAnnotation`: with the
profiler on, it lands on the host plane of the trace, on the same clock as
the device's operations; with it off, it costs about a microsecond. Names
are `serve.*` (serving loop), `engine.*` (SlotBufferEngine) and `swap.*`
(slot-pool writes). `named_jit` gives a jitted function the name its role
has, so the trace's `XLA Modules` line reads `jit_<role>`.

`count_dispatches` wraps `jax.core.Primitive.bind` to count EAGER primitive
executions — outside of jit, every bind is a separate XLA executable
invocation, which is exactly the per-op dispatch overhead the fused slot
path removes. Binds whose arguments are tracers (i.e. we are inside a jit
trace, not executing) are excluded. Warm jitted calls go through the C++
fast path and never reach Python `bind`; callers count those explicitly
(the engine's `stats.jit_calls` / `stats.swap_calls` do exactly that), so

    total device dispatches = counter.eager + jit_calls + swap_calls
"""
from __future__ import annotations

import contextlib
from dataclasses import dataclass

import jax


def span(name: str, **args):
    """A host span of the profiler's trace, named `name`, with `args` as its
    metadata (a `with` block)."""
    return jax.profiler.TraceAnnotation(name, **args)


def named_jit(name: str, fn, **jit_kwargs):
    """`jax.jit(fn)`, traced and compiled as `jit_<name>`."""
    fn.__name__ = fn.__qualname__ = name
    return jax.jit(fn, **jit_kwargs)


@dataclass
class DispatchCount:
    eager: int = 0


@contextlib.contextmanager
def count_dispatches():
    """Context manager yielding a DispatchCount of eager primitive binds."""
    counter = DispatchCount()
    orig = jax.core.Primitive.bind

    def bind(self, *args, **params):
        if not any(isinstance(a, jax.core.Tracer) for a in args):
            counter.eager += 1
        return orig(self, *args, **params)

    jax.core.Primitive.bind = bind
    try:
        yield counter
    finally:
        jax.core.Primitive.bind = orig


# ---------------------------------------------------------------------------
# jit-cache / compile-count probe
# ---------------------------------------------------------------------------

def jit_cache_stats(fns) -> dict:
    """Snapshot of a jitted-fn registry (e.g. `SlotBufferEngine._fns`).

    `entries` counts registered functions (one per layer-shape/role key);
    `compiles` sums each function's compiled specializations (one per input
    shape/dtype signature, via jax's `_cache_size`). Chunked prefill's
    contract is that `compiles` stays FLAT across distinct prompt lengths —
    every chunk dispatch reuses the one padded (1, C) specialization — which
    tests and `bench_prefill --smoke` assert through this probe.
    """
    compiles = 0
    for fn in fns.values():
        size = getattr(fn, "_cache_size", None)
        if callable(size):
            compiles += int(size())
    return {"entries": len(fns), "compiles": compiles}


@dataclass
class CompileProbe:
    """Before/after jit-cache snapshots around a block (see
    `track_compiles`)."""
    before: dict
    after: dict = None

    @property
    def new_entries(self) -> int:
        return self.after["entries"] - self.before["entries"]

    @property
    def new_compiles(self) -> int:
        return self.after["compiles"] - self.before["compiles"]


@contextlib.contextmanager
def track_compiles(engine):
    """Track jit-cache growth of an engine across a block:

        with track_compiles(eng) as probe:
            eng.prefill_chunked(prompt)
        assert probe.new_compiles == 0

    Works on anything exposing a `_fns` jitted-fn registry."""
    probe = CompileProbe(before=jit_cache_stats(engine._fns))
    try:
        yield probe
    finally:
        probe.after = jit_cache_stats(engine._fns)


@dataclass
class Dispatcher:
    """Counted jitted-dispatch funnel.

    Every warm jitted call the engine issues goes through ONE of these
    (`self._dispatch(fn, *args)`) instead of ~20 hand-sprinkled
    `stats.jit_calls += 1` sites, so dispatch accounting cannot drift from
    the calls actually made. Each call is an `engine.dispatch` span
    carrying the function's name.
    """
    stats: object

    def __call__(self, fn, *args, **kwargs):
        self.stats.jit_calls += 1
        with span("engine.dispatch", fn=fn.__name__):
            return fn(*args, **kwargs)
