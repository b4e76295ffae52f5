"""One run of one cell: build, warm up, serve open-loop through a measured
window, read the metrics, check the served tokens.

Everything that belongs to one configuration, traffic mix or metric is
found by its name in `BENCHMARK.json`:

    bench/configs/<config>.json     sizes, as run (`model_type` names both:)
    bench/models/<model_type>.py    its plain reference and tensor names
    bench/program/<model_type>.py   where the program keeps those tensors
    bench/traffic/<traffic>.json    the mix, read by bench/traffic/generator.py
    bench/limits/<cell>.json        the limit of the comparison
    bench/metrics/<metric>.py       `read(ctx)` -> a number, or None

The program is driven only through its serving entry points:
`SlotBufferEngine` under `ServingEngine.serve`. The harness wraps the
engine's `prefill_chunk` and `decode_step`, the batcher's `step` (where the
host receives each token) and, in a traced run, the swap writes, to stamp
tokens, open and close the window, and record spans.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import os
import time
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


class WindowClosed(Exception):
    """Raised from inside the serving loop once the window is over."""


class NoDevice(RuntimeError):
    """The machine lacks the accelerator the cell asks for."""


def load_json(root: str, *parts) -> dict:
    with open(os.path.join(root, "bench", *parts)) as f:
        return json.load(f)


def load_module(root: str, *parts):
    path = os.path.join(root, "bench", *parts)
    name = "bench_" + "_".join(parts).replace(".py", "").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def cell_of(bench: dict, workload: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == workload:
            return w
    raise KeyError(f"no workload {workload!r} in BENCHMARK.json")


def metrics_for(bench: dict, workload: str, trace: bool) -> List[dict]:
    """The metrics a run of this cell reports: end-to-end ones untraced,
    per-layer ones traced; a metric with `workloads` only in those cells."""
    ms = bench["per_layer" if trace else "end_to_end"]
    return [m for m in ms if workload in m.get("workloads", [workload])]


@dataclass
class Cell:
    """A cell's files, loaded by name."""
    name: str
    chips: int
    cfg: dict
    traffic: dict
    limits: dict
    model: Any            # bench/models/<model_type>.py
    program: Any          # bench/program/<model_type>.py

    @classmethod
    def load(cls, bench: dict, workload: str, root: str = ROOT) -> "Cell":
        w = cell_of(bench, workload)
        cfg = load_json(root, "configs", w["config"] + ".json")
        mt = cfg["model_type"]
        return cls(w["name"], w["chips"], cfg,
                   load_json(root, "traffic", w["traffic"] + ".json"),
                   load_json(root, "limits", w["name"] + ".json"),
                   load_module(root, "models", mt + ".py"),
                   load_module(root, "program", mt + ".py"))


# -- compile events ----------------------------------------------------------

class CompileCounter:
    """Counts jaxpr traces and backend compiles (persistent-cache hits
    included) from the moment it is installed."""

    def __init__(self):
        import jax
        self.n = 0
        self.seconds = 0.0
        self.names: List[str] = []

        def on_duration(event, duration, fun_name="", **_):
            if event in ("/jax/core/compile/jaxpr_trace_duration",
                         "/jax/core/compile/backend_compile_duration"):
                self.n += 1
                self.names.append(fun_name)
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += duration

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.n += 1
                self.names.append("cache hit")
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


# -- building the system under test -----------------------------------------

def build_engine(cell: Cell, seed: int):
    """Weights made on the device from the seed, one block per jitted call;
    routed experts go to the program's host store, the rest stays on the
    device; then the slot-path engine and its server."""
    from repro.core.expert_buffer import HostExpertStore
    from repro.models import Model
    from repro.runtime.engine import ServingParams, SlotBufferEngine
    from repro.runtime.serving import EngineServingConfig, ServingEngine

    from bench.models.common import generate
    from bench.traffic.generator import max_seq

    cfg, mod, prog, tr = cell.cfg, cell.model, cell.program, cell.traffic
    pcfg = prog.config(cfg)
    model = Model(pcfg)
    top = prog.top(generate(mod.top_spec(cfg), seed, 0))
    layers, store = [], HostExpertStore()
    for i in range(cfg["num_hidden_layers"]):
        p, experts = prog.layer(cfg, i, generate(mod.layer_spec(cfg, i), seed, i + 1))
        layers.append(p)
        if experts is not None:
            store.add_layer(len(store), *experts)
        del experts
    eng = SlotBufferEngine(pcfg, ServingParams(top, layers, store), model,
                           n_slots_per_layer=cfg["slots_per_layer"],
                           max_seq=max_seq(tr))
    srv = ServingEngine(eng, EngineServingConfig(
        max_batch=tr["max_batch"], prefill_chunk=tr["prefill_chunk"],
        admission_cap=tr["admission_cap"]))
    return eng, srv


def make_requests(specs, first_id: int = 0):
    from repro.runtime.request import Request
    return [Request(prompt=s["prompt"], max_new_tokens=s["max_new"],
                    temperature=0.0, arrival_s=s["arrival_s"],
                    request_id=first_id + i) for i, s in enumerate(specs)]


def warm_up(cell: Cell, eng, srv, seed: int) -> None:
    """Compile every shape the cell's traffic reaches, on the serving path:
    a prompt that reaches each cache-prefix bucket, every prefetch horizon
    the controller may pick, every slot a prefill can land in, every pair
    of mask counts a decode step joins, and every size of swap write.
    Writes put back what a slot already holds, so residency is left as
    serving made it."""
    import numpy as np

    from repro.core.expert_buffer import swap_in_many
    from repro.runtime.engine import SWAP_CHUNK

    tr = cell.traffic
    B, C = tr["max_batch"], tr["prefill_chunk"]
    rng = np.random.default_rng([seed, 1])
    V = cell.cfg["vocab_size"]
    longest = -(-tr["prompt_len"][1] // tr["pad_bucket"]) * tr["pad_bucket"]
    lens = [C]                        # one prompt per cache-prefix bucket
    while lens[-1] * 2 < longest:
        lens.append(lens[-1] * 2)
    lens.append(longest)
    specs = [{"arrival_s": 0.0, "max_new": 2,
              "prompt": rng.integers(0, V, L).astype(np.int32)} for L in lens]
    srv.serve(make_requests(specs, 10_000_000))
    s_max = eng.controller.cfg.s_max
    for s in range(1, s_max + 1):
        eng.fixed_s = s
        srv.serve(make_requests([{"arrival_s": 0.0, "max_new": 2,
                                  "prompt": rng.integers(0, V, C).astype(np.int32)}],
                                11_000_000 + s))
    eng.fixed_s = None
    state = eng.alloc_decode_state(B)
    for slot in range(B):
        eng.prefill_into(state, slot, rng.integers(0, V, (1, C)).astype(np.int32),
                         chunk_size=C)
    # a decode step stacks the needed masks of its speculative layers, and
    # adds the sync layer's rows, outside jit: one shape per pair of counts
    import jax.numpy as jnp
    E = cell.cfg.get("num_experts") or cell.cfg["n_routed_experts"]
    for k in range(1, s_max + 2):
        pending = jnp.stack([jnp.zeros((E,), jnp.bool_)] * k)
        for rows in range(1, s_max + 2):
            jnp.concatenate([pending, jnp.zeros((rows, E), jnp.bool_)], 0)
    resident = [(s, k) for s, k in enumerate(eng.table.key_of_slot) if k]
    m = 1
    while m <= min(SWAP_CHUNK, len(resident)):
        slots, keys = zip(*resident[:m])
        eng.buffer = swap_in_many(eng.buffer, list(slots),
                                  *zip(*eng.store.experts(list(keys))))
        m *= 2
    import jax
    jax.block_until_ready(eng.buffer)


# -- the measured window -------------------------------------------------------

@dataclass
class Recorder:
    """Hooks on the serving path that stamp tokens, keep the window, and
    (traced run) record host spans around each layer's calls."""
    srv: Any
    eng: Any
    open_at: float
    seconds: float
    compiles: CompileCounter
    traced: bool = False
    trace_dir: Optional[str] = None
    opened: Optional[float] = None
    closed: Optional[float] = None
    tokens: Dict[int, List[float]] = field(default_factory=lambda: defaultdict(list))
    ctx_len: List[int] = field(default_factory=list)      # decode tokens' keys
    prompt_pos: List[tuple] = field(default_factory=list)  # (offset, n, done)
    spans: List[tuple] = field(default_factory=list)
    bw: List[tuple] = field(default_factory=list)
    at_open: Dict[str, float] = field(default_factory=dict)
    at_close: Dict[str, float] = field(default_factory=dict)
    _win_span: Any = None
    _after_decode: Optional[float] = None
    _restore: Any = None

    def now(self) -> float:
        return time.perf_counter() - self.srv._t0

    def in_window(self, t: float) -> bool:
        return self.opened is not None and self.opened <= t and \
            (self.closed is None or t < self.closed)

    def _snapshot(self) -> Dict[str, float]:
        st = self.eng.stats
        return {"swap_experts": st.swap_experts, "prefetch_hits": st.prefetch_hits,
                "demand_misses": st.demand_misses, "host_syncs": st.host_syncs,
                "compiles": self.compiles.n}

    def tick(self) -> None:
        """At each call into the engine: open or close the window."""
        t = self.now()
        if self.opened is None and t >= self.open_at:
            if self.traced:
                import jax
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0      # spans, not every call
                opts.enable_hlo_proto = False
                jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
                self._win_span = jax.profiler.TraceAnnotation("bench.window")
                self._win_span.__enter__()
            self.at_open = self._snapshot()
            self.opened = self.now()
        elif self.opened is not None and t >= self.opened + self.seconds:
            self.finish()
            raise WindowClosed

    def finish(self) -> None:
        if self.closed is not None:
            return
        self.closed = self.now()
        self.at_close = self._snapshot()
        if self._win_span is not None:
            import jax
            self._win_span.__exit__(None, None, None)
            jax.profiler.stop_trace()

    def _span(self, name, fn, *args, ready=None):
        import jax
        t0 = self.now()
        with jax.profiler.TraceAnnotation("bench." + name):
            out = fn(*args)
            if ready is not None:
                jax.block_until_ready(ready(out, *args))
        self.spans.append((name, t0, self.now()))
        return out

    def install(self) -> None:
        eng, srv, rec = self.eng, self.srv, self
        prefill, decode = eng.prefill_chunk, eng.decode_step
        step = srv.batcher.step

        def prefill_chunk(cursor):
            rec.tick()
            o = cursor.offset
            if rec.traced:
                done = rec._span("prefill_chunk", prefill, cursor,
                                 ready=lambda d, c: (c.caches, c.logits))
            else:
                done = prefill(cursor)
            if rec.in_window(rec.now()):
                rec.prompt_pos.append((o, cursor.offset - o, bool(done)))
            return done

        def decode_step(tok, state):
            rec.tick()
            if rec.traced:
                out = rec._span("decode_step", decode, tok, state,
                                ready=lambda o, *_: o[0])
            else:
                out = decode(tok, state)
            rec._after_decode = rec.now()
            return out

        def batcher_step(next_tokens):
            t = rec.now()
            if rec.traced and rec._after_decode is not None:
                rec.spans.append(("sample", rec._after_decode, t))
            for slot in next_tokens:
                req = srv.batcher.active.get(slot)
                if req is not None:
                    rec.tokens[req.request_id].append(t)
                    if rec.in_window(t):
                        rec.ctx_len.append(req.prompt_len + len(req.output))
            return step(next_tokens)

        eng.prefill_chunk, eng.decode_step = prefill_chunk, decode_step
        srv.batcher.step = batcher_step

        # the loop sleeps while nothing has arrived: wake it when the window
        # opens or closes, so that both happen on time
        import repro.runtime.serving as serving_mod
        real = serving_mod.time

        class _Time:
            perf_counter = staticmethod(real.perf_counter)

            @staticmethod
            def sleep(s):
                rec.tick()
                edge = rec.open_at if rec.opened is None else rec.opened + rec.seconds
                real.sleep(max(0.0, min(s, edge - rec.now())))
                rec.tick()
        serving_mod.time = _Time
        self._restore = lambda: setattr(serving_mod, "time", real)

        ctl = eng.controller
        update_bw = ctl.update_bandwidth

        def update_bandwidth(nbytes, seconds):
            rec.bw.append((rec.now(), nbytes, seconds))
            return update_bw(nbytes, seconds)
        ctl.update_bandwidth = update_bandwidth

        if self.traced:
            swap = eng._dispatch_swap
            eng._dispatch_swap = lambda slots, keys: rec._span(
                "swap_write", swap, slots, keys)


@dataclass
class Ctx:
    """What a metric reader may read."""
    cfg: dict
    model: Any
    peaks: dict
    setup_s: float
    opened: float
    closed: float
    requests: list
    tokens: Dict[int, List[float]]
    ctx_len: List[int]
    prompt_pos: List[tuple]
    spans: List[tuple]
    bw: List[tuple]
    at_open: Dict[str, float]
    at_close: Dict[str, float]
    trace: Optional[dict] = None

    @property
    def seconds(self) -> float:
        return self.closed - self.opened

    def inside(self, t: float) -> bool:
        return self.opened <= t < self.closed

    def delta(self, key: str) -> float:
        return self.at_close[key] - self.at_open[key]

    def token_times(self, req) -> List[float]:
        """Host time of each of the request's tokens, first one included."""
        if req.first_token_s < 0:
            return []
        return [req.first_token_s] + self.tokens.get(req.request_id, [])


def percentile(values, q: float) -> Optional[float]:
    import numpy as np
    return float(np.percentile(np.asarray(values, float), q)) if len(values) else None


# -- one run -----------------------------------------------------------------

def run(workload: str, seed: int, seconds: float, trace: bool,
        t_start: float, require_tpu: bool = True, rate: Optional[float] = None,
        n_req: Optional[int] = None, log=print, root: str = ROOT,
        control: bool = False) -> dict:
    """One run of one cell; returns the result line as a dict. `rate` and
    `n_req` override the cell's offered load (for finding its capacity);
    `root` is the checkout whose BENCHMARK.json and bench/ files are read.
    With `control` the tokens that the lower-precision control puts first
    on the same sample stand in for the served ones: `correct` and `checks`
    then judge the control, which has to come out as not correct."""
    import jax
    import numpy as np

    from bench.lib import check, peaks
    from bench.models.common import CONTROL, Reference
    from bench.traffic import generator

    bench = load_benchmark(root)
    cell = Cell.load(bench, workload, root)
    devs = jax.devices()
    d0 = devs[0]
    device = {"platform": d0.platform, "kind": d0.device_kind, "count": len(devs)}
    log(f"device: {device}")
    log(f"host memory: {os.sysconf('SC_PAGE_SIZE') * os.sysconf('SC_PHYS_PAGES')} bytes")
    if require_tpu and (d0.platform != "tpu" or len(devs) < cell.chips):
        raise NoDevice(f"cell {workload} needs {cell.chips} TPU chip(s); JAX "
                       f"found {len(devs)} {d0.platform} device(s)")
    pk = peaks.peak(d0.device_kind) if require_tpu else None
    compiles = CompileCounter()
    tr = cell.traffic
    rate = tr["rate_rps"] if rate is None else rate
    n = n_req or generator.n_requests(tr, seconds, rate)
    specs = generator.schedule(tr, seed, cell.cfg["vocab_size"], n, rate)

    eng, srv = build_engine(cell, seed)
    mem = d0.memory_stats() or {}
    log(f"build: {time.perf_counter() - t_start:.1f}s since start; slots "
        f"{cell.cfg['slots_per_layer']}/layer; {mem.get('bytes_in_use')} of "
        f"{mem.get('bytes_limit')} device bytes in use")
    warm_up(cell, eng, srv, seed)
    log(f"warm: {time.perf_counter() - t_start:.1f}s since start; "
        f"{compiles.n} compiles, {compiles.seconds:.1f}s of backend compile")

    trace_dir = os.path.join(root, ".bench_cache", "trace")
    if trace:
        import shutil
        shutil.rmtree(trace_dir, ignore_errors=True)
    requests = make_requests(specs)
    rec = Recorder(srv, eng, tr["ramp_s"], seconds, compiles, traced=trace,
                   trace_dir=trace_dir)
    rec.install()
    try:
        srv.serve(requests)
    except WindowClosed:
        pass
    finally:
        rec._restore()
    if rec.opened is None:
        raise RuntimeError("serving ended before the window opened")
    rec.finish()
    setup_s = srv._t0 + rec.opened - t_start
    stats = d0.memory_stats() or {}
    mem_peak = int(stats.get("peak_bytes_in_use", 0))
    device["memory_peak_bytes"] = mem_peak
    shed = len(srv.batcher.shed)

    tr_red = None
    if trace:
        from jax.profiler import ProfileData
        from bench.lib import trace as trace_mod
        import glob
        paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
        try:
            tr_red = trace_mod.reduce(ProfileData.from_file(paths[0]))
        except ValueError:
            if require_tpu:
                raise
        if tr_red is not None:
            device["busy_s"] = tr_red["busy_s"]
            device["window_s"] = tr_red["window_s"]

    ctx = Ctx(cell.cfg, cell.model, pk, setup_s, rec.opened, rec.closed,
              requests, rec.tokens, rec.ctx_len, rec.prompt_pos, rec.spans,
              rec.bw, rec.at_open, rec.at_close, tr_red)
    metrics = {}
    for m in metrics_for(bench, workload, trace):
        v = load_module(root, "metrics", m["name"] + ".py").read(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    window_compiles = rec.at_close["compiles"] - rec.at_open["compiles"]
    if window_compiles:
        log(f"compiled inside the window: {compiles.names[rec.at_open['compiles']:rec.at_close['compiles']]}")
    attempted = sum(1 for r in requests if ctx.inside(r.arrival_s))
    finished = [r for r in requests if 0 <= r.finish_s < rec.closed]
    log(f"window: {ctx.seconds:.2f}s from t={rec.opened:.2f}s; {attempted} "
        f"arrivals, {len(finished)} finished so far, {window_compiles} "
        f"compiles inside; memory peak {mem_peak}")

    # free the program before the reference runs: its peak is read
    del eng, srv, rec
    gc.collect()

    lim = cell.limits["served_tokens"]
    rows = lim["rows"]
    chosen = check.sample_requests(finished, seed, rows, lim["tokens"])
    t_max = generator.max_seq(tr)
    bad = [r.request_id for r in chosen
           if len(r.output) != r.max_new_tokens
           or not all(0 <= t < cell.cfg["vocab_size"] for t in r.output)]
    t0 = time.perf_counter()
    low = Reference(cell.model, cell.cfg, CONTROL[cell.cfg["torch_dtype"]]) \
        if control else None
    res = check.served_gap(Reference(cell.model, cell.cfg), seed, chosen, rows,
                           t_max, low) if chosen else {"served_tokens": 0}
    log(f"reference: {time.perf_counter() - t0:.1f}s for "
        f"{res['served_tokens']} served tokens in {len(chosen)} requests")
    compare = ("control_" if control else "") + lim["compare"]
    value = res.get(compare, math.inf)
    checks = {compare: [value, lim["limit"]],
              "served_tokens_checked": [res["served_tokens"], lim["min_tokens"]],
              "bad_requests": [len(bad), 0],
              "window_compiles": [window_compiles, 0]}
    correct = (value <= lim["limit"] and not bad and not window_compiles
               and res["served_tokens"] >= lim["min_tokens"])
    out = {"correct": bool(correct), "attempted": attempted,
           "failed": shed + len(bad), "metrics": metrics, "device": device}
    if tr_red is not None:
        out["breakdown"] = {"device_ops": tr_red["device_ops"],
                            "idle_gaps": tr_red["idle_gaps"]}
    log(f"served tokens vs reference: widest gap {res.get('max_gap')}, mean "
        f"gap {res.get('mean_gap')}, share off the best {res.get('mismatch_share')}")
    if control:
        out["control"] = {k: res.get(k) for k in
                          ("control_max_gap", "control_mean_gap",
                           "control_mismatch_share", "max_gap", "mean_gap",
                           "mismatch_share")}
    out["checks"] = checks
    return out
