"""The program's spans, its named modules and the idle gaps they label, and
the four readers built on them: on traces built by hand, and on the small
trace recorded on a TPU v5e from a program without such spans or names."""
import os
from types import SimpleNamespace as NS

import pytest

from bench.lib import program_trace as pt
from bench.lib import trace
from bench.tests.test_metrics import make_ctx, reader
from bench.tests.test_trace import SMALL, _ev


def _pd(ops, spans, modules=(), planes=1):
    devs = [NS(name=f"/device:TPU:{i}", lines=[
        NS(name="XLA Modules", events=[_ev(*m) for m in modules]),
        NS(name="XLA Ops", events=[_ev(*o) for o in ops])]) for i in range(planes)]
    idle = NS(name=f"/device:TPU:{planes}", lines=[NS(name="XLA Ops", events=[])])
    host = NS(name="/host:CPU", lines=[NS(name="python",
                                          events=[_ev(*s) for s in spans])])
    return NS(planes=[host, *devs, idle])


# one decode step [0, 100) holding a mask pull, a sampled swap window and
# many dispatches; a prefill chunk [110, 150); the window [0, 200)
STEP = [("bench.window", 0, 200),
        ("engine.decode_step#step=3,rows=2,S=1#", 0, 100),
        ("engine.mask_pull#layer=0,rows=2#", 10, 20),
        ("engine.residency", 20, 62),
        ("swap.write#experts=2,bytes=8,sampled=1#", 30, 60),
        ("swap.drain", 30, 40), ("swap.sample_wait", 50, 60)] + \
       [("engine.dispatch#fn=moe_ffn_decode#", 62 + 0.25 * i, 62.1 + 0.25 * i)
        for i in range(70)] + \
       [("engine.prefill_chunk", 110, 150), ("jit_other", 0, 200),
        ("serve.sleep", 160, 170)]
MODULES = [("jit_moe_ffn_decode(123)", 20, 30), ("jit_moe_ffn_decode(123)", 60, 90),
           ("jit_moe_ffn_decode_kernel(9)", 95, 99), ("jit_moe_ffn_chunk(77)", 120, 150),
           ("jit_logits(5)", 190, 210)]
OPS = [("dot", 20, 30), ("dot", 60, 90), ("dot", 95, 99), ("dot", 120, 150),
       ("dot", 190, 210)]


def test_program_spans_cut_arguments_and_keep_the_program_prefixes():
    spans = pt.program_spans(_pd(OPS, STEP))
    names = [n for n, _, _ in spans]
    assert names[:3] == ["engine.decode_step", "engine.mask_pull", "engine.residency"]
    assert "bench.window" not in names and "jit_other" not in names
    assert names.count("engine.dispatch") == 70
    # two spans that start together: the outer first
    assert names.index("swap.write") + 1 == names.index("swap.drain")
    starts = [s for _, s, _ in spans]
    assert starts == sorted(starts)


def test_module_seconds_strip_the_hash_clip_and_average_over_busy_planes():
    m = pt.module_seconds(_pd(OPS, STEP, MODULES, planes=2), 0, 200)
    # each of the two busy planes holds the same modules; the idle plane
    # does not count
    assert m == pytest.approx({"jit_moe_ffn_decode": 40e-9,
                               "jit_moe_ffn_decode_kernel": 4e-9,
                               "jit_moe_ffn_chunk": 30e-9, "jit_logits": 10e-9})


def test_innermost_looks_past_any_number_of_closed_spans():
    spans = pt.program_spans(_pd(OPS, STEP))
    got = pt.innermost(spans, [5, 15, 35, 45, 61, 62.05, 62.2, 97, 105, 130, 165, 180])
    assert got == ["engine.decode_step", "engine.mask_pull", "swap.drain",
                   "swap.write", "engine.residency", "engine.dispatch",
                   "engine.decode_step", "engine.decode_step", "none",
                   "engine.prefill_chunk", "serve.sleep", "none"]
    # the harness's own labeller looks back 64 spans: past 70 dispatches it
    # no longer finds the step
    inner = [(n.replace("engine.", "bench."), s, e) for n, s, e in spans
             if n.startswith("engine.")]
    assert trace.label_at(inner, [s for _, s, _ in inner], 97) == "none"


def test_program_idle_gaps_by_hand():
    pd = _pd(OPS, STEP, MODULES, planes=2)
    gaps = dict(pt.program_idle_gaps(pd, 0, 200))
    # idle: [0,20) step (mid 10 -> mask_pull), [30,60) mid 45 -> swap.write,
    # [90,95) mid 92.5 -> step, [99,120) mid 109.5 -> none, [150,190) mid
    # 170 -> none (the sleep ends at 170)
    assert gaps == pytest.approx({"engine.mask_pull": 20e-9, "swap.write": 30e-9,
                                  "engine.decode_step": 5e-9, "none": 61e-9})
    assert sum(gaps.values()) == pytest.approx(
        trace.reduce(pd)["window_s"] - trace.reduce(pd)["busy_s"])


def test_self_seconds_take_out_only_the_named_descendants():
    spans = pt.program_spans(_pd(OPS, STEP))
    waits = ("engine.mask_pull", "swap.drain", "swap.sample_wait")
    assert pt.self_seconds(spans, "engine.decode_step", waits, 0, 200) == \
        pytest.approx([70e-9])
    # overlapping children count once; a span outside the window is left out
    more = spans + [("engine.decode_step", 300, 340), ("engine.mask_pull", 305, 320),
                    ("engine.mask_pull", 310, 330), ("swap.drain", 335, 345)]
    more.sort(key=lambda sp: (sp[1], -sp[2]))
    assert pt.self_seconds(more, "engine.decode_step", waits, 0, 400) == \
        pytest.approx([70e-9, 10e-9])
    assert pt.self_seconds(more, "engine.decode_step", waits, 200, 400) == \
        pytest.approx([10e-9])
    assert pt.covered_seconds(spans, ("swap.drain", "swap.sample_wait"), 0, 200) == \
        pytest.approx(20e-9)


def test_reduce_needs_a_window_and_device_work():
    assert pt.reduce(_pd(OPS, STEP[1:])) is None
    assert pt.reduce(_pd([], STEP)) is None
    r = pt.reduce(_pd(OPS, STEP, MODULES))
    assert (r["lo"], r["hi"]) == (0, 200)
    assert pt.count(r, "engine.decode_step") == 1


@pytest.fixture
def readers(monkeypatch):
    """The four readers, each reading the reduction `view` in place of the
    run's trace file."""
    view = {}
    monkeypatch.setattr(pt, "load", lambda ctx, where: view.get("r"))
    return view, {n: reader(n) for n in ("ffn_decode_ms_per_step", "ffn_chunk_ms_per_chunk",
                                         "decode_host_ms_per_step", "swap_wait_share")}


def test_readers_by_hand(readers):
    view, rd = readers
    ctx = make_ctx([], {}, trace={"busy_s": 0, "window_s": 0})
    view["r"] = pt.reduce(_pd(OPS, STEP, MODULES))
    # decode FFN 40 ns of plain and 4 of the kernel variant, one step
    assert rd["ffn_decode_ms_per_step"].read(ctx) == pytest.approx(44e-6)
    assert rd["ffn_chunk_ms_per_chunk"].read(ctx) == pytest.approx(30e-6)
    assert rd["decode_host_ms_per_step"].read(ctx) == pytest.approx(70e-6)
    assert rd["swap_wait_share"].read(ctx) == pytest.approx(100 * 20 / 200)


def test_readers_read_nothing_from_a_program_without_spans(readers):
    view, rd = readers
    ctx = make_ctx([], {}, trace={"busy_s": 0, "window_s": 0})
    from jax.profiler import ProfileData
    view["r"] = r = pt.reduce(ProfileData.from_file(SMALL))
    assert r["program_spans"] == [] and list(r["modules"]) == ["jit__lambda"]
    assert [k for k, _ in r["program_idle_gaps"]] == ["none"]
    for m in rd.values():
        assert m.read(ctx) is None
    view["r"] = None
    for m in rd.values():
        assert m.read(ctx) is None


def test_load_reads_the_run_trace_of_the_checkout(tmp_path):
    import shutil
    d = tmp_path / ".bench_cache" / "trace" / "plugins" / "profile" / "x"
    d.mkdir(parents=True)
    shutil.copy(SMALL, d / "host.xplane.pb")
    metrics = str(tmp_path / "bench" / "metrics")
    assert pt.load(make_ctx([], {}), metrics) is None          # untraced run
    r = pt.load(make_ctx([], {}, trace={"busy_s": 0, "window_s": 0}), metrics)
    assert r is not None and r["hi"] > r["lo"]
    elsewhere = os.path.join(str(tmp_path), "a", "bench", "metrics")
    assert pt.load(make_ctx([], {}, trace={}), elsewhere) is None
