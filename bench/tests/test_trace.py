"""From a trace to busy time and idle gaps: on intervals by hand, and on a
small trace recorded on a TPU v5e (three 1024x1024 bf16 matmul steps under
harness spans, with 2 ms host sleeps between them)."""
import os
from types import SimpleNamespace as NS

import pytest

from bench.lib import trace

SMALL = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "small.xplane.pb")


def test_union_and_gaps_by_hand():
    iv = [(5, 7), (0, 2), (1, 3), (6, 9), (12, 20)]
    assert trace.union(iv, 0, 15) == [(0, 3), (5, 9), (12, 15)]
    assert trace.gaps(trace.union(iv, 0, 15), 0, 15) == [(3, 5), (9, 12)]
    assert trace.gaps([], 0, 4) == [(0, 4)]


def _ev(name, s, e):
    return NS(name=name, start_ns=s, duration_ns=e - s)


def _pd(ops, spans):
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[_ev("jit_fn", 0, 100)]),
        NS(name="XLA Ops", events=[_ev(*o) for o in ops])])
    host = NS(name="/host:CPU", lines=[NS(name="python",
                                          events=[_ev(*s) for s in spans])])
    return NS(planes=[host, dev])


def test_reduce_by_hand():
    pd = _pd(ops=[("dot", 10, 30), ("add", 25, 40), ("dot", 60, 80),
                  ("dot", 200, 300)],
             spans=[("bench.window", 0, 100), ("bench.decode_step", 5, 50),
                    ("bench.swap_write", 42, 48), ("bench.sample", 50, 90)])
    r = trace.reduce(pd)
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx(50e-9)          # [10,40] + [60,80]
    assert dict(r["device_ops"]) == pytest.approx({"dot": 40e-9, "add": 15e-9})
    # gaps [0,10] and [40,60] in decode_step (their middles 5 and 50->
    # sample starts at 50), [80,100] in none
    idle = dict(r["idle_gaps"])
    assert sum(idle.values()) == pytest.approx(50e-9)
    assert idle["decode_step"] == pytest.approx(10e-9)
    assert idle["sample"] == pytest.approx(20e-9)
    assert idle["none"] == pytest.approx(20e-9)


def test_reduce_refuses_a_trace_without_device_work():
    with pytest.raises(ValueError):
        trace.reduce(_pd(ops=[], spans=[("bench.window", 0, 10)]))


def test_reduce_recorded_tpu_trace():
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(SMALL)
    r = trace.reduce(pd)
    ops = trace.device_ops(pd)
    assert list(ops) == ["/device:TPU:0"]
    win = [s for s in trace.host_spans(pd) if s[0] == "bench.window"][0]
    iv = [(s, e) for _, s, e in ops["/device:TPU:0"]]
    busy = sum(e - s for s, e in trace.union(iv, win[1], win[2]))
    assert r["busy_s"] == pytest.approx(busy / 1e9)
    assert r["window_s"] == pytest.approx((win[2] - win[1]) / 1e9)
    assert 0 < r["busy_s"] < r["window_s"]
    assert sum(v for _, v in r["idle_gaps"]) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-9)
    labels = {k for k, _ in r["idle_gaps"]}
    assert "sample" in labels          # the host's sleeps leave the chip idle
