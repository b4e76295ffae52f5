"""The harness is driven by data: a new configuration, traffic mix and
metric reader are files, found by the names in BENCHMARK.json. And it
prints no result where it cannot measure."""
import filecmp
import json
import os
import shutil
import subprocess
import sys
import time

from bench.lib import harness
from bench.tests.conftest import DATA, ROOT, add_cell, make_checkout

READER = '''"""Host syncs per step: blocking device-to-host pulls in the window."""


def read(ctx):
    return ctx.delta("host_syncs") / max(1, len(ctx.spans))
'''


def test_new_config_traffic_and_metric_are_files_only(tmp_path):
    root = make_checkout(tmp_path)
    before = {p: open(os.path.join(root, p), "rb").read()
              for p in ("bench/lib/harness.py", "bench/run.py")}
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # a configuration, a traffic mix, a limit and a metric: new files only
    add_cell(root, bench, "dsv2-new", "dsv2-smoke")
    with open(os.path.join(root, "bench", "metrics", "host_syncs_per_step.py"), "w") as f:
        f.write(READER)
    bench["per_layer"].append({
        "name": "host_syncs_per_step", "unit": "syncs/step", "better": "lower",
        "source": "program_counter", "layer": "engine", "moves": "itl_p95_ms",
        "workloads": ["dsv2-new"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    out = harness.run("dsv2-new", 3, 3.0, True, time.perf_counter(),
                      require_tpu=False, root=root, log=lambda *a: None)
    assert out["correct"]
    assert out["metrics"]["host_syncs_per_step"]["unit"] == "syncs/step"
    assert out["metrics"]["host_syncs_per_step"]["value"] > 0
    assert "decode_step_p50_ms" in out["metrics"]
    # every file the benchmark already had is as it was
    cmp = filecmp.dircmp(os.path.join(ROOT, "bench"), os.path.join(root, "bench"),
                         ignore=["__pycache__", "tests"])

    def same(c):
        assert not c.diff_files, c.diff_files
        assert not c.left_only, c.left_only
        for sub in c.subdirs.values():
            same(sub)
    same(cmp)
    for p, data in before.items():
        assert open(os.path.join(root, p), "rb").read() == data


def _cli(root, *args, env=None):
    e = dict(os.environ, JAX_PLATFORMS="cpu")
    e.update(env or {})
    return subprocess.run([sys.executable, os.path.join(root, "bench", "run.py"),
                           *args], cwd=root, env=e, capture_output=True,
                          text=True, timeout=300)


def _has_result(stdout):
    lines = stdout.strip().splitlines()
    if not lines:
        return False
    try:
        return isinstance(json.loads(lines[-1]), dict)
    except ValueError:
        return False


def test_refuses_without_a_tpu(tmp_path):
    root = make_checkout(tmp_path)
    p = _cli(root, "--workload", "olmoe-decode", "--seed", str(2 ** 33 + 1),
             "--seconds", "51", "--trace", "0")
    assert p.returncode == 3, (p.returncode, p.stderr[-2000:])
    assert not _has_result(p.stdout)
    assert "device: {'platform': 'cpu', 'kind': 'cpu', 'count': 1}" in p.stdout
    assert "no result" in p.stderr


def test_fails_with_only_the_benchmark_files(tmp_path):
    root = make_checkout(tmp_path, with_src=False)
    p = _cli(root, "--workload", "olmoe-decode", "--seed", "1",
             "--seconds", "51", "--trace", "0")
    assert p.returncode != 0
    assert not _has_result(p.stdout)
