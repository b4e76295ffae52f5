"""Fixtures for the benchmark's CPU tests (run them with
`JAX_PLATFORMS=cpu python -m pytest bench/tests`; the repository's
`pytest.ini` collects only `tests/`).

`checkout` builds a checkout in a temporary directory: the benchmark's
files as they are, BENCHMARK.json with two smoke cells added, and the
smoke configurations, traffic and limits of `data/` dropped in as files.
"""
from __future__ import annotations

import json
import os
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
DATA = os.path.join(HERE, "data")
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

SMOKE_CELLS = {"olmoe-smoke": "olmoe-smoke", "dsv2-smoke": "dsv2-smoke"}


def add_cell(root: str, bench: dict, cell: str, config: str,
             traffic: str = "smoke-traffic", limits: str = "smoke-limits"):
    """Drop a cell's files into the checkout at `root` and name it in
    `bench` (BENCHMARK.json's content)."""
    b = os.path.join(root, "bench")
    shutil.copy(os.path.join(DATA, config + ".json"),
                os.path.join(b, "configs", config + ".json"))
    shutil.copy(os.path.join(DATA, traffic + ".json"),
                os.path.join(b, "traffic", cell + ".json"))
    shutil.copy(os.path.join(DATA, limits + ".json"),
                os.path.join(b, "limits", cell + ".json"))
    if not any(c["name"] == config for c in bench["configs"]):
        bench["configs"].append({"name": config, "source": "test",
                                 "file": f"bench/configs/{config}.json",
                                 "reduced": [], "why": "smoke size"})
    bench["workloads"].append({"name": cell, "config": config,
                               "traffic": cell, "chips": 1, "why": "smoke"})


def make_checkout(tmp_path, with_src: bool = True) -> str:
    root = str(tmp_path / "checkout")
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    if with_src:
        os.symlink(os.path.join(ROOT, "src"), os.path.join(root, "src"))
    return root


@pytest.fixture
def checkout(tmp_path):
    root = make_checkout(tmp_path)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for cell, config in SMOKE_CELLS.items():
        add_cell(root, bench, cell, config)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root
