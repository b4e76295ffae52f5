#!/usr/bin/env python3
"""Run one cell of the benchmark on the machine it is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic and metrics are named in
BENCHMARK.json at the root of the checkout. Earlier lines of standard
output say what the run did; the last is one JSON object with `correct`,
`attempted`, `failed`, `metrics` and `device` (and, with `--trace 1`,
`breakdown`), and last of all `checks`: each number compared, beside its
limit. The same numbers end standard error. Without a TPU, or with fewer
chips than the cell asks for, the run prints no result and exits 3.

`--rate` (requests/s, or `inf` for a standing backlog) and `--requests`
replace the cell's offered load; they serve to find a cell's capacity.
`--control 1` judges the tokens that the lower-precision control puts
first in place of the served ones: such a run has to print `correct` false.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
# a fixed directory inside the checkout: only a cell's first run compiles
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".bench_cache", "jax")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rate", type=float, default=None)
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from bench.lib import harness
    try:
        out = harness.run(args.workload, args.seed, args.seconds,
                          bool(args.trace), T_START, rate=args.rate,
                          n_req=args.requests, control=bool(args.control))
    except harness.NoDevice as e:
        print(f"no result: {e}", file=sys.stderr)
        return 3
    sys.stdout.flush()
    for name, (value, limit) in out["checks"].items():
        print(f"check {name}: {value} (limit {limit})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
