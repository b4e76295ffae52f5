#!/usr/bin/env python3
"""Readings that a cell's comparison limit is set from, several seeds in one
process: for each seed one run of the cell in control mode (short window,
the cell's own load). It gives the gaps of the served tokens under the
reference, the gaps of the tokens that the lower-precision control puts
first on the same prompts and tokens, and the run's verdict, which judges
the control's tokens by the cell's limit and has to be `correct: false`.

    python3 bench/calibrate.py --workload <cell> --seconds 20 --seeds 1 2 3

Prints one JSON line per seed. The benchmark's own runs do not run this.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".bench_cache", "jax")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from bench.lib import harness
    for seed in args.seeds:
        t0 = time.perf_counter()
        out = harness.run(args.workload, seed, args.seconds, False, t0,
                          control=True)
        line = {"seed": seed, "control_correct": out["correct"],
                "checks": out["checks"],
                "served_tokens": out["checks"]["served_tokens_checked"][0],
                **out.get("control", {}),
                "metrics": {k: v["value"] for k, v in out["metrics"].items()},
                "memory_peak_bytes": out["device"]["memory_peak_bytes"],
                "run_s": time.perf_counter() - t0}
        print("CALIBRATE " + json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
