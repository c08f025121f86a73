"""Run one cell of the benchmark once and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``.  The last
line of standard output is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` ``breakdown``,
and ``checks`` last); the last lines of standard error give each
compared number beside its limit.  Without a TPU, or with fewer chips
than the cell asks for, the run prints no result and exits 3.

JAX's persistent compilation cache is kept in ``bench/.cache/jax`` of
the checkout, a fixed path, so only a checkout's first run of a cell
compiles.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
EXIT_NO_CHIP = 3


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / "bench" / ".cache" / "jax")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    # cache every executable, however quickly it compiled: set-up then
    # finds all of them on every run after a checkout's first
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from bench.harness import check
    from bench.harness.runner import NoChip, run_cell

    try:
        line = run_cell(args.workload, args.seed, args.seconds, bool(args.trace), ROOT, T_PROCESS)
    except NoChip as e:
        print(f"bench: {e}; no result", file=sys.stderr)
        return EXIT_NO_CHIP
    print(json.dumps(line), flush=True)
    numbers = {k: v["value"] for k, v in line["checks"].items()}
    print("\n".join(check.lines(numbers)), file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
