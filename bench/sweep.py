"""Find the knee of an online cell: the highest rate its engine sustains.

    python bench/sweep.py --workload <online cell> --rates 5000,10000,... \
        --seconds 5 --seed <n>

One process, one engine, the cell's own configuration and serving
settings; each rate runs the cell's open loop for ``--seconds`` and
prints one JSON line.  A rate is sustained when every request is
answered, none fails, the answers keep pace with the arrivals (the
last fifth of the requests waits no longer than the middle of the
window, and the queue is empty soon after the last arrival).  Cells are
set at about four fifths of the knee found here, once, when a cell is
defined; the benchmark's own runs never search for a rate.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / "bench" / ".cache" / "jax")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    import numpy as np

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from bench.harness import loadgen, network, runner
    from bench.harness.cell import load_cell
    from repro.flow import Flow, ServeConfig

    cell = load_cell(args.workload, ROOT)
    t = cell.traffic
    if t["kind"] != "open_poisson":
        raise SystemExit(f"{args.workload} is not an online cell")
    name = cell.config["name"]
    design, _ = network.load_design(cell.config, ROOT)
    rng = np.random.default_rng(args.seed)
    pool = runner.events(cell.config, t["pool_events"], rng)
    serve = dict(t["serve"], buckets=tuple(t["serve"]["buckets"]))
    dev = jax.devices()[0]
    with Flow.serve(ServeConfig(**serve)) as dep:
        dep.register(name, design, warmup=True)

        def submit(x):
            return dep.submit(name, x)

        for rate in [float(r) for r in args.rates.split(",")]:
            due = loadgen.schedule(rate, args.seconds, rng)
            pick = rng.integers(0, len(pool), size=len(due))
            s0 = dep.stats(name)
            run = loadgen.open_loop(submit, lambda i, p=pick: pool[p[i]], due,
                                    tuple(design.out_shape))
            s1 = dep.stats(name)
            lat = run.latency_s * 1e3
            n = len(lat)
            mid, last = lat[n * 2 // 5 : n * 3 // 5], lat[n * 4 // 5 :]
            answered = int(np.isfinite(lat).sum())
            drain_ms = (np.nanmax(run.done) - run.due[-1]) * 1e3
            sustained = bool(
                answered == n
                and np.percentile(last, 50) <= 1.2 * np.percentile(mid, 50) + 0.5
                and drain_ms < 50.0
            )
            print(json.dumps({
                "workload": args.workload, "rate_per_s": rate, "requests": n,
                "answered": answered, "answered_per_s": answered / run.seconds,
                "p50_ms": float(np.percentile(lat, 50)), "p95_ms": float(np.percentile(lat, 95)),
                "p99_ms": float(np.percentile(lat, 99)),
                "p50_ms_middle": float(np.percentile(mid, 50)),
                "p50_ms_last_fifth": float(np.percentile(last, 50)),
                "drain_ms": float(drain_ms),
                "lag_p99_ms": float(np.percentile(run.lag_s, 99)) * 1e3,
                "batches": s1["n_batches"] - s0["n_batches"],
                "sustained": sustained, "device": dev.device_kind,
            }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
