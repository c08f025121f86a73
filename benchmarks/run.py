"""Benchmark harness: one module per paper table/figure.

    table2_random_matrices  Table 2  (adders/depth/runtime vs H_cmvm)
    table3_4_resources      Tables 3-4 (resource proxies, 8/4-bit)
    tables5_12_networks     Tables 5-12 (network-level DA vs latency)
    fig7_runtime_scaling    Fig. 7 (solver runtime scaling)
    solver_smoke            solver fast-path wall-clock budget check
    serve_load              artifact round-trip + microbatched serve load
    rtl_cosim               RTL co-simulation gate (three-way bit-exact)
    obs_trace               telemetry layer gate (trace/metrics/flight)
    lint_designs            static design-verifier gate (repro.analysis)
    chaos_soak              fault-injection soak gate (repro.chaos)
    lm_step_bench           framework substrate microbench

Prints ``name,us_per_call,derived`` CSV.  ``run.py smoke --json PATH``
additionally writes the smoke result as JSON (the CI perf artifact) AND
refreshes ``BENCH_solver.json`` at the repo root — the committed perf
baseline that ``benchmarks/perf_gate.py`` compares future runs against
(solve seconds, adder counts, and cost bits per size and engine).
Exits 1 if the smoke budget/exactness/engine-equivalence gate fails.
JAX's persistent compilation cache is placed by
``repro.runtime.compile_cache.enable_compile_cache`` before the first
bench that compiles (``JAX_COMPILATION_CACHE_DIR`` or ``.jax_cache/`` at
the repo root); the numpy-only ``smoke`` never imports JAX.
Roofline numbers live in EXPERIMENTS.md (derived from the dry-run, see
repro.launch.dryrun).
"""

from __future__ import annotations

import importlib
import sys
from pathlib import Path

_REPO_ROOT = Path(__file__).resolve().parent.parent
BENCH_SOLVER_JSON = _REPO_ROOT / "BENCH_solver.json"
# benches whose --json run also refreshes a committed trajectory baseline
# (only when the gate passed, so a regressing run never poisons the ref)
_BASELINES = {
    "smoke": BENCH_SOLVER_JSON,
    "rtl": _REPO_ROOT / "BENCH_rtl.json",
    "chaos": _REPO_ROOT / "BENCH_chaos.json",
}


def main() -> None:
    args = [a for a in sys.argv[1:]]
    json_path = None
    if "--json" in args:
        k = args.index("--json")
        if k + 1 >= len(args):
            sys.exit("usage: benchmarks.run [name] --json PATH")
        json_path = args[k + 1]
        del args[k : k + 2]
    only = args[0] if args else None
    # modules are imported lazily so jax-free benches (e.g. `smoke`, which
    # only needs numpy + repro.core) run in minimal environments
    if only != "smoke":
        from repro.runtime.compile_cache import enable_compile_cache

        enable_compile_cache()  # before the first compile, which fixes it
    mods = {
        "table2": "table2_random_matrices",
        "table34": "table3_4_resources",
        "networks": "tables5_12_networks",
        "fig7": "fig7_runtime_scaling",
        "smoke": "solver_smoke",
        "serve": "serve_load",
        "rtl": "rtl_cosim",
        "obs": "obs_trace",
        "lint": "lint_designs",
        "chaos": "chaos_soak",
        "lm": "lm_step_bench",
    }
    failed = False
    for name, modname in mods.items():
        if only and only != name:
            continue
        mod = importlib.import_module(f".{modname}", __package__)
        print(f"# --- {name} ({mod.__name__}) ---", flush=True)
        if name in ("smoke", "serve", "rtl", "obs", "lint", "chaos"):
            # gated benches: JSON artifact + exit-1 on budget/exactness
            # failure.  --json targets the explicitly selected bench
            # (or smoke, the historical default, when running all).
            jp = json_path if (only == name or (name == "smoke" and only is None)) else None
            result = mod.main(json_path=jp)
            ok = mod.passed(result)
            if name in _BASELINES and jp is not None and ok:
                # --json runs refresh the committed perf baseline — but
                # only when the gate passed, so a regressing run can
                # never poison the reference
                import json as _json

                with open(_BASELINES[name], "w") as fh:
                    _json.dump(result, fh, indent=2, sort_keys=True)
                print(
                    f"# refreshed {_BASELINES[name]} with this run — "
                    "solver timings are machine-specific (commit those only "
                    "from the canonical perf box); rtl numbers are "
                    "deterministic",
                    file=sys.stderr,
                )
            failed = failed or not ok
        else:
            mod.main()
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
