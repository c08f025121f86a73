"""The control of the correctness check, at a cell's own size.

    python bench/control.py --workload <cell> --seeds 1,2,3 --seconds 10 [--calls K]

For each seed it draws the cell's inputs exactly as a run does, puts the
reference computed in a lower precision in the program's place, and
judges those answers with the run's own comparison against the float64
reference.  ``bfloat16`` (matrix products on bfloat16 operands and
results, float32 accumulation) is the control: it has to come out not
correct.  ``float32`` is printed beside it: every value of these
networks is a dyadic rational that float32 holds exactly, so it reads 0
and cannot serve as a control.  A bulk cell compares ``--calls`` chunks,
as many as a run answers.  Needs no chip; the benchmark's runs never
run it.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def readings(workload: str, seed: int, seconds: float, calls: int, root: Path = ROOT) -> dict:
    """Mismatched answers of each lower precision in the program's place."""
    import numpy as np

    from bench.harness import check, network, reference, runner
    from bench.harness.cell import load_cell

    cell = load_cell(workload, root)
    rng = runner.rng_of(seed)
    params = network.make_params(cell.config, root)
    if cell.traffic["kind"] == "open_poisson":
        pool, _due, pick, _, _ = runner.draw_online(cell, seconds, rng)
        rows = [pick]
    else:
        pool, offsets = runner.draw_bulk(cell, rng)
        chunk = int(cell.traffic["chunk_events"])
        rows = [np.arange(o, o + chunk) for o in offsets[:calls]]
    want = reference.forward(cell.config, params, pool, root=root)
    out = {"workload": workload, "seed": seed, "answers": int(sum(len(r) for r in rows))}
    for precision in ("float32", "bfloat16"):
        got = reference.forward(cell.config, params, pool, precision, root)
        mism = sum(check.mismatched(got[r], 1.0, want[r]) for r in rows)
        numbers = {"mismatched": mism, "failed": 0}
        out[precision] = {"mismatched": mism, "correct": check.verdict(numbers)}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--calls", type=int, default=100)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(readings(args.workload, seed, args.seconds, args.calls)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
