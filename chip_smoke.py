"""Serve the paper's networks on one TPU through the ``Flow`` entry points.

    python chip_smoke.py

For ``jet_tagger``, ``svhn_cnn`` at the published 32x32x3 frame and
full-size ``mlp_mixer_jet`` (the widths of ``repro/nn/models.py``), with
weights made from a fixed seed:

1. ``Flow.compile`` with the default solver and the "cheap" verify tier;
2. ``design.save`` under the checkout, then ``Flow.load`` (zero solver
   calls);
3. ``Flow.serve`` with ``fallback="none"`` and two batch buckets, then
   ``register(..., warmup=True)``, which compiles both bucket shapes;
4. a few ``infer`` calls and one ``submit_batch`` of a few hundred
   requests: seeded integers on the input grid;
5. every reply is compared bit for bit with the plain numpy reference
   (``repro.nn.interpreter.numpy_forward_fn``); no batch may take the
   fallback and no request may be shed or fail.

Each network prints one JSON line.  The last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Any failed phase raises, so the script exits non-zero without that
line.  When the first JAX device is not a TPU, every phase still runs,
then the script exits 1 and says why: there is no CPU fallback.

Everything runs in this one process, which starts no other.  JAX's
compilation cache goes where ``repro.runtime.compile_cache`` puts it,
so a second run in the same checkout finds the executables of the first.
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.flow import CompileConfig, Flow, ServeConfig  # noqa: E402
from repro.nn import init_params, models  # noqa: E402
from repro.nn.interpreter import numpy_forward_fn  # noqa: E402
from repro.runtime.compile_cache import enable_compile_cache  # noqa: E402

NETWORKS = {
    "jet_tagger": models.jet_tagger,
    "svhn_cnn": lambda: models.svhn_cnn(full_frame=True),
    "mlp_mixer_jet": lambda: models.mlp_mixer_jet(full_size=True),
}
SEED = 0
BUCKETS = (16, 256)  # warmup compiles these two shapes only
N_INFER = 4
N_BATCH = 300
ARTIFACT_DIR = ROOT / ".smoke_designs"


def _check(ok: bool, msg: str) -> None:
    if not ok:
        raise RuntimeError(msg)


def serve_network(name: str, builder, artifact_dir: Path, seed: int = SEED) -> dict:
    """Compile, round-trip and serve one network; check every reply.

    Raises on any failed phase.  Returns the network's result line,
    with ``platform`` the platform of the device that holds the jitted
    output (the caller decides whether that is acceptable)."""
    model, in_shape, in_quant = builder()
    params, _ = init_params(jax.random.PRNGKey(seed), model, in_shape)
    t0 = time.perf_counter()
    design = Flow.compile(
        model, params, in_shape, in_quant, config=CompileConfig(verify="cheap")
    )
    compile_s = time.perf_counter() - t0

    path = Path(artifact_dir) / name
    design.save(path)
    loaded = Flow.load(path)
    n_solves = loaded.solver_stats["n_solves"]
    _check(n_solves == 0, f"{name}: Flow.load ran {n_solves} solver calls")

    grid = in_quant.qint
    rng = np.random.default_rng(seed)
    xs = rng.integers(
        grid.lo, grid.hi, size=(N_INFER + N_BATCH, *in_shape), endpoint=True
    ).astype(np.int32)

    cfg = ServeConfig(max_batch=BUCKETS[-1], buckets=BUCKETS, fallback="none")
    with Flow.serve(cfg) as dep:
        t0 = time.perf_counter()
        dep.register(name, loaded, warmup=True)
        warmup_s = time.perf_counter() - t0
        replies = [dep.infer(name, x) for x in xs[:N_INFER]]
        futures = dep.submit_batch(name, xs[N_INFER:])
        replies += [f.result(timeout=120) for f in futures]
        stats = dep.stats(name)

    got = np.stack(replies)
    want = numpy_forward_fn(loaded)(xs)
    bitexact = got.dtype == want.dtype and np.array_equal(got, want)
    _check(bitexact, f"{name}: served replies differ from numpy_forward_fn")
    _check(stats["n_requests"] == len(xs), f"{name}: served {stats['n_requests']} of {len(xs)}")
    for key in ("n_fallback_batches", "n_shed", "n_rejected", "n_fast_failed",
                "n_client_timeouts"):
        _check(stats[key] == 0, f"{name}: stats {key} = {stats[key]}")

    # the serve path's own jitted forward (same executable: a warmed bucket)
    out = jax.jit(loaded.forward_int)(xs[: BUCKETS[0]])
    platforms = sorted({d.platform for d in out.devices()})
    return {
        "network": name,
        "compile_s": compile_s,
        "warmup_s": warmup_s,
        "adders": loaded.total_adders,
        "n_checked": int(got.shape[0]),
        "bitexact": bool(bitexact),
        "n_fallback_batches": stats["n_fallback_batches"],
        "platform": ",".join(platforms),
    }


def main() -> int:
    cache_dir = enable_compile_cache()
    dev = jax.devices()[0]
    results = []
    for name, builder in NETWORKS.items():
        r = serve_network(name, builder, ARTIFACT_DIR)
        r.update(device_kind=dev.device_kind, cache_dir=cache_dir)
        print(json.dumps(r), flush=True)
        results.append(r)
    off_chip = [r["network"] for r in results if r["platform"] != "tpu"]
    if dev.platform != "tpu" or off_chip:
        print(
            f"chip_smoke: FAILED: the first JAX device is {dev.platform!r} "
            f"({dev.device_kind}), not a TPU; outputs off the TPU: {off_chip}",
            file=sys.stderr,
        )
        return 1
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": dev.platform,
            "kind": dev.device_kind,
            "count": jax.device_count(),
        },
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
