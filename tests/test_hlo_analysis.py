"""Weighted HLO cost analysis: calibration against known-cost programs."""

import os
import subprocess
import sys

import json
import pytest

SCRIPT = r"""
import os, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch.hlo_analysis import analyze
from repro.launch.mesh import make_test_mesh

mesh = make_test_mesh(2, 4)  # Auto-axis mesh
out = {}

# 1) scan with known trip count: flops must be trips * body
W = jax.ShapeDtypeStruct((8, 512, 512), jnp.float32)
X = jax.ShapeDtypeStruct((128, 512), jnp.float32)
def f(w, x):
    return jax.lax.scan(lambda c, wi: (jnp.tanh(c @ wi), None), x, w)[0]


c = jax.jit(f, in_shardings=(NamedSharding(mesh, P(None, "data", "model")),
                             NamedSharding(mesh, P("data", None)))).lower(W, X).compile()
r = analyze(c.as_text(), 8)
out["scan_flops"] = r.flops
out["scan_expected"] = 8 * 2 * 128 * 512 * 512 / 8  # per chip

# 2) single sharded matmul: per-chip flops
A = jax.ShapeDtypeStruct((1024, 1024), jnp.float32)
g = jax.jit(lambda a, b: a @ b,
            in_shardings=(NamedSharding(mesh, P("data", None)),
                          NamedSharding(mesh, P(None, "model"))))
c2 = g.lower(A, A).compile()
r2 = analyze(c2.as_text(), 8)
out["mm_flops"] = r2.flops
out["mm_expected"] = 2 * 1024**3 / 8

# 3) explicit psum via constraint: nonzero collective bytes
h = jax.jit(lambda a: jax.lax.with_sharding_constraint(a.sum(axis=0), NamedSharding(mesh, P())),
            in_shardings=(NamedSharding(mesh, P("data", None)),))
c3 = h.lower(jax.ShapeDtypeStruct((128, 256), jnp.float32)).compile()
r3 = analyze(c3.as_text(), 8)
out["reduce_coll"] = r3.coll_wire_bytes
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def result():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_scan_trip_weighting_exact(result):
    assert result["scan_flops"] == pytest.approx(result["scan_expected"], rel=1e-6)


def test_single_matmul_per_chip(result):
    assert result["mm_flops"] == pytest.approx(result["mm_expected"], rel=1e-6)


def test_collectives_detected(result):
    assert result["reduce_coll"] > 0
