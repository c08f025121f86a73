"""Distributed correctness on a virtual multi-device CPU mesh.

Runs in a subprocess (XLA_FLAGS must be set before jax initialises) and
checks that the *sharded* train/decode paths produce the same numbers as
the unsharded ones — i.e. the sharding rules change layout, not math —
and that checkpoints written under one mesh restore under another.
"""

import json
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro import configs
from repro.configs.base import RunConfig
from repro.distributed import MeshRules, use_rules
from repro.launch.mesh import make_test_mesh
from repro.models import init_params, param_shardings, loss_fn, decode_step
from repro.models.transformer import prefill
from repro.train.train_lib import make_train_step
from repro.train import checkpoint

cfg = configs.get_smoke("qwen3-moe-30b-a3b")  # MoE: hardest sharding path
run_cfg = RunConfig(learning_rate=1e-3, warmup_steps=1)
params = init_params(cfg, jax.random.PRNGKey(0))
key = jax.random.PRNGKey(1)
batch = {
    "tokens": jax.random.randint(key, (4, 16), 0, cfg.vocab_size),
    "labels": jax.random.randint(key, (4, 16), 0, cfg.vocab_size),
}
step_fn, opt_init = make_train_step(cfg, run_cfg)

# --- single device reference ---
p1, o1, m1 = jax.jit(step_fn)(params, opt_init(params), batch, 0)
ref_loss = float(m1["loss"])

# --- sharded on a 2x4 (data x model) mesh ---
mesh = make_test_mesh(2, 4)
rules = MeshRules(mesh)
with use_rules(rules):
    p_sh = param_shardings(cfg, rules)
    params_s = jax.device_put(params, p_sh)
    opt_s = jax.jit(opt_init, out_shardings=None)(params_s)
    batch_s = jax.device_put(
        batch, jax.tree.map(lambda x: rules.sharding(("batch",) + (None,)*(x.ndim-1), x.shape), batch)
    )
    p2, o2, m2 = jax.jit(step_fn)(params_s, opt_s, batch_s, 0)
    sh_loss = float(m2["loss"])

# params must match elementwise after the update
dmax = max(
    float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max())
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2))
)

# --- decode equivalence under sharding ---
with use_rules(rules):
    lg_s, cache_s = jax.jit(lambda p, b: prefill(cfg, p, b, 24))(params_s, batch_s)
lg_r, cache_r = jax.jit(lambda p, b: prefill(cfg, p, b, 24))(params, batch)
dec_diff = float(jnp.abs(lg_s - lg_r).max())

# --- checkpoint written sharded, restored unsharded (reshard) ---
import tempfile, shutil
d = tempfile.mkdtemp()
checkpoint.save(d, 1, {"p": p2})
restored = checkpoint.restore(d, 1, {"p": p1})
ck_diff = max(
    float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max())
    for a, b in zip(jax.tree.leaves(p2), jax.tree.leaves(restored["p"]))
)
shutil.rmtree(d)

print(json.dumps({
    "ref_loss": ref_loss, "sh_loss": sh_loss, "param_dmax": dmax,
    "decode_dmax": dec_diff, "ckpt_dmax": ck_diff,
    "n_dev": jax.device_count(),
}))
"""


@pytest.fixture(scope="module")
def dist_result():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT], capture_output=True, text=True,
        env=env, timeout=900,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_virtual_mesh_active(dist_result):
    assert dist_result["n_dev"] == 8


def test_sharded_train_step_matches_reference(dist_result):
    assert abs(dist_result["ref_loss"] - dist_result["sh_loss"]) < 1e-4
    assert dist_result["param_dmax"] < 5e-5


def test_sharded_decode_matches_reference(dist_result):
    assert dist_result["decode_dmax"] < 1e-3


def test_checkpoint_reshard_roundtrip(dist_result):
    assert dist_result["ckpt_dmax"] == 0.0
