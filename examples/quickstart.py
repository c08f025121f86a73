"""Quickstart: optimize one CMVM with da4ml and inspect everything.

    PYTHONPATH=src python examples/quickstart.py

Covers the full core API: solve, verify bit-exactness, compare against
the hls4ml latency-strategy baseline, execute the adder graph (through
the Pallas kernel in interpret mode on the CPU backend, through the jnp
executor elsewhere), pipeline, and emit synthesizable Verilog.
"""

import jax
import numpy as np

from repro.core import (
    emit_verilog,
    naive_adder_tree,
    pipeline,
    solve_cmvm,
)
from repro.flow import SolverConfig
from repro.kernels.adder_graph import adder_graph_apply, compile_tables

# --- a random 16x16 8-bit constant matrix (paper Table 2 convention) ---
rng = np.random.default_rng(42)
M = rng.integers(2**7 + 1, 2**8, size=(16, 16))

baseline = naive_adder_tree(M)
# delay constraint: 2 extra adder levels
sol = solve_cmvm(M, config=SolverConfig(dc=2))

print(f"matrix 16x16, 8-bit  |  baseline adders: {baseline.n_adders}")
print(
    f"da4ml (dc=2): {sol.n_adders} adders "
    f"({1 - sol.n_adders / baseline.n_adders:.0%} fewer), "
    f"depth {sol.depth}, LUT-bit estimate {sol.cost_bits}, "
    f"solved in {sol.solver_time_s*1e3:.1f} ms"
)

# --- bit-exactness: the adder graph computes x @ M exactly ---
assert sol.verify(), "never happens: full numerical precision is guaranteed"
x = rng.integers(-128, 128, size=(8, 16))
np.testing.assert_array_equal(sol.evaluate(x), x @ M)
print("bit-exact vs x @ M: OK")

# --- execute the levelized adder graph (TPU adaptation) ---
# The Pallas kernel runs only on the CPU backend, in interpret mode: it
# does not lower for the TPU, where the jnp executor (what Flow serves) runs.
tables = compile_tables(sol.program)
on_cpu = jax.default_backend() == "cpu"
y = adder_graph_apply(tables, x.astype(np.int32), use_pallas=on_cpu, block_b=8)
np.testing.assert_array_equal(np.asarray(y), x @ M)
executor = "Pallas kernel, interpret mode" if on_cpu else "jnp executor"
print(f"adder graph on {jax.default_backend()} ({executor}): OK")

# --- pipelining + RTL ---
rep = pipeline(sol.program, max_delay_per_stage=5)
print(f"pipelined: {rep.n_stages} stages, {rep.ff_bits} FF bits, II=1")
verilog = emit_verilog(sol.program, module_name="cmvm16", max_delay_per_stage=5)
print(f"Verilog: {len(verilog.splitlines())} lines; first 3:")
print("\n".join(verilog.splitlines()[:3]))
