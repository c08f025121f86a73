"""The exact MXU dot executor of CMVM steps.

Where ``dot_matrix`` proves it exact, one bfloat16 dot must return the
adder graph's int32 answers bit for bit: on random solved matrices at
their interval corners, and on whole designs before and after an
artifact round trip.  Where the proof fails, the step keeps its adder
graph, and ``executors``, ``summary()`` and the engine's
``serve_cmvm_dot_share`` gauge say so."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import solve_cmvm
from repro.core.dais import DAISProgram, Term
from repro.core.fixed_point import QInterval
from repro.flow import CompileConfig, Flow, ServeConfig
from repro.kernels.adder_graph import compile_tables
from repro.kernels.adder_graph.dot import cmvm_dot, dot_matrix
from repro.kernels.adder_graph.ref import adder_graph_ref
from repro.nn import CompiledDesign, StepSpec, build_steps, init_params, models
from repro.nn.interpreter import numpy_forward_fn
from repro.obs.metrics import get_registry
from repro.runtime import ServeEngine, load_design, save_design


def _corners(qin: list[QInterval], rng, n_random: int = 29) -> np.ndarray:
    """Every input at its low end, at its high end, alternating both
    ways, then random points of the intervals."""
    lo = np.array([q.lo for q in qin])
    hi = np.array([q.hi for q in qin])
    alt = np.arange(len(qin)) % 2 == 0
    rand = rng.integers(lo, hi + 1, size=(n_random, len(qin)))
    return np.vstack([lo, hi, np.where(alt, lo, hi), np.where(alt, hi, lo), rand]).astype(
        np.int32
    )


@pytest.mark.parametrize(
    "d_in,d_out,w_bits,x_bits,signed",
    [
        (8, 8, 6, 8, True),
        (16, 12, 6, 8, False),
        (12, 16, 4, 4, True),
        (3, 7, 8, 8, True),
        (5, 5, 2, 1, False),
        (64, 32, 6, 8, False),
    ],
)
def test_dot_equals_adder_graph_on_solved_matrices(d_in, d_out, w_bits, x_bits, signed):
    rng = np.random.default_rng(d_in * 1000 + d_out * 10 + w_bits)
    m = rng.integers(-(2 ** (w_bits - 1)), 2 ** (w_bits - 1), size=(d_in, d_out))
    q = (
        QInterval(-(2 ** (x_bits - 1)), 2 ** (x_bits - 1) - 1, 0)
        if signed
        else QInterval(0, 2**x_bits - 1, 0)
    )
    prog = solve_cmvm(m, qint_in=[q] * d_in).program
    tables = compile_tables(prog)
    mat = dot_matrix(tables, prog.to_arrays())
    assert mat is not None
    np.testing.assert_array_equal(mat, m)
    x = _corners([q] * d_in, rng)
    want = np.asarray(adder_graph_ref(tables, jnp.asarray(x)))
    got = np.asarray(jax.jit(cmvm_dot)(jnp.asarray(mat, jnp.bfloat16), jnp.asarray(x)))
    assert got.dtype == np.int32
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(got, x.astype(np.int64) @ m)


def test_dot_takes_a_column_just_below_two_to_the_24():
    # 7 * 8192 * 256 + 4096 * 256 = 2**24 - 2**20: every sum stays exact
    m = np.array([[8192]] * 7 + [[4096]])
    q = QInterval(-256, 255, 0)
    prog = solve_cmvm(m, qint_in=[q] * 8).program
    tables = compile_tables(prog)
    mat = dot_matrix(tables, prog.to_arrays())
    assert mat is not None
    x = _corners([q] * 8, np.random.default_rng(0))
    got = np.asarray(cmvm_dot(jnp.asarray(mat, jnp.bfloat16), jnp.asarray(x)))
    np.testing.assert_array_equal(got, np.asarray(adder_graph_ref(tables, jnp.asarray(x))))


@pytest.fixture(scope="module")
def bench_designs():
    """jet_tagger and a reduced (16-particle) mlp_mixer_jet."""
    out = {}
    for name, fn in (("jet_tagger", models.jet_tagger), ("mlp_mixer_jet", models.mlp_mixer_jet)):
        model, in_shape, in_quant = fn()
        params, _ = init_params(jax.random.PRNGKey(11), model, in_shape)
        out[name] = Flow.compile(model, params, in_shape, in_quant, config=CompileConfig(jobs=1))
    return out


@pytest.mark.parametrize("round_trip", [False, True], ids=["compiled", "loaded"])
@pytest.mark.parametrize("name", ["jet_tagger", "mlp_mixer_jet"])
def test_design_dot_equals_adder_graph(bench_designs, name, round_trip, tmp_path):
    design = bench_designs[name]
    if round_trip:
        design = load_design(save_design(design, tmp_path / name))
    assert design.executors == ["dot"] * len(design.reports)
    assert design.dot_share == 1.0
    q = design.in_quant.qint
    rng = np.random.default_rng(5)
    x = rng.integers(q.lo, q.hi + 1, size=(97, *design.in_shape)).astype(np.int32)
    x[0], x[1] = q.lo, q.hi  # the input grid's corners
    got = np.asarray(jax.jit(design.forward_int)(x))
    # the same pipeline with every CMVM on its adder graph (no programs)
    adder = CompiledDesign(
        step_specs=design.step_specs, tables=design.tables,
        in_shape=design.in_shape, out_shape=design.out_shape,
    )
    adder.steps = build_steps(design.step_specs, design.tables)
    assert adder.executors == ["adder_graph"] * len(design.reports)
    np.testing.assert_array_equal(got, np.asarray(jax.jit(adder.forward_int)(x)))
    np.testing.assert_array_equal(got, numpy_forward_fn(design)(x))


# ----------------------------------------------------------------------
# refusals: the step keeps its adder graph
# ----------------------------------------------------------------------
def _negative_output_shift():
    prog = DAISProgram()
    a, b = prog.add_input(QInterval(-128, 127, 0)), prog.add_input(QInterval(0, 255, 0))
    s = prog.add_op(a, b, 0, 0, 1)
    prog.outputs = [Term(1, s, -1), Term(1, s, 0)]  # floor((a + b) / 2): not linear
    return prog, True


def _sixteen_bit_grid():
    q = QInterval(-(2**15), 2**15 - 1, 0)
    return solve_cmvm(np.array([[3, 5], [7, -2]]), qint_in=[q, q]).program, True


def _column_at_two_to_the_24():
    # 8 * 8192 * 256 = 2**24: one more than float32 counts exactly
    q = QInterval(-256, 255, 0)
    return solve_cmvm(np.array([[8192]] * 8), qint_in=[q] * 8).program, True


def _no_program():
    return solve_cmvm(np.array([[3, 5], [7, -2]])).program, False


@pytest.mark.parametrize(
    "make",
    [_negative_output_shift, _sixteen_bit_grid, _column_at_two_to_the_24, _no_program],
    ids=lambda f: f.__name__.strip("_"),
)
def test_refused_step_keeps_the_adder_graph(make):
    prog, packed = make()
    tables = compile_tables(prog)
    parr = prog.to_arrays() if packed else None
    assert dot_matrix(tables, parr) is None
    n = prog.n_inputs
    spec = StepSpec("dense", params={"d_in": n}, table=0)
    design = CompiledDesign(
        step_specs=[spec], tables=[tables], programs=[parr],
        in_shape=(n,), out_shape=(len(prog.outputs),),
    )
    design.steps = build_steps(design.step_specs, design.tables, programs=design.programs)
    assert design.executors == ["adder_graph"]
    assert design.dot_share == 0.0

    qin = [r.qint for r in prog.rows[:n]]
    x = _corners(qin, np.random.default_rng(1))
    np.testing.assert_array_equal(
        np.asarray(jax.jit(design.forward_int)(x)), prog.evaluate(x).astype(np.int32)
    )

    name = f"refused_{make.__name__.strip('_')}"
    with ServeEngine(config=ServeConfig(max_batch=8)) as eng:
        eng.register(name, design)
    assert get_registry().snapshot()["gauges"][f'serve_cmvm_dot_share{{model="{name}"}}'] == 0.0


def test_summary_and_gauge_report_the_executors(bench_designs):
    design = bench_designs["jet_tagger"]
    rows = design.summary().splitlines()
    assert rows[0].split()[-1] == "exec"
    assert [r.split()[-1] for r in rows[2 : 2 + len(design.reports)]] == design.executors
    with ServeEngine(config=ServeConfig(max_batch=8)) as eng:
        eng.register("jet_dot", design)
    assert get_registry().snapshot()["gauges"]['serve_cmvm_dot_share{model="jet_dot"}'] == 1.0

    # use_pallas keeps every step on the adder graph
    pallas = CompiledDesign(
        step_specs=design.step_specs, tables=design.tables, programs=design.programs,
        in_shape=design.in_shape, out_shape=design.out_shape, use_pallas=True,
    )
    assert pallas.executors == ["adder_graph"] * len(design.reports)
    assert pallas.dot_share == 0.0
