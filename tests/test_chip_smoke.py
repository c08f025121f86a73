"""The pieces ``chip_smoke.py`` stands on, checked on the CPU backend:
the one place that decides Pallas interpret mode, the refusal of the
adder-graph kernel off the CPU, the compile-cache placement, and the
smoke's own per-network phase."""

import importlib.util
import inspect
from pathlib import Path

import pytest

import jax

from repro.kernels import resolve_interpret
from repro.kernels.adder_graph import adder_graph_apply
from repro.kernels.adder_graph.kernel import adder_graph_pallas
from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.quant_matmul import quant_matmul
from repro.kernels.quant_matmul.kernel import quant_matmul_pallas
from repro.kernels.ssm_scan import selective_scan
from repro.kernels.ssm_scan.kernel import selective_scan_pallas
from repro.nn import QDense, QuantConfig, init_params, models
from repro.nn.compiler import build_steps
from repro.flow import CompileConfig, Flow
from repro.runtime.compile_cache import REPO_CACHE_DIR, enable_compile_cache

ROOT = Path(__file__).resolve().parent.parent


def test_resolve_interpret_on_cpu():
    assert jax.default_backend() == "cpu"
    assert resolve_interpret() is True
    assert resolve_interpret(None) is True
    assert resolve_interpret(False) is False


def test_resolve_interpret_compiles_off_cpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_interpret() is False


@pytest.mark.parametrize(
    "fn",
    [
        adder_graph_apply, adder_graph_pallas,
        flash_attention, flash_attention_pallas,
        quant_matmul, quant_matmul_pallas,
        selective_scan, selective_scan_pallas,
    ],
    ids=lambda f: f.__name__,
)
def test_no_kernel_defaults_to_interpret(fn):
    # jitted kernels keep their Python signature on __wrapped__
    sig = inspect.signature(getattr(fn, "__wrapped__", fn))
    assert sig.parameters["interpret"].default is None


@pytest.fixture(scope="module")
def tiny_design():
    model = (QDense(4, QuantConfig(6, 2, signed=True)),)
    params, _ = init_params(jax.random.PRNGKey(0), model, (8,))
    return Flow.compile(
        model, params, (8,), QuantConfig(8, 4, signed=True), config=CompileConfig(jobs=1)
    )


def test_pallas_build_refused_off_cpu(tiny_design, monkeypatch):
    specs, tables = tiny_design.step_specs, tiny_design.tables
    assert build_steps(specs, tables, use_pallas=True)  # CPU: interpret mode
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(NotImplementedError, match="adder-graph Pallas kernel.*'tpu'"):
        build_steps(specs, tables, use_pallas=True)
    assert build_steps(specs, tables, use_pallas=False)  # the jnp executor builds


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_honours_env(monkeypatch, tmp_path, restore_cache_dir):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    # JAX reads the variable itself; the helper sets no directory
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_fixed_repo_dir(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = enable_compile_cache()
    assert first == str(ROOT / ".jax_cache") == str(REPO_CACHE_DIR)
    assert enable_compile_cache() == first
    assert jax.config.jax_compilation_cache_dir == first
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().splitlines()


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_phase_jet_tagger_on_cpu(chip_smoke, tmp_path):
    r = chip_smoke.serve_network("jet_tagger", models.jet_tagger, tmp_path)
    assert r["bitexact"] is True
    assert r["n_fallback_batches"] == 0
    assert r["n_checked"] == chip_smoke.N_INFER + chip_smoke.N_BATCH
    assert r["adders"] > 0
    # on the CPU the phase passes and reports where it ran; main() then
    # refuses the run as not a TPU
    assert r["platform"] == "cpu"
