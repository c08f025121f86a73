"""NN substrate: float/STE forward must bit-match the compiled integer
adder-graph pipeline (the paper's 'full numerical precision' claim,
end-to-end), and the DA strategy must beat the latency baseline."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.nn import (
    apply_model,
    compile_model,
    init_params,
    models,
)

jax.config.update("jax_enable_x64", True)  # exact float reference


def _random_input(rng, shape, in_quant, batch=16):
    x = rng.uniform(in_quant.lo, in_quant.hi, size=(batch, *shape))
    return jnp.asarray(x, jnp.float64)


@pytest.mark.parametrize("builder", [
    models.jet_tagger,
    models.muon_tracker,
    lambda: models.mlp_mixer_jet(n_particles=8, n_features=8, d_ff=8),
])
def test_float_matches_integer_pipeline(builder):
    model, in_shape, in_quant = builder()
    rng = np.random.default_rng(0)
    params, _ = init_params(jax.random.PRNGKey(0), model, in_shape)
    design = compile_model(model, params, in_shape, in_quant, dc=2)
    x = _random_input(rng, in_shape, in_quant)
    y_float = apply_model(params, model, x, in_quant=in_quant)
    y_int = design.forward(x)
    np.testing.assert_allclose(
        np.asarray(y_int, np.float64),
        np.asarray(y_float, np.float64),
        rtol=0,
        atol=0,
    )


def test_svhn_cnn_small_exact():
    model, _, in_quant = models.svhn_cnn()
    in_shape = (22, 22, 3)  # reduced spatial size for test speed
    rng = np.random.default_rng(1)
    params, out_shape = init_params(jax.random.PRNGKey(1), model, in_shape)
    design = compile_model(model, params, in_shape, in_quant, dc=2)
    x = _random_input(rng, in_shape, in_quant, batch=4)
    y_float = apply_model(params, model, x, in_quant=in_quant)
    y_int = design.forward(x)
    np.testing.assert_allclose(np.asarray(y_int), np.asarray(y_float), rtol=0, atol=0)


def test_da_beats_latency_strategy():
    model, in_shape, in_quant = models.jet_tagger()
    params, _ = init_params(jax.random.PRNGKey(2), model, in_shape)
    da = compile_model(model, params, in_shape, in_quant, dc=2, strategy="da")
    base = compile_model(model, params, in_shape, in_quant, dc=2, strategy="latency")
    assert da.total_adders < base.total_adders
    assert da.total_cost_bits < base.total_cost_bits
    # both strategies must be bit-exact
    rng = np.random.default_rng(3)
    x = _random_input(rng, in_shape, in_quant)
    np.testing.assert_array_equal(
        np.asarray(da.forward(x)), np.asarray(base.forward(x))
    )


def test_latency_cycles_and_report():
    model, in_shape, in_quant = models.jet_tagger()
    params, _ = init_params(jax.random.PRNGKey(4), model, in_shape)
    design = compile_model(model, params, in_shape, in_quant, dc=2)
    assert design.latency_cycles >= len(design.reports)
    s = design.summary()
    assert "TOTAL" in s and "dense" in s


def test_quantized_training_step_reduces_loss():
    """QAT sanity: a few SGD steps on a toy task reduce loss."""
    model, in_shape, in_quant = models.jet_tagger(w_bits=8)
    params, _ = init_params(jax.random.PRNGKey(5), model, in_shape)
    key = jax.random.PRNGKey(6)
    x = jax.random.normal(key, (256, 16))
    w_true = jax.random.normal(jax.random.PRNGKey(7), (16, 5))
    y = jnp.argmax(x @ w_true, axis=-1)

    def loss_fn(p):
        logits = apply_model(p, model, x, in_quant=in_quant)
        lp = jax.nn.log_softmax(logits)
        return -jnp.take_along_axis(lp, y[:, None], axis=-1).mean()

    loss0 = loss_fn(params)
    grads = jax.grad(loss_fn)(params)
    lr = 0.05
    p2 = jax.tree.map(lambda a, g: a - lr * g, params, grads)
    for _ in range(10):
        g = jax.grad(loss_fn)(p2)
        p2 = jax.tree.map(lambda a, gg: a - lr * gg, p2, g)
    assert loss_fn(p2) < loss0


def test_forward_int_names_each_step_in_the_compiled_program():
    """Every step runs under ``step{i}_{kind}``: the scopes reach the
    compiled program's op_name metadata and change no output bit."""
    model, in_shape, in_quant = models.jet_tagger()
    params, _ = init_params(jax.random.PRNGKey(3), model, in_shape)
    design = compile_model(model, params, in_shape, in_quant, dc=2)
    q = in_quant.qint
    x = np.random.default_rng(3).integers(q.lo, q.hi + 1, size=(64, *in_shape)).astype(np.int32)
    text = jax.jit(design.forward_int).lower(x).compile().as_text()
    for i, spec in enumerate(design.step_specs):
        assert f'op_name="jit(forward_int)/step{i}_{spec.kind}/' in text

    def unscoped(x_int):
        v = x_int.reshape(x_int.shape[0], -1).astype(jnp.int32)
        for step in design.steps:
            v = step(v)
        return v.reshape(x_int.shape[0], *design.out_shape)

    np.testing.assert_array_equal(
        np.asarray(jax.jit(design.forward_int)(x)), np.asarray(jax.jit(unscoped)(x))
    )
