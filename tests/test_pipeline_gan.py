"""SAGIPS core tests: pipeline differentiability, GAN sizes, ensemble,
residuals, reduced workflow convergence sanity."""
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core import gan, pipeline
from repro.core.ensemble import ensemble_response, stack_generators
from repro.core.residuals import mean_abs_residual, normalized_residuals


def test_paper_exact_param_counts():
    g = gan.init_generator(jax.random.PRNGKey(0))
    d = gan.init_discriminator(jax.random.PRNGKey(1))
    assert gan.param_count(g) == 51_206      # §V-A
    assert gan.param_count(d) == 50_049


def test_pipeline_shapes_and_grad():
    key = jax.random.PRNGKey(0)
    params = jax.random.uniform(key, (16, 6))
    u = jax.random.uniform(key, (16, 10, 2))
    ev = pipeline.sample_events(params, u)
    assert ev.shape == (160, 2)

    def loss(p):
        return jnp.sum(pipeline.sample_events(p, u) ** 2)

    g = jax.grad(loss)(params)
    assert g.shape == params.shape
    assert bool(jnp.all(jnp.isfinite(g)))
    assert float(jnp.max(jnp.abs(g))) > 0


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 1000))
def test_loop_closure_truth_gives_zero_residual(seed):
    """Events from the truth params + perfect prediction -> r = 0 (Eq. 6)."""
    r = normalized_residuals(pipeline.TRUE_PARAMS)
    np.testing.assert_allclose(np.asarray(r), 0.0, atol=1e-7)
    # random prediction has non-zero residual
    p = jax.random.uniform(jax.random.PRNGKey(seed), (6,))
    if float(jnp.max(jnp.abs(p - pipeline.TRUE_PARAMS))) > 1e-3:
        assert float(mean_abs_residual(p)) > 0


def test_pipeline_distribution_statistics():
    """Sampled events must follow the (logistic+shear) law: median ~= mu."""
    K, E = 4, 20_000
    p = jnp.tile(pipeline.TRUE_PARAMS[None], (K, 1))
    u = jax.random.uniform(jax.random.PRNGKey(0), (K, E, 2))
    ev = np.asarray(pipeline.sample_events(p, u)).reshape(K, E, 2)
    mu0 = float(pipeline._affine(pipeline.TRUE_PARAMS[0], *pipeline._MU_RANGE))
    med = np.median(ev[..., 0])
    assert abs(med - mu0) < 0.05


def test_ensemble_response_reduces_variance():
    gens = [gan.init_generator(jax.random.PRNGKey(i)) for i in range(8)]
    stacked = stack_generators(gens)
    noise = jax.random.normal(jax.random.PRNGKey(42), (64, gan.NOISE_DIM))
    p2, s2 = ensemble_response(jax.tree.map(lambda x: x[:2], stacked), noise)
    p8, s8 = ensemble_response(stacked, noise)
    assert p8.shape == (6,) and s8.shape == (6,)
    # predictions bounded by the sigmoid head
    assert float(jnp.min(p8)) >= 0 and float(jnp.max(p8)) <= 1


def test_disc_loss_decreases_with_training_signal():
    """One Adam step on the discriminator should reduce its loss."""
    from repro.optim import adam, apply_updates
    key = jax.random.PRNGKey(0)
    d = gan.init_discriminator(key)
    real = pipeline.make_reference_data(jax.random.PRNGKey(1), 1000)
    fake = real + 3.0               # trivially separable
    opt = adam(1e-3)
    st_ = opt.init(d)
    l0 = float(gan.disc_loss(d, real, fake))
    for _ in range(20):
        g = jax.grad(gan.disc_loss)(d, real, fake)
        upd, st_ = opt.update(g, st_)
        d = apply_updates(d, upd)
    l1 = float(gan.disc_loss(d, real, fake))
    assert l1 < l0
