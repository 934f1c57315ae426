"""Pluggable inverse problems — the workload layer of the SAGIPS solver.

SAGIPS (the paper) is a *general* asynchronous generative inverse problem
solver; the 1D proxy app of §V is just its first workload.  This package
makes the forward model the pluggable element of the system (the framing of
Hegde, "Algorithmic Aspects of Inverse Problems Using Generative Models",
and Patel et al., "Solution of Physics-based Bayesian Inverse Problems with
Deep Generative Priors"): everything the solver stack needs to know about a
workload lives behind the `InverseProblem` interface, and the GAN widths,
sampler dispatch, residual metric, drivers, benchmarks and CLIs all derive
from it.  The FusionSpec/ring machinery in `core.sync` never sees the
problem at all — problem-agnosticism of the exchange engine is a tested
invariant (tests/test_problems.py), not an accident.

Registered problems (see `available()`):

    proxy1d      the paper's 1D proxy app — 6 params, 2 independent
                 logistic-family observables (bitwise-identical to the
                 pre-registry behavior under default config)
    proxy2d      correlated-observable variant — 10 params, 3 observables
                 mixed by a learned correlation parameter; exercises the
                 Pallas sampler on a folded [K*C, E] shape
    linear_blur  linear operator y = A x + eps — an 8-pixel source seen
                 through a 4-channel Gaussian blur with logistic measurement
                 noise (sampled by the same inverse-CDF kernel)
    imaging      32x32 inpainting — every pixel observed except a central
                 occluded box; image-valued `param_shape` flips the GAN to
                 the conv generator (megabyte-scale ring payload, ISSUE 9)
    imaging_blur 32x32 compressive blur — Pallas 3-tap blur + stride-2
                 subsample, 1024 -> 256 measurements

## Adding a new inverse problem

The full how-to lives in docs/adding-a-problem.md.  The short version:
subclass `InverseProblem` in `src/repro/problems/<name>.py` (class attrs
`name` / `n_params` / `obs_dim` / `noise_channels`, methods
`true_params()` and a *differentiable* `sample_events(params, u, impl,
interpret)`), call `register(MyProblem())` at the bottom of the module,
and add the module to the `_register_builtin` import list below —
drivers, CLIs, benchmarks and the `scripts/check.sh --problems` lane all
pick it up from the registry with no further wiring.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp


class InverseProblem:
    """Interface every SAGIPS workload implements (see module docstring)."""

    name: str
    n_params: int
    obs_dim: int
    noise_channels: int

    # image-valued parameter spaces set this to their (H, W); the GAN layer
    # then dispatches to the convolutional generator (`models.convgen`)
    # instead of the paper's MLP head.  None (default) = flat parameter
    # vector, MLP generator — the bitwise-pinned historical path.  When
    # set, H * W must equal n_params.
    param_shape: Tuple[int, int] | None = None

    # default events per parameter sample for reference-data generation
    # (Tab. III of the paper)
    events_per_sample: int = 100

    # serving-quality bar: a CPU-scale trained generator stack, solved
    # through `core.workflow.make_solver`, must reach mean|r̂| below this
    # (tests/test_serving.py pins it end-to-end per registered problem).
    # Problems whose truth has near-zero components (where Eq. 6 residuals
    # blow up against the clamped denominator — see `core.residuals`)
    # override it with a looser bar.
    solve_threshold: float = 0.5

    def true_params(self) -> jnp.ndarray:
        """Loop-closure truth in (0,1)^n_params (the generator head is
        sigmoid-bounded, so truths live in the unit cube)."""
        raise NotImplementedError

    def sample_events(self, params, u, impl: str = "jnp", interpret=None):
        """params [K, n_params] in (0,1); u [K, E, noise_channels] uniform.

        Returns events [K*E, obs_dim], differentiable w.r.t. params."""
        raise NotImplementedError

    # -- defaults ------------------------------------------------------------

    def make_reference_data(self, key, n_events: int, params=None):
        """Toy measurement: events generated from the truth parameters."""
        params = self.true_params() if params is None else params
        E = self.events_per_sample
        K = -(-n_events // E)
        u = jax.random.uniform(key, (K, E, self.noise_channels))
        return self.sample_events(jnp.tile(params[None, :], (K, 1)),
                                  u)[:n_events]

    def residuals(self, pred_params, true_params=None):
        """Normalized parameter residuals (Eq. 6) against this problem's
        truth, with the safe denominator of `core.residuals`."""
        from ..core.residuals import normalized_residuals
        tp = self.true_params() if true_params is None else true_params
        return normalized_residuals(pred_params, tp)

    def mean_abs_residual(self, pred_params, true_params=None):
        return jnp.mean(jnp.abs(self.residuals(pred_params, true_params)))


def synthetic_events(problem: InverseProblem, gen_params, key,
                     n_param_samples: int, events_per_sample: int,
                     impl: str = "jnp", interpret=None):
    """Full generator -> forward-model pass for any registered problem.

    Returns (events [K*E, obs_dim], params [K, n_params]).  Key usage is
    identical to the historical `pipeline.synthetic_events`, so proxy1d is
    bitwise-reproducible through this path.
    """
    from ..core import gan
    k1, k2 = jax.random.split(key)
    noise = jax.random.normal(k1, (n_param_samples, gan.NOISE_DIM))
    params = gan.generate_params(gen_params, noise)
    with jax.named_scope("sagips_sample"):
        u = jax.random.uniform(
            k2, (n_param_samples, events_per_sample, problem.noise_channels))
        events = problem.sample_events(params, u, impl=impl,
                                       interpret=interpret)
    return events, params


# ----------------------------------------------------------------------------
# registry


_REGISTRY: Dict[str, InverseProblem] = {}


def register(problem: InverseProblem) -> InverseProblem:
    """Add a problem instance to the registry (idempotent per name)."""
    for attr in ("name", "n_params", "obs_dim", "noise_channels"):
        if getattr(problem, attr, None) is None:
            raise ValueError(f"problem is missing required attribute {attr!r}")
    _REGISTRY[problem.name] = problem
    return problem


def get_problem(name: str) -> InverseProblem:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown inverse problem {name!r}; "
                       f"registered: {sorted(_REGISTRY)}") from None


def available() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def _register_builtin():
    from . import proxy1d, proxy2d, linear, imaging  # noqa: F401  (register on import)


_register_builtin()

__all__ = ["InverseProblem", "available", "get_problem", "register",
           "synthetic_events"]
