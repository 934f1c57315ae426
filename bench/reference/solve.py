"""Plain reference of the solve: candidate scoring under the generative
prior, on each request's own (unpadded) observations.

Each of the R generators proposes M candidates from the solve's fixed
key (split in two: noise, then uniforms); every candidate is pushed
through the forward model for E events.  A candidate's moments are the
per-observable mean and sqrt(variance + 1e-12) of its events; each moment
is scaled by its standard deviation over all R * M candidates (+ 1e-6).
A request's score for a candidate is minus the mean squared scaled
distance between the candidate's moments and those of the request's n
events.  The answer keeps the round(top_frac * R * M) best candidates:
their mean (params), standard deviation (sigma) and mean score."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from reference import nets
from reference.numerics import Ops


def init_generators(cfg, key, n_ranks):
    """R independent generators from the traffic key's first split."""
    return jax.vmap(lambda k: nets.init_generator(k, cfg["generator"]))(
        jax.random.split(key, n_ranks))


def _moments(ev):
    mean = ev.mean(axis=0)
    var = ((ev - mean) ** 2).mean(axis=0)
    return jnp.concatenate([mean, jnp.sqrt(var + 1e-12)])


def candidates(cfg, problem, gens, precision="highest"):
    """(candidate params [R*M, n], moments [R*M, 2*obs], scale)."""
    ops = Ops(precision)
    s = cfg["solve"]
    g = cfg["generator"]
    M, E = s["n_candidates"], s["events_per_candidate"]
    R = jax.tree.leaves(gens)[0].shape[0]
    k_noise, k_u = jax.random.split(jax.random.PRNGKey(s["seed"]))
    noise = jax.random.normal(k_noise, (R, M, nets.noise_dim(g)))
    with jax.default_matmul_precision("highest"):
        cands = jax.vmap(lambda gp, z: nets.generate(ops, gp, z, g))(
            gens, noise).reshape(R * M, -1)
    u = jax.random.uniform(k_u, (R * M, E, problem.NOISE_CHANNELS))
    ev = problem.forward(cands, u).reshape(R * M, E, -1)
    mom = jax.vmap(_moments)(ev)
    return jax.device_get((cands, mom, mom.std(axis=0) + 1e-6))


def answer(cfg, cands, mom, scale, y):
    """The answer to one request of events `y` [n, obs], in float64."""
    y = np.asarray(y, np.float64)
    n_keep = max(1, int(round(cfg["solve"]["top_frac"] * cands.shape[0])))
    mean = y.mean(axis=0)
    y_mom = np.concatenate([mean, np.sqrt(((y - mean) ** 2).mean(axis=0)
                                          + 1e-12)])
    d = (np.asarray(mom, np.float64) - y_mom) / np.asarray(scale, np.float64)
    score = -np.mean(d * d, axis=1)
    idx = np.argsort(-score, kind="stable")[:n_keep]
    kept = np.asarray(cands, np.float64)[idx]
    return {"params": kept.mean(axis=0), "sigma": kept.std(axis=0),
            "score": score[idx].mean()}
