"""Plain float32 reference of SAGIPS training (arXiv 2407.00051, §IV-B,
Algorithm 1, Tab. II "RMA-ARAR-ARAR"), rank by rank.

Per epoch each rank r of R = n_outer x n_inner (row-major, outer first):
  1. splits its key three ways (next key, bootstrap, generator draw);
  2. draws K * E real events with replacement from its share of the
     reference data, and K noise vectors and K x E x C uniforms for the
     fake events (generator -> forward model);
  3. takes the gradient of the discriminator's loss
     mean softplus(-D(real)) + mean softplus(D(fake)) and applies Adam;
  4. takes the gradient of the generator's loss mean softplus(-D(fake))
     through the forward model, against the discriminator of step 3's
     start;
  5. exchanges generator *weight* gradients (biases stay local): it adds
     the mailbox its inner-ring predecessor filled last epoch and leaves
     its own fresh gradient in its successor's mailbox; every h epochs
     (epoch % h == 0), where there is more than one group, the first rank
     of each inner group adds its outer predecessor's inner-combined
     gradient;
  6. applies Adam (b1 0.9, b2 0.999, eps 1e-8) to the generator.

Set-up as the configuration states it: the data key makes the reference
data at the truth; the run key is split in two, the second half gives each
rank a permutation whose first `data_fraction` rows are its share, and the
first half is split R ways, each rank's key three ways (generator,
discriminator, rng); every rank starts from rank 0's generator.

Ranks are computed one after another (`lax.map`) so that the reference
fits beside nothing else on one chip.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from reference import nets
from reference.numerics import Ops

B1, B2, EPS = 0.9, 0.999, 1e-8


def init(cfg, problem, keys, n_ranks):
    """(state, data per rank) from the cell's keys."""
    data = problem.reference_data(keys["data"], cfg["reference_events"])
    key, k_sub = jax.random.split(keys["run"])
    n_sub = max(1, int(cfg["data_fraction"] * data.shape[0]))
    shares = jnp.stack([
        jnp.take(data, jax.random.permutation(k, data.shape[0])[:n_sub],
                 axis=0)
        for k in jax.random.split(k_sub, n_ranks)])
    gens, discs, rngs = [], [], []
    for k in jax.random.split(key, n_ranks):
        kg, kd, kr = jax.random.split(k, 3)
        gens.append(nets.init_generator(kg, cfg["generator"]))
        discs.append(nets.init_mlp(kd, cfg["discriminator"]["widths"]))
        rngs.append(kr)
    stack = lambda *xs: jnp.stack(xs)
    gen = jax.tree.map(lambda g: jnp.broadcast_to(g, (n_ranks,) + g.shape),
                       gens[0])
    disc = jax.tree.map(stack, *discs)
    zeros = lambda t: jax.tree.map(jnp.zeros_like, t)
    state = {
        "gen": gen, "disc": disc,
        "gen_opt": {"mu": zeros(gen), "nu": zeros(gen), "step": 0},
        "disc_opt": {"mu": zeros(disc), "nu": zeros(disc), "step": 0},
        "mailbox": zeros(gen), "rng": jnp.stack(rngs), "epoch": 0,
    }
    return state, shares


def _adam(params, grads, opt, lr):
    step = opt["step"] + 1
    mu = jax.tree.map(lambda m, g: B1 * m + (1 - B1) * g, opt["mu"], grads)
    nu = jax.tree.map(lambda v, g: B2 * v + (1 - B2) * g * g, opt["nu"],
                      grads)
    bc1, bc2 = 1 - B1 ** step, 1 - B2 ** step
    new = jax.tree.map(
        lambda p, m, v: p - lr * (m / bc1) / (jnp.sqrt(v / bc2) + EPS),
        params, mu, nu)
    return new, {"mu": mu, "nu": nu, "step": step}


def make_epoch(cfg, problem, precision: str, n_outer: int, n_inner: int):
    """jitted (state arrays, shares, epoch) -> (next state, d_loss,
    g_loss); both optimizers have taken `epoch` steps before it."""
    ops = Ops(precision)
    g = cfg["generator"]
    K, E = cfg["n_param_samples"], cfg["events_per_sample"]
    C = problem.NOISE_CHANNELS
    R = n_outer * n_inner
    h = cfg["sync"]["h"]

    def D(disc, x):
        return nets.mlp(ops, disc, x)[:, 0]

    def rank(args):
        gen, disc, rng, share = args
        rng, k_boot, k_gen = jax.random.split(rng, 3)
        real = jnp.take(share, jax.random.randint(
            k_boot, (K * E,), 0, share.shape[0]), axis=0)
        k1, k2 = jax.random.split(k_gen)
        noise = jax.random.normal(k1, (K, nets.noise_dim(g)))
        u = jax.random.uniform(k2, (K, E, C))
        fake_of = lambda gp: problem.forward(
            nets.generate(ops, gp, noise, g), u)
        fake = lax.stop_gradient(fake_of(gen))

        def d_loss(d):
            return (jnp.mean(jax.nn.softplus(-D(d, real)))
                    + jnp.mean(jax.nn.softplus(D(d, fake))))

        def g_loss(gp):
            return jnp.mean(jax.nn.softplus(-D(disc, fake_of(gp))))

        dl, dg = jax.value_and_grad(d_loss)(disc)
        gl, gg = jax.value_and_grad(g_loss)(gen)
        return rng, dl, dg, gl, gg

    def inner_roll(x):
        x = x.reshape((n_outer, n_inner) + x.shape[1:])
        return jnp.roll(x, 1, axis=1).reshape((R,) + x.shape[2:])

    def outer_roll(x):
        x = x.reshape((n_outer, n_inner) + x.shape[1:])
        return jnp.roll(x, 1, axis=0).reshape((R,) + x.shape[2:])

    def exchange(grads, mailbox, epoch):
        # one group has no outer ring
        first = (jnp.arange(R) % n_inner == 0) & (epoch % h == 0) \
            & (n_outer > 1)

        def leaf(path, gr, mb):
            if not nets.weight_leaf(path):
                return gr, mb
            s = gr + mb
            s = jnp.where(first.reshape((R,) + (1,) * (s.ndim - 1)),
                          s + outer_roll(s), s)
            return s, inner_roll(gr)

        pairs = jax.tree_util.tree_map_with_path(leaf, grads, mailbox)
        is_pair = lambda x: isinstance(x, tuple)
        return (jax.tree.map(lambda p: p[0], pairs, is_leaf=is_pair),
                jax.tree.map(lambda p: p[1], pairs, is_leaf=is_pair))

    @jax.jit
    def epoch_fn(st, shares, epoch):
        rng, dl, dg, gl, gg = lax.map(
            rank, (st["gen"], st["disc"], st["rng"], shares))
        disc, disc_opt = _adam(st["disc"], dg,
                               dict(st["disc_opt"], step=epoch),
                               cfg["disc_lr"])
        synced, mailbox = exchange(gg, st["mailbox"], epoch)
        gen, gen_opt = _adam(st["gen"], synced,
                             dict(st["gen_opt"], step=epoch),
                             cfg["gen_lr"])
        out = dict(st, gen=gen, disc=disc, mailbox=mailbox, rng=rng,
                   gen_opt={"mu": gen_opt["mu"], "nu": gen_opt["nu"]},
                   disc_opt={"mu": disc_opt["mu"], "nu": disc_opt["nu"]})
        return out, dl, gl

    return epoch_fn


def run(cfg, problem, keys, n_outer, n_inner, calls, epochs_per_call,
        precision="highest"):
    """The reference's readings after each of `calls` calls of
    `epochs_per_call` epochs: per-epoch losses, the Adam first moments
    after the first call, the weights at the start and after the last."""
    R = n_outer * n_inner
    with jax.default_matmul_precision("highest"):
        state, shares = init(cfg, problem, keys, R)
        epoch_fn = make_epoch(cfg, problem, precision, n_outer, n_inner)
        arrays = {"gen": state["gen"], "disc": state["disc"],
                  "mailbox": state["mailbox"], "rng": state["rng"],
                  "gen_opt": {"mu": state["gen_opt"]["mu"],
                              "nu": state["gen_opt"]["nu"]},
                  "disc_opt": {"mu": state["disc_opt"]["mu"],
                               "nu": state["disc_opt"]["nu"]}}
        start = jax.device_get({"gen": arrays["gen"], "disc": arrays["disc"]})
        d_losses, g_losses, first = [], [], None
        e = 0
        for c in range(calls):
            for _ in range(epochs_per_call):
                arrays, dl, gl = epoch_fn(arrays, shares, e)
                d_losses.append(dl)
                g_losses.append(gl)
                e += 1
            if c == 0:
                first = jax.device_get({"gen": arrays["gen_opt"]["mu"],
                                        "disc": arrays["disc_opt"]["mu"]})
        end = jax.device_get({"gen": arrays["gen"], "disc": arrays["disc"]})
        losses = jax.device_get({"d_loss": jnp.stack(d_losses),
                                 "g_loss": jnp.stack(g_losses)})
    return {"losses": losses, "mu_first": first, "start": start, "end": end}
