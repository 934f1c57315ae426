"""GAN optimizer networks — the paper's generator / discriminator MLPs.

Sizes match the paper exactly:
  generator     noise(135) -> 128 -> 128 -> 128 -> 6      = 51,206 params
  discriminator (y0,y1)(2) -> 192 -> 192 -> 64 -> 1       = 50,049 params
(§V-A: "The generator has a total of 51,206 trainable parameters, whereas the
discriminator has 50,049"; hidden activations Leaky ReLU, Kaiming-normal
init, generator lr 1e-5, discriminator lr 1e-4.)
"""
from __future__ import annotations

import math
from typing import Sequence

import jax
import jax.numpy as jnp

NOISE_DIM = 135
N_PARAMS = 6                     # p_0..p_5 of the loop-closure test
GEN_WIDTHS = (NOISE_DIM, 128, 128, 128, N_PARAMS)
DISC_WIDTHS = (2, 192, 192, 64, 1)
LEAK = 0.01


def gen_widths(n_params=None, noise_dim=None):
    """Generator widths for a problem with `n_params` outputs.

    Hidden layers come from the module-level GEN_WIDTHS (paper-exact by
    default; benchmarks patch it for capacity sweeps) — only the in/out
    dims vary per problem."""
    base = GEN_WIDTHS
    return ((base[0] if noise_dim is None else noise_dim,)
            + base[1:-1] + (base[-1] if n_params is None else n_params,))


def disc_widths(obs_dim=None):
    """Discriminator widths for a problem with `obs_dim` observables."""
    base = DISC_WIDTHS
    return ((base[0] if obs_dim is None else obs_dim,) + base[1:])


def init_mlp(key, widths: Sequence[int], dtype=jnp.float32):
    """Kaiming-normal MLP init (paper §V-A)."""
    params = []
    for i, (a, b) in enumerate(zip(widths[:-1], widths[1:])):
        key, k = jax.random.split(key)
        w = jax.random.normal(k, (a, b)) * math.sqrt(2.0 / a)
        params.append({"w": w.astype(dtype), "b": jnp.zeros((b,), dtype)})
    return params


def mlp_apply(params, x, final_activation=None):
    for i, layer in enumerate(params):
        x = x @ layer["w"] + layer["b"]
        if i < len(params) - 1:
            x = jax.nn.leaky_relu(x, LEAK)
    if final_activation is not None:
        x = final_activation(x)
    return x


def init_generator(key, n_params=None, dtype=jnp.float32, param_shape=None):
    """Paper MLP generator, or the conv generator (`models.convgen`) when
    the problem declares an image-valued `param_shape` (H, W).  The two
    return structurally distinct pytrees (list vs dict), which is what
    `generate_params` / `weight_mask` dispatch on."""
    if param_shape is not None:
        from ..models.convgen import init_conv_generator
        return init_conv_generator(key, param_shape, NOISE_DIM, dtype)
    return init_mlp(key, gen_widths(n_params), dtype)


def init_discriminator(key, obs_dim=None, dtype=jnp.float32):
    return init_mlp(key, disc_widths(obs_dim), dtype)


def generate_params(gen_params, noise):
    """noise [K, NOISE_DIM] -> parameter samples [K, n_params]
    (sigmoid-bounded to the problem's unit cube).  Dispatches on the
    pytree structure: the conv generator is a dict, the MLP a list —
    a static Python check, so each structure traces its own program."""
    with jax.named_scope("sagips_gen"):
        if isinstance(gen_params, dict):
            from ..models.convgen import conv_generator_apply
            return conv_generator_apply(gen_params, noise)
        return mlp_apply(gen_params, noise, final_activation=jax.nn.sigmoid)


# discriminator forward compute precisions (ParaGAN's remaining headroom
# item: run the dominant per-epoch matmuls in bf16, not just the wire)
DISC_COMPUTE = ("fp32", "bf16")


def compute_dtype_of(precision: str):
    """`WorkflowConfig.disc_compute` -> the dtype `discriminate` casts its
    forward to; None means "keep the master dtype" (the bitwise-pinned
    fp32 default takes NO cast, not an identity astype)."""
    if precision == "fp32":
        return None
    if precision == "bf16":
        return jnp.dtype("bfloat16")
    raise ValueError(
        f"unknown disc_compute {precision!r}; expected one of {DISC_COMPUTE}")


def discriminate(disc_params, events, compute_dtype=None):
    """events [N, obs_dim] -> logits [N].

    `compute_dtype` (from `compute_dtype_of`) runs the forward matmuls in
    a reduced precision — params and activations are cast once on entry
    and the logits cast back to the master fp32, so losses, gradients and
    the Adam state stay fp32 ("fp32 master", the same discipline as the
    bf16 ring payload).  None is the bitwise-pinned default: no casts at
    all."""
    with jax.named_scope("sagips_disc"):
        if compute_dtype is None:
            return mlp_apply(disc_params, events)[..., 0]
        cast = jax.tree.map(lambda p: p.astype(compute_dtype), disc_params)
        logits = mlp_apply(cast, events.astype(compute_dtype))[..., 0]
        return logits.astype(jnp.float32)


def param_count(params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))


# ----------------------------------------------------------------------------
# losses (standard GAN with logits; discriminator: real->1, fake->0)


def disc_loss(disc_params, real_events, fake_events, compute_dtype=None):
    lr_ = discriminate(disc_params, real_events, compute_dtype)
    lf_ = discriminate(disc_params, fake_events, compute_dtype)
    loss_real = jnp.mean(jax.nn.softplus(-lr_))          # -log sigmoid(real)
    loss_fake = jnp.mean(jax.nn.softplus(lf_))           # -log(1-sigmoid(fake))
    return loss_real + loss_fake


def gen_loss(disc_params, fake_events, compute_dtype=None):
    """Non-saturating generator loss: maximize log D(fake)."""
    lf_ = discriminate(disc_params, fake_events, compute_dtype)
    return jnp.mean(jax.nn.softplus(-lf_))


def weight_mask(params):
    """Pytree mask: True for weight matrices, False for biases.

    The paper restricts the ring transfer to *weight* gradients (bias
    gradients are 1-D tensors known to slow the ring and add no convergence
    benefit, §V-C).  Dispatches on the pytree structure like
    `generate_params`: dict -> conv generator, list -> MLP.
    """
    if isinstance(params, dict):
        from ..models.convgen import conv_weight_mask
        return conv_weight_mask(params)
    return [{"w": True, "b": False} for _ in params]
