"""Plain reference of the SAGIPS networks (arXiv 2407.00051, §V-A):
Kaiming-normal MLPs with Leaky-ReLU (slope 0.01) hidden layers, and for
image-valued parameters a convolutional generator (dense projection to an
8x8 grid, two nearest x2 upsamples each followed by a 3x3 convolution and
Leaky-ReLU, a 3x3 convolution to one channel, sigmoid).

A configuration's `generator` / `discriminator` entries give the widths.
Initial weights follow the stated key derivation: per MLP layer
`key, k = split(key)` and `normal(k, (a, b)) * sqrt(2 / a)`; the conv
generator splits its key four ways (projection, three convolutions)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from reference.numerics import leaky_relu

LEAK = 0.01


def init_mlp(key, widths):
    params = []
    for a, b in zip(widths[:-1], widths[1:]):
        key, k = jax.random.split(key)
        params.append({"w": jax.random.normal(k, (a, b)) * math.sqrt(2.0 / a),
                       "b": jnp.zeros((b,), jnp.float32)})
    return params


def mlp(ops, params, x):
    for i, layer in enumerate(params):
        x = ops.matmul(x, layer["w"]) + layer["b"]
        if i < len(params) - 1:
            x = leaky_relu(x, LEAK)
    return x


def init_conv(key, g):
    kp, *kc = jax.random.split(key, 1 + len(g["channels"]))
    base, ch = g["base"], g["channels"]
    proj_out = base * base * ch[0]
    w = jax.random.normal(kp, (g["noise_dim"], proj_out)) \
        * math.sqrt(2.0 / g["noise_dim"])
    convs = []
    for k, cin, cout in zip(kc, ch, ch[1:] + [1]):
        convs.append({"w": jax.random.normal(k, (3, 3, cin, cout))
                      * math.sqrt(2.0 / (9 * cin)),
                      "b": jnp.zeros((cout,), jnp.float32)})
    return {"proj": {"w": w, "b": jnp.zeros((proj_out,), jnp.float32)},
            "convs": convs}


def conv_gen(ops, params, noise, g):
    base, ch = g["base"], g["channels"]
    x = leaky_relu(ops.matmul(noise, params["proj"]["w"])
                   + params["proj"]["b"], LEAK)
    x = x.reshape(noise.shape[0], base, base, ch[0])
    n = len(params["convs"])
    for i, layer in enumerate(params["convs"]):
        if i < n - 1:
            x = jnp.repeat(jnp.repeat(x, 2, axis=1), 2, axis=2)
        x = ops.conv(x, layer["w"]) + layer["b"]
        if i < n - 1:
            x = leaky_relu(x, LEAK)
    return jax.nn.sigmoid(x).reshape(noise.shape[0], -1)


def init_generator(key, g):
    return init_mlp(key, g["widths"]) if g["kind"] == "mlp" \
        else init_conv(key, g)


def generate(ops, params, noise, g):
    """noise [n, noise_dim] -> parameter samples [n, n_params] in (0, 1)."""
    if g["kind"] == "mlp":
        return jax.nn.sigmoid(mlp(ops, params, noise))
    return conv_gen(ops, params, noise, g)


def noise_dim(g) -> int:
    return g["widths"][0] if g["kind"] == "mlp" else g["noise_dim"]


def weight_leaf(path) -> bool:
    """Weights ride the ring, biases do not (§V-C)."""
    return getattr(path[-1], "key", None) == "w"
