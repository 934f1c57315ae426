"""Faults planted under the measured path, to see the check fail.  Each
is a context manager yielding the `wrap_call` the harness applies to the
window's call (identity where the fault is patched elsewhere).

    unchanged       the training step returns its state unchanged
    half_batch      the GAN losses take the mean over half of the events
    no_exchange     the generator gradients skip the ring exchange
    answer_altered  every solve answer's parameters move by 0.02
"""
from __future__ import annotations

import contextlib

FAULTS = ("unchanged", "half_batch", "no_exchange", "answer_altered")


def _unchanged(call):
    import jax
    import jax.numpy as jnp

    def f(state, data):
        _, metrics = call(jax.tree.map(jnp.copy, state), data)
        return state, metrics
    return f


@contextlib.contextmanager
def _patched(module, name, value):
    old = getattr(module, name)
    setattr(module, name, value)
    try:
        yield
    finally:
        setattr(module, name, old)


@contextlib.contextmanager
def fault(name: str):
    if name == "unchanged":
        yield _unchanged
        return
    if name == "half_batch":
        from repro.core import gan
        d, g = gan.disc_loss, gan.gen_loss

        def disc_loss(p, real, fake, compute_dtype=None):
            return d(p, real[:real.shape[0] // 2], fake[:fake.shape[0] // 2],
                     compute_dtype)

        def gen_loss(p, fake, compute_dtype=None):
            return g(p, fake[:fake.shape[0] // 2], compute_dtype)

        with _patched(gan, "disc_loss", disc_loss), \
                _patched(gan, "gen_loss", gen_loss):
            yield None
        return
    if name == "no_exchange":
        from repro.core import sync

        def sync_gradients(comm, cfg, grads, mailbox, epoch, mask=None,
                           spec=None, outer_mailbox=None):
            if outer_mailbox is None:
                return grads, mailbox
            return grads, mailbox, outer_mailbox

        with _patched(sync, "sync_gradients", sync_gradients):
            yield None
        return
    if name == "answer_altered":
        from repro.serving import service
        make = service.make_solver

        def make_solver(problem, cfg):
            solve = make(problem, cfg)

            def altered(*args):
                out = solve(*args)
                return dict(out, params=out["params"] + 0.02)
            return altered

        with _patched(service, "make_solver", make_solver):
            yield None
        return
    raise ValueError(f"unknown fault {name!r}; known: {FAULTS}")
