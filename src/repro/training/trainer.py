"""Distributed trainer with SAGIPS gradient sync as a first-class option.

Sync modes (`TrainConfig.sync_mode`):

  allreduce          synchronous data-parallel mean over (pod, data) — the
                     horovod baseline.  Params FSDP-sharded over all axes.
  arar_grouped       SAGIPS hierarchy at pod granularity: the *inner group*
                     is the pod (full psum over `data` every step — devices
                     sharing fast ICI, per the paper's "inner size = GPUs per
                     node" rule), the *outer group* is the cross-pod ring,
                     exchanged every `sync_h` steps via collective-permute.
                     Each pod keeps its own (FSDP-sharded) model copy which
                     drifts between outer exchanges — exactly the paper's
                     rank-level semantics lifted to pods.
  rma_arar_grouped   as above, but the cross-pod exchange reads the *stale
                     mailbox* the ring predecessor deposited at the previous
                     due step (RMA one-sided semantics; costs one grad copy).
  ensemble           no cross-pod communication ever (§IV-A baseline).

Per §V-C only >=2-D leaves (weight matrices) ride the ring; 1-D leaves
(norm scales, biases) stay local.

On a single-pod mesh the hierarchical modes degenerate to `allreduce`
(the inner group covers all devices), matching the paper: grouping only
matters across slow boundaries.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models import model as model_lib
from ..models.config import ModelConfig
from ..optim import adam, adamw, sgd, apply_updates, clip_by_global_norm
from ..optim.schedules import linear_warmup_cosine
from ..parallel import sharding as shd

HIERARCHICAL_MODES = ("arar_grouped", "rma_arar_grouped", "ensemble")
SYNC_MODES = ("allreduce",) + HIERARCHICAL_MODES


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    lr: float = 3e-4
    warmup: int = 100
    total_steps: int = 10_000
    weight_decay: float = 0.0
    grad_clip: float = 1.0
    optimizer: str = "adamw"
    microbatches: int = 1
    sync_mode: str = "allreduce"
    sync_h: int = 100               # outer-group period (paper Tab. I)
    sync_combine: str = "mean"


def _make_optimizer(tcfg: TrainConfig):
    sched = linear_warmup_cosine(tcfg.lr, tcfg.warmup, tcfg.total_steps)
    if tcfg.optimizer == "adamw":
        return adamw(sched, weight_decay=tcfg.weight_decay)
    if tcfg.optimizer == "adam":
        return adam(sched)
    return sgd(sched, momentum=0.9)


def _is_hierarchical(tcfg: TrainConfig, mesh: Optional[Mesh]) -> bool:
    return (tcfg.sync_mode in HIERARCHICAL_MODES and mesh is not None
            and "pod" in mesh.axis_names and mesh.shape["pod"] > 1)


def _rules_for(tcfg: TrainConfig, mesh: Optional[Mesh]):
    if _is_hierarchical(tcfg, mesh):
        # per-pod model copies: FSDP only over data, batch still over both
        return {"fsdp": ("data",), "batch": ("data",)}
    return None


# ----------------------------------------------------------------------------
# state


def init_train_state(key, cfg: ModelConfig, tcfg: TrainConfig):
    params = model_lib.init(key, cfg)
    opt = _make_optimizer(tcfg).init(params)
    state = {"params": params, "opt": opt, "step": jnp.zeros((), jnp.int32)}
    if tcfg.sync_mode == "rma_arar_grouped":
        state["mailbox"] = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
    return state


def make_train_state(key, cfg: ModelConfig, tcfg: TrainConfig,
                     mesh: Optional[Mesh] = None, abstract: bool = False):
    """Returns (state, state_shardings or None).

    With a hierarchical sync mode on a multi-pod mesh, every leaf gains a
    leading `pod` axis (one model copy per pod).
    """
    init = functools.partial(init_train_state, cfg=cfg, tcfg=tcfg)
    hier = _is_hierarchical(tcfg, mesh)
    n_pod = mesh.shape["pod"] if hier else 0

    if hier:
        base = init
        init = lambda k: jax.tree.map(
            lambda x: jnp.broadcast_to(x, (n_pod,) + x.shape), base(k))

    if abstract:
        state = jax.eval_shape(init, key)
    else:
        state = jax.jit(init)(key) if mesh is None else init(key)

    shardings = None
    if mesh is not None:
        shardings = state_shardings(state, cfg, tcfg, mesh)
        if not abstract:
            state = jax.device_put(state, shardings)
    return state, shardings


def _axes_tree(state, cfg: ModelConfig, tcfg: TrainConfig, hier: bool):
    """Logical-axes pytree matching the train state."""
    params = state["params"]
    if hier:
        params = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype),
                              params)
    paxes = model_lib.param_axes(params, cfg)
    axes = {"params": paxes, "opt": {"mu": paxes, "nu": paxes, "step": ()},
            "step": ()}
    if tcfg.optimizer == "sgd":
        axes["opt"] = {"step": ()} if "mom" not in state["opt"] else \
            {"mom": paxes, "step": ()}
    if "mailbox" in state:
        axes["mailbox"] = paxes
    if hier:
        axes = jax.tree.map(lambda a: ("pod_copy",) + tuple(a), axes,
                            is_leaf=lambda v: isinstance(v, tuple))
    return axes


def state_shardings(state, cfg: ModelConfig, tcfg: TrainConfig, mesh: Mesh):
    hier = _is_hierarchical(tcfg, mesh)
    axes = _axes_tree(state, cfg, tcfg, hier)
    rules = dict(_rules_for(tcfg, mesh) or {})
    rules["pod_copy"] = ("pod",)
    with shd.axis_rules(mesh, rules):
        return shd.tree_shardings(state, axes)


def batch_shardings(batch_tree, mesh: Mesh):
    return jax.tree.map(
        lambda _: NamedSharding(
            mesh, P(tuple(a for a in ("pod", "data") if a in mesh.axis_names))),
        batch_tree)


# ----------------------------------------------------------------------------
# gradient computation (shared by both paths)


def _compute_grads(params, batch, cfg: ModelConfig, tcfg: TrainConfig):
    """Value+grad with optional microbatch accumulation."""
    M = tcfg.microbatches
    vg = jax.value_and_grad(model_lib.loss_fn, has_aux=True)
    if M <= 1:
        (loss, metrics), grads = vg(params, batch, cfg)
        return loss, metrics, grads

    def split(x):
        return x.reshape((M, x.shape[0] // M) + x.shape[1:])

    mb = jax.tree.map(split, batch)

    def body(carry, mbatch):
        loss_a, grads_a = carry
        (loss, metrics), grads = vg(params, mbatch, cfg)
        grads_a = jax.tree.map(
            lambda a, g: a + g.astype(jnp.float32) / M, grads_a, grads)
        return (loss_a + loss / M, grads_a), metrics

    zero = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    (loss, grads), metrics = jax.lax.scan(body, (jnp.zeros(()), zero), mb)
    metrics = jax.tree.map(lambda x: x[-1], metrics)
    grads = jax.tree.map(lambda g, p: g.astype(p.dtype), grads, params)
    return loss, metrics, grads


def _apply(state, grads, tcfg: TrainConfig, extra=None):
    if tcfg.grad_clip:
        grads, gnorm = clip_by_global_norm(grads, tcfg.grad_clip)
    else:
        gnorm = jnp.zeros(())
    opt = _make_optimizer(tcfg)
    updates, opt_state = opt.update(grads, state["opt"], state["params"])
    params = apply_updates(state["params"], updates)
    new_state = dict(state, params=params, opt=opt_state, step=state["step"] + 1)
    if extra:
        new_state.update(extra)
    return new_state, gnorm


# ----------------------------------------------------------------------------
# train steps


def _step_allreduce(state, batch, cfg: ModelConfig, tcfg: TrainConfig):
    loss, metrics, grads = _compute_grads(state["params"], batch, cfg, tcfg)
    new_state, gnorm = _apply(state, grads, tcfg)
    return new_state, dict(metrics, loss=loss, gnorm=gnorm)


def _ring_exchange(grads, mailbox, step, tcfg: TrainConfig, n_pod: int):
    """Cross-pod SAGIPS exchange: >=2-D leaves ride the ring every sync_h."""
    perm = [(i, (i + 1) % n_pod) for i in range(n_pod)]

    def comb(a, b):
        out = a + b
        return out * 0.5 if tcfg.sync_combine == "mean" else out

    def exchange(fresh, stale):
        def leaf(g, mb):
            if g.ndim < 2:          # §V-C: biases / scales stay local
                return g, mb
            if tcfg.sync_mode == "rma_arar_grouped":
                new_mb = jax.lax.ppermute(g, "pod", perm)
                return comb(g, mb), new_mb
            recv = jax.lax.ppermute(g, "pod", perm)
            return comb(g, recv), mb
        pairs = jax.tree.map(lambda g, mb: leaf(g, mb), fresh, stale)
        g_new = jax.tree.map(lambda pr: pr[0], pairs,
                             is_leaf=lambda v: isinstance(v, tuple))
        mb_new = jax.tree.map(lambda pr: pr[1], pairs,
                              is_leaf=lambda v: isinstance(v, tuple))
        return g_new, mb_new

    if tcfg.sync_mode == "ensemble":
        return grads, mailbox
    due = (step % tcfg.sync_h) == 0

    def do(args):
        return exchange(*args)

    def skip(args):
        return args

    return jax.lax.cond(due, do, skip, (grads, mailbox))


def _step_hierarchical(state, batch, cfg: ModelConfig, tcfg: TrainConfig,
                       n_pod: int):
    """Inside shard_map manual over ('pod',): state leaves have local leading
    dim 1; batch leading (global) dim is pod-local."""
    state1 = jax.tree.map(lambda x: x[0], state)
    loss, metrics, grads = _compute_grads(state1["params"], batch, cfg, tcfg)
    mailbox = state1.get("mailbox",
                         jax.tree.map(lambda g: jnp.zeros((), jnp.float32), grads))
    if tcfg.sync_mode == "rma_arar_grouped":
        grads_f = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
        synced, mailbox = _ring_exchange(grads_f, state1["mailbox"],
                                         state1["step"], tcfg, n_pod)
        synced = jax.tree.map(lambda s, g: s.astype(g.dtype), synced, grads)
        extra = {"mailbox": mailbox}
    else:
        synced, _ = _ring_exchange(grads, grads, state1["step"], tcfg, n_pod)
        extra = None
    new_state, gnorm = _apply(state1, synced, tcfg, extra)
    out = jax.tree.map(lambda x: x[None], new_state)
    metrics = dict(metrics, loss=loss, gnorm=gnorm)
    # pod-mean metrics for logging
    metrics = jax.tree.map(lambda m: jax.lax.pmean(m, "pod"), metrics)
    return out, metrics


def make_train_step(cfg: ModelConfig, tcfg: TrainConfig,
                    mesh: Optional[Mesh] = None, state_example=None,
                    donate: bool = True):
    """Build the jitted train step.  Returns (fn, in_state_shardings)."""
    if mesh is None:
        def step(state, batch):
            return _step_allreduce(state, batch, cfg, tcfg)
        return jax.jit(step, donate_argnums=(0,) if donate else ()), None

    hier = _is_hierarchical(tcfg, mesh)
    rules = _rules_for(tcfg, mesh)
    st_shardings = state_shardings(state_example, cfg, tcfg, mesh) \
        if state_example is not None else None

    if not hier:
        def step(state, batch):
            with shd.axis_rules(mesh, rules):
                return _step_allreduce(state, batch, cfg, tcfg)
        fn = jax.jit(step, in_shardings=(st_shardings, None) if st_shardings
                     else None,
                     out_shardings=(st_shardings, None) if st_shardings else None,
                     donate_argnums=(0,) if donate else ())
        return fn, st_shardings

    n_pod = mesh.shape["pod"]

    flags = {"embed_onehot": True}

    def step(state, batch):
        # embed_onehot: XLA cannot partition gathers under manual subaxes
        with shd.axis_rules(mesh, rules, flags=flags):
            return _step_hierarchical(state, batch, cfg, tcfg, n_pod)

    wrapped = jax.shard_map(
        step, mesh=mesh,
        in_specs=(P("pod"), P("pod")),
        out_specs=(P("pod"), P()),
        axis_names={"pod"}, check_vma=False)
    in_sh = (st_shardings, None) if st_shardings else None
    fn = jax.jit(wrapped, in_shardings=in_sh,
                 donate_argnums=(0,) if donate else ())
    return fn, st_shardings


make_train_state.__doc__ += "\n(see module docstring for sync semantics)"


class Trainer:
    """Convenience loop wrapper used by examples."""

    def __init__(self, cfg: ModelConfig, tcfg: TrainConfig, key,
                 mesh: Optional[Mesh] = None):
        self.cfg, self.tcfg, self.mesh = cfg, tcfg, mesh
        self.state, self.shardings = make_train_state(key, cfg, tcfg, mesh)
        self.step_fn, _ = make_train_step(cfg, tcfg, mesh,
                                          state_example=self.state)

    def run(self, stream, steps: int, log_every: int = 10, log=print):
        import time
        t0 = time.time()
        for i, batch in zip(range(steps), stream):
            self.state, metrics = self.step_fn(self.state, batch)
            if i % log_every == 0 or i == steps - 1:
                loss = float(metrics["loss"])
                log(f"step {i:5d} loss {loss:.4f} "
                    f"ce {float(metrics['ce']):.4f} "
                    f"({(time.time()-t0)/(i+1):.2f}s/step)")
        return self.state
