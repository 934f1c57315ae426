"""The SAGIPS workflow — optimizer ⇄ environment loop, distributed.

Per epoch, each rank (§IV-B):
  1. bootstraps a sub-sample of its local reference data (50% by default),
  2. runs the generator -> pipeline to produce synthetic events,
  3. trains its *local* discriminator (never synchronized),
  4. computes generator gradients through pipeline + discriminator,
  5. exchanges generator *weight* gradients per the configured sync mode
     (fused single-buffer ring payload by default; with
     `SyncConfig.overlap` the pod-boundary segment is shipped at epoch t
     and consumed at t+1, overlapping the slow-link transfer with the
     next epoch's compute — see `core.sync`),
  6. applies its Adam update (generator copies may drift — the ensemble
     response over ranks is the estimator, §VI-A).

Asymmetric update cadence (`WorkflowConfig.disc_every` / `gen_every`,
ISSUE 7): step 3 runs only when `epoch % disc_every == 0`, steps 4–6 only
when `epoch % gen_every == 0`.  Off-epochs ride a SPMD-uniform `lax.cond`
(predicate derived from the rank-uniform epoch counter), so the skipped
forward/backward genuinely disappears from the executed HLO branch — the
dominant per-epoch matmuls (the discriminator's real+fake batches) can be
paid every other epoch.  The default (1, 1) is the paper's every-epoch
schedule, bitwise-pinned.

Three drivers share the per-rank functions:
  * `train_vmap`     — R simulated ranks on one device (convergence studies)
  * `make_epoch_fn_shard` — shard_map over a mesh (production / dry-run)
  * `train_proc`     — N REAL worker processes free-running over the
                       `repro.runtime` mailbox fabric (`ProcComm`); the
                       only backend whose deposit tags carry measured
                       (not simulated) skew

Step 5 is owned by a `core.sync.SyncSchedule` (ISSUE 4): every sync-side
buffer — the fused ring payload, the (depth-k or adaptive max-depth) RMA
mailbox, the overlap outer mailbox and the adaptive controller state —
lives inside ONE schedule-owned pytree at `state["sync"]`, and the epoch
body calls the schedule's single `exchange(comm, grads, sync_state,
epoch)` entry point.  Drivers never see individual mailboxes.

Both epoch factories DONATE the state argument (`donate_argnums=(0,)`,
since PR 2): the whole `state["sync"]` pytree rides inside the donated
state, so XLA aliases the exchange buffers in place instead of
reallocating them every epoch (pinned by tests/test_problems.py::
test_epoch_state_donation_aliases_exchange_buffers).

The forward model is pluggable: `WorkflowConfig.problem` names a registered
`repro.problems.InverseProblem`, and the GAN widths, sampler dispatch and
residual metric all derive from it (default: the paper's 1D proxy app).
See docs/architecture.md for the end-to-end tour.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from . import gan, pipeline, sync as sync_lib
from .ring import Comm, ShardComm, VmapComm
from ..obs.config import ObsConfig
from ..optim import adam


@dataclasses.dataclass(frozen=True)
class WorkflowConfig:
    sync: sync_lib.SyncConfig = sync_lib.SyncConfig()
    n_param_samples: int = pipeline.PARAM_SAMPLES       # Tab. III
    events_per_sample: int = pipeline.EVENTS_PER_SAMPLE
    data_fraction: float = 0.5                          # §VI-C2
    gen_lr: float = 1e-5                                # §V-A
    disc_lr: float = 1e-4
    sampler_impl: str = "jnp"                           # 'jnp' | 'pallas'
    sampler_interpret: Optional[bool] = None            # None: auto per backend
    problem: str = "proxy1d"                            # registry key
    disc_every: int = 1            # discriminator update cadence: epochs
    #                                where epoch % disc_every != 0 skip the
    #                                disc forward/backward AT THE HLO LEVEL
    #                                (SPMD-uniform lax.cond, like the
    #                                overlap ship gate)
    gen_every: int = 1             # generator cadence: off-epochs skip gen
    #                                grads, the ring exchange AND the Adam
    #                                apply (disc-only epochs)
    disc_compute: str = "fp32"     # discriminator forward compute precision
    #                                ('fp32' | 'bf16'): bf16 runs the
    #                                dominant per-epoch matmuls reduced,
    #                                with fp32 master weights/optimizer —
    #                                the compute-side analogue of the bf16
    #                                ring payload (BENCH_precision.json)
    obs: ObsConfig = ObsConfig()   # telemetry (ISSUE 10): metrics pytree +
    #                                flush/trace/profile sinks.  The default
    #                                is inert — every obs branch below is a
    #                                Python-level gate, so disabled configs
    #                                lower to byte-identical HLO (pinned)

    def __post_init__(self):
        if self.disc_every < 1 or self.gen_every < 1:
            raise ValueError(
                "disc_every/gen_every are update cadences (update when "
                f"epoch %% N == 0) and must be >= 1; got "
                f"disc_every={self.disc_every}, gen_every={self.gen_every}")
        if self.disc_compute not in gan.DISC_COMPUTE:
            raise ValueError(
                f"disc_compute must be one of {gan.DISC_COMPUTE}, got "
                f"{self.disc_compute!r}")

    @property
    def disc_batch(self) -> int:
        return self.n_param_samples * self.events_per_sample

    @property
    def problem_obj(self):
        """Resolve the registered `InverseProblem` (lazy import so the
        config stays a plain hashable dataclass and `repro.problems` can
        import `repro.core` without a cycle)."""
        from ..problems import get_problem
        return get_problem(self.problem)


def init_rank_state(key, wcfg: WorkflowConfig, schedule=None):
    """State of ONE rank (no leading rank axis); GAN widths derive from the
    problem's param/observable dims.

    `state["sync"]` is the configured `SyncSchedule`'s own pytree (RMA
    mailbox, overlap outer mailbox, adaptive controller — whatever the
    schedule needs); the structure is fixed per schedule, so drivers thread
    it opaquely.  Multi-rank callers (`init_state`) build the schedule once
    and pass it in."""
    prob = wcfg.problem_obj
    kg, kd, kr = jax.random.split(key, 3)
    gen_p = gan.init_generator(kg, n_params=prob.n_params,
                               param_shape=prob.param_shape)
    disc_p = gan.init_discriminator(kd, obs_dim=prob.obs_dim)
    gen_opt = adam(wcfg.gen_lr).init(gen_p)
    disc_opt = adam(wcfg.disc_lr).init(disc_p)
    if schedule is None:
        schedule = make_schedule(wcfg)
    state = {
        "gen": gen_p, "disc": disc_p,
        "gen_opt": gen_opt, "disc_opt": disc_opt,
        "sync": schedule.init_state(),
        "rng": kr,
        "epoch": jnp.zeros((), jnp.int32),
    }
    if wcfg.obs.metrics:
        state["obs"] = schedule.init_obs_state()
    return state


def init_state(key, n_ranks: int, wcfg: WorkflowConfig, same_generator=True):
    """Stacked state for `n_ranks` simulated ranks.

    Generators start from identical copies (the paper sends "initial copies
    of the generator weights to each rank"); discriminators are independent.
    """
    keys = jax.random.split(key, n_ranks)
    schedule = make_schedule(wcfg)
    states = [init_rank_state(k, wcfg, schedule=schedule) for k in keys]
    if same_generator:
        for s in states[1:]:
            s["gen"] = states[0]["gen"]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *states)


def init_run(key, n_ranks: int, wcfg: WorkflowConfig, data, rank=None):
    """Seed -> (initial state, bootstrap data split): THE derivation every
    driver shares.  `train_vmap`, the shard driver's callers and the proc
    workers (`runtime/launch.py`) must see bitwise-identical initial
    states and per-rank data for the cross-backend parity pins to hold,
    so the key-splitting order lives in exactly one place — change it
    here or nowhere.

    `rank=None` returns the stacked layout: (state `[R, ...]`,
    data `[R, n_sub, obs]`).  An int returns (per-rank state, per-rank
    data) for that rank only — bitwise equal to slicing the stacked
    result, without paying the full R-rank build inside every worker
    process (which would cost O(R) inits x O(R) workers job-wide).
    """
    key, k_sub = jax.random.split(key)
    n_sub = max(1, int(wcfg.data_fraction * data.shape[0]))
    sub_keys = jax.random.split(k_sub, n_ranks)

    def split_for(k):
        return jnp.take(
            data, jax.random.permutation(k, data.shape[0])[:n_sub], axis=0)

    if rank is None:
        return init_state(key, n_ranks, wcfg), \
            jnp.stack([split_for(k) for k in sub_keys])
    keys = jax.random.split(key, n_ranks)
    state = init_rank_state(keys[rank], wcfg)
    if rank != 0:
        # same_generator: every rank starts from rank 0's generator copy
        # (init_rank_state splits its key (kg, kd, kr) and feeds kg to
        # init_generator — reproduce exactly that for rank 0's key)
        kg0 = jax.random.split(keys[0], 3)[0]
        state["gen"] = gan.init_generator(
            kg0, n_params=wcfg.problem_obj.n_params,
            param_shape=wcfg.problem_obj.param_shape)
    return state, split_for(sub_keys[rank])


# ----------------------------------------------------------------------------
# inference-time solving (build/compile split, ISSUE 8)


@dataclasses.dataclass(frozen=True)
class SolveConfig:
    """How a trained generator stack is inverted against a submitted
    observation batch (the serving path; the trainer's final report uses
    the same factory, so "what the solver computes" has one definition).

    The solve is candidate scoring under the generative prior: each of the
    R stacked generators proposes `n_candidates` parameter draws, each
    candidate is pushed through the problem's forward model for
    `events_per_candidate` events, and candidates are scored by how well
    their simulated event moments match the (masked) moments of the
    submitted `y`.  The estimate is the mean of the best `top_frac`
    fraction of candidates; `top_frac=1.0` degenerates to the unweighted
    ensemble prior mean — independent of `y` by construction (pinned by
    tests/test_serving.py::test_top_frac_one_is_prior_mean).
    """
    n_candidates: int = 128        # candidate draws PER generator rank
    events_per_candidate: int = 64
    top_frac: float = 0.25         # fraction of candidates kept (0, 1]
    seed: int = 0                  # solve is deterministic per config
    sampler_impl: str = "jnp"      # 'jnp' | 'pallas' (same dispatch as train)
    sampler_interpret: Optional[bool] = None

    def __post_init__(self):
        if self.n_candidates < 1 or self.events_per_candidate < 1:
            raise ValueError(
                f"need n_candidates >= 1 and events_per_candidate >= 1, got "
                f"{self.n_candidates} / {self.events_per_candidate}")
        if not (0.0 < self.top_frac <= 1.0):
            raise ValueError(
                f"top_frac must be in (0, 1], got {self.top_frac}")


def make_solver(problem, cfg: SolveConfig):
    """Build (do NOT run or compile) the solve function for `problem`.

    Returns `solve(gen_stack, ys, mask) -> {"params", "sigma", "score"}`:

      gen_stack   stacked generator pytree `[R, ...]` (a trained
                  checkpoint's `state["gen"]`, or one rank's `[1, ...]`)
      ys          `[B, bucket, obs_dim]` padded observation batches
      mask        `[B, bucket]` bool, True on real event rows
      params      `[B, n_params]` posterior estimate per request
      sigma       `[B, n_params]` spread of the kept candidates
      score       `[B]` mean moment-match score of the kept candidates
                  (higher is better; 0 is a perfect moment match)

    The function is pure and shape-specialized in (R, B, bucket) — the
    serving layer owns WHERE it is compiled (`serving.cache`, one warm
    executable per (problem, bucket)); this factory owns only WHAT it
    computes.  Candidate generation and forward simulation depend only on
    `gen_stack`, so inside one call they are computed once and shared
    across the B requests; only the cheap moment scoring is vmapped per
    request.
    """
    M, E = cfg.n_candidates, cfg.events_per_candidate
    key = jax.random.PRNGKey(cfg.seed)
    k_noise, k_u = jax.random.split(key)

    def _moments(events, w):
        """Masked per-dim mean/std of events [N, obs] with weights [N]."""
        n = jnp.maximum(w.sum(), 1.0)
        mean = (events * w[:, None]).sum(axis=0) / n
        var = (((events - mean) ** 2) * w[:, None]).sum(axis=0) / n
        return jnp.concatenate([mean, jnp.sqrt(var + 1e-12)])

    def solve(gen_stack, ys, mask):
        R = jax.tree.leaves(gen_stack)[0].shape[0]
        noise = jax.random.normal(k_noise, (R, M, gan.NOISE_DIM))
        cands = jax.vmap(gan.generate_params)(gen_stack, noise)
        cands = cands.reshape(R * M, -1)              # [RM, n_params]
        u = jax.random.uniform(
            k_u, (R * M, E, problem.noise_channels))
        events = problem.sample_events(
            cands, u, impl=cfg.sampler_impl,
            interpret=cfg.sampler_interpret)
        events = events.reshape(R * M, E, -1)          # [RM, E, obs]
        ones = jnp.ones((E,), events.dtype)
        cand_mom = jax.vmap(lambda ev: _moments(ev, ones))(events)  # [RM, 2*obs]
        # scale-free scoring: normalize each moment dim by its spread
        # across candidates so no observable dominates the distance
        scale = cand_mom.std(axis=0) + 1e-6

        def score_one(y, w):
            y_mom = _moments(y, w.astype(y.dtype))
            d = (cand_mom - y_mom[None, :]) / scale[None, :]
            return -jnp.mean(d * d, axis=1)            # [RM], 0 = perfect

        scores = jax.vmap(score_one)(ys, mask)         # [B, RM]
        k = max(1, int(round(cfg.top_frac * R * M)))
        top_scores, top_idx = jax.lax.top_k(scores, k)
        kept = jnp.take(cands, top_idx, axis=0)        # [B, k, n_params]
        return {
            "params": kept.mean(axis=1),
            "sigma": kept.std(axis=1),
            "score": top_scores.mean(axis=1),
        }

    return solve


# ----------------------------------------------------------------------------
# per-rank compute


@jax.custom_batching.custom_vmap
def _take_rows(data, idx):
    """`data[idx]` along axis 0: one rank's rows from its own table."""
    return jnp.take(data, idx, axis=0)


@_take_rows.def_vmap
def _take_rows_vmap(axis_size, in_batched, data, idx):
    """One unbatched gather per rank; an unbatched operand is shared by
    every rank's trip."""
    data_b, idx_b = in_batched

    def one(xs):
        d, i = xs
        return _take_rows(data if d is None else d, idx if i is None else i)

    return jax.lax.map(one, (data if data_b else None,
                             idx if idx_b else None)), True


def _bootstrap(rng, data, n_draw: int):
    """Random draw with replacement (bootstrap, §IV-B).

    Under `vmap` the gather runs once per rank (`_take_rows`' rule), the
    form the shard program runs: batched over (rank, row), the TPU
    compiler packs both indices into one gather that took 3.4 times as
    long per rank in Tab. III's epoch on a v5e."""
    with jax.named_scope("sagips_sample"):
        idx = jax.random.randint(rng, (n_draw,), 0, data.shape[0])
        return _take_rows(data, idx)


def rank_grads(state, data_local, wcfg: WorkflowConfig,
               update_disc: bool = True, update_gen: bool = True):
    """Steps 1–4 for one rank.  Returns (partial_state, gen_grads, metrics).

    `update_disc` / `update_gen` are STATIC (Python-bool) cadence flags:
    each combination traces its own branch, so a skipped half genuinely
    disappears from that branch's HLO (the epoch bodies hang the branches
    on a SPMD-uniform `lax.cond` over the epoch counter — see
    `_epoch_body_vmap`).  The rng stream advances identically regardless
    of the flags, so cadenced runs stay comparable draw-for-draw with the
    every-epoch schedule.  Skipped halves report NaN losses and (when no
    forward ran at all) NaN parameter metrics; `g_grads` is a zero tree
    when the generator is skipped (callers on the cadence path never
    exchange or apply it)."""
    from .. import problems as problems_lib
    prob = wcfg.problem_obj
    cdt = gan.compute_dtype_of(wcfg.disc_compute)
    rng, k_boot, k_gen = jax.random.split(state["rng"], 3)
    pred_params = None

    if update_disc:
        # identical real/fake counts (§V-A): draw the synthetic batch size
        real = _bootstrap(k_boot, data_local, wcfg.disc_batch)

        fake, pred_params = problems_lib.synthetic_events(
            prob, state["gen"], k_gen, wcfg.n_param_samples,
            wcfg.events_per_sample,
            impl=wcfg.sampler_impl, interpret=wcfg.sampler_interpret)

        # --- discriminator update (local, immediate — §IV-B) -----------------
        d_loss, d_grads = jax.value_and_grad(gan.disc_loss)(
            state["disc"], real, jax.lax.stop_gradient(fake),
            compute_dtype=cdt)
        with jax.named_scope("sagips_apply"):
            d_upd, disc_opt = adam(wcfg.disc_lr).update(d_grads,
                                                        state["disc_opt"])
            disc = jax.tree.map(lambda p, u: p + u, state["disc"], d_upd)
    else:
        d_loss = jnp.full((), jnp.nan, jnp.float32)
        disc, disc_opt = state["disc"], state["disc_opt"]

    if update_gen:
        # --- generator gradients through forward model + (old) discriminator -
        def g_objective(gen_p):
            fake_ev, pred = problems_lib.synthetic_events(
                prob, gen_p, k_gen, wcfg.n_param_samples,
                wcfg.events_per_sample,
                impl=wcfg.sampler_impl, interpret=wcfg.sampler_interpret)
            return gan.gen_loss(state["disc"], fake_ev,
                                compute_dtype=cdt), pred

        (g_loss, pred_aux), g_grads = jax.value_and_grad(
            g_objective, has_aux=True)(state["gen"])
        if pred_params is None:     # disc-off epoch: metrics from the aux
            pred_params = pred_aux
    else:
        g_loss = jnp.full((), jnp.nan, jnp.float32)
        g_grads = jax.tree.map(jnp.zeros_like, state["gen"])

    if pred_params is None:         # neither half sampled this epoch
        pred_mean = jnp.full((prob.n_params,), jnp.nan, jnp.float32)
    else:
        pred_mean = pred_params.mean(axis=0)
    metrics = {
        "d_loss": d_loss, "g_loss": g_loss,
        "pred_params": pred_mean,
        "residuals": prob.residuals(pred_mean),
    }
    new_state = dict(state, disc=disc, disc_opt=disc_opt, rng=rng)
    return new_state, g_grads, metrics


def rank_apply(state, synced_grads, new_sync, wcfg: WorkflowConfig):
    """Steps 5–6: apply the synchronized generator update.  `new_sync` is
    the schedule's refreshed SyncState pytree (opaque to this layer)."""
    with jax.named_scope("sagips_apply"):
        g_upd, gen_opt = adam(wcfg.gen_lr).update(synced_grads,
                                                  state["gen_opt"])
        gen = jax.tree.map(lambda p, u: p + u, state["gen"], g_upd)
        return dict(state, gen=gen, gen_opt=gen_opt, sync=new_sync,
                    epoch=state["epoch"] + 1)


# ----------------------------------------------------------------------------
# drivers


def _gen_example(wcfg: WorkflowConfig):
    """Abstract per-rank generator pytree (shapes/dtypes only, no compute)."""
    prob = wcfg.problem_obj
    return jax.eval_shape(
        lambda k: gan.init_generator(k, n_params=prob.n_params,
                                     param_shape=prob.param_shape),
        jax.random.PRNGKey(0))


def make_schedule(wcfg: WorkflowConfig) -> sync_lib.SyncSchedule:
    """The configured `SyncSchedule`: weight mask + cached FusionSpec built
    once per driver construction (never re-derived leaf-by-leaf inside the
    jitted epoch), then handed to the schedule factory.  Derived from the
    problem's generator shape — the schedule machinery itself stays
    problem-agnostic."""
    example = _gen_example(wcfg)
    mask = gan.weight_mask(example)
    spec = sync_lib.FusionSpec.build(
        example, mask,
        payload_dtype=sync_lib.payload_dtype_of(wcfg.sync.payload_precision),
        chunk_bytes=wcfg.sync.ring_chunking)
    return sync_lib.make_schedule(wcfg.sync, mask, spec)


def _epoch_body_vmap(comm, schedule, wcfg: WorkflowConfig):
    """One stacked-[R] epoch.  With the default every-epoch cadence this is
    exactly the historical body (bitwise-pinned).  With `disc_every` /
    `gen_every` > 1 the skipped halves ride a `lax.cond` OUTSIDE the vmap:
    the predicate is derived from the (rank-uniform) epoch counter, so the
    branch is SPMD-uniform and lowers to a real HLO conditional — under
    vmap a batched predicate would silently become a select that computes
    both halves (the same trick as the overlap ship gate, PR 3).  A
    generator off-epoch skips gradients, ring exchange AND Adam apply; the
    epoch counter still advances."""
    de, ge = wcfg.disc_every, wcfg.gen_every

    def grads_phase(update_disc, update_gen):
        def f(state, data_per_rank):
            return jax.vmap(lambda s, d: rank_grads(
                s, d, wcfg, update_disc=update_disc,
                update_gen=update_gen))(state, data_per_rank)
        return f

    def epoch(state, data_per_rank):
        epoch_idx = state["epoch"][0]
        if de == 1 and ge == 1:
            new_state, g_grads, metrics = grads_phase(True, True)(
                state, data_per_rank)
        elif ge == 1:
            new_state, g_grads, metrics = jax.lax.cond(
                (epoch_idx % de) == 0,
                grads_phase(True, True), grads_phase(False, True),
                state, data_per_rank)
        elif de == 1:
            new_state, g_grads, metrics = jax.lax.cond(
                (epoch_idx % ge) == 0,
                grads_phase(True, True), grads_phase(True, False),
                state, data_per_rank)
        else:
            idx = ((epoch_idx % de) == 0).astype(jnp.int32) * 2 \
                + ((epoch_idx % ge) == 0).astype(jnp.int32)
            new_state, g_grads, metrics = jax.lax.switch(
                idx, [grads_phase(False, False), grads_phase(False, True),
                      grads_phase(True, False), grads_phase(True, True)],
                state, data_per_rank)

        def gen_segment(ns, gg):
            # obs is a Python-level gate (wcfg.obs.metrics is a plain
            # bool): the disabled branch traces the literally-unchanged
            # exchange, so disabled configs lower to byte-identical HLO
            with jax.named_scope("sagips_exchange"):
                if wcfg.obs.metrics:
                    synced, new_sync, row = schedule.exchange_with_obs(
                        comm, gg, ns["sync"], epoch_idx)
                else:
                    synced, new_sync = schedule.exchange(
                        comm, gg, ns["sync"], epoch_idx)
            out = jax.vmap(lambda s, g, n2: rank_apply(s, g, n2, wcfg))(
                ns, synced, new_sync)
            if wcfg.obs.metrics:
                out["obs"] = schedule.accumulate_obs(ns["obs"], row)
            return out

        if ge == 1:
            out = gen_segment(new_state, g_grads)
        else:
            out = jax.lax.cond(
                (epoch_idx % ge) == 0, gen_segment,
                lambda ns, gg: dict(ns, epoch=ns["epoch"] + 1),
                new_state, g_grads)
        if wcfg.obs.metrics:
            metrics = dict(metrics, obs=out["obs"])
        return out, metrics
    return epoch


def make_epoch_fn_vmap(n_outer: int, n_inner: int, wcfg: WorkflowConfig):
    """Epoch step over stacked state [R, ...]; data_per_rank [R, N, obs].

    The state argument is DONATED: every sync-side buffer (the schedule's
    whole `state["sync"]` pytree) lives inside the state, so donation lets
    XLA alias the exchange buffers in place instead of allocating a fresh
    [R, D] payload every epoch.  Callers must not reuse the state they
    pass in.
    """
    comm = VmapComm(n_outer, n_inner)
    schedule = make_schedule(wcfg)
    return jax.jit(_epoch_body_vmap(comm, schedule, wcfg),
                   donate_argnums=(0,))


def make_chunk_fn_vmap(n_outer: int, n_inner: int, wcfg: WorkflowConfig,
                       chunk: int):
    """`chunk` epochs fused into ONE jitted lax.scan — the multi-epoch
    driver stops round-tripping to Python per epoch.

    Returns fn(state, data_per_rank) -> (state, metrics) with every metric
    leaf gaining a leading [chunk] axis (one row per epoch in the chunk).
    The state argument is donated (see `make_epoch_fn_vmap`).
    """
    comm = VmapComm(n_outer, n_inner)
    schedule = make_schedule(wcfg)
    epoch = _epoch_body_vmap(comm, schedule, wcfg)

    def chunked(state, data_per_rank):
        def body(s, _):
            return epoch(s, data_per_rank)
        return jax.lax.scan(body, state, xs=None, length=chunk)

    return jax.jit(chunked, donate_argnums=(0,))


def make_epoch_fn_shard(mesh, wcfg: WorkflowConfig,
                        outer_axis="pod", inner_axis="data"):
    """Epoch step over a device mesh: state/data sharded per-rank.

    State pytrees carry a leading rank axis of size n_ranks =
    prod(mesh.shape) sharded over (outer, inner); inside shard_map each
    rank sees leading dim 1.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P
    axes = tuple(a for a in (outer_axis, inner_axis) if a in mesh.axis_names)
    n_outer = mesh.shape[outer_axis] if outer_axis in mesh.axis_names else 1
    n_inner = mesh.shape[inner_axis]
    comm = ShardComm(n_outer, n_inner, outer_axis, inner_axis)
    schedule = make_schedule(wcfg)

    de, ge = wcfg.disc_every, wcfg.gen_every

    def grads_phase(update_disc, update_gen):
        def f(state1, data1):
            return rank_grads(state1, data1, wcfg, update_disc=update_disc,
                              update_gen=update_gen)
        return f

    def epoch(state, data_local):
        # leading axis has local size 1 inside shard_map
        state1 = jax.tree.map(lambda x: x[0], state)
        epoch_idx = state1["epoch"]
        # cadence gates: the epoch counter is identical on every rank, so
        # the cond is SPMD-uniform (a real branch, not a select) — the same
        # contract as the overlap ship gate
        if de == 1 and ge == 1:
            new_state, g_grads, metrics = grads_phase(True, True)(
                state1, data_local[0])
        elif ge == 1:
            new_state, g_grads, metrics = jax.lax.cond(
                (epoch_idx % de) == 0,
                grads_phase(True, True), grads_phase(False, True),
                state1, data_local[0])
        elif de == 1:
            new_state, g_grads, metrics = jax.lax.cond(
                (epoch_idx % ge) == 0,
                grads_phase(True, True), grads_phase(True, False),
                state1, data_local[0])
        else:
            idx = ((epoch_idx % de) == 0).astype(jnp.int32) * 2 \
                + ((epoch_idx % ge) == 0).astype(jnp.int32)
            new_state, g_grads, metrics = jax.lax.switch(
                idx, [grads_phase(False, False), grads_phase(False, True),
                      grads_phase(True, False), grads_phase(True, True)],
                state1, data_local[0])

        def gen_segment(ns, gg):
            # same Python-level obs gate as the vmap body: disabled
            # configs trace the unchanged exchange (HLO-identity pin)
            with jax.named_scope("sagips_exchange"):
                if wcfg.obs.metrics:
                    synced, new_sync, row = schedule.exchange_with_obs(
                        comm, gg, ns["sync"], ns["epoch"])
                else:
                    synced, new_sync = schedule.exchange(
                        comm, gg, ns["sync"], ns["epoch"])
            out1 = rank_apply(ns, synced, new_sync, wcfg)
            if wcfg.obs.metrics:
                out1["obs"] = schedule.accumulate_obs(ns["obs"], row)
            return out1

        if ge == 1:
            out = gen_segment(new_state, g_grads)
        else:
            out = jax.lax.cond(
                (epoch_idx % ge) == 0, gen_segment,
                lambda ns, gg: dict(ns, epoch=ns["epoch"] + 1),
                new_state, g_grads)
        if wcfg.obs.metrics:
            metrics = dict(metrics, obs=out["obs"])
        out = jax.tree.map(lambda x: x[None], out)
        metrics = jax.tree.map(lambda x: x[None], metrics)
        return out, metrics

    spec = P(axes)
    fn = jax.shard_map(epoch, mesh=mesh, in_specs=(spec, spec),
                       out_specs=(spec, spec), check_vma=False)
    shardings = NamedSharding(mesh, spec)
    # donate the state (mailbox + exchange buffers alias in place)
    return jax.jit(fn, donate_argnums=(0,)), shardings


def chunk_schedule(n_epochs: int, chunk: int):
    """Yield (start_epoch, n) per scan chunk covering [0, n_epochs)."""
    e = 0
    while e < n_epochs:
        n = min(chunk, n_epochs - e)
        yield e, n
        e += n


def make_chunk_runner(n_outer: int, n_inner: int, wcfg: WorkflowConfig):
    """Compiled-chunk cache: run(state, data_per_rank, n) scans n epochs.

    Scan length is static, so each distinct n compiles once (a schedule
    from `chunk_schedule` produces at most two lengths).
    """
    fns = {}

    def run(state, data_per_rank, n: int):
        if n not in fns:
            fns[n] = make_chunk_fn_vmap(n_outer, n_inner, wcfg, n)
        return fns[n](state, data_per_rank)

    return run


def train_vmap(key, wcfg: WorkflowConfig, n_outer: int, n_inner: int,
               n_epochs: int, data, checkpoint_every: int = 0,
               chunk: int = 0, checkpoint_dir: Optional[str] = None,
               resume: bool = False):
    """Convergence-study driver: R = n_outer*n_inner simulated ranks.

    `data` [N, obs_dim] is the full reference set (from the configured
    problem's `make_reference_data`); the master rank "distributes"
    a copy to every rank (§IV-B: each rank has its own copy, analyzes a
    random fraction).  Returns (final_state, history dict of stacked
    metrics at each recorded epoch).

    Epochs run `chunk` at a time inside a single jitted `lax.scan`
    (default: `checkpoint_every`, else min(n_epochs, 64)), so the driver
    crosses the Python/device boundary once per chunk instead of once per
    epoch.  Recorded history: epochs where `e % checkpoint_every == 0`
    plus the final epoch; with `checkpoint_every=0` the final epoch is
    STILL recorded, so the history is never empty.

    `checkpoint_dir` persists the FULL state pytree (generator,
    discriminator, optimizers, rng, epoch counter and the whole
    `state["sync"]` pytree) via `checkpoint.store` at every chunk boundary
    that lands on the `checkpoint_every` cadence (and at the end);
    `resume=True` restores the newest `step_N` and continues from epoch N
    — the per-rank data split re-derives from `key` and everything else
    lives in the saved state, so a resume from a chunk-aligned step is
    BITWISE the uninterrupted run.  A checkpoint that landed off the
    chunk grid (a final-epoch save) resumes exactly as many epochs as
    remain, through a partial first chunk — same schedule, fp-identical
    up to scan-partition fusion noise.
    """
    R = n_outer * n_inner
    # each rank keeps a random sub-sample = data_fraction of the input
    # (§VI-C2); the derivation is shared bitwise with the proc workers
    state, data_per_rank = init_run(key, R, wcfg, data)

    if chunk <= 0:
        chunk = checkpoint_every if checkpoint_every > 0 else min(n_epochs, 64)
    chunk = max(1, min(chunk, n_epochs))
    run = make_chunk_runner(n_outer, n_inner, wcfg)

    start = 0
    if checkpoint_dir and resume:
        from ..checkpoint.store import restore_latest
        restored, step = restore_latest(checkpoint_dir, state)
        if restored is not None:
            state, start = restored, step

    # observability sinks (ISSUE 10): chunk-boundary metric flushes plus
    # an optional device-side jax.profiler capture around the epoch loop
    writer = None
    if wcfg.obs.metrics_out:
        from ..obs.metrics import MetricsWriter
        sched = make_schedule(wcfg)
        writer = MetricsWriter(wcfg.obs.metrics_out, header={
            "problem": wcfg.problem, "schedule": sched.name,
            "payload_bytes": sched.payload_bytes, "n_ranks": R,
            "n_epochs": n_epochs})
    if wcfg.obs.profile_dir:
        jax.profiler.start_trace(wcfg.obs.profile_dir)

    hist = []
    try:
        for e, n in chunk_schedule(n_epochs, chunk):
            done = e + n
            if done <= start:      # chunk fully covered by the checkpoint
                continue
            if e < start:          # checkpoint landed mid-chunk (e.g. a
                e, n = start, done - start  # final-epoch save): run only
            #                          the epochs past it, labels stay global
            # operator spans for `profile_dir` traces; this module may not
            # import obs.trace (lint check 9), so they call jax.profiler
            with jax.profiler.StepTraceAnnotation("sagips.train.chunk",
                                                  step_num=e):
                state, metrics = run(state, data_per_rank, n)
            with jax.profiler.TraceAnnotation("sagips.train.flush"):
                if writer is not None:
                    from ..obs.metrics import chunk_row
                    writer.write_row(chunk_row(done, metrics))
                for j in range(n):
                    ge = e + j
                    if (checkpoint_every and ge % checkpoint_every == 0) \
                            or ge == n_epochs - 1:
                        hist.append(jax.tree.map(
                            lambda x: jnp.asarray(x[j]), metrics))
            if checkpoint_dir and (done == n_epochs or (
                    checkpoint_every and done % checkpoint_every == 0)):
                from ..checkpoint.store import save_checkpoint
                with jax.profiler.TraceAnnotation("sagips.train.checkpoint"):
                    save_checkpoint(checkpoint_dir, done, state,
                                    metadata={"epochs": done,
                                              "problem": wcfg.problem})
    finally:
        if wcfg.obs.profile_dir:
            jax.profiler.stop_trace()
        if writer is not None:
            writer.close()
    history = jax.tree.map(lambda *xs: jnp.stack(xs), *hist) if hist else {}
    return state, history


def train_proc(seed: int, wcfg: WorkflowConfig, n_outer: int, n_inner: int,
               n_epochs: int, data, **kw):
    """The third driver (ISSUE 5): N = n_outer*n_inner REAL worker
    processes on this host, spawned via `jax.distributed.initialize`,
    exchanging gradients through the `repro.runtime` mailbox fabric
    (`ProcComm`) with the unchanged `SyncSchedule` layer on top.

    `seed` replaces `train_vmap`'s key argument (workers rebuild
    `PRNGKey(seed)` so the initial state and per-rank data split are
    BITWISE the vmap driver's).  Keyword args pass through to
    `runtime.launch.run_proc`: `lockstep` (default True — zero-jitter
    lock-step runs reproduce the vmap trajectory bitwise), `jitter` (a
    `runtime.JitterConfig` for reproducible asynchrony; implies
    free-running), `ckpt_every`/`resume` (per-process checkpoints),
    `run_dir`, `use_distributed`, `timeout`.

    Returns (state, history) like `train_vmap`: `state` is the per-rank
    final states stacked back into the `[R, ...]` layout, `history` maps
    metric name -> `[n_epochs, R]` arrays (per-epoch, every epoch —
    including the measured `skew_ema` / `k_eff` under the adaptive
    schedule).  Use `runtime.launch.run_proc` directly when you need the
    raw per-rank summaries (wall times, jitter config, distributed
    status) as well.
    """
    from ..runtime.launch import run_proc
    if kw.get("jitter") is not None and "lockstep" not in kw:
        kw["lockstep"] = False         # jitter only bites when free-running
    out = run_proc(wcfg, n_outer, n_inner, n_epochs, data, seed=seed, **kw)
    return out["state"], out["history"]
