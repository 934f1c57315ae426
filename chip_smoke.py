"""Smoke run of the SAGIPS main path on a TPU.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # the cross-chip ring on a 2x2 mesh

One chip runs four phases at the paper's Tab. III widths
(`configs.sagips_gan.PAPER`: 1024 parameter samples x 100 events per
rank, proxy1d), with weights and data made from fixed seeds:

  sampler      the Pallas inverse-CDF kernel for R=8 ranks, forward and
               VJP, against the `kernels/ref.py` oracle, compiled (not
               interpreted)
  trainer      `workflow.train_vmap`, R = 2 outer x 4 inner ranks, once
               with each sampler implementation
  chip vs CPU  one epoch at R=2 on the chip and on the host's CPU device
               from copies of one state, at highest matmul precision
  serving      the trained stack saved as a checkpoint, served by
               `SolveService` across the bucket ladder

`--four-chips` runs only the shard path (`make_epoch_fn_shard`, one rank
per chip on a (pod=2, data=2) mesh) against the one-chip vmap path.

Everything runs in this one process: a chip belongs to one process at a
time.  Earlier lines give each phase's compile and wall seconds (smoke
timings, not benchmark metrics).  The last line is one JSON object
`{"ok": ..., "device": {"platform", "kind", "count"}}`; the exit code is
0 only when every check passed on a TPU.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import tempfile
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent

# Tab. III on one chip: R = 2 outer x 4 inner simulated ranks
N_OUTER, N_INNER = 2, 4
# outer-ring period, lowered from PAPER's h=1000 so that the pod-boundary
# ring fires inside these short runs (epochs 0, 5, 10, 15)
TRAIN_H = 5
TRAIN_EPOCHS, TRAIN_CHUNK = 20, 10
FOUR_CHIP_H, FOUR_CHIP_EPOCHS = 2, 4
REF_EVENTS = 102_400            # reference set; each rank keeps half (§VI-C2)
SERVE_SIZES = (40, 64, 100, 256, 17, 700, 1024, 500)   # events per request

# stated tolerances
SAMPLER_RTOL = SAMPLER_ATOL = 1e-5     # kernel forward vs jnp oracle
SAMPLER_GRAD_TOL = 1e-4                # VJP: max |error| / max |oracle|
LOSS_RTOL = 1e-4                       # epoch losses, one path vs the other
# Adam's first steps move each weight by ~lr * sign(grad), so a gradient
# element within rounding of zero may step the other way on the other
# path (|diff| up to 2 * lr per epoch).  All other weights agree to
# WEIGHT_ATOL, and at most FLIP_SHARE of the weights may be such flips.
WEIGHT_ATOL = 1e-6
FLIP_SHARE = 1e-3


class SmokeFailure(RuntimeError):
    """A check of the smoke run failed."""


def check(ok: bool, msg: str):
    if not ok:
        raise SmokeFailure(msg)


# ----------------------------------------------------------------------------
# compile accounting


class CompileWatch:
    """Counts the executables XLA builds and the seconds it spends on
    them, from `jax.monitoring` events."""

    def __init__(self):
        import jax
        self.executables = 0      # built by XLA or read from the disk cache
        self.disk_hits = 0        # read from the persistent cache
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.executables += 1
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.disk_hits += 1

    def counts(self):
        return self.executables, self.disk_hits, self.seconds


@contextlib.contextmanager
def phase(name: str, watch: CompileWatch):
    import jax
    n0, h0, s0 = watch.counts()
    t0 = time.perf_counter()
    yield
    wall = time.perf_counter() - t0
    n1, h1, s1 = watch.counts()
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    print(f"[{name}] smoke timing: wall {wall:.2f} s, XLA compile "
          f"{s1 - s0:.2f} s "
          f"for {n1 - n0} executables ({h1 - h0} read from the persistent "
          f"cache); peak_bytes_in_use "
          f"{'not reported' if peak is None else peak}", flush=True)


# ----------------------------------------------------------------------------
# comparisons


def max_rel_error(a, b) -> float:
    import numpy as np
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


def compare_losses(m_a, m_b, what: str):
    import numpy as np
    for k in ("d_loss", "g_loss"):
        a, b = np.asarray(m_a[k]), np.asarray(m_b[k])
        check(np.all(np.isfinite(a)) and np.all(np.isfinite(b)),
              f"{what}: non-finite {k}")
        err = float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-30)))
        print(f"[{what}] {k}: max relative difference {err:.3e} "
              f"(tolerance {LOSS_RTOL:g})", flush=True)
        check(err <= LOSS_RTOL, f"{what}: {k} differs by {err:.3e}")


def compare_weights(gen_a, gen_b, lr: float, n_epochs: int, what: str):
    import jax
    import numpy as np
    d = np.concatenate([
        np.abs(np.asarray(a, np.float64) - np.asarray(b, np.float64)).ravel()
        for a, b in zip(jax.tree.leaves(gen_a), jax.tree.leaves(gen_b))])
    off = int(np.sum(d > WEIGHT_ATOL))
    bound = 2 * lr * n_epochs + WEIGHT_ATOL
    print(f"[{what}] generator weights: max |diff| {d.max():.3e}; "
          f"{off} of {d.size} beyond {WEIGHT_ATOL:g} (allowed: share "
          f"{FLIP_SHARE:g}, each <= {bound:.2e})", flush=True)
    check(np.all(np.isfinite(d)), f"{what}: non-finite generator weights")
    check(off <= FLIP_SHARE * d.size and d.max() <= bound,
          f"{what}: generator weights disagree (max {d.max():.3e}, "
          f"{off} elements beyond {WEIGHT_ATOL:g})")


# ----------------------------------------------------------------------------
# phases


def phase_sampler(n_ranks: int, n_samples: int, n_events: int):
    """Pallas inverse CDF through `kernels.ops`, vmapped over ranks as the
    trainer runs it, forward and VJP against the oracle."""
    import jax
    import numpy as np
    from repro.kernels import ops, ref
    from repro.kernels.inverse_cdf import interpret_default

    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    shape = (n_ranks, n_samples, n_events)
    # inside the clip band [1e-6, 1 - 1e-6]: there the closed-form VJP and
    # the oracle's autodiff are the same function
    u = jax.random.uniform(ks[0], shape, minval=1e-3, maxval=1 - 1e-3)
    mu = jax.random.normal(ks[1], shape[:2])
    s = jax.nn.softplus(jax.random.normal(ks[2], shape[:2]))
    k = jax.random.normal(ks[3], shape[:2])
    g = jax.random.normal(ks[4], shape)

    def kernel_vjp(u, mu, s, k, g):
        y, vjp = jax.vjp(jax.vmap(ops.inverse_cdf), u, mu, s, k)
        return y, vjp(g)

    def oracle_vjp(u, mu, s, k, g):
        y, vjp = jax.vjp(jax.vmap(ref.inverse_cdf_ref), u, mu, s, k)
        return y, vjp(g)

    fn = jax.jit(kernel_vjp)
    text = fn.lower(u, mu, s, k, g).as_text()
    compiled = "tpu_custom_call" in text
    print(f"[sampler] inverse_cdf {list(shape)}: compiled Mosaic kernel "
          f"in the program: {compiled}", flush=True)
    check(compiled == (not interpret_default()),
          "the sampler program does not hold the kernel its backend needs "
          f"(tpu_custom_call present: {compiled}, backend "
          f"{jax.default_backend()})")
    y, grads = jax.block_until_ready(fn(u, mu, s, k, g))
    y_ref, grads_ref = jax.jit(oracle_vjp)(u, mu, s, k, g)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=SAMPLER_RTOL, atol=SAMPLER_ATOL)
    for name, a, b in zip(("u", "mu", "s", "k"), grads, grads_ref):
        err = max_rel_error(a, b)
        print(f"[sampler] d{name}: max |error| / max |oracle| {err:.3e} "
              f"(tolerance {SAMPLER_GRAD_TOL:g})", flush=True)
        check(err <= SAMPLER_GRAD_TOL, f"sampler VJP d{name} off by {err:.3e}")


def phase_trainer(wcfg, n_outer: int, n_inner: int, n_epochs: int,
                  chunk: int, n_events: int, impl: str):
    """`workflow.train_vmap` with the given sampler; returns the state."""
    import jax
    import numpy as np
    from repro.core import workflow

    cfg = dataclasses.replace(wcfg, sampler_impl=impl)
    data = cfg.problem_obj.make_reference_data(jax.random.PRNGKey(99),
                                               n_events)
    state, hist = workflow.train_vmap(
        jax.random.PRNGKey(0), cfg, n_outer, n_inner, n_epochs, data,
        checkpoint_every=chunk, chunk=chunk)
    jax.block_until_ready(state)
    for k in ("d_loss", "g_loss", "residuals"):
        check(np.all(np.isfinite(np.asarray(hist[k]))),
              f"trainer ({impl} sampler): non-finite {k}")
    check(int(np.asarray(state["epoch"])[0]) == n_epochs,
          f"trainer ({impl} sampler): ran {state['epoch']} epochs")
    print(f"[trainer/{impl}] {n_epochs} epochs, R={n_outer}x{n_inner}: "
          f"d_loss {float(np.mean(hist['d_loss'][-1])):.4f}, g_loss "
          f"{float(np.mean(hist['g_loss'][-1])):.4f}, mean |residual| "
          f"{float(np.mean(np.abs(hist['residuals'][-1]))):.4f}",
          flush=True)
    return state


def phase_chip_vs_cpu(wcfg, n_events: int):
    """One epoch at R=2 on the default device and on the host's CPU device
    from copies of one state; losses and generator weights must agree."""
    import jax
    import jax.numpy as jnp
    from repro.core import workflow

    data = wcfg.problem_obj.make_reference_data(jax.random.PRNGKey(98),
                                                n_events)
    state, dpr = workflow.init_run(jax.random.PRNGKey(1), 2, wcfg, data)
    out = {}
    with jax.default_matmul_precision("highest"):
        for label, dev in (("chip", jax.devices()[0]),
                           ("cpu", jax.devices("cpu")[0])):
            # the epoch factory donates its state: give each side a copy
            s = jax.device_put(jax.tree.map(jnp.copy, state), dev)
            x = jax.device_put(dpr, dev)
            s, metrics = workflow.make_epoch_fn_vmap(2, 1, wcfg)(s, x)
            out[label] = jax.device_get((s["gen"], metrics))
    compare_losses(out["chip"][1], out["cpu"][1], "chip vs CPU")
    compare_weights(out["chip"][0], out["cpu"][0], wcfg.gen_lr, 1,
                    "chip vs CPU")


def phase_serving(state, problem: str, serving_cfg, sizes,
                  watch: CompileWatch):
    """Save the trained stack, serve it, and check the warm pool answers
    every request without a new compile."""
    import jax
    import numpy as np
    from repro.checkpoint import save_checkpoint
    from repro.problems import get_problem
    from repro.serving import SolveService

    prob = get_problem(problem)
    svc = SolveService(serving_cfg)
    with tempfile.TemporaryDirectory() as ckpt:
        step = int(np.asarray(state["epoch"])[0])
        save_checkpoint(ckpt, step, state)
        got = svc.register_problem(problem, checkpoint_dir=ckpt)
    check(got == step, f"restored step {got}, saved {step}")
    svc.warm(problem)
    requests = [np.asarray(prob.make_reference_data(
        jax.random.PRNGKey(500 + i), n)) for i, n in enumerate(sizes)]

    before = dict(svc.cache.stats)
    n0 = watch.executables
    t0 = time.perf_counter()
    tickets = [svc.submit(problem, y) for y in requests]
    svc.run_until_empty()
    outs = [t.result(timeout=60.0) for t in tickets]
    wall = time.perf_counter() - t0
    after = dict(svc.cache.stats)
    n_built = watch.executables - n0

    buckets = sorted({t.bucket for t in tickets})
    print(f"[serving] {len(outs)} requests over buckets {buckets} in "
          f"{wall:.3f} s (smoke timing); compile cache hits "
          f"{after['hits'] - before['hits']}, new compiles "
          f"{after['compiles'] - before['compiles']}, XLA executables built "
          f"{n_built}", flush=True)
    check(after["hits"] > before["hits"], "warm pool was never hit")
    check(after["compiles"] == before["compiles"] and n_built == 0,
          "a request compiled after the warm-up")
    check(buckets == sorted(serving_cfg.buckets),
          f"requests reached buckets {buckets}, not the whole ladder")
    res = [float(prob.mean_abs_residual(o["params"])) for o in outs]
    check(all(np.isfinite(r) for r in res), f"non-finite residuals {res}")
    print(f"[serving] mean |residual| per request: "
          f"{', '.join(f'{r:.4f}' for r in res)}", flush=True)


def phase_four_chips(wcfg, n_events: int, n_epochs: int):
    """One rank per chip on a (pod=2, data=2) mesh against the one-chip
    vmap path; returns the ids of the devices that held the state."""
    import jax
    import jax.numpy as jnp
    from repro.core import workflow
    from repro.launch.mesh import make_mesh

    devices = jax.devices()
    check(len(devices) == 4, f"need 4 devices, found {len(devices)}")
    mesh = make_mesh((2, 2), ("pod", "data"))
    data = wcfg.problem_obj.make_reference_data(jax.random.PRNGKey(97),
                                                n_events)
    state, dpr = workflow.init_run(jax.random.PRNGKey(2), 4, wcfg, data)
    with jax.default_matmul_precision("highest"):
        fn_s, shardings = workflow.make_epoch_fn_shard(mesh, wcfg)
        ss = jax.device_put(jax.tree.map(jnp.copy, state), shardings)
        ds = jax.device_put(dpr, shardings)
        fn_v = workflow.make_epoch_fn_vmap(2, 2, wcfg)
        sv = jax.device_put(jax.tree.map(jnp.copy, state), devices[0])
        dv = jax.device_put(dpr, devices[0])
        for _ in range(n_epochs):
            ss, ms = fn_s(ss, ds)
            sv, mv = fn_v(sv, dv)
        jax.block_until_ready((ss, sv))
    used = set()
    for leaf in jax.tree.leaves(ss):
        held = {sh.device for sh in leaf.addressable_shards}
        check(len(held) == 4, f"a state leaf {leaf.shape} sits on "
              f"{len(held)} device(s), not 4")
        used |= held
    ids = sorted(d.id for d in used)
    print(f"[four chips] shard state on devices {ids}", flush=True)
    ss, ms, sv, mv = jax.device_get((ss, ms, sv, mv))
    compare_losses(ms, mv, "shard vs vmap")
    compare_weights(ss["gen"], sv["gen"], wcfg.gen_lr, n_epochs,
                    "shard vs vmap")
    return ids


# ----------------------------------------------------------------------------
# driver


def run(four_chips: bool) -> dict:
    """All phases; raises on the first failed check.  Returns the device
    record of the last line."""
    # the chip-vs-CPU phase needs the host's CPU backend next to the chip
    platforms = os.environ.get("JAX_PLATFORMS")
    if platforms and "cpu" not in platforms.split(","):
        os.environ["JAX_PLATFORMS"] = platforms + ",cpu"
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()

    import jax
    dev = jax.devices()[0]
    record = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"device_kind {dev.device_kind!r}, platform {dev.platform}, "
          f"{record['count']} device(s); jax {jax.__version__}; "
          f"compile cache {cache_dir}", flush=True)
    check(dev.platform == "tpu",
          f"no TPU: JAX's first device is on {dev.platform!r}")

    from repro.configs import sagips_gan
    from repro.configs import serving as serving_cfg
    watch = CompileWatch()
    paper = sagips_gan.PAPER

    if four_chips:
        wcfg = dataclasses.replace(
            paper, sync=dataclasses.replace(paper.sync, h=FOUR_CHIP_H))
        print(f"[four chips] override: sync.h {paper.sync.h} -> "
              f"{FOUR_CHIP_H}, {FOUR_CHIP_EPOCHS} epochs", flush=True)
        with phase("four chips", watch):
            record["devices"] = phase_four_chips(wcfg, REF_EVENTS,
                                                 FOUR_CHIP_EPOCHS)
        return record

    with phase("sampler", watch):
        phase_sampler(N_OUTER * N_INNER, paper.n_param_samples,
                      paper.events_per_sample)

    wcfg = dataclasses.replace(
        paper, sync=dataclasses.replace(paper.sync, h=TRAIN_H))
    print(f"[trainer] override: sync.h {paper.sync.h} -> {TRAIN_H} so the "
          f"outer ring fires in {TRAIN_EPOCHS} epochs", flush=True)
    state = None
    for impl in ("jnp", "pallas"):
        with phase(f"trainer/{impl}", watch):
            state = phase_trainer(wcfg, N_OUTER, N_INNER, TRAIN_EPOCHS,
                                  TRAIN_CHUNK, REF_EVENTS, impl)

    with phase("chip vs CPU", watch):
        phase_chip_vs_cpu(wcfg, REF_EVENTS)

    with phase("serving", watch):
        phase_serving(state, wcfg.problem, serving_cfg.DEFAULT,
                      SERVE_SIZES, watch)
    return record


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__.splitlines()[0],
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the shard path on a 2x2 mesh of four "
                         "chips against the one-chip vmap path")
    args = ap.parse_args(argv)
    result = {"ok": False}
    try:
        result["device"] = run(args.four_chips)
        result["ok"] = True
    except Exception as e:     # report any failure on the last line
        traceback.print_exc()
        result["error"] = f"{type(e).__name__}: {e}"
    print(json.dumps(result), flush=True)
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
