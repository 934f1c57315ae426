"""The comparisons that decide `correct`.

Training (a call of the window's own compiled step is one step):
  loss_gap    the worst relative gap, over every epoch of the first three
              calls and every rank, between the program's discriminator
              and generator losses and the reference's
  grad_gap    after the first call: the worst leaf's gap between the
              norms of Adam's first moment (the gradient as the optimizer
              holds it; after one epoch it is 0.1 x the gradient) in the
              program and in the reference
  update_gap  after the third call: the worst leaf's gap between the
              norms of the weights' change since the start
A leaf's gap is |program norm - reference norm| over the larger of the
reference's norm of that leaf and of the median leaf.  Leaves whose
reference first moment is under a thousandth of the median leaf's are
left out of `update_gap` (rounding alone moves them under Adam).

Solve, over a sample of the requests answered in the window:
  params_gap  the largest |program - reference| of any parameter estimate
  score_gap   the largest relative gap of the kept candidates' mean score
"""
from __future__ import annotations

import numpy as np

NOUGHT = 1e-3


def leaf_norms(tree) -> dict:
    import jax
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(p): float(np.linalg.norm(
        np.asarray(x, np.float64).ravel())) for p, x in flat}


def worst_leaf_gap(prog: dict, ref: dict, keep=None) -> float:
    if set(prog) != set(ref):
        raise ValueError(f"leaves differ: {sorted(set(prog) ^ set(ref))}")
    med = float(np.median(list(ref.values())))
    keys = [k for k in ref if keep is None or k in keep]
    return max(abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
               for k in keys)


def change_norms(start, end) -> dict:
    import jax
    return leaf_norms(jax.tree.map(
        lambda a, b: np.asarray(b, np.float64) - np.asarray(a, np.float64),
        start, end))


def train_numbers(prog: dict, ref: dict) -> dict:
    """`prog` and `ref` each hold: losses {d_loss, g_loss} [epochs, R],
    mu_first {gen, disc}, start {gen, disc}, end {gen, disc}."""
    gaps = []
    for k in ("d_loss", "g_loss"):
        p = np.asarray(prog["losses"][k], np.float64)
        r = np.asarray(ref["losses"][k], np.float64)
        if p.shape != r.shape:
            raise ValueError(f"{k}: program {p.shape}, reference {r.shape}")
        gaps.append(np.max(np.abs(p - r) / np.maximum(np.abs(r), 1e-30)))
    loss_gap = float(max(gaps))
    if not np.isfinite(loss_gap):
        loss_gap = float("inf")
    ref_mu = leaf_norms(ref["mu_first"])
    med = float(np.median(list(ref_mu.values())))
    keep = {k for k, v in ref_mu.items() if v >= NOUGHT * med}
    return {
        "loss_gap": loss_gap,
        "grad_gap": worst_leaf_gap(leaf_norms(prog["mu_first"]), ref_mu),
        "update_gap": worst_leaf_gap(
            change_norms(prog["start"], prog["end"]),
            change_norms(ref["start"], ref["end"]), keep),
    }


def solve_numbers(prog_answers: list, ref_answers: list) -> dict:
    pg, sg = 0.0, 0.0
    for p, r in zip(prog_answers, ref_answers):
        pg = max(pg, float(np.max(np.abs(np.asarray(p["params"], np.float64)
                                         - np.asarray(r["params"])))))
        rs = float(r["score"])
        sg = max(sg, abs(float(p["score"]) - rs) / max(abs(rs), 1e-30))
    return {"params_gap": pg, "score_gap": sg}


def judge(numbers: dict, limits: dict) -> tuple:
    """(correct, {name: {value, limit}}).  A number that is not finite,
    or that has no limit, fails."""
    checks, ok = {}, True
    for name, value in numbers.items():
        lim = limits.get(name, {}).get("limit")
        checks[name] = {"value": value, "limit": lim}
        if lim is None or not np.isfinite(value) or value > lim:
            ok = False
    return ok, checks
