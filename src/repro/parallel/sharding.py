"""Logical-axis sharding: model code annotates tensors with *logical* axis
names; a rule table maps them to physical mesh axes.  Outside a mesh context
all annotations are no-ops, so the same model runs on 1 CPU device (smoke
tests) and on the 512-chip production mesh (dry-run) unchanged.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# logical axis -> physical mesh axes (tuple => sharded over multiple axes)
DEFAULT_RULES = {
    "batch": ("pod", "data"),      # data parallel over pods x data
    "fsdp": ("pod", "data"),       # fully-sharded param dim
    "model": ("model",),           # tensor / expert / head parallel
    "seq": None,                   # unsharded by default (see §Perf)
    "seq_shard": ("model",),       # sequence parallelism (context parallel)
    "vocab": ("model",),
    "expert": ("model",),
    "heads": ("model",),
    "ff": ("model",),
    "kv_heads": ("model",),
    "ssm_heads": ("model",),
}


class _State(threading.local):
    def __init__(self):
        self.mesh: Optional[Mesh] = None
        self.rules: dict = dict(DEFAULT_RULES)
        self.flags: dict = {}


_STATE = _State()


@contextlib.contextmanager
def axis_rules(mesh: Optional[Mesh], rules: Optional[dict] = None,
               flags: Optional[dict] = None):
    prev = (_STATE.mesh, _STATE.rules, _STATE.flags)
    _STATE.mesh = mesh
    merged = dict(DEFAULT_RULES)
    if rules:
        merged.update(rules)
    _STATE.rules = merged
    _STATE.flags = dict(flags or {})
    try:
        yield
    finally:
        _STATE.mesh, _STATE.rules, _STATE.flags = prev


def flag(name: str) -> bool:
    return bool(_STATE.flags.get(name, False))


def current_mesh() -> Optional[Mesh]:
    return _STATE.mesh


def logical_to_spec(logical: Sequence[Optional[str]]) -> P:
    """Map logical axis names to a PartitionSpec under the active rules."""
    mesh_axes = set(_STATE.mesh.axis_names) if _STATE.mesh is not None else set()
    parts = []
    for name in logical:
        if name is None:
            parts.append(None)
            continue
        rule = _STATE.rules.get(name)
        if rule is None:
            parts.append(None)
            continue
        axes = tuple(a for a in (rule if isinstance(rule, tuple) else (rule,))
                     if a in mesh_axes)
        parts.append(axes if len(axes) > 1 else (axes[0] if axes else None))
    return P(*parts)


def shard(x, *logical: Optional[str]):
    """Annotate activation x with logical axes (no-op without a mesh)."""
    if _STATE.mesh is None:
        return x
    spec = logical_to_spec(logical)
    return jax.lax.with_sharding_constraint(x, NamedSharding(_STATE.mesh, spec))


def named_sharding(*logical: Optional[str]) -> Optional[NamedSharding]:
    if _STATE.mesh is None:
        return None
    return NamedSharding(_STATE.mesh, logical_to_spec(logical))


def resolve_sharding(shape, logical) -> Optional[NamedSharding]:
    """Logical axes -> NamedSharding with dedupe + divisibility in one pass.

    A mesh axis is used by the leftmost dim whose size it divides; later
    dims fall back to their remaining candidates (e.g. MoE expert weights
    [E, D, F] with axes (expert, fsdp, model): when E doesn't divide the
    `model` axis, F picks it up instead).
    """
    mesh = _STATE.mesh
    if mesh is None:
        return None
    rules = _STATE.rules
    mesh_axes = set(mesh.axis_names)
    parts = []
    used = set()
    logical = tuple(logical) + (None,) * (len(shape) - len(logical))
    for size, name in zip(shape, logical):
        if name is None or rules.get(name) is None:
            parts.append(None)
            continue
        rule = rules[name]
        candidates = rule if isinstance(rule, tuple) else (rule,)
        kept, factor = [], 1
        for a in candidates:
            if a in mesh_axes and a not in used and \
                    size % (factor * mesh.shape[a]) == 0:
                kept.append(a)
                used.add(a)
                factor *= mesh.shape[a]
        parts.append(tuple(kept) if len(kept) > 1
                     else (kept[0] if kept else None))
    return NamedSharding(mesh, P(*parts))


def tree_shardings(tree, axes_tree):
    """Map a pytree of logical-axes tuples + a matching value tree to
    NamedShardings (None without an active mesh)."""
    if _STATE.mesh is None:
        return jax.tree.map(lambda a: None, axes_tree,
                            is_leaf=lambda v: isinstance(v, tuple))
    return jax.tree.map(lambda a, x: resolve_sharding(x.shape, a),
                        axes_tree, tree,
                        is_leaf=lambda v: isinstance(v, tuple))


def divisible_sharding(shape, sharding: NamedSharding) -> NamedSharding:
    """Drop mesh axes that do not evenly divide their dim.

    Explicit input shardings (unlike with_sharding_constraint) require exact
    divisibility; assigned configs have vocab/expert/head counts that don't
    divide the 16-way axes (e.g. 60 experts, vocab 50280).  Axes are dropped
    right-to-left from each dim's tuple until the cumulative factor divides.
    """
    mesh, spec = sharding.mesh, sharding.spec
    parts = list(spec) + [None] * (len(shape) - len(spec))
    new = []
    used = set()          # a mesh axis may appear at most once per spec
    for size, part in zip(shape, parts):
        if part is None:
            new.append(None)
            continue
        axes = part if isinstance(part, tuple) else (part,)
        kept, factor = [], 1
        for a in axes:
            n = mesh.shape[a]
            if a not in used and size % (factor * n) == 0:
                kept.append(a)
                used.add(a)
                factor *= n
        new.append(tuple(kept) if len(kept) > 1 else (kept[0] if kept else None))
    return NamedSharding(mesh, P(*new))


def fix_shardings(tree, shardings):
    """Apply divisible_sharding leafwise over (arrays/SDS, NamedShardings)."""
    return jax.tree.map(
        lambda x, sh: divisible_sharding(x.shape, sh) if sh is not None else None,
        tree, shardings)


def spec_tree_for_params(param_logical):
    """Map a pytree of logical-axes tuples to NamedShardings (or None)."""
    if _STATE.mesh is None:
        return None
    return jax.tree.map(
        lambda ax: NamedSharding(_STATE.mesh, logical_to_spec(ax)),
        param_logical, is_leaf=lambda v: isinstance(v, tuple))

