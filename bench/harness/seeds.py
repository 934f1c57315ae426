"""Keys from `--seed`.  A seed may be any whole number up to and past
2**32: its low and high 32-bit words both enter the key."""
from __future__ import annotations

STREAMS = ("data", "run", "traffic")


def root_key(seed: int):
    import jax
    if seed < 0:
        raise ValueError(f"--seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed % 2 ** 32)
    return jax.random.fold_in(key, seed // 2 ** 32)


def cell_keys(seed: int) -> dict:
    """One key per stream: the reference data, the run's state and the
    traffic.  Program and reference both derive their inputs from these."""
    import jax
    return dict(zip(STREAMS, jax.random.split(root_key(seed), len(STREAMS))))
