"""JAX set-up, the device check and the table of peaks.

`setup_jax` runs before anything touches a device: it keeps JAX's
persistent compilation cache in one fixed directory of the checkout and
caches every program, however short its compile, so that a cell's second
run in a checkout compiles nothing.
"""
from __future__ import annotations

import json
from pathlib import Path

from .spec import BENCH, ROOT

# fixed, inside the checkout: the directory is part of the cache's key
CACHE_DIR = ROOT / ".bench_jax_cache"


class DeviceError(RuntimeError):
    """No accelerator, too few chips, or a chip with no row of peaks."""


def setup_jax(cache_dir: Path = CACHE_DIR):
    import jax
    jax.config.update("jax_compilation_cache_dir", str(cache_dir))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # no eviction: a size limit from the environment turns on JAX's LRU
    # bookkeeping, whose writes were seen to fail on a TPU host, leaving
    # every run to compile again; the cell's programs are a few MB
    jax.config.update("jax_compilation_cache_max_size", -1)
    return jax


def check_devices(chips: int, require_tpu: bool = True) -> dict:
    """The device record of the result line; raises `DeviceError` where
    JAX finds no TPU or fewer chips than the cell asks for."""
    import jax
    devs = jax.devices()
    dev = devs[0]
    if require_tpu and dev.platform != "tpu":
        raise DeviceError(f"no TPU: JAX's first device is on "
                          f"{dev.platform!r}; this benchmark measures the "
                          f"chip and never falls back to another platform")
    if len(devs) < chips:
        raise DeviceError(f"the cell asks for {chips} chip(s), JAX finds "
                          f"{len(devs)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": chips}


def load_peaks(path: Path = BENCH / "peaks.json") -> dict:
    return json.loads(Path(path).read_text())["devices"]


def peaks_for(kind: str, table: dict | None = None) -> dict:
    """Peak rates of one chip of `kind`; an unknown kind is an error."""
    table = load_peaks() if table is None else table
    if kind not in table:
        raise DeviceError(f"no peaks for device_kind {kind!r} in "
                          f"bench/peaks.json (known: {sorted(table)})")
    return table[kind]


def memory_peak_bytes(chips: int) -> int:
    """Peak device memory on the fullest of the cell's chips.  On a TPU
    `peak_bytes_in_use` counts buffers only; the executables' temporary
    space is held in `peak_bytes_reserved`, so the larger of the two is
    taken (0 where the runtime reports neither)."""
    import jax
    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peaks.append(max(int(stats.get("peak_bytes_in_use", 0)),
                         int(stats.get("peak_bytes_reserved", 0))))
    return max(peaks) if peaks else 0

