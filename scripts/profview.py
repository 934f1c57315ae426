#!/usr/bin/env python
"""Where a profiled run's device time and solve steps go, by layer.

    python scripts/profview.py PROFILE_DIR [--window NAME] [--chips N]

PROFILE_DIR is a `jax.profiler` trace directory: what
`ObsConfig(profile_dir=...)` / `--profile-dir` writes around the
`train_vmap` epoch loop, or any `jax.profiler.start_trace` target around
`SolveService` calls.  The newest `*.xplane.pb` under it is reduced by
`repro.obs.xplane` and printed as one JSON object: device seconds per
layer scope (`sagips_sample`, `sagips_gen`, `sagips_disc`,
`sagips_exchange`, `sagips_apply`) averaged over chips, the busy time no
scope covers and its largest ops, and for the solve service each
`sagips.solve.step` child's mean per batch and the mean queue wait.
`--window` names a host span whose extent bounds the reading (default:
the extent of the device's operations).

See docs/observability.md for the spans and scopes.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src"))

from repro.obs.xplane import find_trace, reduce_file, summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("profile_dir")
    ap.add_argument("--window", default=None,
                    help="host span bounding the reading")
    ap.add_argument("--chips", type=int, default=None,
                    help="read TPU chips 0..N-1 only")
    args = ap.parse_args(argv)
    try:
        path = find_trace(args.profile_dir)
    except FileNotFoundError as e:
        print(f"profview: {e}", file=sys.stderr)
        return 1
    data = reduce_file(path, window=args.window, chips=args.chips)
    print(json.dumps(dict(summary(data), trace=path),
                     indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
