"""Run one cell of the benchmark once.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell `<config>.<traffic>` of BENCHMARK.json names its configuration
(bench/configs), its traffic mix (bench/traffic), the limits of its check
(bench/limits) and the chips it needs.  The run sets up from the seed,
measures for `--seconds` (with `--trace 1`, a traced window of at most
the mix's `trace_seconds`), checks what the measured path produced
against the plain reference, and prints one JSON object as the last line
of standard output: the end-to-end metrics (`--trace 0`) or the per-layer
metrics (`--trace 1`), with `correct`, `attempted`, `failed`, `device`
and, last, `checks` (each compared number beside its limit).

It refuses to run, and prints no result, where JAX finds no TPU or fewer
chips than the cell asks for.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from harness.device import DeviceError, setup_jax
    from harness.spec import SpecError
    setup_jax()
    from harness.cell import log, run_cell
    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace), T_START)
    except (DeviceError, SpecError) as e:
        log(f"refused: {e}")
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
