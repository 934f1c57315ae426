"""Everything the harness finds by name.

A cell `<config>.<traffic>` of `BENCHMARK.json` names:

    configs/<config>.json      the configuration as it is run
    traffic/<traffic>.json     the traffic mix: its parameters, the
                               `kind` of runner that reads them and,
                               where it sends requests, its `arrivals`
    kinds/<kind>.py            one runner per kind: `run(ctx)` drives one
                               entry of the program through the window
    arrivals/<process>.py      one arrival process per file:
                               `offsets(traffic, seconds, rng)`
    limits/<cell>.json         the limit of each number that decides
                               `correct`, with the readings it was set from
    counts/<config>.py         the work the configuration requires
    metrics/<metric>.py        one reader per per-layer metric
    reference/<name>.py        the plain references (named by the config)

Nothing here knows a cell, a configuration or a metric by name: a later
one is a new file and a new entry in `BENCHMARK.json`.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType

BENCH = Path(__file__).resolve().parents[1]          # <checkout>/bench
ROOT = BENCH.parent                                  # <checkout>


class SpecError(RuntimeError):
    """A name in BENCHMARK.json has no file, or a file is malformed."""


def load_json(path: Path) -> dict:
    try:
        return json.loads(Path(path).read_text())
    except FileNotFoundError:
        raise SpecError(f"missing file {path}") from None


def load_module(path: Path, name: str) -> ModuleType:
    """Import a Python file by path (file names may hold dots)."""
    if not Path(path).is_file():
        raise SpecError(f"missing file {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Bench:
    """`BENCHMARK.json` and the files it names, rooted at `bench_dir`."""

    def __init__(self, root: Path = ROOT, bench_dir: Path = BENCH):
        self.root = Path(root)
        self.dir = Path(bench_dir)
        self.spec = load_json(self.root / "BENCHMARK.json")

    def workload(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise SpecError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        for c in self.spec["configs"]:
            if c["name"] == name:
                return load_json(self.root / c["file"])
        raise SpecError(f"no config {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        return load_json(self.dir / "traffic" / f"{name}.json")

    def kind(self, name: str) -> ModuleType:
        return load_module(self.dir / "kinds" / f"{name}.py", f"kind_{name}")

    def arrivals(self, name: str) -> ModuleType:
        return load_module(self.dir / "arrivals" / f"{name}.py",
                           f"arrivals_{name}")

    def limits(self, workload: str) -> dict:
        return load_json(self.dir / "limits" / f"{workload}.json")

    def counts(self, config: str) -> ModuleType:
        return load_module(self.dir / "counts" / f"{config}.py",
                           f"counts_{config}")

    def reference(self, name: str) -> ModuleType:
        return load_module(self.dir / "reference" / f"{name}.py",
                           f"reference_{name}")

    def end_to_end(self, workload: str) -> list:
        return [m for m in self.spec["end_to_end"]
                if workload in m.get("workloads", [workload])]

    def per_layer(self, workload: str) -> list:
        """Per-layer metrics that this cell reports: those listing it, and
        those without a `workloads` key whose end-to-end metric it has."""
        e2e = {m["name"] for m in self.end_to_end(workload)}
        out = []
        for m in self.spec["per_layer"]:
            cells = m.get("workloads")
            if (workload in cells) if cells is not None else (m["moves"] in e2e):
                out.append(m)
        return out

    def metric_reader(self, name: str):
        return load_module(self.dir / "metrics" / f"{name}.py",
                           f"metric_{name}").read
