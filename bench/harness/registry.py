"""Everything the harness runs is found by name from BENCHMARK.json:

  configuration  the `file` its entry names (bench/configs/<name>.json)
  topology       the configuration's `deployment.topology`: an edge list
                 bench/topologies/<name>.json, or a generator
                 bench/topologies/<name>.py whose `edges(deployment)`
                 returns one
  traffic mix    bench/traffic/<traffic>.json
  loop           the mix's `loop`: bench/loops/<loop>.py, whose `Loop`
                 sets up, warms up, drives the window and checks it
  metric         bench/metrics/<metric name>.py, whose `read(run)`
                 returns the value or None when it finds nothing to read
  limits         bench/limits/<workload>.json, the comparison's limits

A later change adds a cell, a topology, a mix, a loop or a metric by
adding files and entries; no file of the harness names one.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def workload(spec: dict, name: str) -> dict:
    for w in spec["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(spec: dict, name: str, root: Path = ROOT) -> dict:
    for c in spec["configs"]:
        if c["name"] == name:
            return json.loads((root / c["file"]).read_text())
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def traffic(name: str, bench: Path = BENCH) -> dict:
    return json.loads((bench / "traffic" / f"{name}.json").read_text())


def _module(kind: str, name: str, bench: Path):
    """bench/<kind>/<name>.py, imported from its path."""
    path = bench / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def topology(dep: dict, bench: Path = BENCH) -> dict:
    """{"V": nodes, "edges": undirected [u, v] pairs} of a configuration's
    `deployment`."""
    name = dep["topology"]
    path = bench / "topologies" / f"{name}.json"
    if path.exists():
        return json.loads(path.read_text())
    V, edges = _module("topologies", name, bench).edges(dep)
    return {"V": int(V), "edges": [list(e) for e in edges]}


def loop(name: str, bench: Path = BENCH):
    """The `Loop` class of bench/loops/<name>.py."""
    return _module("loops", name, bench).Loop


def reader(name: str, bench: Path = BENCH):
    """The `read` function of bench/metrics/<name>.py."""
    return _module("metrics", name, bench).read


def metrics_for(spec: dict, cell: str, kind: str) -> list:
    """The cell's `end_to_end` or `per_layer` metric entries.  A metric
    with a `workloads` list applies to those cells; a per-layer metric
    without one applies wherever its `moves` metric is reported."""
    e2e = [m for m in spec["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if kind == "end_to_end":
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if cell in m.get("workloads", [cell] if m["moves"] in names
                             else [])]
