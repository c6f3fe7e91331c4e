"""A cell is defined by files and entries alone: a toy configuration,
topology, traffic mix, loop, metric and limits, added only as new files
beside a copy of BENCHMARK.json, are found by name and run."""
import json
import shutil
import time
from pathlib import Path

from harness import registry
from harness.cell import run_cell

BENCH = Path(__file__).resolve().parents[1]

TOY_TOPOLOGY = '''
def edges(dep):
    V = int(dep["V"])
    ring = [(i, (i + 1) % V) for i in range(V)]
    return V, ring + [(i, (i + 3) % V) for i in range(0, V, 2)]
'''

TOY_LOOP = '''
import time

from repro import core

from harness import check
from harness.drive import Window, host_phi, program_net


class Loop:
    def __init__(self, cfg, mix, dep, seed, spans):
        self.net, self.K = program_net(dep), int(cfg["entry"]["K"])

    def solve(self):
        net = self.net
        phi0 = core.spt_phi_sparse(net, core.build_neighbors(net.adj))
        phi, hist = core.run(net, phi0, n_iters=self.K, method="sparse")
        return host_phi(phi), float(hist["final_cost"])

    def warm_up(self):
        self.solve()

    def window(self, seconds, log):
        start, lat, answers = time.perf_counter(), [], []
        while not lat or sum(lat) < seconds:
            t0 = time.perf_counter()
            host, cost = self.solve()
            lat.append(time.perf_counter() - t0)
            answers.append((len(lat) - 1, host, cost))
        return Window(time.perf_counter() - start, lat, self.K * len(lat),
                      answers, 0)

    @staticmethod
    def check(dep, answers, control=False):
        return check.check_solves(dep, answers, control=control)
'''


def test_toy_cell_from_new_files_only(tmp_path):
    root, bench = tmp_path, tmp_path / "bench"
    for d in ("configs", "topologies", "traffic", "loops", "metrics",
              "limits"):
        (bench / d).mkdir(parents=True)
    spec = registry.load_benchmark()
    for m in spec["end_to_end"] + spec["per_layer"]:
        shutil.copy(BENCH / "metrics" / f"{m['name']}.py", bench / "metrics")
    cfg = json.loads((BENCH / "configs" / "geant.json").read_text())
    cfg["deployment"].update(topology="toy_ring", V=8, S=4, R=2)
    cfg["entry"]["K"] = 10
    (bench / "configs" / "toy.json").write_text(json.dumps(cfg))
    (bench / "topologies" / "toy_ring.py").write_text(TOY_TOPOLOGY)
    (bench / "loops" / "toy_loop.py").write_text(TOY_LOOP)
    (bench / "traffic" / "toy_mix.json").write_text(
        json.dumps({"loop": "toy_loop"}))
    (bench / "metrics" / "toy_solves.py").write_text(
        "def read(run):\n    return float(len(run.window.latencies))\n")
    (bench / "limits" / "toy.toy_mix.json").write_text(json.dumps(
        {"limits": {"layout": 0, "loops": 0, "simplex_err": 1e-4,
                    "delivery_loss": 1e-4, "cost_gap": 1e-4,
                    "not_descended": 0}}))
    spec["configs"].append({"name": "toy", "source": "test",
                            "file": "bench/configs/toy.json",
                            "reduced": ["S"], "why": "test"})
    spec["workloads"].append({"name": "toy.toy_mix", "config": "toy",
                              "traffic": "toy_mix", "chips": 1,
                              "why": "test"})
    for m in spec["end_to_end"]:
        if m["name"] == "solve_s":
            m["workloads"].append("toy.toy_mix")
    spec["per_layer"].append({"name": "toy_solves", "unit": "count",
                              "better": "higher", "source": "host_clock",
                              "layer": "test", "moves": "solve_s",
                              "workloads": ["toy.toy_mix"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    spec = registry.load_benchmark(root)
    cell = registry.workload(spec, "toy.toy_mix")
    cfg = registry.config(spec, "toy", root)
    mix = registry.traffic("toy_mix", bench)
    assert cfg["deployment"]["S"] == 4 and mix == {"loop": "toy_loop"}
    topo = registry.topology(cfg["deployment"], bench)
    assert topo["V"] == 8 and len(topo["edges"]) == 12
    names = [m["name"] for m in
             registry.metrics_for(spec, "toy.toy_mix", "per_layer")]
    assert names == ["toy_solves"]
    out = run_cell(spec, cell, cfg, mix, 3, 0.3, False, time.perf_counter(),
                   say=lambda *_: None, bench=bench)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"setup_s", "solve_s"}
    traced = run_cell(spec, cell, cfg, mix, 3, 0.3, True, time.perf_counter(),
                      say=lambda *_: None, bench=bench)
    assert traced["metrics"]["toy_solves"]["value"] == traced["attempted"]


def test_topology_from_edge_list():
    topo = registry.topology({"topology": "geant"})
    assert topo["V"] == 22 and len(topo["edges"]) == 33
