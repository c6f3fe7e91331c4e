"""Back-to-back cold solves of one deployment.

One operation is `core.spt_phi_sparse` on `core.build_neighbors(net.adj)`
and then `core.run` for the configuration's K iterations (`entry.K`,
`entry.method`, `entry.bucketed`); it ends when the final φ and cost
are on the host.  Every solve starts from the deployment made at
set-up, and every solve of the window is checked.
"""
from __future__ import annotations

import time

from repro import core

from harness import check
from harness.drive import Window, host_phi, program_net


class Loop:
    def __init__(self, cfg: dict, mix: dict, dep, seed: int, spans):
        e = cfg["entry"]
        self.K, self.method = int(e["K"]), str(e["method"])
        self.bucketed = bool(e["bucketed"])
        self.dep, self.spans = dep, spans
        self.net = program_net(dep)

    def solve(self):
        net, sp = self.net, self.spans
        with sp("seed"):
            nbrs = core.build_neighbors(net.adj)
            phi0 = core.spt_phi_sparse(net, nbrs)
        with sp("run"):
            phi, hist = core.run(net, phi0, n_iters=self.K,
                                 method=self.method, bucketed=self.bucketed)
        with sp("fetch"):
            host = host_phi(phi)
            cost = float(hist["final_cost"])
        iters = len(hist["costs"]) - 1 + int(hist["n_rejected"])
        return host, cost, iters

    def warm_up(self):
        self.solve()

    def window(self, seconds: float, log) -> Window:
        c0 = log.compiles
        lat, answers, iters = [], [], 0
        start = time.perf_counter()
        while True:
            t0 = time.perf_counter()
            host, cost, n = self.solve()
            t1 = time.perf_counter()
            lat.append(t1 - t0)
            iters += n
            answers.append((len(lat) - 1, host, cost))
            if t1 - start >= seconds:
                break
        return Window(time.perf_counter() - start, lat, iters, answers,
                      log.compiles - c0)

    @staticmethod
    def check(dep, answers, control: bool = False) -> dict:
        """Run after the loop is released: nothing of the program is
        left on the device."""
        return check.check_solves(dep, answers, control=control)
