"""Live churn on one deployment: events applied back to back, each
followed by a few warm SGP iterations.

Set-up makes the cold solve an operator deploys with (`core.run` for the
configuration's K iterations from `core.spt_phi_sparse`, as `cold_solve`
times it) and draws the mix's event schedule from the seed
(`traffic/generate.py churn_schedule`).  Warm-up plays a fixed list of
every event kind on a throwaway `core.ReplayEngine`, failing and
recovering the highest-degree node so that both slot widths the live
graph takes are compiled; the window's engine then starts afresh from
the cold solve.  Every engine runs with the configuration's
`entry.invariant_checks`.

One operation is `ReplayEngine.apply_event(event)` then
`ReplayEngine.iterate(g)` for the schedule's g; it ends when the live φ
and its cost are on the host, and the next event follows at once.
Every answer keeps the `ChurnModel` snapshot it belongs to and is held
to the reference on `snapshot.live()` after the window; the cold solve
the window starts from is held to it too, as a cold solve.  The warm
iterations are held to what they must do at the window's rate events
(`warm_steps`): move every task, and lower the cost on most events.
The window's mean task gap follows the event stream more than the one
to three iterations an event, and is printed only.  In a traced run the program's own spans and
counters (`repro.obs`) are on for the window, and their aggregates are
left on the window as `program`.
"""
from __future__ import annotations

import time

import numpy as np

from repro import core, obs

from harness import check
from harness import reference as ref
from harness.churn_model import ChurnModel
from harness.drive import Window, host_phi, program_net
from traffic import generate


def program_event(ev):
    """The program's event for one of the generator's tuples (the
    semantics `harness/churn_model.py` keeps)."""
    kind = ev[0]
    if kind == "rate":
        return core.RateScale(float(ev[1]), task=ev[2])
    if kind == "source":
        return core.SourceRedraw(int(ev[1]), seed=int(ev[2]))
    if kind == "dest":
        return core.DestRedraw(int(ev[1]), node=int(ev[2]))
    if kind == "fail":
        return core.NodeFail(int(ev[1]))
    if kind == "recover":
        return core.NodeRecover(int(ev[1]))
    if kind == "cut":
        return core.LinkCut(int(ev[1]), int(ev[2]))
    if kind == "restore":
        return core.LinkRestore(int(ev[1]), int(ev[2]))
    raise ValueError(f"unknown event {ev!r}")


def warm_up_events(dep) -> list:
    """Every event kind at both slot widths: the highest-degree node h
    fails and recovers (its failure, or the cut of one of its links,
    lowers the largest degree), and link cuts, node failures and
    routing events run while it is down and while it is up."""
    deg = np.bincount(dep.src, minlength=dep.V)
    h = int(np.argmax(deg))
    x = int(dep.dst[dep.src == h][0])              # a neighbour of h
    u, v = next((int(a), int(b)) for a, b in zip(dep.src, dep.dst)
                if h not in (a, b))
    near = set(dep.dst[dep.src == h].tolist())
    f = next(i for i in range(dep.V) if i not in near | {h, u, v})
    up = [("rate", 1.1, None), ("rate", 0.9, 0), ("source", 0, 1),
          ("dest", 1, u), ("cut", u, v), ("restore", u, v),
          ("fail", f), ("recover", f)]
    return (up + [("fail", h)] + up + [("recover", h), ("cut", h, x),
                                       ("source", 2, 3), ("dest", 3, v),
                                       ("restore", h, x)])


class Loop:
    def __init__(self, cfg: dict, mix: dict, dep, seed: int, spans):
        e = cfg["entry"]
        self.dep, self.spans = dep, spans
        self.net = program_net(dep)
        self.schedule = generate.churn_schedule(dep, mix, seed)
        nbrs = core.build_neighbors(self.net.adj)
        phi, hist = core.run(self.net, core.spt_phi_sparse(self.net, nbrs),
                             n_iters=int(e["K"]), method=str(e["method"]),
                             bucketed=bool(e["bucketed"]))
        self.phi0, self.checks = phi, bool(e["invariant_checks"])
        self.start = (host_phi(phi), float(hist["final_cost"]))

    def engine(self) -> "core.ReplayEngine":
        return core.ReplayEngine(self.net, phi0=self.phi0,
                                 invariant_checks=self.checks)

    def warm_up(self):
        eng = self.engine()
        for i, ev in enumerate(warm_up_events(self.dep)):
            eng.apply_event(program_event(ev))
            eng.iterate(1 + i % 3)
            host_phi(eng.phi)

    def window(self, seconds: float, log) -> Window:
        eng, sp = self.engine(), self.spans
        model = ChurnModel(self.dep)
        answers = [(-1,) + self.start + (model, None)]
        lat, iters = [], 0
        if sp.annotate:
            obs.reset()
            obs.enable()
        c0 = log.compiles
        start = time.perf_counter()
        for i, (ev, g) in enumerate(self.schedule):
            t0 = time.perf_counter()
            it0 = eng.total_iters
            with sp("apply"):
                eng.apply_event(program_event(ev))
            with sp("iterate"):
                eng.iterate(g)
            with sp("fetch"):
                host = host_phi(eng.phi)
                cost = float(eng.cost)
            t1 = time.perf_counter()
            lat.append(t1 - t0)
            iters += eng.total_iters - it0
            model = model.clone()
            model.apply(ev)
            answers.append((i, host, cost, model, ev[0]))
            if t1 - start >= seconds:
                break
        else:
            raise RuntimeError(f"the {len(self.schedule)} events ran out "
                               f"before {seconds} s")
        win = Window(time.perf_counter() - start, lat, iters, answers,
                     log.compiles - c0)
        if sp.annotate:
            obs.disable()
            win.program = obs.snapshot()
        return win

    @staticmethod
    def check(dep, answers, control: bool = False) -> dict:
        """The cold solve the window starts from is held to what a cold
        solve is (`check.check_solves`: `task_gap`, `not_descended`),
        every window answer to the live network of its snapshot; each
        number is the worst over them all, `window_task_gap` the mean
        of the window answers' task gaps, and `warm_steps` reads the
        warm iterations."""
        start = check.check_solves(dep, [answers[0][:3]], control=control)
        rows = []
        for _, phi, cost, model, _kind in answers[1:]:
            net = model.live()
            if control:
                phi, cost = check.control_answer(net, phi)
            rows.append(check.answer_numbers(net, phi, cost)[0])
        out = check.worst([start] + rows)
        out.pop("task_gap", None)
        if "task_gap" in start:
            out["task_gap"] = start["task_gap"]
        gaps = [r["task_gap"] for r in rows if "task_gap" in r]
        if gaps:
            out["window_task_gap"] = float(np.mean(gaps))
        out.update(warm_steps(answers))
        return out


def warm_steps(answers) -> dict:
    """What the warm iterations did, read at the window's rate events.
    A rate event repairs nothing, so the previous answer's φ is the
    iterate the event's iterations start from, on the same live
    network:

      stalled_tasks      share of tasks whose strategy no rate event's
                         iterations changed
      rate_not_descended share of rate events after whose iterations
                         the reference cost is not below the previous
                         answer's on the event's network

    Empty if the window held no rate event."""
    moved, flat = None, 0
    for prev, now in zip(answers, answers[1:]):
        if now[4] != "rate":
            continue
        step = np.zeros(len(now[1][0]), bool)
        for a, b in zip(prev[1], now[1]):
            step |= (a != b).reshape(len(step), -1).any(-1)
        moved = step if moved is None else moved | step
        net = now[3].live()
        g = ref.graph_of(net.V, net.src, net.dst)
        flat += not (ref.flows(net, g, *now[1]).cost
                     < ref.flows(net, g, *prev[1]).cost)
    if moved is None:
        return {}
    n = sum(a[4] == "rate" for a in answers)
    return {"stalled_tasks": float(1.0 - np.mean(moved)),
            "rate_not_descended": flat / n}
