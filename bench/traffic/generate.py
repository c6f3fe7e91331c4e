"""The one traffic generator: reads a mix file of this directory and
draws the cell's operations from the run's seed.

A mix file names its loop (`bench/loops/<loop>.py`).  `cold_solve`
needs nothing drawn: every operation is a cold solve of the deployment
made at set-up.  `churn_schedule` draws a closed-loop event stream with
the mix of a churn file (`churn.json`), after the program's
`core.events.random_schedule`: the file gives each event kind's weight
(`recover` and `restore` join only while something is down), the caps on
nodes down and links cut at once, the range of iterations after each
event, and the range of load levels.  A rate event moves one task's
load, or every task's, to a level drawn from that range (relative to the
deployment's own rates), so the load stays bounded however long the
stream runs.  No event may leave a live source cut off from its
destination; one that would becomes a rate change.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

from harness.churn_model import ChurnModel

HERE = Path(__file__).resolve().parent


def load(name: str) -> dict:
    return json.loads((HERE / f"{name}.json").read_text())


def churn_schedule(dep, mix: dict, seed: int, n_events: int | None = None):
    """[(event, iterations)] of `n_events` (default: the file's count)."""
    rng = np.random.default_rng([int(seed), 1])
    n = int(mix["events"] if n_events is None else n_events)
    wts = mix["weights"]
    g_lo, g_hi = mix["iterations"]
    lv_lo, lv_hi = mix["rate_level"]
    level = np.ones(dep.S)
    model = ChurnModel(dep)
    V, S = dep.V, dep.S
    links = sorted({(min(u, v), max(u, v))
                    for u, v in zip(dep.src.tolist(), dep.dst.tolist())})
    out = []

    def feasible(ev) -> bool:
        trial = model.clone()
        trial.apply(ev)
        return trial.delivered()

    for _ in range(n):
        kinds = {k: wts[k] for k in ("rate", "source", "dest", "fail", "cut")}
        if model.failed:
            kinds["recover"] = wts["recover"]
        if model.cut:
            kinds["restore"] = wts["restore"]
        names = sorted(kinds)
        p = np.array([kinds[k] for k in names], float)
        kind = names[rng.choice(len(names), p=p / p.sum())]
        ev = None
        if kind == "fail" and len(model.failed) < mix["max_failed"]:
            cand = [i for i in range(V) if i not in model.failed
                    and i not in set(model.dest.tolist())]
            if cand:
                ev = ("fail", int(cand[rng.integers(len(cand))]))
        elif kind == "recover":
            down = sorted(model.failed)
            ev = ("recover", int(down[rng.integers(len(down))]))
        elif kind == "cut":
            ncut = len(model.cut) // 2
            cand = [(u, v) for u, v in links if u not in model.failed
                    and v not in model.failed and (u, v) not in model.cut]
            if ncut < mix["max_cut"] and cand:
                u, v = cand[rng.integers(len(cand))]
                ev = ("cut", int(u), int(v))
        elif kind == "restore":
            cut = sorted({(min(u, v), max(u, v)) for u, v in model.cut})
            u, v = cut[rng.integers(len(cut))]
            ev = ("restore", int(u), int(v))
        elif kind == "source":
            ev = ("source", int(rng.integers(S)), int(rng.integers(1 << 16)))
        elif kind == "dest":
            task = int(rng.integers(S))
            alive = [i for i in range(V) if i not in model.failed
                     and i != int(model.dest[task])]
            ev = ("dest", task, int(alive[rng.integers(len(alive))]))
        if ev is not None and not feasible(ev):
            ev = None
        if ev is None:
            target = float(rng.uniform(lv_lo, lv_hi))
            if rng.random() < 0.5:
                factor = target / float(np.exp(np.mean(np.log(level))))
                level = level * factor
                ev = ("rate", factor, None)
            else:
                task = int(rng.integers(S))
                factor = target / float(level[task])
                level[task] = target
                ev = ("rate", factor, task)
        model.apply(ev)
        out.append((ev, int(rng.integers(g_lo, g_hi + 1))))
    return out
