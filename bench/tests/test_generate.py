"""The churn generator: a seed reproduces its schedule, and no event it
draws cuts a live source off from its destination."""
import json
from collections import deque
from pathlib import Path

import numpy as np
import pytest

from harness import deploy, registry
from harness.churn_model import ChurnModel
from traffic import generate

BENCH = Path(__file__).resolve().parents[1]


def _geant(seed):
    cfg = json.loads((BENCH / "configs" / "geant.json").read_text())
    dep = cfg["deployment"]
    return deploy.make_deployment(dep, registry.topology(dep), seed)


def _bfs_reaches(V, edges, dest):
    """Nodes that reach `dest`: a plain breadth-first search backwards."""
    preds = {v: [] for v in range(V)}
    for u, v in edges:
        preds[v].append(u)
    seen, todo = {dest}, deque([dest])
    while todo:
        for u in preds[todo.popleft()]:
            if u not in seen:
                seen.add(u)
                todo.append(u)
    return seen


@pytest.mark.parametrize("seed", [0, 2**31 + 7])
def test_seed_reproduces_schedule(seed):
    mix = generate.load("churn")
    dep = _geant(seed)
    a = generate.churn_schedule(dep, mix, seed, 300)
    b = generate.churn_schedule(_geant(seed),
                                mix, seed, 300)
    assert a == b
    other = generate.churn_schedule(dep, mix, seed + 1, 300)
    assert a != other


@pytest.mark.parametrize("seed", [1, 5, 123456789012])
def test_no_event_disconnects_a_live_source(seed):
    mix = generate.load("churn")
    dep = _geant(seed)
    sched = generate.churn_schedule(dep, mix, seed, 400)
    kinds = {ev[0] for ev, _ in sched}
    assert {"rate", "source", "dest", "fail", "cut", "recover",
            "restore"} <= kinds
    model = ChurnModel(dep)
    for ev, g in sched:
        assert mix["iterations"][0] <= g <= mix["iterations"][1]
        model.apply(ev)
        assert len(model.failed) <= mix["max_failed"]
        assert len(model.cut) // 2 <= mix["max_cut"]
        net = model.live()
        edges = list(zip(net.src.tolist(), net.dst.tolist()))
        for s in range(net.S):
            srcs = np.nonzero(net.r[s] > 0)[0]
            if len(srcs):
                reach = _bfs_reaches(net.V, edges, int(net.dest[s]))
                assert set(srcs.tolist()) <= reach, (ev, s)


def test_rate_levels_stay_bounded():
    mix = generate.load("churn")
    dep = _geant(3)
    model = ChurnModel(dep)
    for ev, _ in generate.churn_schedule(dep, mix, 3, 2000):
        model.apply(ev)
    total = model.r.sum() / dep.r.sum()
    lo, hi = mix["rate_level"]
    assert lo ** 2 / hi <= total <= hi ** 2 / lo
