"""The comparison that decides `correct`.

Every answer kept from the window is held to the plain reference
(`reference.py`) on the network it belongs to:

  layout         φ's slot width is not the graph's largest degree
  simplex_err    largest departure from the strategy's constraints
  loops          tasks whose data or result support has a cycle
  delivery_loss  largest share of a task's results not delivered
  cost_gap       |reported cost - reference cost| / reference cost
  task_gap       mean over the tasks of each task's relative Frank-Wolfe
                 gap: how far the solve left the tasks from optimal
  not_descended  cold solves whose cost is not below the shortest-path
                 start's
  fw_gap         Frank-Wolfe gap over the reference cost, a certified
                 bound on (T - T*) / T

Each cell's limits live in `bench/limits/<workload>.json`; a number that
file does not name is printed but not compared.  The control
(`control_answer`) is the reference put in the program's place at
bfloat16: φ rounded to bfloat16 and its cost computed in bfloat16.
"""
from __future__ import annotations

import json
from pathlib import Path

import ml_dtypes
import numpy as np

from . import reference as ref
from .deploy import spt_next_hop

BENCH = Path(__file__).resolve().parents[1]


def load_limits(workload: str, bench: Path = BENCH) -> dict:
    return json.loads(
        (bench / "limits" / f"{workload}.json").read_text())["limits"]


def answer_numbers(net, phi, cost) -> tuple[dict, "ref.Flows | None"]:
    """The numbers of one answer (φ as (data, local, result) slots, the
    reported cost) on the network `net`."""
    g = ref.graph_of(net.V, net.src, net.dst)
    data, local, result = phi
    S = len(net.dest)
    if data.shape != (S, g.V, g.D) or result.shape != (S, g.V, g.D):
        return {"layout": 1.0}, None
    out = {"layout": 0.0,
           "simplex_err": ref.simplex_error(g, data, local, result,
                                            net.dest)}
    d_e = ref.edge_view(np.asarray(data, np.float64), g)
    r_e = ref.edge_view(np.asarray(result, np.float64), g)
    out["loops"] = float(ref.count_loops(g, d_e) + ref.count_loops(g, r_e))
    if out["loops"]:
        return out, None
    fl = ref.flows(net, g, data, local, result)
    out["delivery_loss"] = ref.delivery_loss(net, fl)
    out["cost_gap"] = abs(cost - fl.cost) / fl.cost
    gaps, now = ref.task_gaps(net, g, fl)
    out["task_gap"] = float(np.mean(gaps))
    out["fw_gap"] = ref.fw_gap(gaps, now, fl.cost)
    return out, fl


def worst(rows) -> dict:
    keys = {k for r in rows for k in r}
    return {k: max(r.get(k, -np.inf) for r in rows) for k in sorted(keys)}


def start_cost(dep) -> float:
    """Reference cost of the shortest-path start φ⁰."""
    g = ref.graph_of(dep.V, dep.src, dep.dst)
    nxt = spt_next_hop(dep.V, dep.src, dep.dst, dep.cap, dep.dest)
    return ref.flows(dep, g, *ref.spt_strategy(dep, g, nxt)).cost


def control_answer(net, phi):
    """(φ, cost) as the reference gives them at bfloat16."""
    low = tuple(np.asarray(x, np.float32).astype(ml_dtypes.bfloat16)
                .astype(np.float32) for x in phi)
    g = ref.graph_of(net.V, net.src, net.dst)
    return low, ref.flows(net, g, *low, precision="bfloat16").cost


def check_solves(dep, answers, control: bool = False) -> dict:
    """answers: [(index, (data, local, result), reported cost)], every
    cold solve of the window.  Answers that are equal bit for bit are
    held to the reference once."""
    t0 = start_cost(dep)
    rows, seen = [], {}
    for _, phi, cost in answers:
        key = (b"".join(np.ascontiguousarray(x).tobytes() for x in phi),
               float(cost))
        if key not in seen:
            if control:
                phi, cost = control_answer(dep, phi)
            num, fl = answer_numbers(dep, phi, cost)
            num["not_descended"] = float(fl is None or not fl.cost < t0)
            seen[key] = num
        rows.append(seen[key])
    return worst(rows)


def verdict(numbers: dict, limits: dict):
    """(correct, [(name, value, limit)]) over the numbers with a limit."""
    rows = [(k, float(numbers.get(k, np.inf)), float(v))
            for k, v in sorted(limits.items())]
    return all(v <= lim for _, v, lim in rows), rows
