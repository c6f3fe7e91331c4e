"""The plain reference: flows, cost and feasibility of a strategy φ.

Straightforward numpy/scipy in float64 over an explicit edge list.  It
imports nothing of the program and takes nothing the program made: the
network comes from the benchmark's own deployment and churn model, and
φ is read in the edge-slot layout the program documents (node i's
out-edges in ascending order of the neighbour, at slots 0..deg(i)-1,
Dmax = the largest out-degree).

`precision="bfloat16"` is the control: every intermediate array is
rounded to bfloat16 (accumulating in float32), the step that would
tempt a later change to carry φ and flows in half precision.
"""
from __future__ import annotations

import dataclasses

import ml_dtypes
import numpy as np
from scipy.sparse import csr_matrix
from scipy.sparse.csgraph import connected_components, dijkstra

from .deploy import SAT


@dataclasses.dataclass
class Graph:
    V: int
    src: np.ndarray      # [E] sorted by (src, dst)
    dst: np.ndarray
    slot: np.ndarray     # [E] position of the edge in its source's list
    deg: np.ndarray      # [V] out-degree
    D: int               # slot width: max out-degree, at least 1
    into: csr_matrix     # [V, E] incidence of each edge's head


def graph_of(V: int, src, dst) -> Graph:
    src, dst = np.asarray(src, np.int64), np.asarray(dst, np.int64)
    order = np.lexsort((dst, src))
    src, dst = src[order], dst[order]
    deg = np.bincount(src, minlength=V)
    start = np.concatenate([[0], np.cumsum(deg)[:-1]])
    slot = np.arange(len(src)) - start[src]
    into = csr_matrix((np.ones(len(src)), (dst, np.arange(len(src)))),
                      shape=(V, len(src)))
    return Graph(V, src, dst, slot, deg, max(int(deg.max(initial=0)), 1),
                 into)


def _rounder(precision: str):
    if precision == "float64":
        return np.float64, (lambda x: x)
    if precision == "bfloat16":
        def rnd(x):
            return np.asarray(x, np.float32).astype(
                ml_dtypes.bfloat16).astype(np.float32)
        return np.float32, rnd
    raise ValueError(f"unknown precision {precision!r}")


def queue_value(F, cap):
    """M/M/1 delay F/(cap - F), continued as its second-order expansion
    above SAT * cap (convex, increasing, finite everywhere)."""
    Fs = SAT * cap
    v0 = Fs / (cap - Fs)
    g0 = cap / (cap - Fs) ** 2
    h0 = 2.0 * cap / (cap - Fs) ** 3
    inner = np.minimum(F, Fs)
    dF = F - Fs
    return np.where(F <= Fs, inner / (cap - inner),
                    v0 + g0 * dF + 0.5 * h0 * dF ** 2)


def queue_d1(F, cap):
    Fs = SAT * cap
    inner = np.minimum(F, Fs)
    g0 = cap / (cap - Fs) ** 2
    h0 = 2.0 * cap / (cap - Fs) ** 3
    return np.where(F <= Fs, cap / (cap - inner) ** 2, g0 + h0 * (F - Fs))


def propagate(w_e, inject, g: Graph, rnd):
    """Solve t = inject + Φᵀ t by rounds of message passing over the
    edges; a loop-free Φ settles in at most V rounds."""
    t = rnd(inject)
    for _ in range(g.V + 1):
        msg = rnd(t[:, g.src] * w_e)
        acc = np.asarray(g.into @ msg.T, t.dtype).T
        new = rnd(inject + rnd(acc))
        if np.array_equal(new, t):
            break
        t = new
    return t


@dataclasses.dataclass
class Flows:
    t_data: np.ndarray   # [S, V]
    t_result: np.ndarray
    g: np.ndarray        # [S, V] data computed at each node
    f_task: np.ndarray   # [S, E] each task's data + result flow per edge
    F: np.ndarray        # [E] link flow
    G: np.ndarray        # [V] compute load
    cost: float


def edge_view(phi_slots, g: Graph):
    return phi_slots[:, g.src, g.slot]


def flows(net, g: Graph, data, local, result,
          precision: str = "float64") -> Flows:
    dt, rnd = _rounder(precision)
    d_e = rnd(edge_view(np.asarray(data, dt), g))
    r_e = rnd(edge_view(np.asarray(result, dt), g))
    loc = rnd(np.asarray(local, dt))
    t_data = propagate(d_e, np.asarray(net.r, dt), g, rnd)
    gg = rnd(t_data * loc)
    t_result = propagate(r_e, rnd(np.asarray(net.a, dt)[:, None] * gg),
                         g, rnd)
    f_task = rnd(t_data[:, g.src] * d_e + t_result[:, g.src] * r_e)
    F = rnd(np.sum(f_task, axis=0))
    G = rnd(np.sum(rnd(np.asarray(net.w, dt) * gg), axis=0))
    cap = np.asarray(net.cap, dt)
    comp = np.asarray(net.comp_cap, dt)
    cost = (np.sum(rnd(queue_value(F, cap)), dtype=np.float64)
            + np.sum(rnd(queue_value(G, comp)), dtype=np.float64))
    return Flows(t_data, t_result, gg, f_task, F, G, float(cost))


def count_loops(g: Graph, w_e) -> int:
    """Tasks whose support (edges with φ > 0) holds a directed cycle."""
    n = 0
    for s in range(w_e.shape[0]):
        on = w_e[s] > 0
        if not on.any():
            continue
        m = csr_matrix((np.ones(int(on.sum())), (g.src[on], g.dst[on])),
                       shape=(g.V, g.V))
        k, _ = connected_components(m, directed=True, connection="strong")
        n += int(k < g.V)
    return n


def simplex_error(g: Graph, data, local, result, dest) -> float:
    """Largest departure from the strategy's constraints: data rows
    (out-edges and local column) on the simplex, result rows on the
    simplex or empty, destination result rows empty, no negative
    fraction, nothing on slots past a node's degree."""
    data, local, result = (np.asarray(x, np.float64)
                           for x in (data, local, result))
    S, V, D = data.shape
    pad = np.arange(D)[None, :] >= g.deg[:, None]               # [V, D]
    errs = [np.max(np.abs(data.sum(-1) + local - 1.0)),
            max(0.0, -float(min(data.min(), local.min(), result.min()))),
            float(np.max(np.abs(np.where(pad[None], data, 0.0)))),
            float(np.max(np.abs(np.where(pad[None], result, 0.0))))]
    rsum = result.sum(-1)                                       # [S, V]
    off = np.minimum(np.abs(rsum - 1.0), np.abs(rsum))
    is_dest = np.arange(V)[None, :] == np.asarray(dest)[:, None]
    errs.append(float(np.max(np.where(is_dest, np.abs(rsum), off))))
    return float(max(errs))


def delivery_loss(net, fl: Flows) -> float:
    """Largest share of a task's results that never reaches its
    destination."""
    S = fl.t_result.shape[0]
    made = np.asarray(net.a, np.float64) * fl.g.sum(-1)
    got = fl.t_result[np.arange(S), np.asarray(net.dest)]
    live = made > 0
    if not live.any():
        return 0.0
    return float(np.max(np.abs(made[live] - got[live]) / made[live]))


def task_gaps(net, g: Graph, fl: Flows):
    """Each task's relative Frank-Wolfe gap: (its linearised cost now -
    its best response) / its linearised cost now, [S].

    The cost is convex in the flows.  Linearised at the current flows
    (link weights D'(F), compute weights C'(G)), a task's best response
    sends each source's data along a shortest path to the compute node
    that minimises path + w C'(G) + a * shortest result path to the
    destination.  A task at its optimum reads 0; the gaps summed over
    the tasks bound T - T* (`fw_gap`)."""
    V, S = g.V, len(net.dest)
    dl = queue_d1(fl.F, np.asarray(net.cap, np.float64))
    dc = queue_d1(fl.G, np.asarray(net.comp_cap, np.float64))
    w = np.asarray(net.w, np.float64)
    r = np.asarray(net.r, np.float64)
    now = fl.f_task @ dl + (w * fl.g) @ dc                       # [S]
    rev = csr_matrix((dl, (g.dst, g.src)), shape=(V, V))
    uniq = np.unique(np.asarray(net.dest))
    to_dest = dijkstra(rev, indices=uniq)                        # [U, V]
    where = {int(d): k for k, d in enumerate(uniq)}
    # the data graph reversed, plus a virtual sink entered from node i
    # at the cost h(i) of computing there and returning the result
    sink = V
    rows = np.concatenate([g.dst, np.full(V, sink)])
    cols = np.concatenate([g.src, np.arange(V)])
    gaps = np.zeros(S)
    for s in range(S):
        if not now[s] > 0:
            continue
        h = w[s] * dc + float(net.a[s]) * to_dest[where[int(net.dest[s])]]
        m = csr_matrix((np.concatenate([dl, np.maximum(h, 1e-300)]),
                        (rows, cols)), shape=(V + 1, V + 1))
        val = dijkstra(m, indices=sink)[:V]
        on = r[s] > 0
        gaps[s] = (now[s] - float(np.sum(r[s][on] * val[on]))) / now[s]
    return gaps, now


def fw_gap(gaps, now, cost: float) -> float:
    """The Frank-Wolfe gap over the cost, a certified bound on
    (T - T*) / T."""
    return float(np.sum(gaps * now)) / max(cost, 1e-300)


def spt_strategy(net, g: Graph, nxt):
    """φ⁰ in slots: data all local, results one-hot on the next hop."""
    S, V = len(net.dest), g.V
    data = np.zeros((S, V, g.D))
    local = np.ones((S, V))
    result = np.zeros((S, V, g.D))
    keys = g.src * V + g.dst
    for s in range(S):
        ok = nxt[s] >= 0
        e = np.searchsorted(keys, np.nonzero(ok)[0] * V + nxt[s][ok])
        result[s, g.src[e], g.slot[e]] = 1.0
    return data, local, result
