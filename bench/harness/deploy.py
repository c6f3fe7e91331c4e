"""Deployments drawn from a seed: the arXiv 2205.07178 section V recipe.

A configuration file names its topology and fixes the recipe's
parameters; the run's seed draws everything else (destinations, task
types, sources and rates, result ratios, compute weights, link and
compute capacities).  The topology is the same for every seed, so every
seed of a cell runs on the same graph and the same compiled shapes.  A
topology is found by name (`registry.topology`): an edge list in
`bench/topologies/<name>.json`, or a generator `bench/topologies/
<name>.py` whose `edges(deployment)` returns the same.

This is the benchmark's own copy of the recipe (the program keeps its
own in `core/scenarios.py`); a later change to the program cannot move
it.
"""
from __future__ import annotations

import dataclasses

import numpy as np
from scipy.sparse import csr_matrix
from scipy.sparse.csgraph import dijkstra

SAT = 0.95          # queue costs turn quadratic above SAT * capacity

@dataclasses.dataclass
class Deployment:
    """One deployment on the host, in float64 holding float32 values (the
    program receives the same numbers as float32)."""
    V: int
    src: np.ndarray        # [E] directed edges, sorted by (src, dst)
    dst: np.ndarray        # [E]
    cap: np.ndarray        # [E] link capacity (queue cost)
    comp_cap: np.ndarray   # [V] compute capacity (queue cost)
    dest: np.ndarray       # [S] destination of each task
    r: np.ndarray          # [S, V] exogenous input rates
    a: np.ndarray          # [S] result-to-data ratio
    w: np.ndarray          # [S, V] compute weight
    task_type: np.ndarray  # [S]

    @property
    def S(self) -> int:
        return int(self.dest.shape[0])


def f32(x) -> np.ndarray:
    return np.asarray(x, np.float64).astype(np.float32).astype(np.float64)


def spt_next_hop(V, src, dst, cap, dest):
    """Next hop [S, V] toward each task's destination (-1 at the
    destination and where none exists), on edge weights D'(0) = 1/cap:
    Dijkstra to each destination, then per node the out-edge minimising
    weight + distance, the smallest neighbour on a tie."""
    w = 1.0 / cap
    g = csr_matrix((w, (dst, src)), shape=(V, V))       # reversed graph
    uniq = np.unique(dest)
    dist = dijkstra(g, indices=uniq)                    # [U, V]
    nxt = np.full((len(dest), V), -1, np.int64)
    order = np.lexsort((dst, src))
    s_sorted, d_sorted = src[order], dst[order]
    for k, d in enumerate(uniq):
        cand = w[order] + dist[k][d_sorted]
        best = np.full(V, np.inf)
        np.minimum.at(best, s_sorted, cand)
        hit = cand == best[s_sorted]
        first = np.full(V, -1, np.int64)
        # the first hit of each source in (src, dst) order: smallest dst
        idx = np.nonzero(hit)[0][::-1]
        first[s_sorted[idx]] = d_sorted[idx]
        row = np.where(np.isfinite(best), first, -1)
        row[d] = -1
        nxt[dest == d] = row
    return nxt


def local_spt_flows(V, src, dst, nxt, dest, r, a, w):
    """Link flows [E] and compute loads [V] of the pure-local,
    shortest-path-result strategy: all data computed at its source,
    results forwarded along `nxt` (a tree, so at most V rounds)."""
    keys = src * V + dst                        # sorted: edges are (src, dst)
    G = np.sum(w * r, axis=0)
    F = np.zeros(len(src))
    nodes = np.arange(V)
    for s in range(len(dest)):
        ok = nxt[s] >= 0
        e_of = np.full(V, -1, np.int64)
        e_of[ok] = np.searchsorted(keys, nodes[ok] * V + nxt[s][ok])
        cur = a[s] * r[s]
        for _ in range(V):
            go = ok & (cur != 0)
            if not go.any():
                break
            np.add.at(F, e_of[go], cur[go])
            moved = np.zeros(V)
            np.add.at(moved, nxt[s][go], cur[go])
            cur = moved
    return F, G


def make_deployment(dep: dict, topology: dict, seed: int) -> Deployment:
    """Draw one deployment from the configuration `dep`, its topology
    ({"V": nodes, "edges": undirected [u, v] pairs}) and the run's seed
    (any non-negative integer)."""
    V, und = int(topology["V"]), topology["edges"]
    pairs = {(min(u, v), max(u, v)) for u, v in und if u != v}
    both = [(u, v) for u, v in pairs] + [(v, u) for u, v in pairs]
    src = np.array([u for u, _ in both], np.int64)
    dst = np.array([v for _, v in both], np.int64)
    order = np.lexsort((dst, src))
    src, dst = src[order], dst[order]

    rng = np.random.Generator(np.random.PCG64(int(seed)))
    S, R, M = int(dep["S"]), int(dep["R"]), int(dep["M"])
    dest = rng.integers(0, V, size=S)
    ttype = rng.integers(0, M, size=S)
    a_m = np.clip(rng.exponential(float(dep["a_mean"]), size=M), 0.1, 5.0)
    r = np.zeros((S, V))
    for s in range(S):
        srcs = rng.choice(V, size=min(R, V), replace=False)
        r[s, srcs] = rng.uniform(dep["r_min"], dep["r_max"], size=len(srcs))
    w_im = rng.uniform(1.0, 5.0, size=(V, M))
    w = w_im[:, ttype].T
    a = a_m[ttype]
    d_mean, s_mean = float(dep["d_mean"]), float(dep["s_mean"])
    cap = np.maximum(rng.uniform(0.0, 2.0 * d_mean, size=len(src)),
                     0.05 * d_mean)
    comp_cap = np.maximum(rng.exponential(s_mean, size=V), 0.05 * s_mean)
    r, a, w, cap, comp_cap = map(f32, (r, a, w, cap, comp_cap))

    # the paper's feasibility rule: scale capacities until the pure-local
    # strategy runs every queue below margin * SAT of its capacity
    nxt = spt_next_hop(V, src, dst, cap, dest)
    F, G = local_spt_flows(V, src, dst, nxt, dest, r, a, w)
    limit = float(dep["feasibility_margin"]) * SAT
    cap = f32(cap * max(1.0, float(np.max(F / (limit * cap)))))
    comp_cap = f32(comp_cap * max(1.0, float(np.max(G / (limit * comp_cap)))))
    return Deployment(V=V, src=src, dst=dst, cap=cap, comp_cap=comp_cap,
                      dest=dest.astype(np.int64), r=r, a=a, w=w,
                      task_type=ttype.astype(np.int64))
