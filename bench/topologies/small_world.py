"""Kleinberg small-world graph, the topology of the Table II SW row of
arXiv 2205.07178: a ring of V nodes, `n_short` distance-2 chords and
`n_long` random long-range links (V=100, 100, 120: 320 undirected
links).  The graph is drawn once from the configuration's
`topology_seed`; the benchmark's own copy of the recipe."""
import numpy as np


def edges(dep: dict):
    V = int(dep["V"])
    n_short, n_long = int(dep["n_short"]), int(dep["n_long"])
    rng = np.random.RandomState(int(dep["topology_seed"]))
    out = [(i, (i + 1) % V) for i in range(V)]
    have = {tuple(sorted(e)) for e in out}
    shorts = [(i, (i + 2) % V) for i in range(V)]
    rng.shuffle(shorts)
    for e in shorts:
        if len(out) >= V + n_short:
            break
        t = tuple(sorted(e))
        if t not in have:
            have.add(t)
            out.append(e)
    while len(out) < V + n_short + n_long:
        i, j = (int(x) for x in rng.randint(0, V, 2))
        t = (min(i, j), max(i, j))
        if i == j or t in have:
            continue
        have.add(t)
        out.append(t)
    return V, out
