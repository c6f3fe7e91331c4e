"""The benchmark's own model of a churning deployment.

It keeps the pristine deployment plus the churn facts (failed nodes,
cut links, logical rates, destinations) and derives the live network
from them, with the semantics the program documents for its events
(`core/events.py`): a failed node loses its links and its compute
(capacity 1e-3), its own inputs stop, and tasks destined to it go dark;
a recovery restores exactly what was there.  The generator uses it to
keep every live source connected to its destination; `live()` is the
network an answer after those events belongs to.

Events are plain tuples, for a churn loop to turn into the program's
event objects:

    ("rate", factor, task or None)   ("source", task, seed)
    ("dest", task, node)             ("fail", node)   ("recover", node)
    ("cut", u, v)                    ("restore", u, v)
"""
from __future__ import annotations

import numpy as np
from scipy.sparse import csr_matrix
from scipy.sparse.csgraph import breadth_first_order

from .deploy import Deployment, f32

FAILED_COMP_CAP = 1e-3


class ChurnModel:
    def __init__(self, base: Deployment):
        self.base = base
        self.failed: frozenset = frozenset()
        self.cut: frozenset = frozenset()      # directed (u, v) pairs
        self.r = base.r.copy()
        self.dest = base.dest.copy()
        self._reach: dict = {}                 # shared reachability memo

    def clone(self) -> "ChurnModel":
        c = ChurnModel.__new__(ChurnModel)
        c.base, c.failed, c.cut = self.base, self.failed, self.cut
        c.r, c.dest, c._reach = self.r, self.dest, self._reach
        return c

    def apply(self, ev) -> None:
        """Fold one event in (copy-on-write: snapshots stay valid)."""
        kind = ev[0]
        if kind == "rate":
            _, factor, task = ev
            r = self.r.copy()
            if task is None:
                r = r * factor
            else:
                r[task] = r[task] * factor
            self.r = r
        elif kind == "source":
            _, task, seed = ev
            rng = np.random.RandomState(seed)
            row = self.r[task].copy()
            vals = row[row > 0.0]
            alive = np.setdiff1d(np.arange(row.shape[0]),
                                 np.array(sorted(self.failed), int))
            if vals.size and alive.size >= vals.size:
                src = rng.choice(alive, size=vals.size, replace=False)
                row[:] = 0.0
                row[src] = rng.permutation(vals)
                r = self.r.copy()
                r[task] = row
                self.r = r
        elif kind == "dest":
            _, task, node = ev
            if node not in self.failed:
                dest = self.dest.copy()
                dest[task] = node
                self.dest = dest
        elif kind == "fail":
            self.failed = self.failed | {ev[1]}
        elif kind == "recover":
            self.failed = self.failed - {ev[1]}
        elif kind == "cut":
            self.cut = self.cut | {(ev[1], ev[2]), (ev[2], ev[1])}
        elif kind == "restore":
            self.cut = self.cut - {(ev[1], ev[2]), (ev[2], ev[1])}
        else:
            raise ValueError(f"unknown event {ev!r}")

    def live_edges(self):
        b = self.base
        keep = np.ones(len(b.src), bool)
        if self.failed:
            dead = np.zeros(b.V, bool)
            dead[list(self.failed)] = True
            keep &= ~dead[b.src] & ~dead[b.dst]
        if self.cut:
            cut = {u * b.V + v for u, v in self.cut}
            keep &= ~np.isin(b.src * b.V + b.dst, list(cut))
        return keep

    def live(self) -> Deployment:
        """The network the program should be solving now."""
        b = self.base
        keep = self.live_edges()
        r = self.r.copy()
        comp = b.comp_cap.copy()
        for node in self.failed:
            r[:, node] = 0.0
            r[self.dest == node, :] = 0.0
            comp[node] = f32(FAILED_COMP_CAP)
        return Deployment(V=b.V, src=b.src[keep], dst=b.dst[keep],
                          cap=b.cap[keep], comp_cap=comp,
                          dest=self.dest.copy(), r=r, a=b.a, w=b.w,
                          task_type=b.task_type)

    def _reach_rows(self, dests):
        """[len(dests), V] bool: which nodes reach each destination."""
        key = (self.failed, self.cut)
        memo = self._reach.setdefault(key, {})
        missing = [d for d in set(int(d) for d in dests) if d not in memo]
        if missing:
            b = self.base
            keep = self.live_edges()
            rev = csr_matrix((np.ones(int(keep.sum())),
                              (b.dst[keep], b.src[keep])), shape=(b.V, b.V))
            for d in missing:
                row = np.zeros(b.V, bool)
                row[breadth_first_order(rev, d, directed=True,
                                        return_predecessors=False)] = True
                memo[d] = row
        return np.stack([memo[int(d)] for d in dests])

    def delivered(self) -> bool:
        """Every live source reaches its task's destination (a BFS of the
        benchmark's own on the live graph)."""
        net = self.live()
        reach = self._reach_rows(net.dest)
        return bool(np.all(~(net.r > 0) | reach))
