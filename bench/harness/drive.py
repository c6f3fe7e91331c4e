"""What every traffic loop (`bench/loops/<loop>.py`) shares: the
deployment as the program takes it, the answers fetched to the host,
and the record of a measured window.

A loop module defines `Loop(cfg, mix, dep, seed, spans)` with
`warm_up()`, `window(seconds, compile_log) -> Window` and
`check(dep, answers, control=False) -> numbers` (a static method:
the harness runs it once the loop is released).  The window ends at the
first operation boundary after `seconds`; its answers are held to the
reference after it has closed (`harness/check.py`).
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

from repro import core
from repro.core.costs import Cost


def program_net(dep) -> "core.CECNetwork":
    """The deployment as the program takes it: dense [V, V] adjacency and
    link parameters (1.0 off the edges), float32 on the device."""
    V = dep.V
    adj = np.zeros((V, V), bool)
    adj[dep.src, dep.dst] = True
    cap = np.ones((V, V), np.float32)
    cap[dep.src, dep.dst] = dep.cap
    f = np.float32
    return core.CECNetwork(
        adj=jnp.asarray(adj),
        link_cost=Cost("queue", jnp.asarray(cap)),
        comp_cost=Cost("queue", jnp.asarray(dep.comp_cap.astype(f))),
        dest=jnp.asarray(dep.dest, jnp.int32),
        r=jnp.asarray(dep.r.astype(f)), a=jnp.asarray(dep.a.astype(f)),
        w=jnp.asarray(dep.w.astype(f)),
        task_type=jnp.asarray(dep.task_type, jnp.int32))


def host_phi(phi):
    """(data, local, result) slots of an edge-slot φ, on the host."""
    return tuple(np.asarray(x) for x in (phi.data, phi.local[..., 0],
                                         phi.result))


@dataclasses.dataclass
class Window:
    seconds: float                 # from the first op's start to the last's end
    latencies: list                # per operation, seconds
    iterations: int                # SGP iterations executed in the window
    answers: list                  # for the loop's `check`
    compiles: int                  # backend compiles inside the window
