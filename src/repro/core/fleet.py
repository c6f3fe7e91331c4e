"""Batched fleet solver: B scenarios on ONE topology, one dispatch.

"Millions of users" is not one big instance — it is thousands of
concurrent solver instances (one per cell/cluster/time-window) that
share a physical topology but differ in task structure: exogenous
rates `r`, destinations `dest`, result ratios `a`, compute weights
`w`.  Solving them one at a time wastes the accelerator twice: each
dispatch carries the whole launch overhead for one small instance, and
each per-iteration host sync stalls the pipeline B times per round.

This driver stacks the B networks leaf-wise (leading lane axis) and
runs `jax.vmap` over the SAME step/accept pair the solo driver's
block runs in its loop body (`sgp._sgp_step_flows_impl` +
`sgp._accept_update_impl`, see `sgp._sgp_block`), so one dispatch per
iteration advances the whole fleet and ONE
`jax.device_get` at the end of `run_fleet` fetches every lane's
accepted-cost trajectory.  Because the batched kernels are the solo
kernels vmapped — reductions stay on their original axes, the QP
bisection's bracket-freeze is select-based, and the fixed-point
recursions have exact fixed points (a lane that converged earlier
no-ops through the extra rounds) — each lane's φ/cost trajectory is
BITWISE the solo `run_chunk(driver="fused")` trajectory (locked by
tests/test_fleet.py on every lane of a B=8 fleet).

Warm-start cache: `FleetCache` memoizes converged strategies keyed by
(adjacency bytes, task-pattern hash) — the hash covers exactly the
per-lane fields (`dest`, `task_type`, `a`, `r`, `w`, plus the cost
params) — so a recurring scenario pattern (the serving router's
steady-state traffic mix re-appearing across fleet windows) re-enters
at its converged φ instead of the cold shortest-path tree.

Stopping: lanes carry the solo driver's `stopped` flag (σ blow-up or
tol exit) and freeze exactly as the solo fused chunk would; the chunk
itself always runs its full `n_iters` dispatches — a host-side
all-stopped probe per round would re-introduce the sync this module
exists to amortize.
"""
from __future__ import annotations

import dataclasses
import hashlib
from collections import OrderedDict
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import sgp
from .network import (CECNetwork, Neighbors, PhiSparse, build_neighbors,
                      flows_carry_and_cost_jit, phi_to_sparse,
                      spt_phi_sparse)


# ----------------------------------------------------------- warm cache
def fleet_cache_key(net: CECNetwork, active=None) -> tuple:
    """(adjacency bytes, task-pattern sha1) for one scenario.

    The pattern hash covers every field that distinguishes lanes on a
    shared topology (dest/task_type/a/r/w and the cost params); two
    scenarios with equal keys are the same optimization problem, so a
    converged φ transfers exactly.

    `active` (the [S_cap] slot mask of a dynamic task-slot pool) is
    part of the problem identity too: inert slots carry stale
    dest/task_type, so two pool states can share every hashed field
    yet differ in WHICH slots are live — the mask (and with it S_cap,
    via the hashed shapes) keeps a warm φ from leaking across pool
    reconfigurations.
    """
    adj = np.ascontiguousarray(np.asarray(net.adj))
    h = hashlib.sha1()
    for x in (net.dest, net.task_type, net.a, net.r, net.w,
              net.link_cost.params, net.comp_cost.params):
        arr = np.ascontiguousarray(np.asarray(x))
        h.update(str(arr.dtype).encode())
        h.update(str(arr.shape).encode())
        h.update(arr.tobytes())
    h.update(net.link_cost.family.encode())
    h.update(net.comp_cost.family.encode())
    if active is None:
        h.update(b"|fixed-S")
    else:
        act = np.ascontiguousarray(np.asarray(active, dtype=bool))
        h.update(b"|pool:" + str(act.shape[0]).encode())
        h.update(act.tobytes())
    return (adj.tobytes(), h.hexdigest())


class FleetCache:
    """LRU of converged strategies, keyed by `fleet_cache_key`.

    Stores host copies (the cache must not pin device buffers for
    scenarios that may never recur); `get` rehydrates to device arrays.
    """

    def __init__(self, maxsize: int = 64):
        self.maxsize = maxsize
        self._d: OrderedDict = OrderedDict()
        self.hits = 0
        self.misses = 0

    def __len__(self) -> int:
        return len(self._d)

    def get(self, net: CECNetwork, active=None) -> Optional[PhiSparse]:
        key = fleet_cache_key(net, active=active)
        hit = self._d.get(key)
        if hit is None:
            self.misses += 1
            return None
        self._d.move_to_end(key)
        self.hits += 1
        return PhiSparse(*[jnp.asarray(x) for x in hit])

    def put(self, net: CECNetwork, phi: PhiSparse, active=None) -> None:
        key = fleet_cache_key(net, active=active)
        self._d[key] = tuple(np.asarray(x) for x in
                             (phi.data, phi.local, phi.result))
        self._d.move_to_end(key)
        while len(self._d) > self.maxsize:
            self._d.popitem(last=False)


# ------------------------------------------------------------- executables
_EXEC_CACHE: dict = {}


def _fleet_executables(method, variant, scaling, kappa, use_blocking,
                       proj_impl, engine_impl):
    """One (vstep, vupd) pair per static-option tuple — vmapped versions
    of the solo fused driver's two kernels, shared across every fleet of
    any batch size (jit re-specializes per shape under the same wrapper,
    exactly like the solo drivers' module-level jits)."""
    key = (method, variant, scaling, kappa, use_blocking, proj_impl,
           engine_impl)
    hit = _EXEC_CACHE.get(key)
    if hit is not None:
        return hit

    def step(net, phi, fl, consts, sigma, nbrs):
        return sgp._sgp_step_flows_impl(
            net, phi, fl, consts, variant=variant, method=method,
            use_blocking=use_blocking, scaling=scaling, sigma=sigma,
            kappa=kappa, proj_impl=proj_impl, engine_impl=engine_impl,
            nbrs=nbrs)

    vstep = jax.jit(jax.vmap(step, in_axes=(0, 0, 0, 0, 0, None)))

    adaptive = scaling == "adaptive"

    def upd(phi_new, fl_new, cost_new, phi, fl, sigma, prev, n_costs,
            n_rej, stopped, tol):
        return sgp._accept_update_impl(
            phi_new, fl_new, cost_new, phi, fl, sigma, prev, n_costs,
            n_rej, stopped, None, None, tol, adaptive=adaptive)

    vupd = jax.jit(jax.vmap(upd, in_axes=(0,) * 10 + (None,)))
    _EXEC_CACHE[key] = (vstep, vupd)
    return vstep, vupd


# ------------------------------------------------------------ fleet state
@dataclasses.dataclass
class FleetState:
    """Device-resident carry of a running fleet (NOT a pytree).

    Every leaf of `net`/`phi`/`flows`/`consts` has a leading lane axis
    [B, ...]; `nbrs` is the single shared index-tile set (the
    one-topology contract).  `costs` mirrors the solo `RunState.costs`
    per lane — [T0, accepted...] host floats, appended once per chunk's
    single fetch.  `n_dispatches` counts jitted launches since init:
    the one-dispatch-per-iteration property the fleet exists for, and
    what tests assert is independent of B.
    """
    net: CECNetwork                  # stacked leaves [B, ...]
    phi: PhiSparse                   # [B, S, V, Dmax]
    flows: object                    # FlowsCarry, stacked
    consts: sgp.SGPConsts            # stacked
    nbrs: Neighbors                  # shared tiles
    sigma: jnp.ndarray               # [B] f32
    prev: jnp.ndarray                # [B] f32 last accepted cost
    n_costs: jnp.ndarray             # [B] i32
    n_rej: jnp.ndarray               # [B] i32
    stopped: jnp.ndarray             # [B] bool
    costs: List[List[float]]
    warm: List[bool]                 # per lane: φ⁰ came from the cache
    min_scale: float = 0.05
    engine_impl: Optional[str] = None
    it: int = 0
    n_dispatches: int = 0

    @property
    def B(self) -> int:
        return int(self.sigma.shape[0])

    def lane_phi(self, b: int) -> PhiSparse:
        """One lane's iterate (same layout as the solo driver's)."""
        return PhiSparse(self.phi.data[b], self.phi.local[b],
                         self.phi.result[b])


def _stack(trees):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def stack_fleet(nets: Sequence[CECNetwork]) -> CECNetwork:
    """Leaf-stack B one-topology scenarios into a lane-batched network.

    Raises unless every scenario shares the adjacency and cost families
    byte-for-byte — the contract that lets the whole fleet share one
    `Neighbors` tile set and one compiled step.
    """
    if not nets:
        raise ValueError("empty fleet")
    adj0 = np.asarray(nets[0].adj)
    for b, net in enumerate(nets[1:], start=1):
        if not np.array_equal(np.asarray(net.adj), adj0):
            raise ValueError(
                f"fleet lane {b} has a different adjacency: the batched "
                "driver shares one topology (and one Neighbors tile set) "
                "across every lane — solve topology variants as separate "
                "fleets")
        for fam0, fam in ((nets[0].link_cost.family, net.link_cost.family),
                          (nets[0].comp_cost.family, net.comp_cost.family)):
            if fam != fam0:
                raise ValueError(
                    f"fleet lane {b} mixes cost families ({fam!r} vs "
                    f"{fam0!r}): families are static in the compiled step")
    return _stack(list(nets))


def init_fleet_state(nets: Sequence[CECNetwork], phi0s=None,
                     min_scale: float = 0.05,
                     nbrs: Optional[Neighbors] = None,
                     engine_impl: Optional[str] = None,
                     cache: Optional[FleetCache] = None) -> FleetState:
    """Mirror `sgp.init_run_state` per lane, batched.

    φ⁰ per lane: the caller's `phi0s[b]` if given (dense φ converted at
    the boundary), else a `cache` hit for that lane's task pattern,
    else the cold shortest-path tree.  No host sync here beyond the
    topology checks (numpy on host-resident adjacency).
    """
    netB = stack_fleet(nets)
    if nbrs is None:
        nbrs = build_neighbors(nets[0].adj)
    warm = [False] * len(nets)
    phis = []
    for b, net in enumerate(nets):
        p = phi0s[b] if phi0s is not None else None
        if p is None and cache is not None:
            p = cache.get(net)
            warm[b] = p is not None
        if p is None:
            p = spt_phi_sparse(net, nbrs)
        elif not isinstance(p, PhiSparse):
            p = phi_to_sparse(p, nbrs)
        phis.append(p)
    phiB = _stack(phis)

    def fc(net, phi):
        return flows_carry_and_cost_jit(net, phi, "sparse", nbrs=nbrs,
                                        engine_impl=engine_impl)

    flB, T0B = jax.vmap(fc)(netB, phiB)
    constsB = jax.vmap(sgp.make_consts, in_axes=(0, 0, None))(
        netB, T0B, min_scale)
    B = len(nets)
    return FleetState(
        net=netB, phi=phiB, flows=flB, consts=constsB, nbrs=nbrs,
        sigma=jnp.ones((B,), jnp.float32),
        prev=T0B.astype(jnp.float32),
        n_costs=jnp.ones((B,), jnp.int32),
        n_rej=jnp.zeros((B,), jnp.int32),
        stopped=jnp.zeros((B,), bool),
        costs=[[float(t)] for t in np.asarray(T0B)],
        warm=warm, min_scale=min_scale, engine_impl=engine_impl)


def run_fleet_chunk(state: FleetState, n_iters: int,
                    variant: str = "sgp", tol: float = 0.0,
                    use_blocking: bool = True, scaling: str = "adaptive",
                    kappa: float = 0.0,
                    proj_impl: Optional[str] = None) -> FleetState:
    """Advance every lane `n_iters` iterations: 2·n_iters dispatches
    (propose + accept per round, whatever B is) queued asynchronously,
    then ONE `device_get` folding the accepted costs into each lane's
    host list.  Updates `state` in place and returns it.

    Same option surface as the solo fused chunk minus what a fleet
    cannot share: paper-scaling refreshes (`scaling="paper"`), async
    row masks, faults and guards are per-lane-carry features the solo
    driver owns — request them there.
    """
    if scaling not in ("adaptive",):
        raise NotImplementedError(
            "fleet lanes carry per-lane sigma only; scaling='paper' "
            "consts refreshes are a solo-driver feature")
    if n_iters <= 0:
        return state
    vstep, vupd = _fleet_executables("sparse", variant, scaling, kappa,
                                     use_blocking, proj_impl,
                                     state.engine_impl)
    tol32 = jnp.float32(tol)
    phi, fl = state.phi, state.flows
    sigma, prev = state.sigma, state.prev
    n_costs, n_rej, stopped = state.n_costs, state.n_rej, state.stopped
    cost_h, take_h = [], []
    for _ in range(n_iters):
        phi_new, fl_new, cost_new = vstep(state.net, phi, fl,
                                          state.consts, sigma, state.nbrs)
        (phi, fl, sigma, prev, n_costs, n_rej, stopped, _rng, take,
         _live) = vupd(phi_new, fl_new, cost_new, phi, fl, sigma, prev,
                       n_costs, n_rej, stopped, tol32)
        cost_h.append(cost_new)
        take_h.append(take)
        state.n_dispatches += 2
    # the chunk's single host sync: every queued round drains here
    cost_h, take_h = jax.device_get((jnp.stack(cost_h), jnp.stack(take_h)))
    for b in range(state.B):
        state.costs[b].extend(
            float(c) for c, t in zip(cost_h[:, b], take_h[:, b]) if t)
    state.phi, state.flows = phi, fl
    state.sigma, state.prev = sigma, prev
    state.n_costs, state.n_rej, state.stopped = n_costs, n_rej, stopped
    state.it += n_iters
    return state


def run_fleet(nets: Sequence[CECNetwork], n_iters: int = 200,
              phi0s=None, min_scale: float = 0.05, tol: float = 0.0,
              nbrs: Optional[Neighbors] = None,
              engine_impl: Optional[str] = None,
              cache: Optional[FleetCache] = None, **chunk_opts):
    """Solve a whole fleet: init + one chunk + one fetch.

    Returns ``(phis, history)``: per-lane `PhiSparse` strategies (lane
    `b` bitwise-equal to the solo ``run(nets[b], ...)`` under the same
    options) and a history dict with per-lane ``costs``, the per-lane
    ``warm`` cache-hit flags, and ``n_dispatches`` — the whole-fleet
    launch count the batching amortizes.  A `cache` is updated with
    each lane's converged strategy on the way out.
    """
    state = init_fleet_state(nets, phi0s=phi0s, min_scale=min_scale,
                             nbrs=nbrs, engine_impl=engine_impl,
                             cache=cache)
    run_fleet_chunk(state, n_iters, tol=tol, **chunk_opts)
    phis = [state.lane_phi(b) for b in range(state.B)]
    if cache is not None:
        for net, phi in zip(nets, phis):
            cache.put(net, phi)
    history = {"costs": [list(c) for c in state.costs],
               "warm": list(state.warm),
               "n_dispatches": state.n_dispatches,
               "stopped": list(np.asarray(state.stopped))}
    return phis, history
