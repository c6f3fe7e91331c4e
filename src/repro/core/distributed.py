"""Distributed SGP: the paper's per-node algorithm mapped onto JAX SPMD.

The paper distributes Algorithm 1 over NETWORK nodes with a broadcast
protocol.  On an accelerator cluster the natural SPMD decomposition is
over TASKS: each device owns a shard of the |S| tasks (a task's routing
variables, traffic solves, marginal recursions and QP projections are
all task-local), and the only cross-task coupling — total link flows
F_ij and workloads G_i, i.e. the paper's "measurement" phase — is a
single `psum` per iteration (of the [V, Dmax] edge-slot flow tiles
under method="sparse").

This scales the optimizer itself: a 512-chip pod solves 512× the tasks
per iteration at the cost of one all-reduce of a link-flow buffer, and
is the engine behind the serving-layer request router
(`repro.serving.router`), where |S| is the number of active request
classes.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .faults import fault_state_specs, init_fault_state
from .network import (CECNetwork, FlowsCarry, Neighbors, Phi, PhiSparse,
                      _phi_edge_views, build_neighbors,
                      flows_carry_and_cost_jit, gather_edges,
                      phi_to_sparse, sparse_to_phi)
from .sgp import (SGPConsts, _accept_update, _fold_fused_histories,
                  _sgp_step_flows_impl, _sgp_step_impl, _tol_converged,
                  accept_step, make_consts)
from ..kernels.ref import fold_reduce

AXIS = "tasks"
NODE_AXIS = "nodes"


def task_mesh(n_devices: Optional[int] = None) -> Mesh:
    devs = np.asarray(jax.devices()[: n_devices or len(jax.devices())])
    return Mesh(devs, (AXIS,))


def pad_tasks(net: CECNetwork, phi, n_shards: int):
    """Pad the task dimension to a multiple of the device count.

    Padding tasks have zero input rate: they generate no flow, no cost,
    and their (irrelevant) routing variables stay feasible.  Both φ
    layouts are handled; an edge-slot `PhiSparse` is padded in its own
    layout — no dense [S, V, V+1] detour (at the V ~ 10³ × S ~ 10⁴
    scale this function exists for, that array would not fit).
    """
    S = net.S
    Sp = ((S + n_shards - 1) // n_shards) * n_shards
    if Sp == S:
        return net, phi, S

    def pad(x, fill=0.0):
        widths = [(0, Sp - S)] + [(0, 0)] * (x.ndim - 1)
        return jnp.pad(x, widths, constant_values=fill)

    net_p = dataclasses.replace(
        net, dest=pad(net.dest), r=pad(net.r),
        a=pad(net.a, 1.0), w=pad(net.w, 1.0), task_type=pad(net.task_type))
    if isinstance(phi, PhiSparse):
        # padded φ: all-local data, empty result rows (zero rate means
        # zero result traffic, so the empty — trivially loop-free — row
        # is feasible and the step's zero-traffic jump governs anyway)
        local = pad(phi.local).at[S:].set(1.0)
        return net_p, PhiSparse(pad(phi.data), local,
                                pad(phi.result)), S
    # padded φ: all-local data, result parked one-hot on the first
    # out-neighbor (any feasible loop-free row works: rate is zero)
    data = pad(phi.data)
    data = data.at[S:, :, -1].set(1.0)
    first_nbr = jnp.argmax(net.adj, axis=1)                    # [V]
    onehot = jax.nn.one_hot(first_nbr, net.V, dtype=phi.result.dtype)
    result = pad(phi.result)
    result = result.at[S:].set(onehot[None])
    result = result.at[S:, 0, :].set(0.0)  # dest of padded tasks = node 0
    return net_p, Phi(data, result), S


_TASK_SHARDED_NET = CECNetwork(
    adj=P(), link_cost=P(), comp_cost=P(),
    dest=P(AXIS), r=P(AXIS), a=P(AXIS), w=P(AXIS), task_type=P(AXIS))
_CONSTS_SPEC = SGPConsts(P(), P(), P(), P())
# only the cross-task couplings (F, G) are replicated post-psum
_CARRY_SPEC = FlowsCarry(t_data=P(AXIS), t_result=P(AXIS), F=P(), G=P())


def _phi_spec(method: str):
    return (PhiSparse(P(AXIS), P(AXIS), P(AXIS)) if method == "sparse"
            else Phi(P(AXIS), P(AXIS)))


def _buckets_spec(buckets):
    """Replicated in_spec for a `NeighborBuckets` pytree (every device
    holds the full degree-bucket tiles, exactly like the Neighbors
    index tiles); None passes through as the empty pytree."""
    return (jax.tree.map(lambda _: P(), buckets)
            if buckets is not None else None)


def make_distributed_step(mesh: Mesh, variant: str = "sgp",
                          scaling: str = "adaptive", kappa: float = 0.0,
                          method: str = "dense",
                          nbrs: Optional[Neighbors] = None,
                          engine_impl: Optional[str] = None,
                          buckets=None):
    """Build the jitted shard_map SGP step for a 1-D task mesh.

    method="sparse" shard_maps the neighbor-list engine over the task
    axis: per-task edge_rounds recursions are shard-local (the
    `Neighbors` index tiles are replicated on every device), and the
    only collective stays the one psum of F/G.  The step then takes and
    returns the edge-slot `PhiSparse` layout — each shard's φ lives in
    [S/n, V, Dmax] slots end-to-end, so no [S, V, V+1] array exists on
    any device (`run_distributed` converts at the boundary).  `nbrs`
    must then be the precomputed `build_neighbors(adj)`; engine_impl
    picks the message-passing backend (see kernels.ops.edge_rounds).

    This is the standalone (phi -> phi_new, cost-of-phi) step kept for
    external callers; the drivers use `make_distributed_step_flows`,
    which also carries the flows so each iterate's flow solve runs
    exactly once.
    """
    if method == "sparse" and nbrs is None:
        raise ValueError("method='sparse' needs nbrs=build_neighbors(adj) "
                         "precomputed outside jit")
    # replicated index tiles (None, an empty pytree, off the sparse path)
    nbrs_spec = (Neighbors(P(), P(), P(), P(), P())
                 if nbrs is not None else None)

    def step(net, phi, consts, sigma, nbrs, buckets):
        new_phi, aux = _sgp_step_impl(
            net, phi, consts, variant=variant, scaling=scaling,
            sigma=sigma, kappa=kappa, method=method, psum_axis=AXIS,
            engine_impl=engine_impl, nbrs=nbrs, buckets=buckets)
        return new_phi, aux["cost"]

    sharded = jax.shard_map(
        step, mesh=mesh,
        in_specs=(_TASK_SHARDED_NET, _phi_spec(method), _CONSTS_SPEC, P(),
                  nbrs_spec, _buckets_spec(buckets)),
        out_specs=(_phi_spec(method), P()), check_vma=False)
    jitted = jax.jit(sharded)
    # keep the public step signature (net, phi, consts, sigma)
    return partial(_call_with_nbrs, jitted, nbrs, buckets)


def _call_with_nbrs(jitted, nbrs, buckets, net, phi, consts, sigma):
    return jitted(net, phi, consts, sigma, nbrs, buckets)


def make_distributed_step_flows(mesh: Mesh, variant: str = "sgp",
                                scaling: str = "adaptive",
                                kappa: float = 0.0, method: str = "dense",
                                nbrs: Optional[Neighbors] = None,
                                engine_impl: Optional[str] = None,
                                buckets=None, fault_plan=None):
    """The drivers' shard_mapped per-iteration primitive:
    step(net, phi, fl, consts, sigma) -> (phi_new, fl_new, cost_new).

    `fl` is the current iterate's `FlowsCarry` (F/G replicated
    post-psum, traffic task-sharded; under method="sparse" F is the
    [V, Dmax] edge-slot tile, so the per-iteration collective shrinks
    to one psum of [V, Dmax]+[V]).  The candidate's flows/cost are
    evaluated INSIDE the same call — the host loop's separate
    total_cost recomputation (a second flow solve per iteration) is
    gone.  Both `run_distributed_chunk` drivers dispatch THIS compiled
    executable, which is what makes the fused pipeline bitwise the
    python loop.

    fault_plan (faults.FaultPlan) arms the fault injectors INSIDE the
    shard_mapped step: the step then additionally takes and returns a
    `FaultState` (rng replicated — every shard draws the same node
    masks/lags, exactly one applies a given corruption — ring/held
    sharded with their task dim).
    """
    if method == "sparse" and nbrs is None:
        raise ValueError("method='sparse' needs nbrs=build_neighbors(adj) "
                         "precomputed outside jit")
    nbrs_spec = (Neighbors(P(), P(), P(), P(), P())
                 if nbrs is not None else None)

    if fault_plan is not None:
        fs_spec = fault_state_specs(fault_plan, AXIS)

        def step_f(net, phi, fl, consts, sigma, nbrs, buckets, fs):
            return _sgp_step_flows_impl(
                net, phi, fl, consts, variant=variant, scaling=scaling,
                sigma=sigma, kappa=kappa, method=method, psum_axis=AXIS,
                engine_impl=engine_impl, nbrs=nbrs, buckets=buckets,
                fault_plan=fault_plan, fault_state=fs)

        sharded = jax.shard_map(
            step_f, mesh=mesh,
            in_specs=(_TASK_SHARDED_NET, _phi_spec(method), _CARRY_SPEC,
                      _CONSTS_SPEC, P(), nbrs_spec, _buckets_spec(buckets),
                      fs_spec),
            out_specs=(_phi_spec(method), _CARRY_SPEC, P(), fs_spec),
            check_vma=False)
        jitted = jax.jit(sharded)
        return partial(_call_with_nbrs_flows_faulted, jitted, nbrs,
                       buckets)

    def step(net, phi, fl, consts, sigma, nbrs, buckets):
        return _sgp_step_flows_impl(
            net, phi, fl, consts, variant=variant, scaling=scaling,
            sigma=sigma, kappa=kappa, method=method, psum_axis=AXIS,
            engine_impl=engine_impl, nbrs=nbrs, buckets=buckets)

    sharded = jax.shard_map(
        step, mesh=mesh,
        in_specs=(_TASK_SHARDED_NET, _phi_spec(method), _CARRY_SPEC,
                  _CONSTS_SPEC, P(), nbrs_spec, _buckets_spec(buckets)),
        out_specs=(_phi_spec(method), _CARRY_SPEC, P()),
        check_vma=False)
    jitted = jax.jit(sharded)
    return partial(_call_with_nbrs_flows, jitted, nbrs, buckets)


def _call_with_nbrs_flows(jitted, nbrs, buckets, net, phi, fl, consts,
                          sigma):
    return jitted(net, phi, fl, consts, sigma, nbrs, buckets)


def _call_with_nbrs_flows_faulted(jitted, nbrs, buckets, net, phi, fl,
                                  consts, sigma, fs):
    return jitted(net, phi, fl, consts, sigma, nbrs, buckets, fs)


@dataclasses.dataclass
class DistributedRunState:
    """Resumable host-side state of `run_distributed` (NOT a pytree).

    Mirrors `sgp.RunState` for the shard_map driver: the padded net and
    φ, the current iterate's `FlowsCarry` (each iterate's flow solve
    runs exactly once — when it was the candidate), the compiled
    shard_map step (reused across chunks — same-graph churn events swap
    `net_p` in via `rebaseline_distributed_state` without retracing;
    topology events rebuild the state since the index tiles change
    shape), and the accept/reject bookkeeping.
    `init_distributed_state` + chunks of `run_distributed_chunk` walk
    exactly `run_distributed`'s trajectory.
    """
    phi: object                      # padded iterate (PhiSparse if sparse)
    consts: SGPConsts
    nbrs: Optional[Neighbors]
    net_p: CECNetwork                # task-padded network
    step: object                     # jitted shard_map step-flows fn
    mesh: Mesh
    method: str
    scaling: str
    variant: str
    engine_impl: Optional[str]
    S: int                           # original (unpadded) task count
    costs: list
    min_scale: float = 0.05
    sigma: float = 1.0
    n_rejected: int = 0
    it: int = 0                      # iterations EXECUTED (incl. rejected)
    stopped: bool = False
    flows: Optional[FlowsCarry] = None   # flows of `phi` (device carry)
    buckets: object = None           # NeighborBuckets (bucketed sparse mode)
    fault_plan: object = None        # faults.FaultPlan (static; None = off)
    fault_state: object = None       # faults.FaultState (device carry)
    guard_cfg: object = None         # guards.GuardConfig (None = unguarded)
    guard_state: object = None       # guards.GuardState (device carry)
    guard_events: list = dataclasses.field(default_factory=list)


def init_distributed_state(net: CECNetwork, phi0,
                           mesh: Optional[Mesh] = None,
                           variant: str = "sgp", scaling: str = "adaptive",
                           kappa: float = 0.0, min_scale: float = 0.05,
                           method: str = "dense",
                           engine_impl: Optional[str] = None,
                           bucketed: bool = False,
                           fault_plan=None,
                           fault_rng: Optional[jax.Array] = None,
                           guards=None
                           ) -> DistributedRunState:
    """Pad, convert at the boundary, build the shard_map step and
    evaluate φ⁰'s flows + T⁰ (one solve, both carried) — exactly
    `run_distributed`'s prologue.  bucketed=True (sparse method only)
    replicates the degree-bucketed tiles on every device and runs each
    shard's fixed-point recursions over them (bitwise the padded
    shard_map trajectory, ΣVb·Db per-round work per shard).
    fault_plan/fault_rng arm the on-device fault injectors inside the
    shard_mapped step; guards (a guards.GuardConfig) arms the
    sentinel/rollback layer — both live on the PADDED tensors (padded
    rows are fault-transparent: local=1 data rows pass the mass
    sentinel, empty result rows have |rsum|=0)."""
    from .network import build_buckets
    mesh = mesh or task_mesh()
    n_dev = mesh.devices.size
    nbrs = build_neighbors(net.adj) if method == "sparse" else None
    buckets = (build_buckets(net.adj)
               if bucketed and method == "sparse" else None)
    sparse_in = isinstance(phi0, PhiSparse)
    if sparse_in and method != "sparse":
        # same contract as core.run / compute_flows: the dense engines
        # need dense coordinates — at the scale PhiSparse exists for,
        # silently materializing them would be an OOM, not a favor
        raise ValueError("PhiSparse requires method='sparse'; convert "
                         "with sparse_to_phi for the dense/broadcast "
                         "engines")
    net_p, phi_p, S = pad_tasks(net, phi0, n_dev)
    if method == "sparse" and not sparse_in:
        # boundary: the loop iterates natively in edge slots
        phi_p = phi_to_sparse(phi_p, nbrs)
    step = make_distributed_step_flows(mesh, variant=variant,
                                       scaling=scaling, kappa=kappa,
                                       method=method, nbrs=nbrs,
                                       engine_impl=engine_impl,
                                       buckets=buckets,
                                       fault_plan=fault_plan)
    fl_p, T0 = flows_carry_and_cost_jit(net_p, phi_p, method, nbrs=nbrs,
                                        engine_impl=engine_impl,
                                        buckets=buckets)
    consts = make_consts(net_p, T0, min_scale)
    fault_state = None
    if fault_plan is not None:
        fault_state = init_fault_state(net_p, phi_p, fl_p, fault_plan,
                                       rng=fault_rng, method=method,
                                       nbrs=nbrs, engine_impl=engine_impl,
                                       buckets=buckets)
    guard_state = None
    if guards is not None:
        from .guards import init_guard_state
        guard_state = init_guard_state(phi_p, fl_p, T0, guards)
    return DistributedRunState(
        phi=phi_p, consts=consts, nbrs=nbrs, net_p=net_p, step=step,
        mesh=mesh, method=method, scaling=scaling, variant=variant,
        engine_impl=engine_impl, S=S, costs=[float(T0)],
        min_scale=min_scale, flows=fl_p, buckets=buckets,
        fault_plan=fault_plan, fault_state=fault_state,
        guard_cfg=guards, guard_state=guard_state)


def rebaseline_distributed_state(state: DistributedRunState,
                                 net: CECNetwork, phi_sp,
                                 fault_rng: Optional[jax.Array] = None
                                 ) -> DistributedRunState:
    """Swap a SAME-GRAPH network (rate churn: r/cost params moved; or a
    destination re-draw — `dest` is just another step input) into the
    existing state and re-baseline T⁰/φ's flows/the Eq. 16 constants —
    the compiled shard_map step is kept, so such events cost zero
    retraces.  `net.adj` must equal the adjacency the state was built
    from (the step computes with the init-time `Neighbors` tiles);
    topology events must rebuild via `init_distributed_state` instead.

    `fault_rng` re-keys the fault injector for the new segment — the
    ReplayEngine passes a fresh split of its engine-level rng here, the
    same split a full `_init_state` rebuild would take, so the
    post-event fault stream is identical between the two drivers'
    rebaseline paths.  None continues the previous segment's stream
    (the legacy behaviour, for direct callers that manage no engine
    rng)."""
    net_p, phi_p, S = pad_tasks(net, phi_sp, state.mesh.devices.size)
    fl_p, T0 = flows_carry_and_cost_jit(net_p, phi_p, state.method,
                                        nbrs=state.nbrs,
                                        engine_impl=state.engine_impl,
                                        buckets=state.buckets)
    state.net_p, state.phi, state.S = net_p, phi_p, S
    state.flows = fl_p
    state.consts = make_consts(net_p, T0, state.min_scale)
    state.costs = [float(T0)]
    state.sigma, state.n_rejected, state.stopped = 1.0, 0, False
    if state.fault_plan is not None:
        # re-anchor ring/hold on the new baseline's marginals, re-keyed
        # per segment when the caller supplies a split
        state.fault_state = init_fault_state(
            net_p, phi_p, fl_p, state.fault_plan,
            rng=(state.fault_state.rng if fault_rng is None
                 else fault_rng), method=state.method,
            nbrs=state.nbrs, engine_impl=state.engine_impl,
            buckets=state.buckets)
    if state.guard_cfg is not None:
        from .guards import init_guard_state
        state.guard_state = init_guard_state(phi_p, fl_p, T0,
                                             state.guard_cfg)
    return state


def run_distributed_chunk(state: DistributedRunState, n_iters: int,
                          tol: float = 0.0,
                          driver: Optional[str] = None
                          ) -> DistributedRunState:
    """Advance the distributed driver `n_iters` iterations in place —
    `run_distributed`'s loop body, resumable between events.  A stopped
    state (sigma blow-up / tol early exit) stays stopped until
    re-baselined.

    driver="fused" (default) pipelines the whole chunk asynchronously:
    the shard_mapped step and the on-device `_accept_update` select are
    dispatched without ever blocking, and the per-iteration histories
    come back in ONE device_get at the end — bitwise the python loop
    (driver="host"), which shares the step's compiled executable and
    mirrors the select arithmetic in f32 (`accept_step`).  `tol`, like
    the single-process driver, fires only after an ACCEPTED step.
    """
    faulted = (state.fault_plan is not None
               and state.fault_state is not None)
    guarded = (state.guard_cfg is not None
               and state.guard_state is not None)
    if driver is None:
        driver = "fused"
    if driver not in ("host", "fused"):
        raise ValueError(f"unknown driver {driver!r}")
    if faulted or guarded:
        # faults carry on-device state, guards select on device — only
        # the fused pipeline threads them (host == fused bitwise anyway)
        driver = "fused"
    if state.stopped or n_iters <= 0:
        return state
    fl = state.flows
    if fl is None:
        fl, _ = flows_carry_and_cost_jit(state.net_p, state.phi,
                                         state.method, nbrs=state.nbrs,
                                         engine_impl=state.engine_impl,
                                         buckets=state.buckets)
    if driver == "fused":
        return _run_distributed_chunk_fused(state, fl, n_iters, tol)
    phi, costs = state.phi, state.costs
    sigma, n_rejected = state.sigma, state.n_rejected
    for _ in range(n_iters):
        phi_new, fl_new, cost_new = state.step(state.net_p, phi, fl,
                                               state.consts,
                                               jnp.float32(sigma))
        new_cost = float(cost_new)
        state.it += 1
        accepted, sigma, stop = accept_step(new_cost, costs[-1], sigma,
                                            state.scaling, state.variant)
        if not accepted:
            n_rejected += 1
            if stop:
                state.stopped = True
                break
        else:
            phi, fl = phi_new, fl_new
            costs.append(new_cost)
            if _tol_converged(costs, tol):
                state.stopped = True
                break
    state.phi, state.flows = phi, fl
    state.sigma, state.n_rejected = sigma, n_rejected
    return state


def _run_distributed_chunk_fused(state: DistributedRunState, fl,
                                 n_iters: int, tol: float
                                 ) -> DistributedRunState:
    """Async-pipelined distributed chunk: one device sync per chunk
    (see `sgp._run_chunk_fused` — same design, shard_mapped step; the
    fault/guard layers thread exactly as in the single-process fused
    driver, with the fault state flowing through the shard_map)."""
    adaptive = state.scaling == "adaptive" and state.variant == "sgp"
    faulted = (state.fault_plan is not None
               and state.fault_state is not None)
    guarded = (state.guard_cfg is not None
               and state.guard_state is not None)
    if guarded:
        from .guards import _guarded_update   # lazy: guards imports sgp
    phi = state.phi
    fs, gs, cfg = state.fault_state, state.guard_state, state.guard_cfg
    sigma = jnp.float32(state.sigma)
    prev = jnp.float32(state.costs[-1])
    n_costs = jnp.asarray(len(state.costs), jnp.int32)
    n_rej = jnp.asarray(0, jnp.int32)
    stopped = jnp.asarray(False)
    tol32 = jnp.float32(tol)
    cost_hist, take_hist, live_hist = [], [], []
    code_hist, roll_hist, ck_hist = [], [], []
    it_start = state.it
    for it in range(state.it, state.it + n_iters):
        if faulted:
            phi_new, fl_new, cost_new, fs_new = state.step(
                state.net_p, phi, fl, state.consts, sigma, fs)
        else:
            phi_new, fl_new, cost_new = state.step(state.net_p, phi, fl,
                                                   state.consts, sigma)
        stopped_pre = stopped
        if faulted:
            # a stopped carry freezes the fault state too (bitwise
            # chunked resumption past a stop — see sgp._run_chunk_fused)
            fs = jax.tree.map(
                lambda new, old: jnp.where(stopped_pre, old, new),
                fs_new, fs)
        if guarded:
            do_ckpt = bool(cfg.checkpoint_every
                           and it % cfg.checkpoint_every == 0)
            (phi, fl, sigma, prev, n_costs, n_rej, stopped, _, take,
             live, gs, code, rolled, ck_cost) = _guarded_update(
                phi_new, fl_new, cost_new, phi, fl, sigma, prev,
                n_costs, n_rej, stopped, None, None, tol32, gs,
                state.nbrs, adaptive=adaptive, cfg=cfg, do_ckpt=do_ckpt)
            code_hist.append(code)
            roll_hist.append(rolled)
            ck_hist.append(ck_cost)
        else:
            (phi, fl, sigma, prev, n_costs, n_rej, stopped, _, take,
             live) = _accept_update(phi_new, fl_new, cost_new, phi, fl,
                                    sigma, prev, n_costs, n_rej, stopped,
                                    None, None, tol32, adaptive=adaptive)
        cost_hist.append(cost_new)
        take_hist.append(take)
        live_hist.append(live)
    extra = (code_hist, roll_hist, ck_hist) if guarded else None
    cost_h, _, live_h, extra_h = _fold_fused_histories(
        state, sigma, n_rej, stopped, cost_hist, take_hist, live_hist,
        extra)
    if guarded:
        from .guards import GuardEvent, SENTINEL_NAMES
        codes, rolls, cks = extra_h
        for i, (code, rolled, ck) in enumerate(zip(codes, rolls, cks)):
            if live_h[i] and int(code) > 0:
                state.guard_events.append(GuardEvent(
                    it=it_start + i, sentinel=SENTINEL_NAMES[int(code)],
                    action="rollback" if bool(rolled) else "stop",
                    cost=float(cost_h[i]),
                    restored_cost=float(ck) if bool(rolled) else None))
        state.guard_state = gs
    if faulted:
        state.fault_state = fs
    state.phi, state.flows = phi, fl
    return state


def unpad_phi(state: DistributedRunState):
    """The current iterate restricted to the original task count."""
    phi = state.phi
    if isinstance(phi, PhiSparse):
        return PhiSparse(phi.data[:state.S], phi.local[:state.S],
                         phi.result[:state.S])
    return Phi(phi.data[:state.S], phi.result[:state.S])


def run_distributed(net: CECNetwork, phi0, n_iters: int = 200,
                    mesh: Optional[Mesh] = None, variant: str = "sgp",
                    scaling: str = "adaptive", kappa: float = 0.0,
                    min_scale: float = 0.05, method: str = "dense",
                    tol: float = 0.0, engine_impl: Optional[str] = None,
                    driver: Optional[str] = None, bucketed: bool = False,
                    fault_plan=None, fault_rng: Optional[jax.Array] = None,
                    guards=None):
    """Driver: distributed SGP with the same safeguard as `sgp.run`.

    method="sparse" runs the neighbor-list engine on every shard (the
    V ~ 10³ × S ~ 10⁴ regime: per-task edge arrays shard over devices,
    the [V, Dmax] index tiles are replicated, one psum of the edge-slot
    F tile + G couples the shards); φ is converted to the edge-slot
    `PhiSparse` layout at the boundary and iterated natively, so the
    loop materializes neither [S, V, V+1] nor [V, V] arrays.  Returns
    (phi_final [original S], history); the returned φ matches the input
    layout (dense `Phi` in, dense back; a `PhiSparse` φ⁰ is padded,
    iterated AND returned in slot layout, so the huge-S regime never
    touches a dense φ at all).  Bitwise-equivalent to the single-device
    path up to reduction order (validated in tests).  Resumable:
    `init_distributed_state` + `run_distributed_chunk` walk the same
    trajectory in chunks (the streaming replay engine interleaves churn
    events between them).  driver="fused" (default) pipelines each
    chunk with one host sync at the end; driver="host" is the bitwise
    python-loop reference.  `tol` stops after an accepted step improves
    by less than tol·cost (once >4 costs accumulated).
    fault_plan/fault_rng/guards mirror `sgp.run` — either one forces
    the fused driver, and the history then also carries
    "guard_events"/"n_corrupt".
    """
    sparse_in = isinstance(phi0, PhiSparse)
    state = init_distributed_state(net, phi0, mesh=mesh, variant=variant,
                                   scaling=scaling, kappa=kappa,
                                   min_scale=min_scale, method=method,
                                   engine_impl=engine_impl,
                                   bucketed=bucketed,
                                   fault_plan=fault_plan,
                                   fault_rng=fault_rng, guards=guards)
    state = run_distributed_chunk(state, n_iters, tol=tol, driver=driver)
    phi = state.phi
    if method == "sparse" and not sparse_in:
        state.phi = sparse_to_phi(phi, state.nbrs, net.V)  # back to dense
    phi_out = unpad_phi(state)
    hist = {"costs": state.costs, "final_cost": state.costs[-1],
            "n_rejected": state.n_rejected}
    if guards is not None:
        hist["guard_events"] = state.guard_events
    if state.fault_state is not None:
        hist["n_corrupt"] = int(state.fault_state.n_corrupt)
    return phi_out, hist


# ----------------------------------------------------------- node sharding
def task_node_mesh(n_tasks: int, n_nodes: int) -> Mesh:
    """A 2-D ("tasks", "nodes") device mesh: tasks stay the outer SPMD
    axis (they are embarrassingly parallel), nodes the inner one (the
    recursions couple across it, via the halo exchange below)."""
    devs = np.asarray(jax.devices()[: n_tasks * n_nodes])
    return Mesh(devs.reshape(n_tasks, n_nodes), (AXIS, NODE_AXIS))


@dataclasses.dataclass(frozen=True)
class NodePartition:
    """Concrete (numpy, built outside jit) halo plan for sharding the
    NODE axis of the edge-slot recursions over `n` devices.

    Nodes are split into `n` contiguous blocks of `Vl = Vp / n` rows
    (V zero-padded to Vp: padded rows have empty neighbor lists and
    never inject, so they sit at the fixed point from round 0).  A row
    is a BOUNDARY row of its shard if any OTHER shard references it
    through its in- or out-neighbor lists; only those rows travel in
    the per-round `all_gather` — [.., Bmax] per shard instead of the
    full [.., Vl] state, which on a power-law graph cut into contiguous
    blocks is a small fraction of the state.

    The per-shard tables (leading axis `n`, sharded over NODE_AXIS)
    remap every neighbor index into the shard-local CONCAT space
    [x_local (Vl) ; halo (n·Bmax)], where the halo block is the
    NODE_AXIS `all_gather(tiled=True)` of every shard's boundary rows
    in device order — so one gather per round serves every cross-shard
    read, in both edge directions.
    """
    n: int                  # node shards
    V: int                  # original node count
    Vp: int                 # padded node count (n * Vl)
    Bmax: int               # max boundary rows per shard
    bnd: np.ndarray         # [n, Bmax] shard-LOCAL boundary row indices
    in_remap: np.ndarray    # [n, Vl, Din]  in_nbr -> concat space
    in_slot: np.ndarray     # [n, Vl, Din]  source-row slot (unchanged)
    in_mask: np.ndarray     # [n, Vl, Din]
    out_remap: np.ndarray   # [n, Vl, Dout] out_nbr -> concat space
    out_mask: np.ndarray    # [n, Vl, Dout]

    @property
    def Vl(self) -> int:
        return self.Vp // self.n


def build_node_partition(nbrs: Neighbors, n_shards: int) -> NodePartition:
    """Build the contiguous-block halo plan from the padded neighbor
    lists (pure numpy — the plan is adjacency-derived and jit-static)."""
    V = nbrs.V
    in_nbr = np.asarray(nbrs.in_nbr)
    in_slot = np.asarray(nbrs.in_slot)
    in_mask = np.asarray(nbrs.in_mask)
    out_nbr = np.asarray(nbrs.out_nbr)
    out_mask = np.asarray(nbrs.out_mask)
    Vl = -(-V // n_shards)
    Vp = Vl * n_shards

    def pad_rows(x, fill):
        return np.pad(x, [(0, Vp - V)] + [(0, 0)] * (x.ndim - 1),
                      constant_values=fill)

    in_nbr = pad_rows(in_nbr, 0)
    in_slot = pad_rows(in_slot, 0)
    in_mask = pad_rows(in_mask, False)
    out_nbr = pad_rows(out_nbr, 0)
    out_mask = pad_rows(out_mask, False)
    owner = np.arange(Vp) // Vl

    # boundary rows: referenced (through either direction's lists) by a
    # row another shard owns
    boundary = [set() for _ in range(n_shards)]
    for nbr, mask in ((in_nbr, in_mask), (out_nbr, out_mask)):
        src = np.repeat(np.arange(Vp), nbr.shape[1]).reshape(nbr.shape)
        cross = mask & (owner[src] != owner[nbr])
        for u in np.unique(nbr[cross]):
            boundary[owner[u]].add(int(u))
    bnd_lists = [sorted(b) for b in boundary]
    Bmax = max((len(b) for b in bnd_lists), default=0)
    Bmax = max(Bmax, 1)              # keep the all_gather shape nonzero
    bnd = np.zeros((n_shards, Bmax), np.int32)
    pos = np.zeros(Vp, np.int64)     # boundary position of each row
    for s, rows in enumerate(bnd_lists):
        for p, u in enumerate(rows):
            bnd[s, p] = u - s * Vl   # shard-local
            pos[u] = p

    def remap(nbr, mask):
        # local reads -> [0, Vl); remote -> Vl + owner·Bmax + pos
        local = nbr - owner[:, None] * Vl if nbr.ndim == 2 else None
        src_owner = owner[:, None]
        tgt_owner = owner[nbr]
        r = np.where(tgt_owner == src_owner, nbr - tgt_owner * Vl,
                     Vl + tgt_owner * Bmax + pos[nbr])
        r = np.where(mask, r, 0).astype(np.int32)
        return r.reshape(n_shards, Vl, nbr.shape[1])

    shard3 = lambda x: x.reshape(n_shards, Vl, x.shape[1])
    return NodePartition(
        n=n_shards, V=V, Vp=Vp, Bmax=Bmax, bnd=bnd,
        in_remap=remap(in_nbr, in_mask),
        in_slot=shard3(in_slot).astype(np.int32),
        in_mask=shard3(in_mask),
        out_remap=remap(out_nbr, out_mask),
        out_mask=shard3(out_mask))


def _halo_fixed_point(w_loc, inject, remap, bnd, max_rounds: int):
    """Shard-local body of the node-sharded linear fixed point
    x = inject + reduce_e w·x[nbr]: per round, `all_gather` ONLY the
    boundary rows over NODE_AXIS, gather through the concat-space remap
    and fold-reduce each local row.

    Every local row folds the same width with the same weights and the
    same (exact) neighbor states as the single-device engine, so the
    per-round iterates — and the fixed point — are BITWISE the unsharded
    solve's rows.  The stop flag is psum'ed over NODE_AXIS: the coupled
    recursion must keep every node shard stepping until the GLOBAL state
    settles (a shard-local early exit would freeze a shard whose inputs
    are still changing)."""
    def step(x):
        xb = x[..., bnd]                                  # [.., Bmax]
        halo = jax.lax.all_gather(xb, NODE_AXIS, axis=x.ndim - 1,
                                  tiled=True)             # [.., n*Bmax]
        xc = jnp.concatenate([x, halo], axis=-1)
        return inject + fold_reduce(w_loc * xc[..., remap], "sum")

    def changed(a, b):
        flag = jnp.any(a != b).astype(jnp.int32)
        return jax.lax.psum(flag, NODE_AXIS) > 0

    x1 = step(inject)

    def cond(carry):
        k, _, _, go = carry
        return (k < max_rounds) & go

    def body(carry):
        k, x, _, _ = carry
        xn = step(x)
        return k + 1, xn, x, changed(xn, x)

    _, x, _, _ = jax.lax.while_loop(
        cond, body,
        (jnp.asarray(1, jnp.int32), x1, inject, changed(x1, inject)))
    return x


def node_flows_carry_and_cost(net: CECNetwork, phi_sp: PhiSparse,
                              nbrs: Neighbors, mesh: Mesh,
                              part: Optional[NodePartition] = None):
    """`flows_carry_and_cost(method="sparse")` over a 2-D
    (tasks × nodes) mesh — the paper's "measurement" phase with BOTH
    axes sharded.

    Tasks shard exactly as in the 1-D step (independent recursions, one
    F/G psum); the NODE axis of every [.., V(, Dmax)] array is cut into
    contiguous blocks, and each round of the two traffic solves moves
    only the boundary rows (`NodePartition`) over NODE_AXIS.  The
    in-edge weight view — whose source rows can live on other shards —
    is built by ONE boundary-row gather of φ's [.., Bmax, Dmax] tiles
    per solve, then the rounds exchange [.., Bmax] state rows only.

    Returns (FlowsCarry, cost) with F/G unpadded to [V, Dmax]/[V] and
    psum'ed over tasks (replicated, like the 1-D step's carry).
    t_data/t_result are BITWISE the single-device sparse solve (halo
    reads are exact copies; fold_reduce pins every row's reduction
    order); F and the cost differ only in cross-shard summation order
    (~1 ulp).
    """
    n_nodes = mesh.shape[NODE_AXIS]
    if part is None:
        part = build_node_partition(nbrs, n_nodes)
    if part.n != n_nodes:
        raise ValueError(f"partition built for {part.n} node shards, "
                         f"mesh has {n_nodes}")
    Vp, V = part.Vp, part.V

    def pad_nodes(x, axis, fill=0.0):
        widths = [(0, 0)] * x.ndim
        widths[axis] = (0, Vp - V)
        return jnp.pad(x, widths, constant_values=fill)

    phi_d_sp, phi_loc, phi_r_sp = _phi_edge_views(phi_sp, nbrs)
    phi_d_sp = pad_nodes(phi_d_sp, 1)
    phi_r_sp = pad_nodes(phi_r_sp, 1)
    phi_loc = pad_nodes(phi_loc, 1)
    r = pad_nodes(net.r, 1)
    w = pad_nodes(net.w, 1)
    link_sp = pad_nodes(gather_edges(net.link_cost.params, nbrs), 0)
    # padded rows: unit capacity, zero workload -> exactly zero cost
    # (zero capacity would evaluate the queue cost at 0/0)
    comp_params = pad_nodes(net.comp_cost.params, 0, fill=1.0)
    link_fam = net.link_cost.family
    comp_fam = net.comp_cost.family
    max_rounds = nbrs.V

    def body(phi_d, phi_loc, phi_r, r, a, w, link_p, comp_p,
             bnd, in_remap, in_slot, in_mask, out_remap, out_mask):
        # per-shard plan tables arrive with a leading length-1 axis
        bnd, in_remap, in_slot, in_mask, out_remap, out_mask = (
            t[0] for t in (bnd, in_remap, in_slot, in_mask, out_remap,
                           out_mask))
        # in-edge weight view: one boundary-row gather of φ's tiles
        def in_view(phi_e):
            pb = phi_e[:, bnd, :]                  # [Sl, Bmax, Dmax]
            halo = jax.lax.all_gather(pb, NODE_AXIS, axis=1, tiled=True)
            pc = jnp.concatenate([phi_e, halo], axis=1)
            wv = pc[:, in_remap, in_slot]          # [Sl, Vl, Din]
            return jnp.where(in_mask[None], wv, 0.0)

        t_data = _halo_fixed_point(in_view(phi_d), r, in_remap, bnd,
                                   max_rounds)
        g = t_data * phi_loc
        t_result = _halo_fixed_point(in_view(phi_r), a[:, None] * g,
                                     in_remap, bnd, max_rounds)
        F = jnp.sum(t_data[..., None] * phi_d
                    + t_result[..., None] * phi_r, axis=0)
        F = jax.lax.psum(F, AXIS)                  # [Vl, Dmax]
        G = jax.lax.psum(jnp.sum(w * g, axis=0), AXIS)
        from .costs import Cost
        link = jnp.where(out_mask, Cost(link_fam, link_p).value(F), 0.0)
        cost = jnp.sum(link) + jnp.sum(Cost(comp_fam, comp_p).value(G))
        cost = jax.lax.psum(cost, NODE_AXIS)
        return FlowsCarry(t_data, t_result, F, G), cost

    AN, N = P(AXIS, NODE_AXIS), P(NODE_AXIS)
    sharded = jax.shard_map(
        body, mesh=mesh,
        in_specs=(AN, AN, AN, AN, P(AXIS), AN, N, N,
                  N, N, N, N, N, N),
        out_specs=(FlowsCarry(t_data=AN, t_result=AN, F=N, G=N), P()),
        check_vma=False)
    carry, cost = jax.jit(sharded)(
        phi_d_sp, phi_loc, phi_r_sp, r, net.a, w, link_sp, comp_params,
        part.bnd, part.in_remap, part.in_slot, part.in_mask,
        part.out_remap, part.out_mask)
    return FlowsCarry(carry.t_data[:, :V], carry.t_result[:, :V],
                      carry.F[:V], carry.G[:V]), cost
