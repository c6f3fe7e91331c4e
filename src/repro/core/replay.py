"""Streaming churn replay for the sparse engine.

Turns the static-instance solver into an online system: a
`ReplayEngine` owns a live edge-slot `PhiSparse` iterate and applies a
`ChurnSchedule` of events (rate churn, source/destination re-draws,
node failures AND recoveries, link cuts — see core.events) to it,
repairing the iterate with `refeasibilize_sparse` on topology events
and WARM-STARTING the resumable drivers (`sgp.run_chunk` /
`distributed.run_distributed_chunk`) between events instead of
re-solving from the SPT φ⁰ each time.

Same-graph events (everything `event_kind` calls "rate"/"routing" —
the adjacency, and so the `Neighbors` tiles, are unchanged) can skip
the host entirely: `play(..., stream=True)` coalesces every maximal
run of them, warm gaps included, into ONE asynchronous dispatch
stream (`sgp.FusedStream`) whose per-event re-baselines run as eager
device ops, paying a single `device_get` per window instead of one
per event.  Topology events break the stream and take the ordinary
`apply_event` path.

Guarantees the test layer (tests/test_replay.py,
tests/test_replay_stream.py) locks down:

* a zero-event replay is BITWISE `run(method="sparse")` — the engine
  adds nothing to the uninterrupted trajectory;
* the fused stream is BITWISE the event loop on every canned `*_churn`
  schedule — costs, final φ, `EventRecord` segmentation, guard log —
  including fault-injected, guarded and Theorem-2-async replays;
* after every event the iterate satisfies `check_invariants`: data rows
  on the simplex, result rows simplex-or-empty, exactly zero mass on
  dead/padding slots, loop-free supports;
* within each inter-event segment the accepted-cost sequence is
  monotone non-increasing (the adaptive driver's accept/reject), i.e.
  cost recovers monotonically after every shock.

`play(..., cold_baseline=True)` additionally runs a cold SPT restart
beside every repair event and records warm-vs-cold
iterations-to-target — the number the BENCH replay rows
(benchmarks/replay_sweep.py) track across PRs.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .events import (ChurnSchedule, ChurnState, DestRedraw, RateSet,
                     TaskArrive, TaskPool, event_kind)
from .network import (CECNetwork, Neighbors, PhiSparse, build_buckets,
                      build_neighbors, clear_task_slot, is_loop_free,
                      mask_inactive_slots, pad_phi_sparse,
                      refeasibilize_sparse, refeasibilize_sparse_samegraph,
                      seed_task_slot, sparse_to_phi, spt_phi_sparse,
                      spt_result_slots)
from .sgp import FusedStream, init_run_state, run_chunk, warm_sigma
from . import distributed as dist
from .. import obs


# ------------------------------------------------------------ invariants
def check_feasible(phi_sp: PhiSparse, nbrs: Neighbors,
                   dest=None, atol: float = 1e-5, active=None) -> None:
    """Assert the edge-slot iterate is feasible.

    Data rows (slots + local column) lie on the simplex at every node;
    result rows are simplex rows or exactly-empty rows (a failed node,
    with no exits, carries no routing; `refeasibilize_sparse` gives a
    recovered one its shortest-path row); destination rows carry no
    result mass; padding slots hold EXACTLY zero.  The last check is
    deliberately STRICTER than the `PhiSparse` layout contract (which
    lets padding hold garbage because every consumer masks it): the SGP
    step and `refeasibilize_sparse` both PRODUCE exactly-zero padding,
    and the replay engine pins that so any new producer that starts
    leaving scratch values in dead slots is flagged here instead of
    surfacing as a confusing downstream diff.

    `active` ([S] bool, dynamic task-slot pools) splits the check:
    INACTIVE task rows are pinned to the inert-slot convention EXACTLY
    (zero data mass, all-local, empty result rows — any drift means a
    producer leaked mass into a slot the pool considers empty), and the
    simplex/destination checks then run on the active rows only.
    """
    data = np.asarray(phi_sp.data)
    local = np.asarray(phi_sp.local[..., 0])
    result = np.asarray(phi_sp.result)
    if active is not None:
        act = np.asarray(active, dtype=bool)
        ina = ~act
        if not (data[ina] == 0.0).all():
            raise AssertionError("inactive task rows carry data mass")
        if not (result[ina] == 0.0).all():
            raise AssertionError("inactive task rows carry result mass")
        if not (local[ina] == 1.0).all():
            raise AssertionError("inactive task rows are not all-local")
        data, local, result = data[act], local[act], result[act]
        if dest is not None:
            dest = np.asarray(dest)[act]
    pad = ~np.asarray(nbrs.out_mask)[None]
    if not (data[np.broadcast_to(pad, data.shape)] == 0.0).all():
        raise AssertionError("nonzero mass on dead data slots")
    if not (result[np.broadcast_to(pad, result.shape)] == 0.0).all():
        raise AssertionError("nonzero mass on dead result slots")
    # the negativity tolerance is symmetric: a data slot at -1e-9 of
    # projection float error must not trip here while the same value in
    # the local column would pass (data used to be checked strictly)
    if data.min() < -atol or local.min() < -atol:
        raise AssertionError("negative routing fraction")
    np.testing.assert_allclose(data.sum(-1) + local, 1.0, atol=atol,
                               err_msg="data rows off the simplex")
    rsum = result.sum(-1)
    ok = (np.abs(rsum - 1.0) < atol) | (np.abs(rsum) < atol)
    if not ok.all():
        raise AssertionError(
            f"result rows neither simplex nor empty: sums "
            f"{np.unique(np.round(rsum[~ok], 4))[:8]}")
    if dest is not None:
        d = np.asarray(dest)
        if not (rsum[np.arange(d.shape[0]), d] < atol).all():
            raise AssertionError("destination rows carry result mass")


def check_invariants(net: CECNetwork, phi_sp: PhiSparse, nbrs: Neighbors,
                     n_loop_tasks: Optional[int] = None,
                     atol: float = 1e-5, active=None) -> None:
    """`check_feasible` + loop-freedom.

    The boolean-closure loop-free check is O(S·V²·log V), so at V ~ 10³
    pass `n_loop_tasks` to spot-check a task slice (the invariant is
    per-task, slicing loses no soundness for the checked tasks).
    `active` forwards the task-pool mask to `check_feasible`; the
    loop-freedom closure runs on all rows either way (inactive rows are
    support-free — all-local — so they are trivially loop-free).
    """
    check_feasible(phi_sp, nbrs, dest=net.dest, atol=atol, active=active)
    if n_loop_tasks is not None and n_loop_tasks < net.S:
        sl = slice(0, n_loop_tasks)
        net = dataclasses.replace(
            net, dest=net.dest[sl], r=net.r[sl], a=net.a[sl],
            w=net.w[sl], task_type=net.task_type[sl])
        phi_sp = PhiSparse(phi_sp.data[sl], phi_sp.local[sl],
                           phi_sp.result[sl])
    phi = sparse_to_phi(phi_sp, nbrs, net.V)
    if not bool(is_loop_free(net, phi)):
        raise AssertionError("replayed iterate has a support loop")


def iters_to_target(costs, target: float) -> int:
    """Index of the first cost <= target, or -1 if never reached.

    The sentinel is deliberately NOT len(costs): a trajectory that
    never reaches the target used to be indistinguishable from one
    that reached it on its final step.  Consumers that want a number
    comparable against budgets use `iters_or_budget`."""
    for i, c in enumerate(costs):
        if c <= target:
            return i
    return -1


def iters_or_budget(iters: int, budget: int) -> int:
    """Fold `iters_to_target`'s -1 sentinel into a comparable count:
    the count itself when the target was reached, else `budget + 1`
    (strictly worse than exhausting the whole budget), so sums and
    warm-vs-cold comparisons order never-reached outcomes correctly."""
    return budget + 1 if iters < 0 else iters


# ---------------------------------------------------------------- records
@dataclasses.dataclass
class EventRecord:
    """What one churn event did to the live iterate."""
    it: int                      # global iteration the event fired at
    event: object
    kind: str                    # "rate" | "topology" | "routing" |
                                 # "task" | "grow" (pool ladder grew)
    cost_before: float           # last accepted cost on the old network
    cost_after: float            # repaired iterate's cost on the new one
    segment_costs: list = dataclasses.field(default_factory=list)
    segment_iters: int = 0       # iterations EXECUTED after the event
                                 # (rejected steps count; accepted costs
                                 # land in segment_costs)
    # cold-baseline stats (play(cold_baseline=True), repair events only)
    warm_iters: Optional[int] = None
    cold_iters: Optional[int] = None
    cold_final: Optional[float] = None


# ----------------------------------------------------------------- engine
class ReplayEngine:
    """Event-driven streaming replay over a live `PhiSparse` iterate.

    driver="run" resumes the single-process `sgp.run` loop
    (`RunState`/`run_chunk`); driver="distributed" resumes the
    shard_mapped `run_distributed` loop — rate and routing events keep
    the graph and swap the padded network into the existing compiled
    step (no retrace); only topology events rebuild it (their
    `Neighbors` tiles change).

    loop_driver picks how each warm inter-event segment executes:
    "fused" (the default, resolved by the chunk drivers) pipelines the
    whole segment on device with ONE host sync at its end — the
    streaming regime this engine exists for, where per-iteration
    host round-trips would dominate at scale; "host" forces the
    per-iteration python reference loop (bitwise-identical trajectory,
    so replay results do not depend on the choice).

    run_opts are forwarded to every `run_chunk` call (variant, scaling,
    proj_impl, driver, ... — driver="distributed" instead bakes
    variant/scaling in at init; a run_opts "driver" wins over
    loop_driver for the "run" engine).

    invariant_checks (default on) runs `check_invariants` host-side on
    the repaired iterate after every event — a spot check over
    `invariant_loop_tasks` tasks for the loop-freedom closure.  Benches
    pass False: the check is a host sync + O(S·V²) closure that would
    drain the async pipeline a long churn schedule is supposed to keep
    full.

    fault_plan/fault_rng/guards (see core.faults / core.guards) thread
    the robustness layer through every warm segment: each event's
    re-initialized driver state gets a fresh split of the engine's
    fault rng (so replay stays deterministic per seed but segments
    draw independent fault streams) and a guard carry re-anchored at
    the repaired iterate; tripped `GuardEvent`s accumulate across
    segments in `guard_log`.
    """

    def __init__(self, net: CECNetwork, phi0: Optional[PhiSparse] = None,
                 driver: str = "run", engine_impl: Optional[str] = None,
                 min_scale: float = 0.05, mesh=None,
                 run_opts: Optional[dict] = None,
                 loop_driver: Optional[str] = None,
                 bucketed: bool = False,
                 invariant_checks: bool = True,
                 invariant_loop_tasks: Optional[int] = 4,
                 fault_plan=None, fault_rng=None, guards=None,
                 rng=None, pool: Optional[TaskPool] = None):
        if driver not in ("run", "distributed"):
            raise ValueError(f"unknown replay driver {driver!r}")
        if bucketed and driver != "run":
            raise ValueError("bucketed replay needs driver='run' (the "
                             "distributed step shards the padded tile)")
        if rng is not None and driver != "run":
            raise ValueError("the Theorem-2 async rng (rng=) drives "
                             "run_chunk's row masks; driver="
                             "'distributed' does not consume it")
        if pool is not None:
            if driver != "run":
                raise ValueError(
                    "a dynamic task pool needs driver='run': the "
                    "distributed step does not thread the active mask")
            if int(net.S) != pool.S_cap:
                raise ValueError(
                    f"network has S={int(net.S)} task slots but the "
                    f"pool's S_cap={pool.S_cap}; pad the network with "
                    "network.pad_tasks(net, pool.S_cap) first")
        self.pool = pool
        self.admission_log: list = []        # drained, it-stamped pool log
        self.churn = ChurnState(net, pool=pool)
        self.net = net
        self.nbrs = build_neighbors(net.adj)
        # degree-bucketed mode: rebuilt beside nbrs on every topology
        # event (bucket membership is adjacency-derived, like the tiles)
        self.bucketed = bucketed
        self.buckets = build_buckets(net.adj) if bucketed else None
        self.driver = driver
        self.engine_impl = engine_impl
        self.min_scale = min_scale
        self.mesh = mesh
        self.loop_driver = loop_driver
        self.run_opts = dict(run_opts or {})
        if loop_driver is not None and driver == "run":
            self.run_opts.setdefault("driver", loop_driver)
        if engine_impl is not None:
            # thread the backend into every run_chunk call (the
            # distributed driver instead bakes it into its step)
            self.run_opts.setdefault("engine_impl", engine_impl)
        if driver == "distributed":
            # the distributed iterate path consumes none of run_chunk's
            # kwargs beyond what init_distributed_state bakes in —
            # anything else (tol/async_frac/callback/...) would be
            # silently dropped mid-replay, so refuse it up front
            unsupported = set(self.run_opts) - {"variant", "scaling",
                                                "kappa", "engine_impl"}
            if unsupported:
                raise ValueError(
                    f"run_opts {sorted(unsupported)} are not supported "
                    "by driver='distributed' (it bakes variant/scaling/"
                    "kappa/engine_impl into the compiled step and drops "
                    "everything else)")
        if (self.run_opts.get("async_frac", 0.0) > 0.0) and rng is None:
            raise ValueError(
                "run_opts={'async_frac': ...} needs ReplayEngine("
                "rng=...): the engine splits it per inter-event segment "
                "to drive the Theorem-2 row masks")
        self.invariant_checks = invariant_checks
        self.invariant_loop_tasks = invariant_loop_tasks
        self.fault_plan = fault_plan
        self.guards = guards
        self._fault_rng = (jax.random.PRNGKey(0) if fault_rng is None
                           else fault_rng)
        self._rng = rng                      # Theorem-2 async-mask stream
        self._guard_log: list = []           # finished segments' trips
        self._spt_cache: dict = {}           # dest bytes -> SPT result rows
        self.records: list[EventRecord] = []
        self.cost_log: list[float] = []      # finished segments' costs
        self.total_iters = 0
        self._segment_open = False           # iterations attribute to
                                             # records[-1] only while open
        phi0 = spt_phi_sparse(net, self.nbrs) if phi0 is None else phi0
        if not isinstance(phi0, PhiSparse):
            raise TypeError("ReplayEngine iterates natively: pass a "
                            "PhiSparse phi0 (e.g. spt_phi_sparse)")
        self._refresh_active()
        if self.pool is not None and self._active_dev is not None:
            # never trust the caller's φ⁰ on slots the pool says are
            # empty (e.g. an SPT φ⁰ built on a padded net seeds EVERY
            # row, inert slots included)
            phi0 = mask_inactive_slots(phi0, self._active_dev)
        self._init_state(phi0)

    # ------------------------------------------------------------- driver
    def _segment_fault_rng(self):
        """Advance the engine's fault stream by one per-segment split —
        the 'each event's segment draws an independent fault stream'
        contract, shared by BOTH drivers' rebaseline paths (the
        distributed same-graph rebaseline used to skip it and continue
        the previous segment's stream)."""
        self._fault_rng, sub = jax.random.split(self._fault_rng)
        return sub

    def _segment_rng(self):
        """Per-segment split of the Theorem-2 async-mask rng (mirrors
        the fault-rng contract: deterministic per engine seed, but
        segments draw independent mask streams)."""
        self._rng, sub = jax.random.split(self._rng)
        return sub

    def _refresh_active(self) -> None:
        """Re-upload the pool's active-slot mask as the device array the
        drivers thread (`TaskPool.active_for_engine` decides None — the
        fixed-S bitwise pass-through — vs the dynamic mask; see the
        pool's compilation contract).  Called after every task event;
        admitting a task at constant S_cap changes array VALUES only,
        so the compiled step executables are all reused."""
        if self.pool is None:
            self._active_dev = None
            return
        host = self.pool.active_for_engine()
        self._active_dev = None if host is None else jnp.asarray(host)

    def _drain_admissions(self) -> None:
        """Move the pool's un-drained `AdmissionEvent`s into the
        engine's log, stamped with the global iteration count (stream
        windows apply their events before folding iterations, so there
        the stamp is the window-entry count)."""
        if self.pool is None:
            return
        for ev in self.pool.drain_log():
            self.admission_log.append(
                dataclasses.replace(ev, it=self.total_iters))

    def _init_state(self, phi_sp: PhiSparse) -> None:
        robust = {}
        if self.fault_plan is not None:
            # each segment draws an independent fault stream from the
            # engine's deterministic seed
            robust.update(fault_plan=self.fault_plan,
                          fault_rng=self._segment_fault_rng())
        if self.guards is not None:
            robust.update(guards=self.guards)
        if self.driver == "run":
            self.state: object = init_run_state(
                self.net, phi_sp, min_scale=self.min_scale,
                method="sparse", engine_impl=self.engine_impl,
                nbrs=self.nbrs, bucketed=self.bucketed,
                buckets=self.buckets,
                rng=None if self._rng is None else self._segment_rng(),
                active=self._active_dev, **robust)
        else:
            self.state = dist.init_distributed_state(
                self.net, phi_sp, mesh=self.mesh, method="sparse",
                min_scale=self.min_scale, engine_impl=self.engine_impl,
                variant=self.run_opts.get("variant", "sgp"),
                scaling=self.run_opts.get("scaling", "adaptive"),
                kappa=self.run_opts.get("kappa", 0.0), **robust)
            self.mesh = self.state.mesh      # reuse across re-inits

    @property
    def phi(self) -> PhiSparse:
        """The live (unpadded) edge-slot iterate."""
        if self.driver == "run":
            return self.state.phi
        return dist.unpad_phi(self.state)

    @property
    def costs(self) -> list:
        """Full accepted-cost trajectory across all segments so far."""
        return self.cost_log + list(self.state.costs)

    @property
    def cost(self) -> float:
        return self.state.costs[-1]

    @property
    def guard_log(self) -> list:
        """All `GuardEvent`s tripped so far, across segments."""
        return self._guard_log + list(
            getattr(self.state, "guard_events", None) or [])

    def iterate(self, n_iters: int) -> list:
        """Advance the warm driver `n_iters` iterations; returns the
        accepted costs appended by this chunk.  Counters advance by the
        iterations actually EXECUTED (the driver may stop early on a
        sigma blow-up or a tol exit passed via run_opts)."""
        if n_iters <= 0:
            return []
        before = len(self.state.costs)
        it_before = self.state.it
        if self.driver == "run":
            run_chunk(self.net, self.state, n_iters, **self.run_opts)
        else:
            dist.run_distributed_chunk(self.state, n_iters,
                                       driver=self.loop_driver)
        executed = self.state.it - it_before
        self.total_iters += executed
        new = list(self.state.costs[before:])
        if self.records and self._segment_open:
            self.records[-1].segment_costs.extend(new)
            self.records[-1].segment_iters += executed
        return new

    # ------------------------------------------------------------- events
    def apply_event(self, event) -> EventRecord:
        """Fold one churn event into the live system.

        Rate events keep the iterate (still feasible) and only
        re-baseline cost/curvature; topology and routing events repair
        it through `refeasibilize_sparse` (re-slotting onto the new
        graph's index tiles, destination re-draws force-rebuilding the
        moved task).  Either way the driver state is re-initialized
        from the WARM iterate — never from the SPT — and keeps the
        previous segment's sigma (`sgp.warm_sigma`).

        Traced (`repro.obs`) as span `churn.apply`, with the repair in
        `churn.repair` and the re-baseline in `churn.rebaseline`, and
        counted in `churn.events` and `churn.topology_events`.
        """
        obs.count("churn.events")
        with obs.span("churn.apply"):
            return self._apply_event(event)

    def _apply_event(self, event) -> EventRecord:
        cost_before = float(self.state.costs[-1])
        kind = self.churn.apply(event)
        if kind == "topology":
            obs.count("churn.topology_events")
        net_new = self.churn.network()
        phi = self.phi
        if kind in ("task", "grow"):
            # arrival/departure on the task pool: same graph, so the
            # repair is per-slot — clear a departed slot back to inert,
            # seed a claimed slot from the SPT (eager .at ops).  "grow"
            # first pads the iterate to the new rung (S changed: the
            # one admission outcome that recompiles, by design).
            self._refresh_active()
            if kind == "grow":
                phi = pad_phi_sparse(phi, int(net_new.S))
            phi = self._apply_task_repairs(net_new, phi)
        if kind in ("topology", "routing"):
            with obs.span("churn.repair"):
                phi = self._repair(event, net_new, phi, kind)
        self.net = net_new
        self.cost_log.extend(self.state.costs)
        self._guard_log.extend(
            getattr(self.state, "guard_events", None) or [])
        if getattr(self.state, "guard_events", None):
            self.state.guard_events = []     # folded into _guard_log
        sigma = warm_sigma(self.state.sigma)
        with obs.span("churn.rebaseline"):
            if self.driver == "distributed" and kind != "topology":
                # rate/routing events keep the graph (self.nbrs stays the
                # memoized tiles the step was built from): swap the
                # churned net into the compiled step instead of
                # rebuilding it.  The fault rng takes the SAME
                # per-segment engine split _init_state would — the
                # rebaseline used to continue the previous segment's
                # stream, silently breaking the independent-fault-streams
                # contract on this path only
                dist.rebaseline_distributed_state(
                    self.state, net_new, phi,
                    fault_rng=(self._segment_fault_rng()
                               if self.fault_plan is not None else None))
            else:
                self._init_state(phi)         # warm re-baseline
            self.state.sigma = sigma
        self._drain_admissions()
        if self.invariant_checks:
            # post-event feasibility/loop-freedom spot check (see
            # __init__: benches disable this host sync)
            check_invariants(self.net, self.phi, self.nbrs,
                             n_loop_tasks=self.invariant_loop_tasks,
                             active=(None if self.pool is None
                                     else self.pool.active))
        rec = EventRecord(it=self.total_iters, event=event, kind=kind,
                          cost_before=cost_before,
                          cost_after=float(self.state.costs[-1]))
        self.records.append(rec)
        self._segment_open = True
        return rec

    def _repair(self, event, net_new: CECNetwork, phi: PhiSparse,
                kind: str) -> PhiSparse:
        """The iterate repaired onto `net_new` after a topology or
        routing event (`self.nbrs` and the buckets follow the new
        graph; a topology event drops the memoized SPT rows)."""
        rebuild = None
        if isinstance(event, DestRedraw):
            rebuild = np.zeros(net_new.S, bool)
            rebuild[event.task] = True
            rebuild = jnp.asarray(rebuild)
        phi, self.nbrs = refeasibilize_sparse(net_new, phi, self.nbrs,
                                              rebuild_tasks=rebuild)
        if self._active_dev is not None:
            # a whole-iterate repair may write SPT rows into a slot
            # the pool considers empty (e.g. routing churn aimed at
            # a departed task) — pin the convention back
            phi = mask_inactive_slots(phi, self._active_dev)
        if self.bucketed:
            self.buckets = build_buckets(net_new.adj)
        if kind == "topology":
            # the memoized SPT rows are adjacency-derived (see _spt_rows)
            self._spt_cache.clear()
        return phi

    def _apply_task_repairs(self, net_new: CECNetwork,
                            phi: PhiSparse) -> PhiSparse:
        """Run the per-slot φ repairs the last task event recorded on
        `self.churn` (seed an admitted slot from the memoized SPT rows,
        clear a departed one) — all eager device ops."""
        for op, slot in self.churn.last_task_repairs:
            if op == "seed":
                phi = seed_task_slot(phi, slot, self._spt_rows(net_new))
            else:
                phi = clear_task_slot(phi, slot)
        return phi

    def rebaseline_rates(self, r, task: Optional[int] = None,
                         n_iters: int = 0) -> EventRecord:
        """Warm drift rebaseline for the serving bridge: fold a windowed
        request-rate estimate into the live system as a `RateSet` event
        — the iterate is repaired and re-baselined WARM (never re-solved
        from the SPT) — then advance `n_iters` iterations toward the new
        optimum.  Returns the event's record (cost before/after the
        repair)."""
        rec = self.apply_event(RateSet(r, task=task))
        if n_iters > 0:
            self.iterate(n_iters)
        return rec

    # ------------------------------------------------------ fused stream
    def _spt_rows(self, net_new: CECNetwork):
        """Memoized `spt_result_slots` for the live graph: the rows
        depend only on (adjacency, zero-flow link weights, dest vector)
        — never on φ — and same-graph churn leaves the first two fixed,
        so the per-unique-destination Dijkstra (the dominant per-
        routing-event host cost at scale) runs once per distinct dest
        vector.  `apply_event` clears the cache on topology events.

        Under a pool the key also carries (S_cap, active-mask bytes): a
        recycled slot's rows must never warm-start from the assignment
        a PREVIOUS tenant of the slot memoized, even when the stale
        dest vector happens to coincide."""
        key = np.asarray(net_new.dest).tobytes()
        if self.pool is not None:
            key = (key, int(net_new.S), self.pool.active.tobytes())
        rows = self._spt_cache.get(key)
        if rows is None:
            rows = spt_result_slots(net_new, self.nbrs)
            self._spt_cache[key] = rows
        return rows

    def _stream_eligibility(self) -> Optional[str]:
        """None if this engine can run fused churn streams, else why
        not (the reasons are structural, fixed at __init__ time)."""
        if self.driver != "run":
            return ("driver='distributed' replays through its own "
                    "compiled shard_map step")
        if self.run_opts.get("driver") == "host":
            return ("loop_driver='host' forces the per-iteration "
                    "reference loop")
        if self.run_opts.get("callback") is not None:
            return "per-iteration callbacks need the host loop"
        return None

    def _flush_stream(self, window: list, t_prev: int) -> int:
        """Run one maximal same-graph window — gaps and events — as a
        single `FusedStream` dispatch stream with ONE host sync at the
        end, then fold the fetched per-segment records into the
        engine's bookkeeping exactly as the event loop would have.
        Returns the new `t_prev` (the last window event's iteration)."""
        if not window:
            return t_prev
        entering_costs = list(self.state.costs)
        entering_guards = list(getattr(self.state, "guard_events", None)
                               or [])
        opts = {k: v for k, v in self.run_opts.items() if k != "driver"}
        stream = FusedStream(self.net, self.state, **opts)
        pending = []
        for (t_ev, event) in window:
            stream.advance(t_ev - t_prev)
            obs.count("churn.events")
            kind = self.churn.apply(event)
            assert kind != "grow", \
                "_play_stream's pool probe must break the window " \
                "before a ladder-growing arrival"
            net_new = self.churn.network()
            repair = None
            if kind == "task":
                # per-slot repairs (seed admitted / clear departed):
                # eager .at ops, streamable like the same-graph repair
                self._refresh_active()
                repairs = self.churn.last_task_repairs
                spt = (self._spt_rows(net_new)
                       if any(op == "seed" for op, _ in repairs) else None)

                def repair(p, _ops=repairs, _spt=spt):
                    for op, slot in _ops:
                        p = (seed_task_slot(p, slot, _spt) if op == "seed"
                             else clear_task_slot(p, slot))
                    return p
            elif kind == "routing":
                rebuild = None
                if isinstance(event, DestRedraw):
                    rb = np.zeros(net_new.S, bool)
                    rb[event.task] = True
                    rebuild = jnp.asarray(rb)
                spt = self._spt_rows(net_new)
                active_dev = self._active_dev

                def repair(p, _net=net_new, _rb=rebuild, _spt=spt,
                           _act=active_dev):
                    p = refeasibilize_sparse_samegraph(
                        _net, p, self.nbrs, rebuild_tasks=_rb, spt_sp=_spt)
                    # pin inert slots the whole-iterate repair may have
                    # re-seeded (mirrors apply_event's pool path)
                    return p if _act is None else mask_inactive_slots(p, _act)
            stream.rebaseline(
                net_new, repair=repair,
                fault_rng=(self._segment_fault_rng()
                           if self.fault_plan is not None else None),
                rng=(self._segment_rng() if self._rng is not None
                     else None),
                active=self._active_dev if kind == "task" else None)
            self.net = net_new
            pending.append((event, kind))
            t_prev = t_ev
        segments = stream.finish()
        self._fold_stream(segments, pending, entering_costs,
                          entering_guards)
        self._drain_admissions()
        if self.invariant_checks:
            # deferred to the window's end: the per-event check is the
            # host sync the stream exists to avoid (the event loop still
            # checks every event)
            check_invariants(self.net, self.phi, self.nbrs,
                             n_loop_tasks=self.invariant_loop_tasks,
                             active=(None if self.pool is None
                                     else self.pool.active))
        return t_prev

    def _fold_stream(self, segments: list, pending: list,
                     entering_costs: list, entering_guards: list) -> None:
        """Mirror `iterate` + `apply_event`'s bookkeeping from the
        stream's fetched per-segment records: segment k closes with
        event k, the final segment stays open in `self.state` (the
        stream's `finish` already left the state as that segment's warm
        `RunState`)."""
        for k, (event, kind) in enumerate(pending):
            seg = segments[k]
            self.total_iters += seg["executed"]
            if self.records and self._segment_open:
                self.records[-1].segment_costs.extend(seg["accepted"])
                self.records[-1].segment_iters += seg["executed"]
            baseline = (entering_costs if k == 0
                        else [segments[k - 1]["cost_after"]])
            self.cost_log.extend(baseline + seg["accepted"])
            guards_k = seg["guard_events"]
            if k == 0:
                guards_k = entering_guards + guards_k
            self._guard_log.extend(guards_k)
            self.records.append(EventRecord(
                it=self.total_iters, event=event, kind=kind,
                cost_before=seg["cost_before"],
                cost_after=seg["cost_after"]))
            self._segment_open = True
        last = segments[-1]
        self.total_iters += last["executed"]
        if self.records and self._segment_open:
            self.records[-1].segment_costs.extend(last["accepted"])
            self.records[-1].segment_iters += last["executed"]

    def _play_stream(self, schedule: ChurnSchedule,
                     tail_iters: int) -> dict:
        """`play`'s fused-stream path: every maximal run of same-graph
        (rate/routing) events — including the warm gaps between them —
        dispatches as ONE asynchronous stream with a single host sync;
        topology events (whose `Neighbors` tiles change shape) break
        the stream and go through the ordinary `apply_event` path."""
        t_prev = 0
        window: list = []
        # grow pre-check probe: a cloned pool replays each window's
        # admissions ahead of the stream so a ladder-growing arrival
        # (S changes — shapes change — must recompile) breaks the
        # window BEFORE it is deferred behind the dispatch pipeline
        probe = self.pool.clone() if self.pool is not None else None
        for (t_ev, event) in schedule.events:
            breaks = event_kind(event) == "topology"
            if not breaks and probe is not None:
                if probe.would_grow(event):
                    breaks = True
                elif isinstance(event, TaskArrive):
                    probe.admit(event)
                elif event_kind(event) == "task":
                    probe.release(int(event.task))
            if breaks:
                t_prev = self._flush_stream(window, t_prev)
                window = []
                self.iterate(t_ev - t_prev)
                self.apply_event(event)
                t_prev = t_ev
                if probe is not None:
                    probe = self.pool.clone()   # resync after the flush
            else:
                window.append((t_ev, event))
        t_prev = self._flush_stream(window, t_prev)
        self.iterate(tail_iters)
        self._segment_open = False
        return self.history()

    # --------------------------------------------------------------- play
    def play(self, schedule: ChurnSchedule, tail_iters: int = 5,
             cold_baseline: bool = False, rel_tol: float = 0.02,
             callback: Optional[Callable] = None,
             stream: Optional[bool] = None) -> dict:
        """Replay a whole schedule: iterate to each event's firing
        iteration, apply it, continue warm; after the last event run
        `tail_iters` more.

        cold_baseline=True runs, beside every repair (topology/routing)
        event's follow-up segment, a cold SPT restart on the same
        post-event network for the same iteration budget, and records
        warm/cold iterations-to-target where the target is the better
        of the two finals × (1 + rel_tol) — the warm-start win the
        BENCH replay rows track.

        callback(record, engine), if given, fires after each event is
        applied (before its follow-up segment runs).

        stream=True folds every maximal run of SAME-GRAPH events (rate
        scaling, source/destination re-draws) into one on-device
        dispatch stream (`sgp.FusedStream`): the per-event re-baseline
        — repair, flows/T⁰, Eq. 16 constants, fault/guard re-anchoring
        — runs as eager device ops inside the pipeline, so a long churn
        burst pays ONE host sync instead of one per event.  The
        trajectory (costs, final φ, EventRecord segmentation) is
        bitwise the event loop's — the stream dispatches the same
        functions `apply_event`/`_init_state` call, deferring only the
        float() conversions — locked by tests/test_replay_stream.py.
        Per-event invariant checks are deferred to each window's end
        (they are a host sync); topology events break the stream and
        keep the ordinary path.  Incompatible with cold_baseline /
        callback / the host loop driver.  None (the default) streams
        exactly when eligible AND the per-event work is unobserved
        (invariant_checks=False, no cold baseline, no callback), so
        checking engines keep their per-event checks.
        """
        if stream is None:
            stream = (callback is None and not cold_baseline
                      and not self.invariant_checks
                      and self._stream_eligibility() is None)
        if stream:
            reason = self._stream_eligibility()
            if cold_baseline:
                reason = reason or ("cold_baseline probes re-solve per "
                                    "event on the host")
            if callback is not None:
                reason = reason or ("per-event callbacks observe records "
                                    "the stream only builds at its end")
            if reason:
                raise ValueError(f"stream=True: {reason}")
            return self._play_stream(schedule, tail_iters)
        t_prev = 0
        pending: Optional[EventRecord] = None
        for (t_ev, event) in schedule.events:
            self.iterate(t_ev - t_prev)
            self._finish_cold(pending, cold_baseline, rel_tol)
            pending = self.apply_event(event)
            if callback is not None:
                callback(pending, self)
            t_prev = t_ev
        self.iterate(tail_iters)
        self._finish_cold(pending, cold_baseline, rel_tol)
        # the schedule is over: later iterate() calls (timing probes,
        # manual driving) must not pollute the last event's segment
        self._segment_open = False
        return self.history()

    def _finish_cold(self, rec: Optional[EventRecord],
                     cold_baseline: bool, rel_tol: float) -> None:
        """After `rec`'s follow-up segment ran warm, run the cold SPT
        restart on the same network for the same budget and fill in the
        warm/cold iterations-to-target.  The cold side always uses the
        single-process driver (it is a measurement probe, not part of
        the replayed system)."""
        if rec is None or not cold_baseline or rec.kind == "rate":
            return
        n = rec.segment_iters
        if n == 0:
            return
        cold0 = spt_phi_sparse(self.net, self.nbrs)
        cold = init_run_state(self.net, cold0, min_scale=self.min_scale,
                              method="sparse", engine_impl=self.engine_impl,
                              nbrs=self.nbrs, bucketed=self.bucketed,
                              buckets=self.buckets)
        # the probe must stay invisible: no user callback firing, no
        # tol early-exit shortening its budget vs the warm segment
        probe_opts = {k: v for k, v in self.run_opts.items()
                      if k not in ("callback", "tol")}
        run_chunk(self.net, cold, n, **probe_opts)
        warm_costs = [rec.cost_after] + rec.segment_costs
        target = min(warm_costs[-1], cold.costs[-1]) * (1.0 + rel_tol)
        rec.warm_iters = iters_to_target(warm_costs, target)
        rec.cold_iters = iters_to_target(cold.costs, target)
        rec.cold_final = float(cold.costs[-1])

    def history(self) -> dict:
        return {"costs": self.costs, "final_cost": self.cost,
                "records": self.records, "n_iters": self.total_iters,
                "guard_events": self.guard_log,
                "admission_events": list(self.admission_log)}
