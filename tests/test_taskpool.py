"""Dynamic task-slot pool: admission control, arrival/departure churn,
and the compilation contract.

The load-bearing guarantees:

* a FULLY-ACTIVE pool (S_cap == n_tasks) is bitwise the fixed-S
  engine — `active_for_engine()` is None, so the same program compiles;
* INACTIVE slots are inert: exactly zero rate/flow/cost contribution,
  φ rows bitwise frozen by the masked step;
* a `TaskArrive` at constant S_cap triggers ZERO new jit compilations
  (value-only update, locked via the jit cache counters);
* `play(stream=True)` on a task-churn schedule is bitwise the event
  loop (the admission ledger matches too, modulo the stream's
  window-end iteration stamps);
* pool exhaustion degrades gracefully per AdmissionPolicy
  (reject | queue | grow) with a structured `AdmissionEvent` log.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro import core
from repro.core.network import flows_carry_and_cost_jit
from repro.core.replay import check_feasible
from repro.core.sgp import _sgp_block


def _setup(name="sw_queue"):
    jax.config.update("jax_enable_x64", False)
    return core.make_scenario(core.TABLE_II[name])


def _arrival(net, seed=0, scale=0.5):
    rng = np.random.RandomState(seed)
    r = np.zeros(int(net.V))
    r[rng.choice(int(net.V), 2, replace=False)] = scale
    return core.TaskArrive(r=r, dest=int(rng.randint(int(net.V))),
                           a=0.6, w=1.0, task_type=0)


# ---------------------------------------------------------------- unit
class TestTaskPoolUnit:
    def test_capacity_ladder_and_defaults(self):
        pool = core.TaskPool(5)
        assert pool.S_cap == 8 and pool.n_active == 5
        assert pool.ever_padded and pool.free_slot() == 5
        assert core.TaskPool(8).S_cap == 8          # already on a rung
        assert not core.TaskPool(8).ever_padded
        assert core.next_pow2(1) == 1
        assert core.next_pow2(65) == 128

    def test_validation(self):
        with pytest.raises(ValueError):
            core.TaskPool(5, S_cap=4)
        with pytest.raises(ValueError):
            core.TaskPool(5, policy="drop")
        pool = core.TaskPool(4, S_cap=8)
        with pytest.raises(ValueError):
            pool.release(6)                         # already inactive

    def test_admit_release_recycle(self):
        pool = core.TaskPool(3, S_cap=4)
        assert pool.admit(object()) == ("admit", 3)
        assert pool.free_slot() is None
        assert [e.action for e in pool.drain_log()] == ["admit"]
        action, slot, dequeued = pool.release(1)
        assert (action, slot, dequeued) == ("release", 1, None)
        assert pool.free_slot() == 1                # lowest free recycled
        assert pool.drain_log() == []               # plain release unlogged

    def test_policies_on_exhaustion(self):
        ev = object()
        reject = core.TaskPool(4, S_cap=4, policy="reject")
        assert reject.admit(ev) == ("reject", -1)
        queue = core.TaskPool(4, S_cap=4, policy="queue")
        assert queue.admit(ev) == ("queue", -1)
        action, slot, dequeued = queue.release(2)
        assert (action, slot, dequeued) == ("dequeue", 2, ev)
        grow = core.TaskPool(4, S_cap=4, policy="grow")
        assert not grow.ever_padded
        assert grow.admit(ev) == ("grow", 4)
        assert grow.S_cap == 8 and grow.ever_padded

    def test_clone_is_independent(self):
        pool = core.TaskPool(3, S_cap=4, policy="queue")
        c = pool.clone()
        c.admit(object())
        assert pool.free_slot() == 3 and c.free_slot() is None


# ------------------------------------------------------- engine parity
class TestFullyActiveParity:
    @pytest.mark.parametrize("name", ["fog", "abilene"])
    def test_bitwise_fixed_s(self, name):
        """S_cap == n_tasks: the pooled engine runs the identical
        program (active mask is None) — costs bitwise."""
        net = _setup(name)
        pool = core.TaskPool(int(net.S), S_cap=int(net.S))
        assert pool.active_for_engine() is None
        sched = core.ChurnSchedule((
            (2, core.RateScale(1.2)),
            (5, core.SourceRedraw(1, seed=5)),
        ), name="parity")
        h0 = core.ReplayEngine(net).play(sched)
        h1 = core.ReplayEngine(net, pool=pool).play(sched)
        assert h0["costs"] == h1["costs"]
        assert h0["final_cost"] == h1["final_cost"]

    @pytest.mark.slow
    def test_bitwise_fixed_s_table2(self):
        for name in ("connected_er", "balanced_tree", "lhc", "geant",
                     "sw_queue"):
            net = _setup(name)
            pool = core.TaskPool(int(net.S), S_cap=int(net.S))
            sched = core.ChurnSchedule(((2, core.RateScale(1.1)),),
                                       name="parity")
            h0 = core.ReplayEngine(net).play(sched, tail_iters=3)
            h1 = core.ReplayEngine(net, pool=pool).play(sched,
                                                        tail_iters=3)
            assert h0["costs"] == h1["costs"], name


class TestInertSlots:
    def test_inactive_rows_frozen_and_flowless(self):
        """Inactive φ rows are bitwise frozen across warm iterations
        and carry exactly zero flow; the padded cost matches the
        compact engine's."""
        base = _setup("fog")
        S, free = int(base.S), 3
        net = core.pad_tasks(base, S + free)         # 3 inert slots
        pool = core.TaskPool(S, S_cap=S + free)
        eng = core.ReplayEngine(net, pool=pool)
        phi0 = np.asarray(eng.phi.data)[S:].copy()
        eng.iterate(8)
        assert (np.asarray(eng.phi.data)[S:] == phi0).all()
        assert (np.asarray(eng.phi.local)[S:] == 1.0).all()
        assert (np.asarray(eng.phi.result)[S:] == 0.0).all()
        fl = core.compute_flows(net, eng.phi, method="sparse",
                                nbrs=eng.nbrs)
        assert (np.asarray(fl.t_data)[S:] == 0.0).all()
        assert (np.asarray(fl.t_result)[S:] == 0.0).all()
        assert (np.asarray(fl.g)[S:] == 0.0).all()
        # same trajectory cost as the compact fixed-S engine
        eng_c = core.ReplayEngine(base)
        eng_c.iterate(8)
        np.testing.assert_allclose(eng.cost, eng_c.cost, rtol=1e-5)

    def test_marginals_masked(self):
        base = _setup("fog")
        S = int(base.S)
        net = core.pad_tasks(base, S + 2)
        eng = core.ReplayEngine(net, pool=core.TaskPool(S, S_cap=S + 2))
        fl = core.compute_flows(net, eng.phi, method="sparse",
                                nbrs=eng.nbrs)
        active = np.zeros(S + 2, bool)
        active[:S] = True
        mg = core.compute_marginals(net, eng.phi, fl, method="sparse",
                                    nbrs=eng.nbrs,
                                    active=np.asarray(active))
        assert (np.asarray(mg.rho_data)[S:] == 0.0).all()
        assert (np.asarray(mg.rho_result)[S:] == 0.0).all()

    def test_zero_active_tasks(self):
        """An all-inactive pool runs without crashing at zero cost."""
        base = _setup("fog")
        net = core.pad_tasks(base, int(base.S), n_active=0)
        pool = core.TaskPool(1, S_cap=int(base.S))
        pool.release(0)
        eng = core.ReplayEngine(net, pool=pool)
        eng.iterate(3)
        assert eng.cost == 0.0


# -------------------------------------------------- churn through the engine
class TestTaskChurn:
    def test_arrival_zero_new_compilations(self):
        """A TaskArrive at constant S_cap is a value-only update: the
        jit caches gain no entries."""
        net, pool = core.taskchurn_scenario("fog", free=2)
        eng = core.ReplayEngine(net, pool=pool)
        eng.iterate(4)
        eng.apply_event(_arrival(net, seed=0))
        eng.iterate(4)                               # caches fully warm
        n_step = _sgp_block._cache_size()
        n_flows = flows_carry_and_cost_jit._cache_size()
        eng.apply_event(_arrival(net, seed=1))
        eng.iterate(4)
        assert _sgp_block._cache_size() == n_step
        assert flows_carry_and_cost_jit._cache_size() == n_flows

    def test_arrival_departure_loop(self):
        net, pool = core.taskchurn_scenario("fog", free=1)
        eng = core.ReplayEngine(net, pool=pool)
        S_act = pool.n_active
        rec = eng.apply_event(_arrival(net, seed=0))
        assert rec.kind == "task" and eng.pool.n_active == S_act + 1
        eng.iterate(4)
        eng.apply_event(core.TaskDepart(0))
        assert eng.pool.n_active == S_act
        eng.iterate(4)
        # departed slot back to inert; arrival recycles it
        assert (np.asarray(eng.phi.local)[0] == 1.0).all()
        eng.apply_event(_arrival(net, seed=2))
        assert eng.pool.free_slot() is None
        check_feasible(eng.phi, eng.nbrs, dest=eng.net.dest,
                       active=eng.pool.active)

    def test_exhaustion_policies_through_engine(self):
        for policy, want_S, want_log in (
                ("reject", None, ["admit", "reject"]),
                ("queue", None, ["admit", "queue", "dequeue"]),
                ("grow", "next_rung", ["admit", "grow"])):
            net, pool = core.taskchurn_scenario("fog", free=1,
                                                policy=policy)
            S_cap = int(net.S)
            eng = core.ReplayEngine(net, pool=pool)
            eng.apply_event(_arrival(net, seed=0))   # fills the pool
            eng.apply_event(_arrival(net, seed=1))   # exhausted
            if policy == "queue":
                eng.apply_event(core.TaskDepart(0))  # dequeues into 0
            eng.iterate(3)
            got = [e.action for e in eng.admission_log]
            assert got == want_log, policy
            if want_S == "next_rung":
                assert int(eng.net.S) == core.next_pow2(S_cap + 1)
                assert np.isfinite(eng.cost)
            else:
                assert int(eng.net.S) == S_cap

    def test_task_event_without_pool_raises(self):
        net = _setup("fog")
        with pytest.raises(ValueError):
            core.ChurnState(net).apply(_arrival(net))
        eng = core.ReplayEngine(net)
        with pytest.raises(ValueError):
            eng.apply_event(_arrival(net))

    def test_pool_requires_run_driver(self):
        net, pool = core.taskchurn_scenario("fog", free=1)
        with pytest.raises(ValueError):
            core.ReplayEngine(net, pool=pool, driver="distributed")

    def test_pool_shape_mismatch_raises(self):
        net = _setup("fog")
        with pytest.raises(ValueError):
            core.ReplayEngine(net, pool=core.TaskPool(int(net.S) + 4))


class TestStreamParity:
    @pytest.mark.parametrize("name", ["fog", "sw_queue"])
    def test_canned_taskchurn_bitwise(self, name):
        """stream=True on the canned task-churn schedule is bitwise the
        event loop; the admission ledger matches modulo the stream's
        window-end iteration stamps."""
        net, pool = core.taskchurn_scenario(name, free=4, policy="queue")
        sched = core.churn_schedule(f"{name}_taskchurn", net)
        h0 = core.ReplayEngine(net, pool=pool.clone()).play(sched)
        h1 = core.ReplayEngine(net, pool=pool.clone()).play(sched,
                                                           stream=True)
        assert h0["costs"] == h1["costs"]
        assert h0["final_cost"] == h1["final_cost"]
        a0 = [dataclasses.replace(e, it=-1)
              for e in h0["admission_events"]]
        a1 = [dataclasses.replace(e, it=-1)
              for e in h1["admission_events"]]
        assert a0 == a1 and len(a0) > 0

    def test_grow_breaks_stream_window(self):
        """A growing admission recompiles, so the stream must fall back
        to the event loop for that event — still bitwise overall."""
        net, pool = core.taskchurn_scenario("fog", free=1, policy="grow")
        events = ((2, _arrival(net, seed=0)),       # fills the pool
                  (4, _arrival(net, seed=1)),       # grow: window break
                  (6, core.RateScale(1.1)))
        sched = core.ChurnSchedule(events, name="grow_break")
        h0 = core.ReplayEngine(net, pool=pool.clone()).play(sched)
        h1 = core.ReplayEngine(net, pool=pool.clone()).play(sched,
                                                           stream=True)
        assert h0["costs"] == h1["costs"]
        assert [e.action for e in h1["admission_events"]] == \
               ["admit", "grow"]


# ------------------------------------------------------------ plumbing
class TestPlumbing:
    def test_random_schedule_with_pool(self):
        net, pool = core.taskchurn_scenario("fog", free=2,
                                            policy="queue")
        sched = core.random_schedule(net, n_events=12, seed=3,
                                     pool=pool)
        kinds = {type(ev).__name__ for _, ev in sched.events}
        assert kinds & {"TaskArrive", "TaskDepart"}
        h = core.ReplayEngine(net, pool=pool.clone()).play(sched)
        assert np.isfinite(h["final_cost"])

    def test_check_feasible_active_negative(self):
        net, pool = core.taskchurn_scenario("fog", free=2)
        eng = core.ReplayEngine(net, pool=pool)
        check_feasible(eng.phi, eng.nbrs, active=pool.active)
        slot = pool.free_slot()
        bad = dataclasses.replace(
            eng.phi, local=eng.phi.local.at[slot].set(0.7))
        with pytest.raises(AssertionError):
            check_feasible(bad, eng.nbrs, active=pool.active)

    def test_fleet_cache_key_includes_mask(self):
        net, pool = core.taskchurn_scenario("fog", free=2)
        k_fixed = core.fleet_cache_key(net)
        k_pool = core.fleet_cache_key(net, active=pool.active)
        other = pool.active.copy()
        other[-1] = True
        assert k_fixed != k_pool
        assert k_pool != core.fleet_cache_key(net, active=other)

    def test_pad_phi_sparse_contract(self):
        net = _setup("fog")
        phi = core.spt_phi_sparse(net)
        S = int(net.S)
        padded = core.pad_phi_sparse(phi, S + 3)
        assert padded.data.shape[0] == S + 3
        assert (np.asarray(padded.data)[S:] == 0.0).all()
        assert (np.asarray(padded.local)[S:] == 1.0).all()
        assert core.pad_phi_sparse(phi, S) is phi
        with pytest.raises(ValueError):
            core.pad_phi_sparse(phi, S - 1)

    def test_taskchurn_scenario_validation(self):
        with pytest.raises(ValueError):
            core.taskchurn_scenario("fog", free=0)
