"""repro.obs: host spans and counters, off by default, and the driver's
and seeding's spans and counters on a real solve."""
import math
import time

import jax
import numpy as np
import pytest

from repro import core, obs
from repro.core.sgp import BLOCK


@pytest.fixture(autouse=True)
def _obs_off():
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()


def _spin(seconds):
    end = time.perf_counter() + seconds
    while time.perf_counter() < end:
        pass


def test_off_records_nothing():
    assert obs.span("a") is obs.span("b") is obs._OFF
    with obs.span("a"):
        obs.count("c", 3)
    assert obs.snapshot() == {"spans": {}, "counters": {}}


def test_nested_spans_and_counters():
    obs.enable()
    with obs.span("outer"):
        _spin(0.002)
        for _ in range(3):
            with obs.span("inner"):
                _spin(0.001)
                obs.count("n")
        obs.count("n", 4)
    with obs.span("outer"):
        pass
    obs.disable()
    with obs.span("outer"):
        obs.count("n")
    snap = obs.snapshot()
    outer, inner = snap["spans"]["outer"], snap["spans"]["inner"]
    assert snap["counters"] == {"n": 7}
    assert outer["calls"] == 2 and inner["calls"] == 3
    assert inner["self_s"] == inner["total_s"] >= 0.003
    assert outer["total_s"] >= inner["total_s"] + 0.002
    assert outer["self_s"] == pytest.approx(
        outer["total_s"] - inner["total_s"], abs=1e-12)
    obs.reset()
    assert obs.snapshot() == {"spans": {}, "counters": {}}


def _geant():
    net = core.make_scenario(core.TABLE_II["geant"])
    return net, core.spt_phi_sparse(net, core.build_neighbors(net.adj))


def test_run_spans_counters_and_bitwise_result():
    net, phi0 = _geant()
    phi_off, hist_off = core.run(net, phi0, n_iters=5, method="sparse")
    obs.enable()
    phi_on, hist_on = core.run(net, phi0, n_iters=5, method="sparse")
    obs.disable()
    snap = obs.snapshot()
    calls = {k: v["calls"] for k, v in snap["spans"].items()}
    assert calls == {"sgp.run": 1, "sgp.init": 1, "seed.neighbors": 1,
                     "sgp.init.flows": 1, "sgp.init.sync": 1,
                     "sgp.init.consts": 1, "sgp.open": 1, "sgp.advance": 1,
                     "sgp.block": 1, "sgp.finish": 1}
    # the adjacency pull of build_neighbors, float(T0), finish's device_get
    assert snap["counters"] == {"sgp.iterations": 5, "sgp.blocks": 1,
                                "host_syncs": 3}
    spans = snap["spans"]
    inside_run = sum(spans[k]["total_s"] for k in
                     ("sgp.init", "sgp.open", "sgp.advance", "sgp.finish"))
    assert spans["sgp.run"]["self_s"] == pytest.approx(
        spans["sgp.run"]["total_s"] - inside_run, abs=1e-12)
    assert spans["sgp.advance"]["self_s"] == pytest.approx(
        spans["sgp.advance"]["total_s"] - spans["sgp.block"]["total_s"],
        abs=1e-12)
    assert hist_on["costs"] == hist_off["costs"]
    for a, b in zip(jax.tree.leaves(phi_on), jax.tree.leaves(phi_off)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("n_iters", [3, 200])
def test_cold_solve_host_syncs(n_iters):
    """Seeding and solve as one cold solve: 9 device-to-host fetches,
    and one block dispatch per BLOCK iterations."""
    net, _ = _geant()
    obs.enable()
    nbrs = core.build_neighbors(net.adj)        # adjacency
    # next hops: adjacency, link weights, destinations; slots: out_nbr,
    # out_mask
    phi0 = core.spt_phi_sparse(net, nbrs)
    seeded = obs.snapshot()
    core.run(net, phi0, n_iters=n_iters, method="sparse")
    obs.disable()
    snap = obs.snapshot()
    assert seeded["counters"] == {"host_syncs": 6}
    assert {k: v["calls"] for k, v in seeded["spans"].items()} == {
        "seed.neighbors": 1, "seed.next_hops": 1}
    assert snap["counters"]["host_syncs"] == 9
    assert snap["counters"]["sgp.blocks"] == math.ceil(n_iters / BLOCK)
    assert snap["spans"]["sgp.block"]["calls"] == math.ceil(n_iters / BLOCK)


@pytest.mark.parametrize("arm", ["guards", "faults"])
def test_per_iteration_path_spans(arm):
    """Guards feed the carry per iteration, alone or with a fault plan
    riding it: one block dispatch of one iteration (`sgp.step`) and one
    guarded select (`sgp.accept`) an iteration, and still one fetch at
    the end."""
    net, phi0 = _geant()
    kw = {"guards": core.GuardConfig()}
    if arm == "faults":
        kw["fault_plan"] = core.FaultPlan(participation_p=0.9)
    obs.enable()
    core.run(net, phi0, n_iters=5, method="sparse", **kw)
    obs.disable()
    snap = obs.snapshot()
    spans = snap["spans"]
    assert spans["sgp.step"]["calls"] == 5
    assert spans["sgp.accept"]["calls"] == 5
    assert "sgp.block" not in spans
    assert snap["counters"]["sgp.blocks"] == 5
    assert snap["counters"]["sgp.iterations"] == 5
    assert spans["sgp.advance"]["self_s"] == pytest.approx(
        spans["sgp.advance"]["total_s"] - spans["sgp.step"]["total_s"]
        - spans["sgp.accept"]["total_s"], abs=1e-12)
    assert spans["sgp.finish"]["calls"] == 1


@pytest.mark.parametrize("arm", ["faults", "async"])
def test_block_path_arms(arm):
    """A fault plan and the Theorem-2 async masks ride the block's
    carry: one block dispatch per BLOCK iterations, no per-iteration
    spans, one fetch at the end."""
    net, phi0 = _geant()
    kw = ({"fault_plan": core.FaultPlan(participation_p=0.9)}
          if arm == "faults"
          else {"rng": jax.random.PRNGKey(3), "async_frac": 0.3})
    n_iters = 5
    obs.enable()
    core.run(net, phi0, n_iters=n_iters, method="sparse", **kw)
    obs.disable()
    snap = obs.snapshot()
    spans = snap["spans"]
    assert "sgp.step" not in spans and "sgp.accept" not in spans
    assert snap["counters"]["sgp.blocks"] == math.ceil(n_iters / BLOCK)
    assert spans["sgp.block"]["calls"] == math.ceil(n_iters / BLOCK)
    assert snap["counters"]["sgp.iterations"] == n_iters
    assert spans["sgp.finish"]["calls"] == 1


def test_host_driver_counts_each_cost_fetch():
    net, phi0 = _geant()
    obs.enable()
    core.run(net, phi0, n_iters=4, method="sparse", driver="host")
    obs.disable()
    snap = obs.snapshot()
    # build_neighbors' adjacency, float(T0), one float(cost) an iteration
    assert snap["counters"] == {"sgp.iterations": 4, "sgp.blocks": 4,
                                "host_syncs": 6}
    assert "sgp.advance" not in snap["spans"]


def test_churn_spans_and_counters():
    """`ReplayEngine.apply_event` is one `churn.apply` span holding the
    repair (`churn.repair`, topology and routing events only) and the
    warm re-baseline (`churn.rebaseline`, every event), counted in
    `churn.events` and `churn.topology_events`; tracing changes no
    value the engine computes."""
    net, phi0 = _geant()
    hub = core.hub_node(net)
    u, v = (int(x) for x in np.argwhere(np.asarray(net.adj))[0])
    events = [core.RateScale(1.2), core.NodeFail(hub), core.LinkCut(u, v),
              core.NodeRecover(hub), core.SourceRedraw(3, seed=5),
              core.LinkRestore(u, v)]
    engines = []
    for on in (False, True):
        eng = core.ReplayEngine(net, phi0=phi0, invariant_checks=False)
        eng.iterate(2)
        if on:
            obs.enable()
        for ev in events:
            eng.apply_event(ev)
            eng.iterate(2)
        obs.disable()
        engines.append(eng)
    snap = obs.snapshot()
    spans = snap["spans"]
    assert spans["churn.apply"]["calls"] == 6
    assert spans["churn.repair"]["calls"] == 5
    assert spans["churn.rebaseline"]["calls"] == 6
    assert spans["sgp.init"]["calls"] == 6
    assert snap["counters"]["churn.events"] == 6
    assert snap["counters"]["churn.topology_events"] == 4
    inside = spans["churn.repair"]["total_s"] + \
        spans["churn.rebaseline"]["total_s"]
    assert spans["churn.apply"]["total_s"] >= inside
    assert spans["churn.rebaseline"]["self_s"] == pytest.approx(
        spans["churn.rebaseline"]["total_s"] - spans["sgp.init"]["total_s"],
        abs=1e-12)
    off, on = engines
    assert off.costs == on.costs
    for a, b in zip(jax.tree.leaves(off.phi), jax.tree.leaves(on.phi)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
