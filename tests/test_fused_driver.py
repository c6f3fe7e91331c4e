"""Fused pipelined driver vs the python-loop reference oracle.

The contract under test: `run`/`run_chunk` (and the distributed
counterparts) produce BITWISE-identical trajectories under
driver="fused" and driver="host" — costs list, accept/reject sequence,
sigma safeguard, n_rejected, async rng threading, tol early exit, final
φ.  This holds by construction (both drivers dispatch the same compiled
`_sgp_block` executable — the host loop one iteration at a time — and
the block's `_accept_update_impl` select mirrors `accept_step`'s f32
arithmetic op-for-op), and these tests lock it on every Table II
scenario — including rows whose adaptive runs naturally REJECT steps —
plus a crafted instance that rejects every step and stops on the sigma
blow-up.  The fused trajectory does not depend on how the iterations
are split into block dispatches.

Also locked here: the batched recursion stacking (`_taint_pair_sparse`
/ `_max_path_len_pair_sparse` bitwise the unstacked solves), the
slot-domain `FlowsCarry` (driver-side curvature/marginals bitwise the
dense-F evaluation), and the accepted-only tol semantics (a rejected
iteration must NOT re-test the stale cost pair).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import core
from repro.core.marginals import compute_marginals
from repro.core.network import (FlowsCarry, flows_carry_and_cost,
                                _phi_edge_views)
from repro.core.sgp import (BLOCK, SUPPORT_TOL, FusedStream,
                            _max_path_len_pair_sparse,
                            _max_path_len_sparse, _sgp_propose_impl,
                            _taint_pair_sparse, _taint_sparse,
                            init_run_state, make_consts, run_chunk)

SMALL = ["connected_er", "balanced_tree", "fog", "abilene", "lhc", "geant"]
SLOW = ["sw_linear", "sw_queue", "sw_1000", "grid_1024"]

_CACHE = {}


def _setup(name):
    if name not in _CACHE:
        net = core.make_scenario(core.TABLE_II[name])
        _CACHE[name] = (net, core.spt_phi(net))
    return _CACHE[name]


def _assert_bitwise_run(name, n_iters=25, **kw):
    net, phi0 = _setup(name)
    ph, hh = core.run(net, phi0, n_iters=n_iters, method="sparse",
                      driver="host", **kw)
    pf, hf = core.run(net, phi0, n_iters=n_iters, method="sparse",
                      driver="fused", **kw)
    assert hh["costs"] == hf["costs"], name          # full trajectory
    assert hh["n_rejected"] == hf["n_rejected"], name
    for a, b in zip(jax.tree.leaves(ph), jax.tree.leaves(pf)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    return hh


@pytest.mark.parametrize("name", SMALL)
def test_fused_bitwise_table_ii(name):
    """Whole-run bitwise parity; lhc/geant/connected_er reject steps
    under adaptive scaling, so the σ×4 / σ÷1.5 safeguard threading is
    exercised through both accept AND reject branches."""
    hist = _assert_bitwise_run(name)
    if name in ("lhc", "geant", "connected_er"):
        assert hist["n_rejected"] > 0  # the reject branch really ran


@pytest.mark.slow
@pytest.mark.parametrize("name", SLOW)
def test_fused_bitwise_table_ii_slow(name):
    _assert_bitwise_run(name, n_iters=10)


def test_fused_bitwise_dense_method():
    net, phi0 = _setup("abilene")
    _, hh = core.run(net, phi0, n_iters=12, driver="host")
    _, hf = core.run(net, phi0, n_iters=12, driver="fused")
    assert hh["costs"] == hf["costs"]


def test_fused_bitwise_async_rng():
    """Theorem-2 row masks: the rng carry must advance identically
    (split + bernoulli per iteration) through both drivers."""
    net, phi0 = _setup("fog")
    kw = dict(method="sparse", rng=jax.random.PRNGKey(7), async_frac=0.3)
    _, hh = core.run(net, phi0, n_iters=15, driver="host", **kw)
    _, hf = core.run(net, phi0, n_iters=15, driver="fused", **kw)
    assert hh["costs"] == hf["costs"]


def test_fused_bitwise_paper_scaling_refresh():
    """Paper scaling refreshes the Eq. 16 consts every refresh_every
    iterations from the last accepted cost — the fused pipeline applies
    the identical jitted refresh inside the carry."""
    net, phi0 = _setup("abilene")
    kw = dict(method="sparse", scaling="paper", refresh_every=5)
    _, hh = core.run(net, phi0, n_iters=15, driver="host", **kw)
    _, hf = core.run(net, phi0, n_iters=15, driver="fused", **kw)
    assert hh["costs"] == hf["costs"]


def test_fused_bitwise_tol_exit():
    net, phi0 = _setup("abilene")
    _, hh = core.run(net, phi0, n_iters=40, method="sparse", tol=1e-3,
                     driver="host")
    _, hf = core.run(net, phi0, n_iters=40, method="sparse", tol=1e-3,
                     driver="fused")
    assert len(hh["costs"]) < 41         # the exit actually fired
    assert hh["costs"] == hf["costs"]


# ------------------------------------------------- rejection / blow-up
def _nan_state(net, tol=0.0):
    """A state whose every candidate cost is NaN: each iteration is
    rejected, sigma quadruples, and after 20 rejections (4^20 > 1e12)
    the driver stops on the sigma blow-up."""
    phi0 = core.spt_phi(net)
    st = init_run_state(net, phi0, method="sparse")
    bad = st.phi.data.at[..., 0].set(jnp.nan)
    st.phi = dataclasses.replace(st.phi, data=bad)
    st.flows = None                     # force re-evaluation of the carry
    return st


@pytest.mark.parametrize("driver", ["host", "fused"])
def test_sigma_blowup_stop(driver):
    """Crafted all-reject instance: non-finite candidate costs are never
    accepted; sigma ×4 per rejection crosses 1e12 after 20 rejections
    and the driver stops — with the iterate, costs and counters frozen
    at the pre-divergence values."""
    net, _ = _setup("abilene")
    st = run_chunk(net, _nan_state(net), 40, driver=driver)
    assert st.stopped
    assert st.n_rejected == 20
    assert st.it == 20                   # the stopping iteration counts
    assert len(st.costs) == 1            # nothing was ever accepted


def test_sigma_blowup_bitwise():
    net, _ = _setup("abilene")
    sh = run_chunk(net, _nan_state(net), 40, driver="host")
    sf = run_chunk(net, _nan_state(net), 40, driver="fused")
    assert (sh.costs, sh.sigma, sh.n_rejected, sh.it, sh.stopped) \
        == (sf.costs, sf.sigma, sf.n_rejected, sf.it, sf.stopped)


@pytest.mark.parametrize("driver", ["host", "fused"])
def test_tol_only_fires_after_accepted_step(driver):
    """Regression for the stale-pair tol exit: seed a state whose last
    two accepted costs are within tol, then reject every iteration (NaN
    candidates).  The old driver re-tested costs[-2]/costs[-1] on
    REJECTED iterations and stopped immediately; the fixed rule only
    tests after an accept, so the run must keep rejecting until the
    sigma blow-up (21 iterations), not tol-stop at iteration 1."""
    net, _ = _setup("abilene")
    st = _nan_state(net)
    st.costs = [10.0, 9.0, 8.0, 7.5, 7.5000001]   # stale pair within tol
    st = run_chunk(net, st, 40, tol=1e-3, driver=driver)
    assert st.stopped
    assert st.n_rejected == 20           # sigma blow-up, NOT a tol stop
    assert st.it == 20


# ---------------------------------------------- block-size independence
N_BLOCKED = BLOCK + 44     # past one block: chunks of BLOCK, BLOCK + 1 split


def _summary(st):
    return (st.costs, st.sigma, st.n_rejected, st.it, st.stopped)


@pytest.mark.parametrize("case,chunk", [
    ("plain", 1), ("plain", 7), ("plain", 64), ("plain", BLOCK),
    ("plain", BLOCK + 1), ("tol_exit", 5), ("sigma_blowup", 7)])
def test_block_size_independence(case, chunk):
    """One fused stream advanced in chunks of `chunk` iterations (each
    advance split into block dispatches of at most BLOCK) walks the
    trajectory of the uninterrupted fused run bitwise; a tol exit and a
    sigma blow-up that stop inside a block match the host driver."""
    if case == "plain":
        net, phi0 = _setup("geant")     # rejects steps under adaptive
        n, kw = N_BLOCKED, {}

        def fresh():
            return init_run_state(net, phi0, method="sparse")

        ref = run_chunk(net, fresh(), n, driver="fused")
    else:
        net, phi0 = _setup("abilene")
        n = 40
        kw = dict(tol=1e-3) if case == "tol_exit" else {}
        if case == "tol_exit":
            def fresh():
                return init_run_state(net, phi0, method="sparse")
        else:
            def fresh():
                return _nan_state(net)
        ref = run_chunk(net, fresh(), n, driver="host", **kw)
        assert ref.stopped and ref.it % chunk != 0   # inside a block
    st = fresh()
    stream = FusedStream(net, st, **kw)
    for start in range(0, n, chunk):
        stream.advance(min(chunk, n - start))
    stream.finish()
    assert _summary(st) == _summary(ref)
    for a, b in zip(jax.tree.leaves(st.phi), jax.tree.leaves(ref.phi)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------------------- replay
def test_zero_event_replay_fused_is_run():
    """A zero-event replay through the fused driver stays bitwise
    run(method='sparse') — the PR-4 guarantee survives the new loop."""
    net, _ = _setup("fog")
    sp0 = core.spt_phi_sparse(net)
    _, want = core.run(net, sp0, n_iters=8, method="sparse")
    eng = core.ReplayEngine(net, phi0=sp0, loop_driver="fused")
    hist = eng.play(core.ChurnSchedule((), name="empty"), tail_iters=8)
    np.testing.assert_array_equal(np.asarray(want["costs"]),
                                  np.asarray(hist["costs"]))


def test_replay_fused_matches_host_through_churn():
    """The same 3-event schedule replayed with fused and host segment
    drivers walks the identical cost trajectory (events, repairs and
    warm restarts included)."""
    net, _ = _setup("fog")
    hub = core.churn_hub(net)
    sched = core.ChurnSchedule(((2, core.RateScale(1.3)),
                                (5, core.NodeFail(hub)),
                                (8, core.NodeRecover(hub))),
                               name="mini")
    hists = {}
    for ld in ("host", "fused"):
        eng = core.ReplayEngine(net, loop_driver=ld)
        hists[ld] = eng.play(sched, tail_iters=4)
    assert hists["host"]["costs"] == hists["fused"]["costs"]


# -------------------------------------------------------- distributed
def test_distributed_fused_bitwise():
    net, phi0 = _setup("fog")
    _, hh = core.run_distributed(net, phi0, n_iters=10, method="sparse",
                                 driver="host")
    _, hf = core.run_distributed(net, phi0, n_iters=10, method="sparse",
                                 driver="fused")
    assert hh["costs"] == hf["costs"]


def test_distributed_tol_accepted_only():
    """run_distributed honors the accepted-only tol rule and stops the
    chunked driver exactly like the uninterrupted one."""
    net, phi0 = _setup("abilene")
    _, want = core.run_distributed(net, phi0, n_iters=40, method="sparse",
                                   tol=1e-3)
    assert len(want["costs"]) < 41
    st = core.init_distributed_state(net, phi0, method="sparse")
    for n in (15, 15, 10):
        core.run_distributed_chunk(st, n, tol=1e-3)
    assert st.stopped
    assert want["costs"] == st.costs


# ------------------------------------------- stacked recursion batching
@pytest.mark.parametrize("name", ["fog", "geant"])
def test_stacked_taint_bitwise(name):
    """The data+result taint recursions stacked into ONE edge_rounds
    launch are bitwise the two unstacked solves (extra rounds past a
    sub-problem's exact fixed point are no-ops)."""
    net, phi0 = _setup(name)
    nbrs = core.build_neighbors(net.adj)
    sp = core.phi_to_sparse(phi0, nbrs)
    fl = core.compute_flows(net, sp, "sparse", nbrs=nbrs)
    mg = compute_marginals(net, sp, fl, "sparse", nbrs=nbrs)
    pd, _, pr = _phi_edge_views(sp, nbrs)
    sup_d, sup_r = pd > SUPPORT_TOL, pr > SUPPORT_TOL
    td, tr = _taint_pair_sparse(sup_d, mg.rho_data, sup_r, mg.rho_result,
                                nbrs)
    np.testing.assert_array_equal(
        np.asarray(td), np.asarray(_taint_sparse(sup_d, mg.rho_data, nbrs)))
    np.testing.assert_array_equal(
        np.asarray(tr), np.asarray(_taint_sparse(sup_r, mg.rho_result,
                                                 nbrs)))


@pytest.mark.parametrize("name", ["fog", "geant"])
def test_stacked_path_len_bitwise(name):
    net, phi0 = _setup(name)
    nbrs = core.build_neighbors(net.adj)
    sp = core.phi_to_sparse(phi0, nbrs)
    pd, loc, pr = _phi_edge_views(sp, nbrs)
    sup_d = (pd > SUPPORT_TOL) & nbrs.out_mask[None]
    sup_r = (pr > SUPPORT_TOL) & nbrs.out_mask[None]
    h_r, h_d = _max_path_len_pair_sparse(sup_r, sup_d, nbrs)
    np.testing.assert_array_equal(
        np.asarray(h_r), np.asarray(_max_path_len_sparse(sup_r, nbrs)))
    np.testing.assert_array_equal(
        np.asarray(h_d), np.asarray(_max_path_len_sparse(sup_d, nbrs)))


# ------------------------------------------------- slot-domain FlowsCarry
def test_slot_carry_matches_dense_flows():
    """The driver's slot-domain flow evaluation agrees with the public
    dense-F path: traffic bitwise, the slot link-flow tile bitwise the
    gather of dense F, and the cost to reduction-order rounding."""
    net, phi0 = _setup("fog")
    nbrs = core.build_neighbors(net.adj)
    sp = core.phi_to_sparse(phi0, nbrs)
    carry, cost = flows_carry_and_cost(net, sp, "sparse", nbrs=nbrs)
    fl = core.compute_flows(net, sp, "sparse", nbrs=nbrs)
    np.testing.assert_array_equal(np.asarray(carry.t_data),
                                  np.asarray(fl.t_data))
    np.testing.assert_array_equal(np.asarray(carry.t_result),
                                  np.asarray(fl.t_result))
    np.testing.assert_array_equal(np.asarray(carry.F),
                                  np.asarray(core.gather_edges(fl.F, nbrs)))
    want = float(core.cost_of_flows(net, fl))
    assert abs(float(cost) - want) <= 1e-6 * abs(want)


def test_slot_carry_propose_bitwise_dense_carry():
    """_sgp_propose_impl(slot_F=True) on the slot carry produces the
    bitwise-same candidate as the dense-F carry (per-slot curvature and
    D' evaluations are the gathered dense evaluations)."""
    net, phi0 = _setup("fog")
    nbrs = core.build_neighbors(net.adj)
    sp = core.phi_to_sparse(phi0, nbrs)
    carry, _ = flows_carry_and_cost(net, sp, "sparse", nbrs=nbrs)
    fl = core.compute_flows(net, sp, "sparse", nbrs=nbrs)
    dense_carry = FlowsCarry(fl.t_data, fl.t_result, fl.F, fl.G)
    consts = make_consts(net, core.total_cost(net, sp, "sparse", nbrs=nbrs))
    kw = dict(method="sparse", nbrs=nbrs, sigma=jnp.float32(1.0), kappa=0.0)
    p_slot, _ = _sgp_propose_impl(net, sp, carry, consts, slot_F=True, **kw)
    p_dense, _ = _sgp_propose_impl(net, sp, dense_carry, consts,
                                   slot_F=False, **kw)
    for a, b in zip(jax.tree.leaves(p_slot), jax.tree.leaves(p_dense)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
