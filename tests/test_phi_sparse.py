"""Dense↔sparse parity harness for the edge-slot `PhiSparse` layout.

The sparse-native layout is locked to the dense `Phi` API three ways:

* conversion — `phi_to_sparse` / `sparse_to_phi` are mutually inverse
  (bitwise) wherever φ is feasible;
* trajectory — 20 SGP iterations in the native layout produce BITWISE
  the same φ and cost sequence as the dense-Phi sparse path (which
  gathers/scatters at every step boundary) on every Table II scenario;
* component — flows, marginals and the blocked-set taint agree bitwise
  per component under f32 and bf16.

Plus the slot-projection edge cases (isolated nodes, fully-blocked
rows, NaN-poisoned padding — mirroring test_edge_rounds.py's poisoning
style), the shape-capture guarantee that `method="sparse"` materializes
no [S, V, V+1] array inside the iteration loop, and the
`refeasibilize_sparse` repair contract up to the `sw_1000` node-failure
replay (slow).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend.core import ClosedJaxpr, Jaxpr

from repro import core
from repro.core.network import PhiSparse
from repro.core.sgp import _sgp_step_impl, make_consts, sgp_step

SMALL = ["connected_er", "balanced_tree", "fog", "abilene", "lhc", "geant"]
SW100 = ["sw_linear", "sw_queue"]
HUGE = ["sw_1000", "grid_1024"]

_CACHE = {}


def _setup(name):
    if name not in _CACHE:
        net = core.make_scenario(core.TABLE_II[name])
        nbrs = core.build_neighbors(net.adj)
        _CACHE[name] = (net, core.spt_phi(net), nbrs)
    return _CACHE[name]


def _bitwise(a, b, msg=""):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=msg)


# ----------------------------------------------------------------- roundtrip
@pytest.mark.parametrize("name", ["abilene", "fog"])
def test_roundtrip_exact(name):
    """phi_to_sparse ∘ sparse_to_phi is the identity (bitwise) on
    feasible φ, both from the SPT init and after real SGP iterations."""
    net, phi0, nbrs = _setup(name)
    phi10, _ = core.run(net, phi0, n_iters=10)
    for phi in (phi0, phi10):
        back = core.sparse_to_phi(core.phi_to_sparse(phi, nbrs), nbrs, net.V)
        _bitwise(back.data, phi.data)
        _bitwise(back.result, phi.result)


def test_roundtrip_exact_from_slots():
    """sparse_to_phi ∘ phi_to_sparse reproduces arbitrary slot values
    bitwise on real slots (padding comes back zeroed)."""
    net, _, nbrs = _setup("fog")
    rng = np.random.default_rng(0)
    shape = (net.S, net.V, nbrs.Dmax)
    sp = PhiSparse(jnp.asarray(rng.random(shape), jnp.float32),
                   jnp.asarray(rng.random((net.S, net.V, 1)), jnp.float32),
                   jnp.asarray(rng.random(shape), jnp.float32))
    back = core.phi_to_sparse(core.sparse_to_phi(sp, nbrs, net.V), nbrs)
    mask = np.asarray(nbrs.out_mask)[None]
    _bitwise(np.where(mask, np.asarray(back.data), 0.0),
             np.where(mask, np.asarray(sp.data), 0.0))
    _bitwise(back.local, sp.local)
    _bitwise(np.where(mask, np.asarray(back.result), 0.0),
             np.where(mask, np.asarray(sp.result), 0.0))
    # padding slots of the roundtrip are exactly zero
    _bitwise(np.where(mask, 0.0, np.asarray(back.data)), 0.0)


# ---------------------------------------------------------------- trajectory
def _assert_trajectory_bitwise(name, n_iters=20):
    """The native PhiSparse iteration and the dense-Phi sparse path
    (gather on entry, scatter on exit, every step) must produce BITWISE
    identical φ and cost trajectories — the layout change cannot move a
    single ulp."""
    net, phi0, nbrs = _setup(name)
    consts = make_consts(net, core.total_cost(net, phi0, "sparse",
                                              nbrs=nbrs))
    phi_d = phi0
    phi_s = core.phi_to_sparse(phi0, nbrs)
    costs_d, costs_s = [], []
    for _ in range(n_iters):
        phi_d, aux_d = sgp_step(net, phi_d, consts, method="sparse",
                                nbrs=nbrs)
        phi_s, aux_s = sgp_step(net, phi_s, consts, method="sparse",
                                nbrs=nbrs)
        costs_d.append(float(aux_d["cost"]))
        costs_s.append(float(aux_s["cost"]))
    np.testing.assert_array_equal(np.asarray(costs_d), np.asarray(costs_s),
                                  err_msg=f"{name}: cost trajectory")
    assert isinstance(phi_s, PhiSparse)
    back = core.sparse_to_phi(phi_s, nbrs, net.V)
    _bitwise(back.data, phi_d.data, f"{name}: phi.data after {n_iters} it")
    _bitwise(back.result, phi_d.result,
             f"{name}: phi.result after {n_iters} it")


@pytest.mark.parametrize("name", SMALL)
def test_cost_trajectory_bitwise(name):
    _assert_trajectory_bitwise(name)


@pytest.mark.slow
@pytest.mark.parametrize("name", SW100 + HUGE)
def test_cost_trajectory_bitwise_slow(name):
    _assert_trajectory_bitwise(name)


# ---------------------------------------------------- per-component parity
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("name", ["abilene", "fog"])
def test_flows_marginals_taint_parity(name, dtype):
    """Flows, marginals and blocked sets computed from the native
    layout match the dense-Phi sparse reference bitwise per component,
    at f32 and bf16."""
    from repro.core.sgp import blocked_sets_sparse
    net, phi64, nbrs = _setup(name)
    phi = core.Phi(phi64.data.astype(dtype), phi64.result.astype(dtype))
    sp = core.phi_to_sparse(phi, nbrs)
    assert sp.data.dtype == dtype

    fl_d = core.compute_flows(net, phi, "sparse", nbrs=nbrs)
    fl_s = core.compute_flows(net, sp, "sparse", nbrs=nbrs)
    for field in ("t_data", "t_result", "g", "F", "G", "f_data", "f_result"):
        _bitwise(getattr(fl_d, field), getattr(fl_s, field),
                 f"{name}/{dtype.__name__}: Flows.{field}")

    mg_d = core.compute_marginals(net, phi, fl_d, "sparse", nbrs=nbrs)
    mg_s = core.compute_marginals(net, sp, fl_s, "sparse", nbrs=nbrs)
    for field in ("rho_data", "rho_result", "delta_data", "delta_result",
                  "Dp", "Cp"):
        _bitwise(getattr(mg_d, field), getattr(mg_s, field),
                 f"{name}/{dtype.__name__}: Marginals.{field}")

    perm_dd, perm_rd = blocked_sets_sparse(net, phi, mg_d, nbrs)
    perm_ds, perm_rs = blocked_sets_sparse(net, sp, mg_s, nbrs)
    _bitwise(perm_dd, perm_ds, f"{name}: permitted data (taint)")
    _bitwise(perm_rd, perm_rs, f"{name}: permitted result (taint)")

    if dtype == jnp.float32:
        # and the slot values agree with the fully dense engine
        fl_ref = core.compute_flows(net, phi, "dense")
        for field in ("t_data", "t_result", "g", "F", "G"):
            np.testing.assert_allclose(
                np.asarray(getattr(fl_s, field)),
                np.asarray(getattr(fl_ref, field)), rtol=1e-6, atol=1e-6,
                err_msg=f"{name}: Flows.{field} vs dense")


# --------------------------------------------------- slot projection edges
def test_isolated_node_projects_to_local_only():
    """A node whose out-edges all died keeps a valid simplex row: the
    data row collapses onto the local-compute column, the result row
    (nothing permitted) projects to the all-zero row."""
    net, phi0, nbrs0 = _setup("abilene")
    node = 3
    net_f = core.fail_node(net, node)
    sp, nbrs = core.refeasibilize_sparse(
        net_f, core.phi_to_sparse(phi0, nbrs0), nbrs0)
    consts = make_consts(net_f, core.total_cost(net_f, sp, "sparse",
                                                nbrs=nbrs))
    new, _ = _sgp_step_impl(net_f, sp, consts, method="sparse", nbrs=nbrs)
    assert isinstance(new, PhiSparse)
    data = np.asarray(core.mask_slots(new.data, nbrs))
    local = np.asarray(new.local[..., 0])
    result = np.asarray(core.mask_slots(new.result, nbrs))
    # the isolated node: all data mass local, no result mass
    _bitwise(data[:, node], 0.0)
    np.testing.assert_allclose(local[:, node], 1.0, atol=1e-6)
    _bitwise(result[:, node], 0.0)
    # every data row is still on the simplex
    np.testing.assert_allclose(data.sum(-1) + local, 1.0, atol=1e-5)


def test_fully_blocked_result_rows_stay_zero():
    """Destination rows are fully blocked for result flow: the slot
    projection must return the all-zero row there (not a one-hot on a
    blocked slot), and every other row a simplex row."""
    net, phi0, nbrs = _setup("fog")
    sp = core.phi_to_sparse(phi0, nbrs)
    consts = make_consts(net, core.total_cost(net, sp, "sparse", nbrs=nbrs))
    new, _ = _sgp_step_impl(net, sp, consts, method="sparse", nbrs=nbrs)
    result = np.asarray(core.mask_slots(new.result, nbrs))
    rsum = result.sum(-1)
    dests = np.asarray(net.dest)
    for s in range(net.S):
        assert rsum[s, dests[s]] == 0.0, s
    # non-destination rows with result traffic sum to 1
    fl = core.compute_flows(net, sp, "sparse", nbrs=nbrs)
    active = np.asarray(fl.t_result) > 1e-9
    active[np.arange(net.S), dests] = False
    np.testing.assert_allclose(rsum[active], 1.0, atol=1e-5)


def test_nan_poisoned_padding_never_leaks():
    """Garbage (NaN) in PADDED slots of a PhiSparse must be inert: the
    flows, marginals and the full SGP step are finite and bitwise equal
    to the unpoisoned iterate (mirrors test_edge_rounds poisoning)."""
    net, phi0, nbrs = _setup("abilene")
    sp = core.phi_to_sparse(phi0, nbrs)
    pad = ~nbrs.out_mask[None]
    bad = PhiSparse(jnp.where(pad, jnp.nan, sp.data), sp.local,
                    jnp.where(pad, jnp.nan, sp.result))

    fl = core.compute_flows(net, sp, "sparse", nbrs=nbrs)
    fl_b = core.compute_flows(net, bad, "sparse", nbrs=nbrs)
    for field in ("t_data", "t_result", "g", "F", "G", "f_data", "f_result"):
        got = np.asarray(getattr(fl_b, field))
        assert np.isfinite(got).all(), field
        _bitwise(got, getattr(fl, field), field)

    mg = core.compute_marginals(net, sp, fl, "sparse", nbrs=nbrs)
    mg_b = core.compute_marginals(net, bad, fl_b, "sparse", nbrs=nbrs)
    for field in ("rho_data", "rho_result", "delta_data", "delta_result"):
        got = np.asarray(getattr(mg_b, field))
        assert np.isfinite(got).all(), field
        _bitwise(got, getattr(mg, field), field)

    consts = make_consts(net, core.total_cost(net, sp, "sparse", nbrs=nbrs))
    new, aux = _sgp_step_impl(net, sp, consts, method="sparse", nbrs=nbrs)
    new_b, aux_b = _sgp_step_impl(net, bad, consts, method="sparse",
                                  nbrs=nbrs)
    assert np.isfinite(float(aux_b["cost"]))
    _bitwise(aux_b["cost"], aux["cost"])
    for field in ("data", "local", "result"):
        got = np.asarray(getattr(new_b, field))
        assert np.isfinite(got).all(), field
        _bitwise(got, getattr(new, field), field)


# ------------------------------------------------------------ shape capture
def _collect_shapes(jaxpr, acc):
    """All result shapes of a (closed) jaxpr, recursing into sub-jaxprs
    (while_loop/scan/cond bodies, pjit calls)."""
    for v in jaxpr.constvars + jaxpr.invars:
        if hasattr(v.aval, "shape"):
            acc.add(tuple(v.aval.shape))
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            if hasattr(v.aval, "shape"):
                acc.add(tuple(v.aval.shape))
        for p in eqn.params.values():
            for sub in _sub_jaxprs(p):
                _collect_shapes(sub, acc)
    return acc


def _sub_jaxprs(p):
    if isinstance(p, ClosedJaxpr):
        yield p.jaxpr
    elif isinstance(p, Jaxpr):
        yield p
    elif isinstance(p, (list, tuple)):
        for q in p:
            yield from _sub_jaxprs(q)


def _assert_no_dense_phi_shapes(name):
    """Trace one full native sparse step + cost eval and assert NO
    intermediate (or input) has the dense [S, V, V+1] / [S, V, V] φ
    shape — the acceptance criterion of the sparse-native layout."""
    net, phi0, nbrs = _setup(name)
    sp = core.phi_to_sparse(phi0, nbrs)
    consts = make_consts(net, core.total_cost(net, sp, "sparse", nbrs=nbrs))
    S, V = net.S, net.V
    forbidden = {(S, V, V), (S, V, V + 1)}

    def step(net_, sp_, consts_):
        new, aux = _sgp_step_impl(net_, sp_, consts_, method="sparse",
                                  nbrs=nbrs)
        return new, aux["cost"]

    closed = jax.make_jaxpr(step)(net, sp, consts)
    shapes = _collect_shapes(closed.jaxpr, set())
    hit = shapes & forbidden
    assert not hit, f"{name}: dense Phi shapes materialized: {hit}"

    closed = jax.make_jaxpr(
        lambda n, p: core.total_cost(n, p, "sparse", nbrs=nbrs))(net, sp)
    hit = _collect_shapes(closed.jaxpr, set()) & forbidden
    assert not hit, f"{name}: total_cost materializes {hit}"


def test_sparse_step_materializes_no_dense_phi():
    _assert_no_dense_phi_shapes("abilene")


@pytest.mark.slow
def test_sparse_step_materializes_no_dense_phi_V1000():
    _assert_no_dense_phi_shapes("sw_1000")


# -------------------------------------------------------- refeasibilization
def test_refeasibilize_sparse_matches_dense():
    """Slot-level repair after a node failure matches the dense
    refeasibilize exactly (same renormalization, same broken-task SPT
    rebuild), and the repaired iterate is loop-free on the new graph."""
    net, phi0, nbrs = _setup("abilene")
    phi, _ = core.run(net, phi0, n_iters=10)
    net_f = core.fail_node(net, 3)
    want = core.refeasibilize(net_f, phi)
    got_sp, nbrs_f = core.refeasibilize_sparse(
        net_f, core.phi_to_sparse(phi, nbrs), nbrs)
    got = core.sparse_to_phi(got_sp, nbrs_f, net.V)
    _bitwise(got.data, want.data)
    _bitwise(got.result, want.result)
    assert bool(core.is_loop_free(net_f, got_sp))  # PhiSparse accepted too
    # the repaired iterate keeps descending natively
    _, h = core.run(net_f, got_sp, n_iters=5, method="sparse")
    assert h["final_cost"] <= h["costs"][0] + 1e-9


def test_refeasibilize_rejects_sparse_layout():
    net, phi0, nbrs = _setup("abilene")
    with pytest.raises(TypeError):
        core.refeasibilize(net, core.phi_to_sparse(phi0, nbrs))


@pytest.mark.slow
def test_sw1000_failure_replay():
    """Streaming-replay smoke at V=1000: optimize natively, kill the
    highest-degree node, repair in slot layout, and assert the repaired
    φ is feasible (simplex rows) and loop-free, then keeps descending —
    seeds the ROADMAP streaming/online scenario replay item."""
    net, _, nbrs = _setup("sw_1000")
    sp0 = core.spt_phi_sparse(net, nbrs)
    sp, h0 = core.run(net, sp0, n_iters=3, method="sparse")
    assert isinstance(sp, PhiSparse)
    assert h0["final_cost"] < h0["costs"][0]

    node = int(np.argmax(np.asarray(net.adj).sum(axis=1)))
    net_f = core.fail_node(net, node)
    sp_f, nbrs_f = core.refeasibilize_sparse(net_f, sp, nbrs)

    data = np.asarray(core.mask_slots(sp_f.data, nbrs_f))
    local = np.asarray(sp_f.local[..., 0])
    np.testing.assert_allclose(data.sum(-1) + local, 1.0, atol=1e-5)
    rsum = np.asarray(core.mask_slots(sp_f.result, nbrs_f)).sum(-1)
    assert np.all((np.abs(rsum - 1.0) < 1e-5) | (rsum < 1e-8))

    # loop-freedom spot-check on a task slice (boolean closure is
    # O(S·V²·log V): slice tasks, as in test_huge_scenarios_sparse_only)
    sl = slice(0, 4)
    net_sl = dataclasses.replace(
        net_f, dest=net_f.dest[sl], r=net_f.r[sl], a=net_f.a[sl],
        w=net_f.w[sl], task_type=net_f.task_type[sl])
    phi_sl = core.sparse_to_phi(
        PhiSparse(sp_f.data[sl], sp_f.local[sl], sp_f.result[sl]),
        nbrs_f, net_f.V)
    assert bool(core.is_loop_free(net_sl, phi_sl))

    # the replayed run keeps descending on the failed topology
    _, h = core.run(net_f, sp_f, n_iters=3, method="sparse")
    assert h["final_cost"] <= h["costs"][0] + 1e-9


# ------------------------------------------------------------------ drivers
def test_run_native_matches_dense_api_run():
    """core.run(method='sparse') with a PhiSparse φ⁰ returns a PhiSparse
    and walks the same cost trajectory as the dense-Phi entry point."""
    net, phi0, nbrs = _setup("abilene")
    _, h_dense_in = core.run(net, phi0, n_iters=12, method="sparse")
    sp, h_native = core.run(net, core.phi_to_sparse(phi0, nbrs),
                            n_iters=12, method="sparse")
    assert isinstance(sp, PhiSparse)
    np.testing.assert_array_equal(np.asarray(h_dense_in["costs"]),
                                  np.asarray(h_native["costs"]))


def test_run_distributed_phisparse_stays_native():
    """A PhiSparse φ⁰ goes through run_distributed without ever taking
    the dense detour: padding happens in slot layout, the result comes
    back as a PhiSparse, and the cost trajectory matches the dense-Phi
    entry point exactly (padded tasks carry zero rate either way)."""
    net, phi0, nbrs = _setup("fog")
    _, h_dense_in = core.run_distributed(net, phi0, n_iters=8,
                                         method="sparse")
    sp, h_native = core.run_distributed(net, core.phi_to_sparse(phi0, nbrs),
                                        n_iters=8, method="sparse")
    assert isinstance(sp, PhiSparse)
    assert sp.data.shape[0] == net.S
    np.testing.assert_array_equal(np.asarray(h_dense_in["costs"]),
                                  np.asarray(h_native["costs"]))


def test_phisparse_requires_sparse_method():
    net, phi0, nbrs = _setup("abilene")
    sp = core.phi_to_sparse(phi0, nbrs)
    with pytest.raises(ValueError):
        core.compute_flows(net, sp, "dense")
    with pytest.raises(ValueError):
        _sgp_step_impl(net, sp, make_consts(net, jnp.asarray(1.0)),
                       method="dense")
    with pytest.raises(ValueError):
        core.run_distributed(net, sp, n_iters=1, method="dense")
    # optimality checks convert at the boundary instead of raising
    res = core.theorem1_residual(net, sp)
    assert np.isfinite(res["theorem1"])
