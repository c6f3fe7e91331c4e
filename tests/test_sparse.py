"""Sparse neighbor-list engine vs the dense reference, the
fully-blocked-row projection contract, and the run() callback protocol.

Tier-1 covers representative Table II scenarios; the `slow` suite
sweeps every row including the V ~ 10³ additions.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import core
from repro.core.sgp import _sgp_step_impl, make_consts, project_rows
from repro.kernels import ops

# Table II rows by weight: dense-vs-sparse sweeps run on the small ones
SMALL = ["connected_er", "balanced_tree", "fog", "abilene", "lhc", "geant"]
SW100 = ["sw_linear", "sw_queue"]
HUGE = ["sw_1000", "grid_1024"]

_CACHE = {}


def _setup(name):
    if name not in _CACHE:
        net = core.make_scenario(core.TABLE_II[name])
        _CACHE[name] = (net, core.spt_phi(net), core.build_neighbors(net.adj))
    return _CACHE[name]


def _assert_flows_marginals_match(name, rtol=1e-6, atol=1e-6):
    net, phi, nbrs = _setup(name)
    fl_d = core.compute_flows(net, phi, "dense")
    fl_s = core.compute_flows(net, phi, "sparse", nbrs=nbrs)
    for field in ("t_data", "t_result", "g", "F", "G"):
        np.testing.assert_allclose(
            np.asarray(getattr(fl_d, field)),
            np.asarray(getattr(fl_s, field)), rtol=rtol, atol=atol,
            err_msg=f"{name}: Flows.{field}")
    mg_d = core.compute_marginals(net, phi, fl_d, "dense")
    mg_s = core.compute_marginals(net, phi, fl_s, "sparse", nbrs=nbrs)
    np.testing.assert_allclose(np.asarray(mg_d.rho_data),
                               np.asarray(mg_s.rho_data),
                               rtol=rtol, atol=atol)
    np.testing.assert_allclose(np.asarray(mg_d.rho_result),
                               np.asarray(mg_s.rho_result),
                               rtol=rtol, atol=atol)
    # sparse δ (edge-slot layout) == dense δ gathered onto the edges
    mask = np.asarray(nbrs.out_mask)[None]
    for d_dense, d_sp in ((mg_d.delta_result, mg_s.delta_result),
                          (mg_d.delta_data[..., :-1],
                           mg_s.delta_data[..., :-1])):
        gathered = np.asarray(core.gather_edges(d_dense, nbrs))
        diff = np.where(mask, gathered - np.asarray(d_sp), 0.0)
        np.testing.assert_allclose(diff, 0.0, atol=atol)
    np.testing.assert_allclose(np.asarray(mg_d.delta_data[..., -1]),
                               np.asarray(mg_s.delta_data[..., -1]),
                               rtol=rtol, atol=atol)


@pytest.mark.parametrize("name", ["abilene", "fog"])
def test_sparse_flows_marginals_match_dense(name):
    _assert_flows_marginals_match(name)


@pytest.mark.slow
@pytest.mark.parametrize(
    "name", [n for n in SMALL if n not in ("abilene", "fog")]
    + SW100 + HUGE)
def test_sparse_flows_marginals_match_dense_slow(name):
    _assert_flows_marginals_match(name, rtol=1e-5, atol=1e-4)


def _assert_step_matches(name, rtol=1e-6):
    net, phi, nbrs = _setup(name)
    consts = make_consts(net, core.total_cost(net, phi))
    phi_d, aux_d = _sgp_step_impl(net, phi, consts)
    phi_s, aux_s = _sgp_step_impl(net, phi, consts, method="sparse",
                                  nbrs=nbrs)
    np.testing.assert_allclose(np.asarray(phi_d.data),
                               np.asarray(phi_s.data), atol=1e-6)
    np.testing.assert_allclose(np.asarray(phi_d.result),
                               np.asarray(phi_s.result), atol=1e-6)
    c_d = float(core.total_cost(net, phi_d))
    c_s = float(core.total_cost(net, phi_s))
    assert abs(c_d - c_s) <= rtol * abs(c_d), (name, c_d, c_s)
    assert abs(float(aux_d["cost"]) - float(aux_s["cost"])) \
        <= rtol * abs(float(aux_d["cost"]))


@pytest.mark.parametrize("name", ["abilene"])
def test_sparse_step_matches_dense(name):
    _assert_step_matches(name)


@pytest.mark.slow
@pytest.mark.parametrize("name",
                         [n for n in SMALL if n != "abilene"] + SW100)
def test_sparse_step_matches_dense_slow(name):
    _assert_step_matches(name)


def _assert_run_converges(name, n_iters=60, rtol=1e-4):
    net, phi0, _ = _setup(name)
    _, h_d = core.run(net, phi0, n_iters=n_iters)
    _, h_s = core.run(net, phi0, n_iters=n_iters, method="sparse")
    assert abs(h_d["final_cost"] - h_s["final_cost"]) \
        <= rtol * h_d["final_cost"], (name, h_d["final_cost"],
                                      h_s["final_cost"])


def test_sparse_run_converges_like_dense():
    _assert_run_converges("abilene")


@pytest.mark.slow
@pytest.mark.parametrize("name",
                         [n for n in SMALL if n != "abilene"] + SW100)
def test_sparse_run_converges_like_dense_slow(name):
    _assert_run_converges(name)


def test_sparse_run_stays_loop_free():
    net, phi0, _ = _setup("abilene")
    phi, hist = core.run(net, phi0, n_iters=50, method="sparse")
    assert bool(core.is_loop_free(net, phi))
    assert hist["final_cost"] <= hist["costs"][0] + 1e-9


@pytest.mark.slow
@pytest.mark.parametrize("name", HUGE)
def test_huge_scenarios_sparse_only(name):
    """V ~ 10³ rows: the sparse engine descends where dense is
    impractical; loop-freedom spot-checked on a task slice."""
    import dataclasses
    net, phi0, _ = _setup(name)
    assert net.V >= 1000
    phi, hist = core.run(net, phi0, n_iters=10, method="sparse")
    assert hist["final_cost"] < hist["costs"][0]
    sl = slice(0, 4)  # boolean-closure check is O(S V² log V): slice tasks
    net_sl = dataclasses.replace(
        net, dest=net.dest[sl], r=net.r[sl], a=net.a[sl], w=net.w[sl],
        task_type=net.task_type[sl])
    assert bool(core.is_loop_free(
        net_sl, core.Phi(phi.data[sl], phi.result[sl])))


def test_broadcast_early_exit_matches_dense_and_differentiates():
    """The broadcast engine's early-exit fixed point must stay
    numerically identical to the dense solve AND reverse-mode
    differentiable (the while-loop alone is not: the adjoint comes from
    the implicit function theorem in network._solve_fp_broadcast)."""
    net, phi, _ = _setup("abilene")
    c_b = float(core.total_cost(net, phi, "broadcast"))
    c_d = float(core.total_cost(net, phi, "dense"))
    assert abs(c_b - c_d) <= 1e-6 * abs(c_d)

    def cost(method):
        return lambda p: core.total_cost(net, p, method)

    g_b = jax.grad(cost("broadcast"))(phi)
    g_d = jax.grad(cost("dense"))(phi)
    np.testing.assert_allclose(np.asarray(g_b.data), np.asarray(g_d.data),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(g_b.result),
                               np.asarray(g_d.result),
                               rtol=1e-4, atol=1e-5)


# ------------------------------------------------------------- distributed
@pytest.mark.parametrize("name", ["abilene", "fog"])
def test_distributed_sparse_step_matches_single_device(name):
    """make_distributed_step(method="sparse", nbrs=...) shard_maps the
    neighbor-list engine over the task axis (replicated index tiles,
    one psum of F/G) in the edge-slot PhiSparse layout: one step matches
    the single-device native step up to psum reduction order and
    compilation rounding (the shard_mapped step is jitted while the
    reference here runs eagerly; XLA may contract the projection's
    multiply-subtract into an FMA only in the former, so rows agree to
    float32 ulps, not bitwise — the DRIVER-level bitwise locks live in
    tests/test_fused_driver.py, where both sides share one compiled
    executable)."""
    from repro.core.distributed import (make_distributed_step, pad_tasks,
                                        task_mesh)
    net, phi, nbrs = _setup(name)
    mesh = task_mesh()
    consts = make_consts(net, core.total_cost(net, phi, "sparse",
                                              nbrs=nbrs))
    step = make_distributed_step(mesh, method="sparse", nbrs=nbrs)
    net_p, phi_p, S = pad_tasks(net, phi, mesh.devices.size)
    phi_dist, cost = step(net_p, core.phi_to_sparse(phi_p, nbrs), consts,
                          jnp.asarray(1.0))
    assert isinstance(phi_dist, core.PhiSparse)
    # make_distributed_step pins kappa=0.0 (Gallager scaling off)
    phi_s, aux = _sgp_step_impl(net, core.phi_to_sparse(phi, nbrs), consts,
                                method="sparse", nbrs=nbrs, kappa=0.0,
                                sigma=jnp.asarray(1.0))
    np.testing.assert_allclose(np.asarray(phi_dist.result[:S]),
                               np.asarray(phi_s.result), atol=1e-6)
    np.testing.assert_allclose(np.asarray(phi_dist.data[:S]),
                               np.asarray(phi_s.data), atol=1e-6)
    np.testing.assert_allclose(np.asarray(phi_dist.local[:S]),
                               np.asarray(phi_s.local), atol=1e-6)
    np.testing.assert_allclose(float(cost), float(aux["cost"]), rtol=1e-7)


def test_run_distributed_sparse_converges_like_dense():
    """The sparse distributed driver descends to the same cost as the
    dense single-device reference on abilene."""
    net, phi0, _ = _setup("abilene")
    _, h_d = core.run(net, phi0, n_iters=30)
    _, h_s = core.run_distributed(net, phi0, n_iters=30, method="sparse")
    assert abs(h_d["final_cost"] - h_s["final_cost"]) \
        <= 1e-3 * h_d["final_cost"]


# ------------------------------------------------------------ projection edge
def test_fully_blocked_rows_project_to_zero():
    """Regression: a row with nothing permitted must come back all-zero
    (not a one-hot on a blocked coordinate), identically in the jnp
    oracle and the Pallas kernel."""
    R, K = 8, 12
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 3)
    phi = jax.nn.softmax(jax.random.normal(ks[0], (R, K)), -1)
    delta = jax.nn.softplus(jax.random.normal(ks[1], (R, K)))
    M = jax.nn.softplus(jax.random.normal(ks[2], (R, K)))
    perm = jnp.zeros((R, K), dtype=bool)
    perm = perm.at[::2, :3].set(True)   # odd rows fully blocked

    want = project_rows(phi, delta, M, perm)
    got = ops.simplex_project(phi, delta, M, perm, impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(want[1::2]), 0.0)
    np.testing.assert_array_equal(np.asarray(got[1::2]), 0.0)
    # permitted rows still project onto the simplex
    np.testing.assert_allclose(np.asarray(want[::2].sum(-1)), 1.0,
                               atol=1e-5)


def test_step_projection_impl_switch():
    """proj_impl routes both row projections through kernels.ops: the
    interpreted Pallas kernel (rows along the lanes) and the jnp
    oracle agree through one full SGP step."""
    net, phi, nbrs = _setup("abilene")
    consts = make_consts(net, core.total_cost(net, phi))
    p_oracle, _ = _sgp_step_impl(net, phi, consts, proj_impl="oracle")
    p_ref, _ = _sgp_step_impl(net, phi, consts, proj_impl="ref")
    p_pal, _ = _sgp_step_impl(net, phi, consts,
                              proj_impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(p_oracle.data),
                               np.asarray(p_ref.data), atol=1e-6)
    np.testing.assert_allclose(np.asarray(p_oracle.data),
                               np.asarray(p_pal.data), rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(p_oracle.result),
                               np.asarray(p_pal.result), rtol=1e-4,
                               atol=1e-5)


# ---------------------------------------------------------------- callback
def test_run_callback_sees_accepted_phi():
    """The driver's callback receives the post-decision iterate and an
    accepted flag; on accepted iterations the reported phi must match
    the cost trajectory (regression: it used to get the pre-step phi)."""
    net, phi0, _ = _setup("abilene")
    seen = []

    def cb(it, phi, aux, accepted):
        seen.append((it, float(core.total_cost(net, phi)), accepted))

    _, hist = core.run(net, phi0, n_iters=12, callback=cb)
    assert len(seen) == 12
    accepted_costs = [c for _, c, acc in seen if acc]
    # costs[0] is T0; accepted iterations append to the trajectory
    np.testing.assert_allclose(accepted_costs,
                               hist["costs"][1:len(accepted_costs) + 1],
                               rtol=1e-6)
    for _, c, acc in seen:
        if not acc:
            # rejected: phi reverted, cost equals the last accepted one
            assert any(abs(c - ac) <= 1e-6 * max(1.0, abs(ac))
                       for ac in hist["costs"])


def test_baselines_and_failure_smoke():
    """Tier-1 smoke for subsystems whose deep tests are slow-marked
    (test_system.py): restricted baselines, node failure + refeasibilize."""
    import dataclasses
    net, phi0, _ = _setup("abilene")
    _, h_spoo = core.run_spoo(net, n_iters=10)
    c0 = float(core.total_cost(net, phi0))
    assert h_spoo["final_cost"] <= c0 * (1.0 + 1e-6)
    net_f = core.fail_node(net, 3)
    phi_f = core.refeasibilize(net_f, phi0)
    assert bool(core.is_loop_free(net_f, phi_f))
    np.testing.assert_allclose(np.asarray(phi_f.data.sum(-1)), 1.0,
                               atol=1e-6)
    phi2, h = core.run(net_f, phi_f, n_iters=10)
    assert h["final_cost"] <= h["costs"][0] + 1e-9


def test_neighbors_roundtrip():
    """gather_edges / scatter_edges are mutually inverse on edge support."""
    net, phi, nbrs = _setup("fog")
    dense = phi.result * net.adj[None].astype(phi.result.dtype)
    sp = core.gather_edges(phi.result, nbrs)
    back = core.scatter_edges(sp, nbrs, net.V)
    np.testing.assert_allclose(np.asarray(back), np.asarray(dense),
                               atol=0.0)
    # in-edge view used by the traffic solve indexes the same values
    phi_in = np.asarray(sp[:, nbrs.in_nbr, nbrs.in_slot])
    in_nbr, in_mask = np.asarray(nbrs.in_nbr), np.asarray(nbrs.in_mask)
    d = np.asarray(dense)
    for j in range(net.V):
        for e in range(in_nbr.shape[1]):
            if in_mask[j, e]:
                assert phi_in[0, j, e] == d[0, in_nbr[j, e], j]
