"""Optimality certificates (Lemma 1, Theorem 1) and an independent
convex flow-domain reference solver.

The paper's key structural fact: T is NON-convex in φ but jointly convex
in the flow variables (f⁻, f⁺, g) over a polytope.  `flow_domain_optimum`
solves that convex program directly (scipy trust-constr) — giving an
independent global-optimum value that SGP must match (Theorem 1 ⇒
Theorem 2).
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from .marginals import BIG, compute_marginals
from .network import (CECNetwork, Phi, as_dense_phi, compute_flows,
                      is_loop_free)


def theorem1_residual(net: CECNetwork, phi, tol: float = 1e-6) -> Dict:
    """Max violation of the Theorem-1 conditions.

    For every (i, task): active coordinates (φ > tol) must achieve the
    row-min of δ.  Returns the worst absolute gap (δ_active - δ_min) and
    the corresponding Lemma-1 gap (scaled by traffic).  Edge-slot
    `PhiSparse` iterates are converted at this boundary (the check is a
    dense reference computation).
    """
    phi = as_dense_phi(phi, net)
    fl = compute_flows(net, phi)
    mg = compute_marginals(net, phi, fl)
    V = net.V
    is_dest = jnp.arange(V)[None] == net.dest[:, None]

    def gaps(phi_mat, delta, row_valid):
        active = phi_mat > tol
        dmin = jnp.min(jnp.where(delta < BIG / 2, delta, BIG), axis=-1,
                       keepdims=True)
        gap = jnp.where(active, delta - dmin, 0.0)
        gap = jnp.where(row_valid[..., None], gap, 0.0)
        return jnp.max(gap)

    g_d = gaps(phi.data, mg.delta_data, jnp.ones((net.S, V), dtype=bool))
    g_r = gaps(phi.result, mg.delta_result, ~is_dest)

    # Lemma-1 residual = traffic-weighted (the non-sufficient condition)
    l_d = gaps(phi.data, fl.t_data[..., None] * mg.delta_data,
               jnp.ones((net.S, V), dtype=bool))
    l_r = gaps(phi.result, fl.t_result[..., None] * mg.delta_result, ~is_dest)

    return {"theorem1": float(jnp.maximum(g_d, g_r)),
            "lemma1": float(jnp.maximum(l_d, l_r)),
            "loop_free": bool(is_loop_free(net, phi, tol=tol))}


def marginals_vs_autodiff(net: CECNetwork, phi) -> float:
    """Cross-check Eq. 9-12 closed forms against jax.grad of total cost.

    Returns the max abs difference between the analytic gradient
    t⊙δ (Lemma 1) and automatic differentiation through the flow solve.
    Feasibility constraints are not imposed on the perturbation —
    both sides measure the same unconstrained partial derivative.
    """
    from .network import cost_of_flows
    phi = as_dense_phi(phi, net)

    def T_of(phi_):
        return cost_of_flows(net, compute_flows(net, phi_))

    g_auto = jax.grad(lambda p: T_of(p))(phi)
    fl = compute_flows(net, phi)
    mg = compute_marginals(net, phi, fl)
    gd = fl.t_data[..., None] * mg.delta_data
    gr = fl.t_result[..., None] * mg.delta_result

    adjf = net.adj
    mask_d = jnp.concatenate(
        [jnp.broadcast_to(adjf[None], (net.S, net.V, net.V)),
         jnp.ones((net.S, net.V, 1), dtype=bool)], axis=-1)
    err_d = jnp.max(jnp.abs(jnp.where(mask_d, g_auto.data - gd, 0.0)))
    err_r = jnp.max(jnp.abs(jnp.where(adjf[None], g_auto.result - gr, 0.0)))
    return float(jnp.maximum(err_d, err_r))


# ----------------------------------------------------------- convex reference
def flow_domain_optimum(net: CECNetwork, maxiter: int = 3000) -> float:
    """Global optimum via the convex flow-domain program (24), scipy.

    Variables per task s: f⁻[e], f⁺[e] on directed edges, g[i].
    Conservation:  r_i + Σ_in f⁻ = Σ_out f⁻ + g_i          (data)
                   a_s g_i + Σ_in f⁺ = Σ_out f⁺            (result, i≠d)

    Solved by scipy's trust-constr interior point with the sparse
    conservation matrix and the exact Hessian-vector product (the cost
    is separable in the aggregate link flows F and workloads G), which
    reaches the Table II sizes (V=20, S=15: 2,640 variables) in tens of
    seconds where a dense SQP takes hours.
    """
    import scipy.sparse as sparse
    from scipy.optimize import Bounds, LinearConstraint, minimize

    from .costs import FAMILIES
    from .network import spt_phi

    adj = np.asarray(net.adj)
    V, S = net.V, net.S
    src, dst = np.nonzero(adj)
    E = len(src)
    n = 2 * E + V                  # per-task block: f⁻ | f⁺ | g
    lp = jnp.asarray(np.asarray(net.link_cost.params)[src, dst])
    cpar = jnp.asarray(net.comp_cost.params)
    r = np.asarray(net.r)
    a = np.asarray(net.a)
    w = np.asarray(net.w)
    dests = np.asarray(net.dest)
    fam_l = FAMILIES[net.link_cost.family]
    fam_c = FAMILIES[net.comp_cost.family]

    def unpack(z):
        z = z.reshape(S, n)
        return z[:, :E], z[:, E:2 * E], z[:, 2 * E:]

    def aggregates(z):
        fd, fr, g = unpack(z)
        return jnp.asarray((fd + fr).sum(axis=0)), jnp.asarray(
            (w * g).sum(axis=0))

    def spread(dF, dG):
        # d/dz of a function of (F, G), given its partials dF [E], dG [V]
        out = np.empty((S, n))
        out[:, :2 * E] = np.tile(dF, 2)
        out[:, 2 * E:] = w * dG
        return out.ravel()

    def f64(x):
        return np.asarray(x, np.float64)

    def obj(z):
        F, G = aggregates(z)
        return float(fam_l.value(F, lp).sum() + fam_c.value(G, cpar).sum())

    def grad(z):
        F, G = aggregates(z)
        return spread(f64(fam_l.d1(F, lp)), f64(fam_c.d1(G, cpar)))

    def hessp(z, p):
        F, G = aggregates(z)
        pd, pr, pg = unpack(p)
        return spread(f64(fam_l.d2(F, lp)) * (pd + pr).sum(axis=0),
                      f64(fam_c.d2(G, cpar)) * (w * pg).sum(axis=0))

    # conservation constraints, one sparse block per task
    inc = np.zeros((V, E))         # +1 where edge e enters i, -1 leaves
    inc[dst, np.arange(E)] += 1.0
    inc[src, np.arange(E)] -= 1.0
    zero, eye = np.zeros((V, E)), np.eye(V)
    blocks, rhs = [], []
    for s in range(S):
        keep = np.arange(V) != dests[s]
        blocks.append(np.vstack([np.hstack([inc, zero, -eye]),
                                 np.hstack([zero, inc, a[s] * eye])[keep]]))
        rhs.append(np.concatenate([-r[s], np.zeros(int(keep.sum()))]))
    A = sparse.block_diag(blocks, format="csr")
    b = np.concatenate(rhs)

    # feasible start: the flows of the φ⁰ strategy (compute locally,
    # route results along shortest paths)
    fl0 = compute_flows(net, spt_phi(net))
    z0 = np.concatenate([np.asarray(fl0.f_data)[:, src, dst],
                         np.asarray(fl0.f_result)[:, src, dst],
                         np.asarray(fl0.g)], axis=1).ravel()

    res = minimize(obj, z0, jac=grad, hessp=hessp, method="trust-constr",
                   bounds=Bounds(0.0, np.inf),
                   constraints=[LinearConstraint(A, b, b)],
                   options={"maxiter": maxiter, "gtol": 1e-9,
                            "xtol": 1e-12, "barrier_tol": 1e-9})
    return float(res.fun)
