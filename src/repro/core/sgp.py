"""Algorithm 1 — Scaled Gradient Projection (paper §IV).

Per iteration, each (node, task) solves the QP (Eq. 15): a
diagonally-scaled projection of φ_i(d,m) onto the simplex with blocked
coordinates pinned to zero.  Components:

* **Blocked sets** (loop-freedom): Gallager-style taint protocol.  An
  edge (i,j) with φ_ij > 0 is *improper* if the downstream marginal does
  not strictly decrease (ρ_j >= ρ_i).  A node is *tainted* if any
  support path from it contains an improper edge.  Node i may not ADD
  flow toward j (φ_ij == 0 is kept at 0) if ρ_j >= ρ_i or j is tainted.
  Existing positive entries are never force-dropped (their δ is large so
  the projection drains them) — this is the paper's §IV "blocked nodes"
  mechanism, which it inherits from Gallager [20] / Xi-Yeh [21].

* **Scaling matrices** (Eq. 16): diagonal Hessian upper bounds built
  from A_ij(T0) = sup_{T<=T0} D''_ij and path-length bounds h. They give
  stepsize-free descent (Theorem 2).

* **Zero-traffic rows** jump one-hot to the δ-argmin over permitted
  coordinates (the M ∝ t scaling degenerates at t=0; the jump is the
  limit behaviour and matches [21]).

The whole update is one fixed-shape jitted function over all (S, V) rows
at once; asynchronous updates (Theorem 2) are expressed with row masks.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from .costs import Cost
from .faults import (_marginals_jit, fault_step_begin, fault_step_end,
                     init_fault_state)
from .marginals import BIG, Marginals, compute_marginals
from .network import (CECNetwork, Flows, FlowsCarry, Neighbors, Phi,
                      PhiSparse, _phi_edge_views, build_buckets,
                      build_neighbors,
                      compute_flows, cost_of_flows, flows_carry_and_cost,
                      flows_carry_and_cost_jit, gather_edges,
                      link_cost_sparse, mask_slots, phi_to_sparse,
                      psum_flows, scatter_edges, sparse_to_phi)
from ..kernels import ops as kernel_ops

SUPPORT_TOL = 1e-9   # φ below this is treated as zero support
SNAP_TOL = 1e-12     # post-projection snap-to-zero
TRAFFIC_EPS = 1e-9   # rows with traffic below this take the one-hot jump
# the accept/reject safeguard's sigma decay factor, as an explicit f32
# reciprocal: XLA strength-reduces division by a constant into a
# reciprocal multiply inside jit (but NOT eagerly / in numpy), so a
# literal `sigma / 1.5` cannot be bitwise-mirrored on the host — an
# explicit multiply compiles to the same op everywhere
SIGMA_DECAY = np.float32(1.0 / 1.5)
# past this the safeguard's sigma stops the driver (numerically stuck)
SIGMA_STOP = np.float32(1e12)


def warm_sigma(sigma: float) -> float:
    """The safety factor a warm re-baseline starts its segment from:
    the previous segment's, so an event does not send the driver back
    to sigma = 1 (whose first steps overshoot and are rejected), unless
    that segment stopped on a sigma blow-up.  Both warm re-baselines
    (`replay.ReplayEngine.apply_event`, `FusedStream.rebaseline`) use
    it; a cold start takes 1."""
    sigma32 = np.float32(sigma)
    return 1.0 if sigma32 > SIGMA_STOP else float(sigma32)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SGPConsts:
    """Iteration-invariant constants of Algorithm 1 (line 2)."""
    A_link: jnp.ndarray   # [V, V] sup D''_ij on the T0-sublevel set
    A_comp: jnp.ndarray   # [V]    sup C''_i  on the T0-sublevel set
    A_max: jnp.ndarray    # scalar A(T0)
    min_scale: jnp.ndarray  # scalar floor on diag(M)/t (linear-cost case)


def make_consts(net: CECNetwork, T0: jnp.ndarray,
                min_scale: float = 0.05) -> SGPConsts:
    A_link = jnp.where(net.adj, net.link_cost.d2_sup(T0), 0.0)
    A_comp = net.comp_cost.d2_sup(T0)
    A_max = jnp.maximum(jnp.max(A_link), jnp.max(A_comp))
    return SGPConsts(A_link, A_comp, A_max, jnp.asarray(min_scale))


# ------------------------------------------------------------- blocked sets
def _taint(sup: jnp.ndarray, rho: jnp.ndarray) -> jnp.ndarray:
    """[S, V] bool: node has an improper edge on some downstream support path."""
    improper = sup & (rho[:, None, :] >= rho[:, :, None])  # [S, i, j]
    has_improper = jnp.any(improper, axis=-1)              # [S, i]
    V = sup.shape[-1]

    def body(t, _):
        t = has_improper | jnp.any(sup & t[:, None, :], axis=-1)
        return t, None

    t, _ = jax.lax.scan(body, has_improper, None, length=V)
    return t


def blocked_sets(net: CECNetwork, phi: Phi, mg: Marginals):
    """Returns permitted coordinate masks (True = free to carry flow)."""
    adj = net.adj[None]
    sup_d = (phi.data[..., :-1] > SUPPORT_TOL) & adj
    sup_r = (phi.result > SUPPORT_TOL) & adj

    taint_d = _taint(sup_d, mg.rho_data)
    taint_r = _taint(sup_r, mg.rho_result)

    def permitted(sup, rho, taint):
        uphill = rho[:, None, :] >= rho[:, :, None]
        block_new = (~sup) & (uphill | taint[:, None, :])
        return adj & ~block_new  # support edges always permitted

    perm_d_nbr = permitted(sup_d, mg.rho_data, taint_d)
    perm_r = permitted(sup_r, mg.rho_result, taint_r)

    # local offload column: always permitted (a sink for data flow)
    S, V = net.S, net.V
    perm_d = jnp.concatenate(
        [perm_d_nbr, jnp.ones((S, V, 1), dtype=bool)], axis=-1)
    # destinations are result sinks: no outgoing result coordinates
    is_dest = jnp.arange(V)[None] == net.dest[:, None]
    perm_r = jnp.where(is_dest[..., None], False, perm_r)
    return perm_d, perm_r


# --------------------------------------------------------------- path bounds
def _max_path_len(sup: jnp.ndarray) -> jnp.ndarray:
    """h[s,i] = longest support path length (in hops) starting at i.

    Rows without outgoing support (path terminals: the destination for
    result flow, pure-local-offload nodes for data flow) have h = 0."""
    V = sup.shape[-1]
    h = jnp.zeros(sup.shape[:2], dtype=jnp.float32)

    def body(h, _):
        nbr = jnp.where(sup, 1.0 + h[:, None, :], 0.0)
        return jnp.max(nbr, axis=-1), None

    h, _ = jax.lax.scan(body, h, None, length=V)
    return h


# ---------------------------------------------------------------- projection
def project_rows(phi_row: jnp.ndarray, delta: jnp.ndarray, M: jnp.ndarray,
                 permitted: jnp.ndarray, n_iter: int = 60) -> jnp.ndarray:
    """Scaled projection onto the simplex with pinned coordinates (Eq. 14/15).

    Solves  min_v  δ·(v-φ) + (v-φ)ᵀ diag(M) (v-φ)
            s.t.   Σv = 1, v >= 0, v[~permitted] = 0
    via bisection on the simplex dual λ:
            v_j(λ) = max(0, φ_j - (δ_j + λ) / (2 M_j)).

    All inputs are [..., K]; fully vectorized over leading dims.
    This is the pure-jnp oracle for kernels/simplex_project; the Pallas
    kernel runs the same hoisted form for a fixed `n_iter` rounds, which
    end on the bracket this early exit stops at.  The two differ only in
    the order of the float32 sum over K (locked at 1e-4 in the kernel
    tests), not bitwise.
    """
    Msafe = jnp.where(permitted, jnp.maximum(M, 1e-12), 1.0)
    phi0 = jnp.where(permitted, phi_row, 0.0)
    d = jnp.where(permitted, delta, BIG)

    lam_lo = jnp.min(jnp.where(permitted, -d - 2.0 * Msafe * (1.0 - phi0), BIG),
                     axis=-1, keepdims=True)
    lam_hi = jnp.max(jnp.where(permitted, -d + 2.0 * Msafe * phi0, -BIG),
                     axis=-1, keepdims=True)

    # Slope-intercept form of the dual residual: on the permitted set
    # v_j(λ) = max(q_j - λ w_j, 0) with q = φ - d/(2M), w = 1/(2M);
    # blocked coordinates contribute exactly 0 via (q, w) = (-BIG, 0).
    # Hoisting the division out of the bisection makes each halving one
    # multiply-subtract + reduce — this loop is the single hottest
    # computation of the whole driver at V ~ 10³.
    w = jnp.where(permitted, 1.0 / (2.0 * Msafe), 0.0)
    q = jnp.where(permitted, phi0 - d / (2.0 * Msafe), -BIG)

    def v_of(lam):
        return jnp.maximum(q - lam * w, 0.0)

    # Bisection with early exit: once every row's (lo, hi) bracket stops
    # moving (in float32 that happens after ~30 of the 60 halvings — the
    # midpoint rounds onto an endpoint), further iterations reproduce
    # the SAME bracket, so exiting is bitwise identical to running the
    # full `n_iter` at roughly half the memory traffic.  Not
    # reverse-differentiable (while_loop); nothing differentiates
    # through the projection.
    def cond(carry):
        k, _, _, changed = carry
        return jnp.logical_and(k < n_iter, changed)

    def body(carry):
        k, lo, hi, _ = carry
        mid = 0.5 * (lo + hi)
        s = jnp.sum(v_of(mid), axis=-1, keepdims=True)
        lo2 = jnp.where(s > 1.0, mid, lo)
        hi2 = jnp.where(s > 1.0, hi, mid)
        changed = jnp.any(lo2 != lo) | jnp.any(hi2 != hi)
        return k + 1, lo2, hi2, changed

    _, lo, hi, _ = jax.lax.while_loop(
        cond, body,
        (jnp.asarray(0, jnp.int32), lam_lo, lam_hi, jnp.asarray(True)))
    v = v_of(0.5 * (lo + hi))
    v = jnp.where(v > SNAP_TOL, v, 0.0)
    s = jnp.sum(v, axis=-1, keepdims=True)
    # guard: if everything snapped to zero, fall back to argmin-δ one-hot
    onehot = jax.nn.one_hot(jnp.argmin(d, axis=-1), d.shape[-1],
                            dtype=phi_row.dtype)
    v = jnp.where(s > 0.0, v / jnp.maximum(s, 1e-30), onehot)
    # fully-blocked rows have no feasible point on the simplex: the
    # argmin fallback above would pick a *blocked* coordinate (d is
    # all-BIG).  Return the all-zero row instead; callers must mask such
    # rows out (they only arise at result-flow destinations).
    return jnp.where(jnp.any(permitted, axis=-1, keepdims=True), v, 0.0)


def gp_rows(phi_row: jnp.ndarray, delta: jnp.ndarray, t: jnp.ndarray,
            permitted: jnp.ndarray, beta: float) -> jnp.ndarray:
    """Unscaled GP baseline row update (paper §V, Gallager's rule).

    M = (t/β) diag(1,..,1,0@argmin,1,..,1): non-minimal coordinates move
    down by β(δ_j - δ_min)/(2t), clipped at 0; the δ-argmin coordinate
    absorbs the released mass.
    """
    d = jnp.where(permitted, delta, BIG)
    jmin = jnp.argmin(d, axis=-1)
    onehot = jax.nn.one_hot(jmin, d.shape[-1], dtype=phi_row.dtype)
    dmin = jnp.min(d, axis=-1, keepdims=True)
    phi0 = jnp.where(permitted, phi_row, 0.0)
    step = beta * (d - dmin) / (2.0 * jnp.maximum(t[..., None], TRAFFIC_EPS))
    v = jnp.maximum(phi0 - step, 0.0) * (1.0 - onehot)
    v = jnp.where(permitted, v, 0.0)
    vmin = 1.0 - jnp.sum(v, axis=-1, keepdims=True)
    v = v + onehot * vmin
    v = jnp.where(v > SNAP_TOL, v, 0.0)
    s = jnp.sum(v, axis=-1, keepdims=True)
    v = jnp.where(s > 0.0, v / jnp.maximum(s, 1e-30), onehot)
    # fully-blocked rows: all-zero (see project_rows)
    return jnp.where(jnp.any(permitted, axis=-1, keepdims=True), v, 0.0)


def _project(phi_rows: jnp.ndarray, delta: jnp.ndarray, M: jnp.ndarray,
             permitted: jnp.ndarray, impl: Optional[str]) -> jnp.ndarray:
    """Dispatch the [S, V, K] row batch of Eq. 15 QPs.

    impl="oracle" keeps the in-module pure-jnp `project_rows`; anything
    else flattens to [S·V, K] and goes through
    `repro.kernels.ops.simplex_project` (backend dispatch: Pallas kernel
    on TPU, jnp reference on CPU, "pallas_interpret" for validation).
    """
    if impl == "oracle":
        return project_rows(phi_rows, delta, M, permitted)
    S, V, K = phi_rows.shape
    out = kernel_ops.simplex_project(
        phi_rows.reshape(S * V, K), delta.reshape(S * V, K),
        M.reshape(S * V, K), permitted.reshape(S * V, K), impl=impl)
    return out.reshape(S, V, K)


# ------------------------------------------------- sparse (neighbor-list) ops
def _taint_sparse(sup: jnp.ndarray, rho: jnp.ndarray, nbrs: Neighbors,
                  impl: Optional[str] = None, buckets=None) -> jnp.ndarray:
    """_taint in edge-slot layout: sup [S, V, Dmax], gather-based rounds.

    The boolean-or closure runs through the shared edge_rounds kernel
    with a {0, 1} float encoding and a max reduce.  `buckets` (a
    network.NeighborBuckets) runs it over degree-bucketed tiles —
    bitwise identical, ΣVb·Db per-round work."""
    improper = sup & (rho[:, nbrs.out_nbr] >= rho[:, :, None])
    has_improper = jnp.any(improper, axis=-1)
    if buckets is not None:
        t = kernel_ops.edge_rounds_bucketed(
            sup.astype(jnp.float32), has_improper.astype(jnp.float32),
            buckets.out, reduce="max", max_rounds=nbrs.V, impl=impl)
    else:
        t = kernel_ops.edge_rounds(
            sup.astype(jnp.float32), has_improper.astype(jnp.float32),
            nbrs.out_nbr, nbrs.out_mask, reduce="max", max_rounds=nbrs.V,
            impl=impl)
    return t > 0.5


def _max_path_len_sparse(sup: jnp.ndarray, nbrs: Neighbors,
                         impl: Optional[str] = None,
                         buckets=None) -> jnp.ndarray:
    """_max_path_len in edge-slot layout: a max reduce over 1 + h[nbr]
    (shift=1) with zero inject reproduces the longest-path recursion."""
    h0 = jnp.zeros(sup.shape[:2], dtype=jnp.float32)
    if buckets is not None:
        return kernel_ops.edge_rounds_bucketed(
            sup.astype(jnp.float32), h0, buckets.out, reduce="max",
            shift=1.0, max_rounds=nbrs.V, impl=impl)
    return kernel_ops.edge_rounds(
        sup.astype(jnp.float32), h0, nbrs.out_nbr, nbrs.out_mask,
        reduce="max", shift=1.0, max_rounds=nbrs.V, impl=impl)


def _taint_pair_sparse(sup_a: jnp.ndarray, rho_a: jnp.ndarray,
                       sup_b: jnp.ndarray, rho_b: jnp.ndarray,
                       nbrs: Neighbors, impl: Optional[str] = None,
                       buckets=None):
    """Both taint recursions (data + result) in ONE batched launch.

    The two `_taint_sparse` problems share the neighbor tiles, so they
    stack along the task axis into a single `edge_rounds_stacked` call —
    bitwise identical to the two unstacked solves (rounds past a
    sub-problem's exact fixed point are no-ops; locked by
    tests/test_fused_driver.py) at half the recursion launches.
    """
    # bfloat16 carries the {0, 1} encoding EXACTLY (products and maxes
    # of 0/1 stay 0/1), and the boolean-or closure is the deepest
    # memory-bound recursion of the step — half-width floats halve its
    # traffic with bit-identical boolean results
    dt = jnp.bfloat16

    def has_improper(sup, rho):
        improper = sup & (rho[:, nbrs.out_nbr] >= rho[:, :, None])
        return jnp.any(improper, axis=-1)

    t_a, t_b = kernel_ops.edge_rounds_stacked(
        [(sup_a.astype(dt), has_improper(sup_a, rho_a).astype(dt)),
         (sup_b.astype(dt), has_improper(sup_b, rho_b).astype(dt))],
        nbrs.out_nbr, nbrs.out_mask, reduce="max", max_rounds=nbrs.V,
        impl=impl, buckets=buckets.out if buckets is not None else None)
    return t_a > 0.5, t_b > 0.5


def _max_path_len_pair_sparse(sup_a: jnp.ndarray, sup_b: jnp.ndarray,
                              nbrs: Neighbors, impl: Optional[str] = None,
                              buckets=None):
    """Both longest-path recursions (result + data) in ONE batched
    launch — the `_taint_pair_sparse` trick applied to
    `_max_path_len_sparse` (same bitwise-equivalence argument)."""
    h0 = jnp.zeros(sup_a.shape[:2], dtype=jnp.float32)
    return kernel_ops.edge_rounds_stacked(
        [(sup_a.astype(jnp.float32), h0), (sup_b.astype(jnp.float32), h0)],
        nbrs.out_nbr, nbrs.out_mask, reduce="max", shift=1.0,
        max_rounds=nbrs.V, impl=impl,
        buckets=buckets.out if buckets is not None else None)


def blocked_sets_sparse(net: CECNetwork, phi, mg: Marginals,
                        nbrs: Neighbors, engine_impl: Optional[str] = None,
                        buckets=None):
    """`blocked_sets` over edge slots: permitted masks [S, V, Dmax(+1)].

    `phi` may be a dense `Phi` (gathered onto the slots) or an edge-slot
    `PhiSparse` (supports read off the slots in place).  `buckets` (a
    network.NeighborBuckets) runs the taint closures over degree-
    bucketed tiles — bitwise identical at ΣVb·Db per-round work."""
    phi_d_sp, _, phi_r_sp = _phi_edge_views(phi, nbrs)
    sup_d = phi_d_sp > SUPPORT_TOL
    sup_r = phi_r_sp > SUPPORT_TOL

    taint_d, taint_r = _taint_pair_sparse(sup_d, mg.rho_data,
                                          sup_r, mg.rho_result,
                                          nbrs, engine_impl, buckets=buckets)

    def permitted(sup, rho, taint):
        uphill = rho[:, nbrs.out_nbr] >= rho[:, :, None]
        block_new = (~sup) & (uphill | taint[:, nbrs.out_nbr])
        return nbrs.out_mask[None] & ~block_new

    perm_d_nbr = permitted(sup_d, mg.rho_data, taint_d)
    perm_r = permitted(sup_r, mg.rho_result, taint_r)

    S, V = net.S, net.V
    perm_d = jnp.concatenate(
        [perm_d_nbr, jnp.ones((S, V, 1), dtype=bool)], axis=-1)
    is_dest = jnp.arange(V)[None] == net.dest[:, None]
    perm_r = jnp.where(is_dest[..., None], False, perm_r)
    return perm_d, perm_r


# ------------------------------------------------------------------ the step
def _sgp_propose_impl(net: CECNetwork, phi, fl, consts: SGPConsts,
                      variant: str = "sgp", beta: float = 1.0,
                      mask_data: Optional[jnp.ndarray] = None,
                      mask_result: Optional[jnp.ndarray] = None,
                      allowed_data: Optional[jnp.ndarray] = None,
                      allowed_result: Optional[jnp.ndarray] = None,
                      method: str = "dense", use_blocking: bool = True,
                      scaling: str = "adaptive",
                      sigma: jnp.ndarray | float = 1.0,
                      kappa: jnp.ndarray | float = 1.0,
                      proj_impl: Optional[str] = None,
                      engine_impl: Optional[str] = None,
                      nbrs: Optional[Neighbors] = None,
                      slot_F: bool = False, buckets=None,
                      mg: Optional[Marginals] = None):
    """The projection half of one Algorithm-1 iteration: given the
    CURRENT iterate φ and its (already measured, psum'ed if distributed)
    flows `fl`, compute marginals, blocked sets, the Eq. 16 scaling and
    the projected candidate iterate.  Returns (phi_new, marginals).

    `mg` overrides the internally computed marginals — the fault layer
    (core.faults) injects stale/held broadcasts this way; the blocked
    sets then see the SAME (possibly stale) values the projection does,
    exactly as a node acting on an old broadcast would.

    Splitting the step here is what lets the drivers compute each
    iterate's flows exactly once: `fl` is threaded through the driver
    carry (host loop and fused scan alike), so the flow solve of a
    candidate happens when it is PROPOSED and is simply reused when it
    is accepted and stepped FROM.  See `_sgp_step_impl` for the
    argument/layout contract (identical, minus `fl`).
    """
    sparse = method == "sparse"
    native = isinstance(phi, PhiSparse)
    if native and not sparse:
        raise ValueError("PhiSparse iterates require method='sparse'")
    if sparse and nbrs is None:
        raise ValueError("method='sparse' needs nbrs=build_neighbors(adj) "
                         "precomputed outside jit")
    if mg is None:
        mg = compute_marginals(net, phi, fl, method, nbrs=nbrs,
                               engine_impl=engine_impl, slot_F=slot_F,
                               buckets=buckets)

    S, V = net.S, net.V
    is_dest = jnp.arange(V)[None] == net.dest[:, None]

    # row layout: edge slots ([S, V, Dmax(+1)]) when sparse, else dense
    if sparse:
        adj_e = nbrs.out_mask[None]
        phi_d_sp, phi_loc, phi_r_rows = _phi_edge_views(phi, nbrs)
        phi_d_rows = jnp.concatenate([phi_d_sp, phi_loc[..., None]], axis=-1)
    else:
        adj_e = net.adj[None]
        phi_d_rows = phi.data
        phi_r_rows = phi.result
    K = adj_e.shape[-1]
    sup_d = (phi_d_rows[..., :-1] > SUPPORT_TOL) & adj_e
    sup_r = (phi_r_rows > SUPPORT_TOL) & adj_e

    if use_blocking:
        if sparse:
            perm_d, perm_r = blocked_sets_sparse(net, phi, mg, nbrs,
                                                 engine_impl,
                                                 buckets=buckets)
        else:
            perm_d, perm_r = blocked_sets(net, phi, mg)
    else:
        perm_d = jnp.concatenate(
            [jnp.broadcast_to(adj_e, (S, V, K)),
             jnp.ones((S, V, 1), dtype=bool)], axis=-1)
        perm_r = jnp.broadcast_to(adj_e, (S, V, K))
        perm_r = jnp.where(is_dest[..., None], False, perm_r)
    if allowed_data is not None:
        if sparse:
            allowed_data = jnp.concatenate(
                [gather_edges(allowed_data, nbrs, fill=False),
                 allowed_data[..., -1:]], axis=-1)
        perm_d = perm_d & allowed_data
    if allowed_result is not None:
        if sparse:
            allowed_result = gather_edges(allowed_result, nbrs, fill=False)
        perm_r = perm_r & allowed_result

    if variant == "sgp":
        if scaling == "paper":
            A_comp, A_max = consts.A_comp, consts.A_max
            A_link_e = (gather_edges(consts.A_link, nbrs)[None] if sparse
                        else consts.A_link[None])          # [1, V, Dmax]
        elif slot_F:
            # carry F already on the slots: evaluate the curvature there
            # (bitwise the dense evaluation per real slot, ~Dmax/V work)
            A_link_e = (mask_slots(link_cost_sparse(net, nbrs).d2(fl.F),
                                   nbrs) * sigma)[None]
            A_comp = net.comp_cost.d2(fl.G) * sigma
            A_max = jnp.maximum(jnp.max(A_link_e), jnp.max(A_comp))
        else:  # current-flow curvature, safeguarded by the driver
            A_link = jnp.where(net.adj, net.link_cost.d2(fl.F), 0.0) * sigma
            A_comp = net.comp_cost.d2(fl.G) * sigma
            A_max = jnp.maximum(jnp.max(A_link), jnp.max(A_comp))
            A_link_e = (gather_edges(A_link, nbrs)[None] if sparse
                        else A_link[None])                 # [1, V, Dmax]

        if isinstance(kappa, (int, float)) and float(kappa) == 0.0:
            # The drivers' default (kappa=0, Gallager cross-terms off):
            # every κ·n·h·A_max term is exactly 0 for the finite
            # path/degree bounds, so Eq. 16 reduces to the raw
            # link/compute curvature — skip the longest-path recursions
            # and permitted-degree sums entirely (bitwise: A + 0·x == A).
            diag_r = A_link_e
            diag_d = jnp.concatenate(
                [A_link_e, A_comp[None, :, None]], axis=-1)
        else:
            # Eq. 16 scaling matrices (sparse: both longest-path
            # recursions ride one stacked launch, bitwise = the
            # unstacked pair).
            if sparse:
                h_r, h_d = _max_path_len_pair_sparse(
                    sup_r, sup_d, nbrs, engine_impl,
                    buckets=buckets)                       # [S, V]
                hj_r = h_r[:, nbrs.out_nbr]                # h at edge head
                hj_d = h_d[:, nbrs.out_nbr]
            else:
                h_r = _max_path_len(sup_r)
                h_d = _max_path_len(sup_d)
                hj_r = h_r[:, None, :]
                hj_d = h_d[:, None, :]
            n_r = jnp.sum(perm_r, axis=-1).astype(phi.result.dtype)
            n_d = jnp.sum(perm_d, axis=-1).astype(phi.data.dtype)
            kap = jnp.asarray(kappa, dtype=phi.result.dtype)
            diag_r = A_link_e + kap * n_r[..., None] * hj_r * A_max
            diag_d_nbr = A_link_e + kap * n_d[..., None] * hj_d * A_max
            a2 = (net.a ** 2)[:, None]
            diag_d_loc = (A_comp[None]
                          + kap * n_d * a2 * (1.0 + h_r) * A_max)
            diag_d = jnp.concatenate([diag_d_nbr, diag_d_loc[..., None]],
                                     axis=-1)
        Mr = 0.5 * fl.t_result[..., None] * diag_r
        Md = 0.5 * fl.t_data[..., None] * diag_d
        # floor for flat (linear) costs: behaves like conservative GP
        Mr = jnp.maximum(Mr, consts.min_scale * fl.t_result[..., None])
        Md = jnp.maximum(Md, consts.min_scale * fl.t_data[..., None])

        new_d = _project(phi_d_rows, mg.delta_data, Md, perm_d, proj_impl)
        new_r = _project(phi_r_rows, mg.delta_result, Mr, perm_r, proj_impl)
    elif variant == "gp":
        new_d = gp_rows(phi_d_rows, mg.delta_data, fl.t_data, perm_d, beta)
        new_r = gp_rows(phi_r_rows, mg.delta_result, fl.t_result, perm_r,
                        beta)
    else:
        raise ValueError(variant)

    # zero-traffic rows jump one-hot to the δ-argmin over permitted coords
    def onehot_min(delta, perm, dtype):
        d = jnp.where(perm, delta, BIG)
        oh = jax.nn.one_hot(jnp.argmin(d, axis=-1), d.shape[-1], dtype=dtype)
        # fully-blocked rows (result destinations) stay all-zero
        return jnp.where(jnp.any(perm, axis=-1, keepdims=True), oh, 0.0)

    jump_d = onehot_min(mg.delta_data, perm_d, phi.data.dtype)
    jump_r = onehot_min(mg.delta_result, perm_r, phi.result.dtype)
    new_d = jnp.where((fl.t_data > TRAFFIC_EPS)[..., None], new_d, jump_d)
    new_r = jnp.where((fl.t_result > TRAFFIC_EPS)[..., None], new_r, jump_r)

    # destination rows carry no result flow
    new_r = jnp.where(is_dest[..., None], 0.0, new_r)

    # scatter edge-slot rows back to the dense Phi layout — dense-Phi
    # callers only; native PhiSparse iterates stay in slot layout
    if sparse and not native:
        new_d = jnp.concatenate(
            [scatter_edges(new_d[..., :-1], nbrs, V), new_d[..., -1:]],
            axis=-1)
        new_r = scatter_edges(new_r, nbrs, V)

    # asynchronous row masks (Theorem 2); the native no-update rows keep
    # the sanitized slot view (padding zeroed), same values as a
    # dense-layout keep on the edge support
    old_d = phi_d_rows if native else phi.data
    old_r = phi_r_rows if native else phi.result
    if mask_data is not None:
        new_d = jnp.where(mask_data[..., None], new_d, old_d)
    if mask_result is not None:
        new_r = jnp.where(mask_result[..., None], new_r, old_r)

    new_phi = (PhiSparse(new_d[..., :-1], new_d[..., -1:], new_r) if native
               else Phi(new_d, new_r))
    return new_phi, mg


def _sgp_step_impl(net: CECNetwork, phi, consts: SGPConsts,
                   variant: str = "sgp", beta: float = 1.0,
                   mask_data: Optional[jnp.ndarray] = None,
                   mask_result: Optional[jnp.ndarray] = None,
                   allowed_data: Optional[jnp.ndarray] = None,
                   allowed_result: Optional[jnp.ndarray] = None,
                   method: str = "dense", use_blocking: bool = True,
                   scaling: str = "adaptive",
                   sigma: jnp.ndarray | float = 1.0,
                   kappa: float = 1.0,  # static in the jit (0.0 elides Eq.16 cross-terms)
                   psum_axis: Optional[str] = None,
                   proj_impl: Optional[str] = None,
                   engine_impl: Optional[str] = None,
                   nbrs: Optional[Neighbors] = None,
                   buckets=None):
    """One synchronized iteration of Algorithm 1 over every (node, task).

    mask_* : [S, V] bool — rows that update this iteration (Theorem 2
             asynchrony; default: all).
    allowed_* : extra permission masks for restricted baselines
             (SPOO/LCOR); ANDed into the blocked-set permission.
             Always given in the dense [S, V, V+1] / [S, V, V] layout.
    use_blocking=False skips the taint protocol — only valid when the
             allowed masks themselves guarantee loop-freedom (SPOO's
             fixed shortest-path tree).
    scaling : "paper"  — Eq. 16 verbatim: curvature sup over the
                          T0-sublevel set.  Guaranteed descent but
                          extremely conservative when any link has small
                          capacity (A ∝ (1+T0)³/cap²).
              "adaptive" — same Eq. 16 structure, with curvature at the
                          CURRENT flows times safety factor `sigma`; the
                          driver enforces monotone descent by rejecting
                          uphill steps and raising sigma (backtracking).
    proj_impl : QP projection backend, see `_project` ("oracle" = the
             in-module jnp path; default = kernels.ops dispatch).
    engine_impl : sparse message-passing backend for every fixed-point
             recursion (traffic, marginals, taint, path bounds), see
             kernels.ops.edge_rounds — None = backend default
             (`kernels.ops.default_impl`).
    nbrs   : precomputed `Neighbors`; required when method="sparse"
             (the whole iteration then runs in [S, V, Dmax] edge-slot
             layout).
    buckets : optional `network.NeighborBuckets` (sparse method only):
             every fixed-point recursion of the step then iterates
             degree-bucketed [Vb, Db] tiles instead of the [V, Dmax]
             tile — bitwise-identical iterates at ΣVb·Db per-round
             work (the power-law scaling mode).

    φ layout: a dense `Phi` always works; with method="sparse" an
    edge-slot `PhiSparse` is consumed AND produced natively — the step
    then materializes no [S, V, V+1] array at all (the dense-Phi sparse
    path instead gathers on entry and scatters back on exit, and is the
    bitwise reference for the native layout).
    """
    fl = compute_flows(net, phi, method, nbrs=nbrs, engine_impl=engine_impl,
                       buckets=buckets)
    if psum_axis is not None:
        # Distributed mode (shard_map over the task axis): per-task
        # traffic is local; total link flow / workload — the only
        # cross-task coupling — is one all-reduce, exactly the paper's
        # link-measurement phase.
        fl = psum_flows(fl, psum_axis)
    new_phi, mg = _sgp_propose_impl(
        net, phi, fl, consts, variant=variant, beta=beta,
        mask_data=mask_data, mask_result=mask_result,
        allowed_data=allowed_data, allowed_result=allowed_result,
        method=method, use_blocking=use_blocking, scaling=scaling,
        sigma=sigma, kappa=kappa, proj_impl=proj_impl,
        engine_impl=engine_impl, nbrs=nbrs, buckets=buckets)
    return new_phi, {"cost": cost_of_flows(net, fl), "flows": fl,
                     "marginals": mg}


# kappa is static so the default kappa=0.0 eliminates the path-length /
# degree computations at trace time (see _sgp_propose_impl); it is a
# config float, so the extra cache entries are bounded
sgp_step = jax.jit(
    _sgp_step_impl,
    static_argnames=("variant", "method", "use_blocking", "scaling",
                     "kappa", "psum_axis", "proj_impl", "engine_impl"))


def _sgp_step_flows_impl(net: CECNetwork, phi, fl, consts: SGPConsts,
                         variant: str = "sgp", beta: float = 1.0,
                         mask_data: Optional[jnp.ndarray] = None,
                         mask_result: Optional[jnp.ndarray] = None,
                         allowed_data: Optional[jnp.ndarray] = None,
                         allowed_result: Optional[jnp.ndarray] = None,
                         method: str = "dense", use_blocking: bool = True,
                         scaling: str = "adaptive",
                         sigma: jnp.ndarray | float = 1.0,
                         kappa: float = 1.0,  # static in the jit (0.0 elides Eq.16 cross-terms)
                         psum_axis: Optional[str] = None,
                         proj_impl: Optional[str] = None,
                         engine_impl: Optional[str] = None,
                         nbrs: Optional[Neighbors] = None,
                         buckets=None, fault_plan=None, fault_state=None,
                         active: Optional[jnp.ndarray] = None):
    """One DRIVER iteration: propose the candidate from the current
    iterate's carried flows, then measure the candidate (flows + cost).

    fault_plan/fault_state (see core.faults) arm the asynchrony/fault
    injectors INSIDE this same executable: stale/held marginal
    broadcasts feed the propose via `mg=`, partial participation folds
    into the Theorem-2 row masks, and value corruption poisons the
    candidate AFTER its flows/cost were measured.  When armed the
    return becomes (phi_new, carry_new, cost_new, fault_state');
    `fault_plan=None` (the default) traces the identical program as
    before the fault layer existed.

    This is the step of the `_sgp_block` loop body, and every
    single-device driver — the python-loop reference and the fused
    pipeline alike — dispatches it only through that block, so they
    share ONE compiled loop body, which is what makes their trajectories
    bitwise identical (XLA fusion is graph-context-dependent, so
    re-tracing the same ops inside a larger program does NOT reproduce
    the same floats; sharing the compiled block does).  Per iteration
    it runs exactly one `compute_flows` — of the candidate; the current
    iterate's flows arrive via `fl` (a `FlowsCarry`, computed when IT
    was the candidate, or by the boundary
    `network.flows_carry_and_cost` for φ⁰).  Returns
    (phi_new, carry_new, cost_new).
    """
    faulted = fault_plan is not None and fault_state is not None
    mg_in = None
    if faulted:
        mg_in, pmask, k_cor, fs_mid = fault_step_begin(
            net, phi, fl, fault_state, fault_plan, method, nbrs,
            engine_impl, buckets)
        if pmask is not None:
            mask_data = pmask if mask_data is None else mask_data & pmask
            mask_result = (pmask if mask_result is None
                           else mask_result & pmask)
    if active is not None:
        # dynamic task-slot pool (events.TaskPool): fold the [S] active
        # mask into the Theorem-2 row masks exactly like the fault
        # participation mask above — but unconditionally, faults or not
        # — so inactive slots' φ rows are frozen bitwise.  Their r/a
        # rows are zero under the pool contract, so their flows, cost
        # and accept contributions are exactly zero without any
        # masking of the measurement itself.
        am = active[:, None]                                # [S, 1] -> [S, V]
        mask_data = am if mask_data is None else mask_data & am
        mask_result = am if mask_result is None else mask_result & am
    phi_new, mg = _sgp_propose_impl(
        net, phi, fl, consts, variant=variant, beta=beta,
        mask_data=mask_data, mask_result=mask_result,
        allowed_data=allowed_data, allowed_result=allowed_result,
        method=method, use_blocking=use_blocking, scaling=scaling,
        sigma=sigma, kappa=kappa, proj_impl=proj_impl,
        engine_impl=engine_impl, nbrs=nbrs, buckets=buckets,
        slot_F=(method == "sparse"), mg=mg_in)
    carry_new, cost_new = flows_carry_and_cost(
        net, phi_new, method, nbrs=nbrs, engine_impl=engine_impl,
        psum_axis=psum_axis, buckets=buckets)
    if faulted:
        phi_new, fs_new = fault_step_end(
            net, phi_new, k_cor, fault_plan, fs_mid, nbrs=nbrs,
            psum_axis=psum_axis)
        return phi_new, carry_new, cost_new, fs_new
    return phi_new, carry_new, cost_new


sgp_step_flows = jax.jit(
    _sgp_step_flows_impl,
    static_argnames=("variant", "method", "use_blocking", "scaling",
                     "kappa", "psum_axis", "proj_impl", "engine_impl",
                     "fault_plan"))


# ------------------------------------------------------------------- driver
def accept_step(new_cost: float, prev_cost: float, sigma: float,
                scaling: str, variant: str):
    """Shared accept/reject rule + sigma safeguard of both python-loop
    drivers (`run_chunk` and `distributed.run_distributed_chunk`).

    A non-finite cost is never accepted (NaN comparisons are False —
    without the guard a diverged step would poison the trajectory and
    auto-accept forever); under adaptive SGP an uphill step is rejected
    and sigma quadrupled (stopping past 1e12), accepted steps decay
    sigma toward 1.  Returns (accepted, sigma, stopped).

    All arithmetic is float32: the fused on-device driver carries sigma
    and the cost comparisons as f32 scalars, and the python-loop
    reference must walk a bitwise-identical sigma trajectory through
    any reject→accept sequence (f64 host math would diverge at the
    first σ decay after a rejection; see SIGMA_DECAY for why the decay
    is an explicit reciprocal multiply).
    """
    new32, prev32 = np.float32(new_cost), np.float32(prev_cost)
    accepted = bool(np.isfinite(new32)) and not (
        scaling == "adaptive" and variant == "sgp"
        and new32 > prev32 * np.float32(1.0 + 1e-12))
    stopped = False
    sigma32 = np.float32(sigma)
    if not accepted:
        sigma32 = sigma32 * np.float32(4.0)  # reject: step too aggressive
        if sigma32 > SIGMA_STOP:             # numerically stuck: stop
            stopped = True
    else:
        sigma32 = max(sigma32 * SIGMA_DECAY, np.float32(1.0))
    return accepted, float(sigma32), stopped


def _tol_converged(costs: list, tol: float) -> bool:
    """The drivers' relative-improvement early exit, f32 like the fused
    carry: |c[-2] - c[-1]| <= tol * max(c[-1], 1e-12), armed once more
    than 4 costs accumulated.  Callers apply it only after an ACCEPTED
    step — a rejected iteration leaves `costs` unchanged, so re-testing
    the same stale pair could only stop the run spuriously."""
    if not (tol > 0.0 and len(costs) > 4):
        return False
    c2, c1 = np.float32(costs[-2]), np.float32(costs[-1])
    return bool(abs(c2 - c1)
                <= np.float32(tol) * max(c1, np.float32(1e-12)))


@dataclasses.dataclass
class RunState:
    """Resumable host-side state of the `run` driver (NOT a pytree).

    Everything the python loop carries between iterations, so a caller
    can interleave iteration chunks with external events (topology
    churn, rate changes — see core.replay) and `run_chunk` picks up
    EXACTLY where the previous chunk stopped: chunked iteration is
    bitwise identical to one uninterrupted `run` (locked by
    tests/test_replay.py).  `phi` stays in whatever layout the loop
    iterates (edge-slot `PhiSparse` under method="sparse"); `it` is the
    GLOBAL iteration count (drives the paper-scaling refresh cadence
    across chunks); `flows` is the device-resident `FlowsCarry` of
    `phi` (every iterate's flows are computed exactly once — when it
    was the candidate — and carried here across chunk boundaries; None
    forces a re-evaluation at the next chunk's entry).
    """
    phi: object                      # Phi | PhiSparse iterate
    consts: SGPConsts
    nbrs: Optional[Neighbors]
    method: str
    costs: list
    min_scale: float = 0.05          # diag(M) floor consts were built with
    sigma: float = 1.0
    n_rejected: int = 0
    it: int = 0
    rng: Optional[jax.Array] = None
    stopped: bool = False            # sigma blow-up / tol early exit
    flows: Optional[FlowsCarry] = None   # flows of `phi` (device carry)
    buckets: object = None           # NeighborBuckets (bucketed sparse mode)
    fault_plan: object = None        # faults.FaultPlan (static injector arm)
    fault_state: object = None       # faults.FaultState (device carry)
    guard_cfg: object = None         # guards.GuardConfig (static policy)
    guard_state: object = None       # guards.GuardState (device carry)
    guard_events: list = dataclasses.field(default_factory=list)
    # [S] bool active-task mask of a dynamic task-slot pool
    # (events.TaskPool), or None for the fixed-S bitwise pass-through —
    # see TaskPool's compilation contract for when each is used
    active: Optional[jax.Array] = None


def init_run_state(net: CECNetwork, phi0, min_scale: float = 0.05,
                   method: str = "dense", rng: Optional[jax.Array] = None,
                   engine_impl: Optional[str] = None,
                   nbrs: Optional[Neighbors] = None,
                   bucketed: bool = False, buckets=None,
                   fault_plan=None, fault_rng: Optional[jax.Array] = None,
                   guards=None,
                   active: Optional[jax.Array] = None) -> RunState:
    """Set up the resumable driver state exactly as `run` would: build
    (or accept) the neighbor lists, convert a dense φ⁰ to slots under
    method="sparse", evaluate φ⁰'s flows + T⁰ (one solve, both carried)
    and the Eq. 16 constants.

    bucketed=True (sparse method only) additionally builds (or accepts
    via `buckets`) the degree-bucketed `NeighborBuckets` tiles and runs
    EVERY fixed-point recursion of the driver over them — bitwise the
    padded trajectory at ΣVb·Db per-round work (the power-law scaling
    mode; see core.network's layout docstring).

    fault_plan (faults.FaultPlan) arms the asynchrony/fault injectors,
    seeded by `fault_rng` (default PRNGKey(0), a stream separate from
    the Theorem-2 async `rng`); guards (guards.GuardConfig) arms the
    sentinel/rollback recovery layer anchored at φ⁰.  Either forces the
    fused driver in `run_chunk`.

    active ([S] bool device array) threads a dynamic task-slot pool's
    mask through every step: inactive slots' φ rows are frozen bitwise
    and (their r/a rows being zero) contribute exactly zero traffic and
    cost.  None is the fixed-S engine, bit for bit."""
    with obs.span("sgp.init"):
        if method == "sparse":
            nbrs = build_neighbors(net.adj) if nbrs is None else nbrs
            if bucketed and buckets is None:
                buckets = build_buckets(net.adj)
        else:
            nbrs = None
            buckets = None
        if method == "sparse" and not isinstance(phi0, PhiSparse):
            phi0 = phi_to_sparse(phi0, nbrs)   # boundary: iterate in slots
        with obs.span("sgp.init.flows"):
            fl0, T0 = flows_carry_and_cost_jit(net, phi0, method, nbrs=nbrs,
                                               engine_impl=engine_impl,
                                               buckets=buckets)
        with obs.span("sgp.init.sync"):
            obs.count("host_syncs")
            cost0 = float(T0)
        with obs.span("sgp.init.consts"):
            consts = make_consts(net, T0, min_scale)
        fault_state = None
        if fault_plan is not None:
            fault_state = init_fault_state(
                net, phi0, fl0, fault_plan, rng=fault_rng, method=method,
                nbrs=nbrs, engine_impl=engine_impl, buckets=buckets)
        guard_state = None
        if guards is not None:
            from .guards import init_guard_state   # lazy: guards imports sgp
            guard_state = init_guard_state(phi0, fl0, T0, guards)
        return RunState(phi=phi0, consts=consts, nbrs=nbrs, method=method,
                        costs=[cost0], min_scale=min_scale, rng=rng,
                        flows=fl0, buckets=buckets,
                        fault_plan=fault_plan, fault_state=fault_state,
                        guard_cfg=guards, guard_state=guard_state,
                        active=active)


def _accept_update_impl(phi_new, fl_new, cost_new, phi, fl, sigma, prev,
                        n_costs, n_rej, stopped, rng_new, rng, tol,
                        adaptive: bool):
    """`accept_step` + `_tol_converged` as branchless on-device selects
    — one driver iteration's carry update for the fused pipeline.

    Every operation is a single correctly-rounded f32 elementwise op
    (no multiply-add chains XLA could contract differently), so the
    carry walks EXACTLY the python reference's f32 trajectory; `stopped`
    freezes the whole carry, which is the python loop's `break` (later
    pipelined iterations become no-ops whose outputs are discarded).
    Returns the updated carry plus (take, live): whether this iteration
    accepted its candidate / was executed at all.
    """
    live = ~stopped
    acc = jnp.isfinite(cost_new)
    if adaptive:
        acc = jnp.logical_and(acc, ~(cost_new > prev * (1.0 + 1e-12)))
    take = jnp.logical_and(live, acc)

    def sel(a, b):
        return jnp.where(take, a, b)

    phi = jax.tree.map(sel, phi_new, phi)
    fl = jax.tree.map(sel, fl_new, fl)
    sigma_next = jnp.where(acc, jnp.maximum(sigma * SIGMA_DECAY, 1.0),
                           sigma * 4.0)
    sigma = jnp.where(live, sigma_next, sigma)
    stop_sigma = live & ~acc & (sigma > SIGMA_STOP)
    n_costs = n_costs + take.astype(jnp.int32)
    tol_hit = jnp.logical_and(
        tol > 0.0,
        jnp.abs(prev - cost_new) <= tol * jnp.maximum(cost_new, 1e-12))
    stop_tol = take & (n_costs > 4) & tol_hit
    prev = jnp.where(take, cost_new, prev)
    n_rej = n_rej + (live & ~acc).astype(jnp.int32)
    if rng_new is not None:
        rng = jnp.where(live, rng_new, rng)
    stopped = stopped | stop_sigma | stop_tol
    return phi, fl, sigma, prev, n_costs, n_rej, stopped, rng, take, live


_accept_update = jax.jit(_accept_update_impl, static_argnames=("adaptive",))

# History capacity of one block dispatch: a block runs up to BLOCK
# driver iterations and writes one slot of each history buffer per
# iteration.  It sizes the buffers, not the work done.
BLOCK = 256


def _sgp_block_impl(net: CECNetwork, phi, fl, consts: SGPConsts, sigma,
                    prev, n_costs, n_rej, stopped, tol, n, *, step,
                    adaptive: bool, fault_plan=None, fault_state=None,
                    rng=None, async_frac: float = 0.0, **step_kw):
    """Up to `n` (<= BLOCK) driver iterations as one on-device loop.

    Each pass is one `step` call (`sgp_step_flows`, or whatever the
    module holds under that name when the block is dispatched) followed
    by the `_accept_update_impl` carry update; the loop ends early once
    the carry stops (sigma blow-up / tol exit), so slots that never ran
    read live=False — the frozen no-op of a stopped carry, which leaves
    the fault state and the rng frozen too.  With a fault plan the
    `FaultState` rides the loop carry; with an `rng` each pass draws the
    Theorem-2 row masks (keep probability 1 - async_frac) from it.
    Every single-device driver dispatches the step only through this
    block: the fused path with dynamic `n`, the host loop and the
    guarded fused path with n=1 — one compiled loop body, so their
    trajectories are bitwise identical.

    Returns (carry, histories, candidate): the carry (phi, fl, sigma,
    prev, n_costs, n_rej, stopped, fault_state, rng), the [BLOCK]
    candidate cost / take / live buffers, and the last candidate
    (phi_new, fl_new, cost_new) — the carry's own (phi, fl, prev) if no
    pass ran.
    """
    faulted = fault_plan is not None and fault_state is not None

    def cond(c):
        return (c[0] < n) & ~c[7]

    def body(c):
        i, phi, fl, sigma, prev, n_costs, n_rej, stopped, fs, key, hist, _ = c
        mask_d = mask_r = key_new = None
        if key is not None:
            key_new, k1, k2 = jax.random.split(key, 3)
            mask_d = jax.random.bernoulli(k1, 1.0 - async_frac,
                                          (net.S, net.V))
            mask_r = jax.random.bernoulli(k2, 1.0 - async_frac,
                                          (net.S, net.V))
        out = step(net, phi, fl, consts, sigma=sigma, mask_data=mask_d,
                   mask_result=mask_r, fault_plan=fault_plan,
                   fault_state=fs, **step_kw)
        if faulted:
            phi_new, fl_new, cost_new, fs = out
        else:
            phi_new, fl_new, cost_new = out
        (phi, fl, sigma, prev, n_costs, n_rej, stopped, key, take,
         live) = _accept_update_impl(
            phi_new, fl_new, cost_new, phi, fl, sigma, prev, n_costs,
            n_rej, stopped, key_new, key, tol, adaptive)
        cost_h, take_h, live_h = hist
        hist = (cost_h.at[i].set(cost_new), take_h.at[i].set(take),
                live_h.at[i].set(live))
        return (i + 1, phi, fl, sigma, prev, n_costs, n_rej, stopped, fs,
                key, hist, (phi_new, fl_new, cost_new))

    hist0 = (jnp.zeros((BLOCK,), jnp.float32),
             jnp.zeros((BLOCK,), bool), jnp.zeros((BLOCK,), bool))
    c = jax.lax.while_loop(cond, body, (
        jnp.asarray(0, jnp.int32), phi, fl, sigma, prev, n_costs, n_rej,
        stopped, fault_state, rng, hist0, (phi, fl, prev)))
    return c[1:10], c[10], c[11]


_sgp_block = jax.jit(
    _sgp_block_impl,
    static_argnames=("step", "adaptive", "fault_plan", "async_frac",
                     "variant", "method", "use_blocking", "scaling", "kappa",
                     "proj_impl", "engine_impl"))


def _block_opts(state: RunState, *, variant: str, beta: float,
                allowed_data, allowed_result, async_frac: float,
                use_blocking: bool, scaling: str, kappa: float,
                proj_impl: Optional[str],
                engine_impl: Optional[str]) -> dict:
    """The chunk-invariant keywords of a `_dispatch_block` call."""
    return dict(variant=variant, beta=beta, allowed_data=allowed_data,
                allowed_result=allowed_result, async_frac=async_frac,
                method=state.method, use_blocking=use_blocking,
                scaling=scaling, kappa=kappa, proj_impl=proj_impl,
                engine_impl=engine_impl, nbrs=state.nbrs,
                buckets=state.buckets, fault_plan=state.fault_plan,
                adaptive=scaling == "adaptive" and variant == "sgp")


def _dispatch_block(net: CECNetwork, phi, fl, consts: SGPConsts, carry,
                    n: int, opts: dict, **kw):
    """One `_sgp_block` dispatch of `n` iterations from the device carry
    (sigma, prev, n_costs, n_rej, stopped, tol); `kw` holds the
    per-dispatch device inputs (fault_state, rng, active).  The step is
    read from the module at every call, so a step swapped in under the
    name `sgp_step_flows` gets its own executable instead of a stale
    one."""
    obs.count("sgp.blocks")
    return _sgp_block(net, phi, fl, consts, *carry, np.int32(n),
                      step=sgp_step_flows, **opts, **kw)


# the paper-scaling consts refresh must be the SAME executable in both
# drivers (eager vs jitted compilation of the d2_sup chains need not
# round identically), so both call this
_make_consts_jit = jax.jit(make_consts)

def _entry_flows(net: CECNetwork, state: RunState,
                 engine_impl: Optional[str]):
    """The chunk-entry flows carry: reuse the state's device-resident
    `FlowsCarry` of the current iterate, re-evaluating only if a caller
    dropped it (e.g. after mutating `state.phi` by hand)."""
    if state.flows is not None:
        return state.flows
    fl, _ = flows_carry_and_cost_jit(net, state.phi, state.method,
                                     nbrs=state.nbrs,
                                     engine_impl=engine_impl,
                                     buckets=state.buckets)
    return fl


def run_chunk(net: CECNetwork, state: RunState, n_iters: int,
              variant: str = "sgp", beta: float = 1.0,
              allowed_data=None, allowed_result=None,
              async_frac: float = 0.0,
              tol: float = 0.0, callback=None, use_blocking: bool = True,
              refresh_every: int = 20, scaling: str = "adaptive",
              kappa: float = 0.0, proj_impl: Optional[str] = None,
              engine_impl: Optional[str] = None,
              driver: Optional[str] = None) -> RunState:
    """Advance the driver `n_iters` iterations, updating `state` in
    place (and returning it).  This IS `run`'s loop body — `run` is
    init_run_state + one run_chunk — so interleaving chunks with events
    never diverges from the uninterrupted driver.  A state that stopped
    (tol early exit, sigma blow-up) stays stopped: further chunks are
    no-ops, exactly as the uninterrupted loop would not have continued.
    The paper-scaling consts refresh uses the `min_scale` the state was
    initialized with.

    driver : "fused" runs the whole chunk as an async on-device
        pipeline (`_run_chunk_fused`) with ZERO per-iteration host
        syncs and a single `device_get` at the end; "host" is the
        per-iteration python loop, the bitwise reference oracle
        (identical `costs`/sigma/rng trajectory: both drivers dispatch
        the SAME compiled `_sgp_block` executable — the host loop one
        iteration at a time, deciding each step with its own
        `accept_step` — and the fused accept/select kernel mirrors
        `accept_step`'s f32 arithmetic op-for-op).  None (default)
        picks "fused" unless a `callback` needs the host loop's
        per-iteration hook.

    The tol early-exit fires only after an ACCEPTED step (both
    drivers): a rejected iteration leaves `costs` unchanged, and
    re-testing the stale pair — as the driver did before the fused
    rewrite — could stop a resumed chunk before it accepted anything.
    """
    if driver is None:
        driver = "host" if callback is not None else "fused"
    if driver not in ("host", "fused"):
        raise ValueError(f"unknown driver {driver!r}")
    if async_frac > 0.0 and state.rng is None:
        # the Theorem-2 row masks draw from state.rng — without one the
        # masks silently never fired and async_frac was a no-op
        raise ValueError(
            "async_frac > 0 needs a driver rng: pass rng= to "
            "init_run_state (or ReplayEngine(rng=...), which splits it "
            "per inter-event segment)")
    if state.fault_plan is not None or state.guard_cfg is not None:
        if callback is not None:
            raise ValueError(
                "fault injection / guards run the fused on-device "
                "pipeline; per-iteration callbacks need a fault-free "
                "host loop")
        # host and fused are bitwise-identical, so silently routing a
        # robustness run through the fused carry changes nothing but
        # where the fault/guard selects live
        driver = "fused"
    if driver == "fused" and callback is not None:
        raise ValueError("driver='fused' runs the whole chunk on device; "
                         "per-iteration callbacks need driver='host'")
    if state.stopped or n_iters <= 0:
        return state
    if scaling == "paper":
        kappa = 1.0  # Eq. 16 verbatim
    fl = _entry_flows(net, state, engine_impl)
    if driver == "fused":
        return _run_chunk_fused(
            net, state, fl, n_iters, variant=variant, beta=beta,
            allowed_data=allowed_data, allowed_result=allowed_result,
            async_frac=async_frac, tol=tol, use_blocking=use_blocking,
            refresh_every=refresh_every, scaling=scaling, kappa=kappa,
            proj_impl=proj_impl, engine_impl=engine_impl)
    min_scale = state.min_scale
    phi, consts, nbrs = state.phi, state.consts, state.nbrs
    method, costs = state.method, state.costs
    sigma, n_rejected, rng = state.sigma, state.n_rejected, state.rng
    opts = _block_opts(state, variant=variant, beta=beta,
                       allowed_data=allowed_data,
                       allowed_result=allowed_result, async_frac=async_frac,
                       use_blocking=use_blocking, scaling=scaling,
                       kappa=kappa, proj_impl=proj_impl,
                       engine_impl=engine_impl)
    use_rng = async_frac > 0.0 and rng is not None
    # the block's own carry update is discarded (all but the rng it
    # split for the async masks): this loop decides with `accept_step`
    # on the host, the oracle the fused selects mirror
    idle = (jnp.asarray(0, jnp.int32), jnp.asarray(0, jnp.int32),
            jnp.asarray(False), jnp.float32(tol))
    done = state.it                  # iterations executed so far (global)
    for it in range(state.it, state.it + n_iters):
        done = it + 1
        if (scaling == "paper" and refresh_every and it > 0
                and it % refresh_every == 0):
            consts = _make_consts_jit(net, jnp.float32(costs[-1]),
                                      min_scale)
        carry = (jnp.float32(sigma), jnp.float32(costs[-1])) + idle
        out, _, (phi_new, fl_new, cost_new) = _dispatch_block(
            net, phi, fl, consts, carry, 1, opts,
            rng=rng if use_rng else None, active=state.active)
        if use_rng:
            rng = out[8]
        obs.count("host_syncs")
        new_cost = float(cost_new)   # the host driver's per-iteration sync
        accepted, sigma, stop = accept_step(new_cost, costs[-1], sigma,
                                            scaling, variant)
        if callback is not None:
            # aux of the iterate the step started FROM, as sgp_step
            # would report it (its cost IS the last accepted cost;
            # "flows" is the driver's FlowsCarry slice)
            aux = {"cost": jnp.float32(costs[-1]), "flows": fl,
                   "marginals": _marginals_jit(
                       net, phi, fl, method, nbrs=nbrs,
                       engine_impl=engine_impl,
                       slot_F=method == "sparse", buckets=state.buckets)}
        if not accepted:
            n_rejected += 1
            if stop:
                state.stopped = True
                break
        else:
            phi, fl = phi_new, fl_new
            costs.append(new_cost)
        if callback is not None:
            callback(it, phi, aux, accepted)
        if accepted and _tol_converged(costs, tol):
            state.stopped = True
            break
    obs.count("sgp.iterations", done - state.it)
    state.phi, state.consts, state.flows = phi, consts, fl
    state.sigma, state.n_rejected, state.rng = sigma, n_rejected, rng
    state.it = done
    return state


def _flat_histories(hists, counts):
    """Fetched per-dispatch histories as per-iteration host arrays: the
    first `k` slots of each ([BLOCK] buffers of a block, scalars of a
    single-iteration dispatch), concatenated in dispatch order."""
    return tuple(
        np.concatenate([np.reshape(h, -1)[:k] for h, k in zip(hist, counts)])
        if counts else np.zeros((0,)) for hist in hists)


def _fold_fused_histories(state, sigma, n_rej, stopped, cost_hist,
                          take_hist, live_hist, extra=None, counts=None):
    """The fused chunk's single device→host sync + bookkeeping
    writeback, shared by both drivers (`_run_chunk_fused`,
    `distributed._run_distributed_chunk_fused`) so the
    accept_step-mirroring accounting — which executed-and-accepted
    iterations append to `costs`, how `it` advances, when `stopped`
    latches — stays single-sourced.  `counts` (one per dispatch) says
    how many iterations each history entry holds (`_flat_histories`);
    None means one scalar per iteration.  `extra` is any additional
    device pytree to fetch in the SAME device_get (the guard layer's
    sentinel histories); the fetched host histories come back as
    (cost_hist, take_hist, live_hist, extra) so callers can render
    per-iteration records without a second sync."""
    obs.count("host_syncs")
    sigma, n_rej, stopped, cost_hist, take_hist, live_hist, extra = \
        jax.device_get((sigma, n_rej, stopped, cost_hist, take_hist,
                        live_hist, extra))
    if counts is not None:
        cost_hist, take_hist, live_hist = _flat_histories(
            (cost_hist, take_hist, live_hist), counts)
    for c, t, l in zip(cost_hist, take_hist, live_hist):
        if l and t:
            state.costs.append(float(c))
    state.sigma = float(sigma)
    state.n_rejected += int(n_rej)
    state.it += int(np.sum(live_hist))
    state.stopped = bool(stopped)
    return cost_hist, take_hist, live_hist, extra


class FusedStream:
    """The fused chunk's dispatch loop as a RESUMABLE object: a whole
    churn window — warm segments separated by same-graph rebaseline
    events — runs as one asynchronous dispatch stream with a single
    `device_get` at the end (`finish`).

    `_run_chunk_fused` is literally ``FusedStream(...).advance(n);
    finish()`` — one segment, no rebaselines — so the plain fused chunk
    and the streaming replay share every instruction, and the bitwise
    guarantees tests/test_fused_driver.py locks for the chunk carry over
    to the stream for free.

    `rebaseline` folds a same-graph churn event into the device carry
    exactly as `replay.ReplayEngine.apply_event` + `_init_state` would
    build a fresh `RunState` (the SAME eager `make_consts`, the same
    `flows_carry_and_cost_jit`, the same fault/guard re-inits on the
    same values, n_costs/n_rej/stopped reset, sigma carried by
    `warm_sigma`), but WITHOUT the
    per-event host syncs the event loop pays (`float(T0)` drains the
    pipeline; invariant checks drain it AND run an O(S·V²) closure).
    Identical eager ops on identical device values produce identical
    floats, so the stream is bitwise the event loop while the pipeline
    never drains — which is the whole point: a long schedule of
    same-graph events (rate scaling, source/destination re-draws)
    becomes one dispatch stream.  Topology events change the
    `Neighbors` tile shapes and must break the stream (finish, apply
    through the event loop, start a new stream).

    Iterations run as `_sgp_block` dispatches: up to BLOCK iterations
    per dispatch (paper scaling: up to the next consts refresh; a fault
    state and the async masks' rng ride the block's carry), one per
    dispatch — the block's candidate plus a separate guarded select —
    under guards, whose checkpoint cadence follows the host's iteration
    count.

    A stopped carry (sigma blow-up / tol exit) ends a block's loop, and
    later dispatches run no pass — the event loop's early return,
    expressed on device — and the next `rebaseline` un-freezes it, as
    `apply_event`'s fresh state does.
    """

    def __init__(self, net: CECNetwork, state: RunState, fl=None, *,
                 variant: str = "sgp", beta: float = 1.0,
                 allowed_data=None, allowed_result=None,
                 async_frac: float = 0.0, tol: float = 0.0,
                 use_blocking: bool = True, refresh_every: int = 20,
                 scaling: str = "adaptive", kappa: float = 0.0,
                 proj_impl: Optional[str] = None,
                 engine_impl: Optional[str] = None):
        if scaling == "paper":
            kappa = 1.0          # Eq. 16 verbatim (run_chunk's resolution)
        if async_frac > 0.0 and state.rng is None:
            raise ValueError(
                "async_frac > 0 needs a driver rng: pass rng= to "
                "init_run_state (or ReplayEngine(rng=...))")
        self.net = net
        self.state = state
        self._refresh_every = refresh_every
        self._opts = _block_opts(state, variant=variant, beta=beta,
                                 allowed_data=allowed_data,
                                 allowed_result=allowed_result,
                                 async_frac=async_frac,
                                 use_blocking=use_blocking, scaling=scaling,
                                 kappa=kappa, proj_impl=proj_impl,
                                 engine_impl=engine_impl)
        self._adaptive = self._opts["adaptive"]
        self._refresh = scaling == "paper" and refresh_every
        self._use_rng = async_frac > 0.0 and state.rng is not None
        self._faulted = (state.fault_plan is not None
                         and state.fault_state is not None)
        self._guarded = (state.guard_cfg is not None
                         and state.guard_state is not None)
        if self._guarded:
            from .guards import _guarded_update   # lazy: guards imports sgp
            self._guarded_update = _guarded_update
        self._phi, self._consts = state.phi, state.consts
        self._fl = fl if fl is not None else _entry_flows(net, state,
                                                          engine_impl)
        self._active = state.active   # task-pool mask (None = fixed S)
        self._rng = state.rng
        self._fs, self._gs = state.fault_state, state.guard_state
        self._sigma = jnp.float32(state.sigma)
        self._prev = jnp.float32(state.costs[-1])
        self._n_costs = jnp.asarray(len(state.costs), jnp.int32)
        self._n_rej = jnp.asarray(0, jnp.int32)
        self._stopped = jnp.asarray(bool(state.stopped))
        self._tol32 = jnp.float32(tol)
        # per dispatch: its candidate cost / take / live history and
        # how many iterations it was given
        self._cost_h, self._take_h, self._live_h, self._n_h = [], [], [], []
        self._code_h, self._roll_h, self._ck_h = [], [], []
        self._it = state.it           # per-segment iteration counter
        self._seg_it0 = state.it      # `it` the open segment began at
        self._markers: list = []      # closed segments' boundary scalars
        self._finished = False

    # ----------------------------------------------------------- advance
    def advance(self, n_iters: int) -> "FusedStream":
        """Dispatch `n_iters` driver iterations asynchronously — python
        never blocks on a device value.  Candidate costs and
        accepted/executed flags accumulate as device histories for
        `finish`."""
        assert not self._finished, "stream already finished"
        every = self._refresh_every
        obs.count("sgp.iterations", n_iters)
        with obs.span("sgp.advance"):
            it, end = self._it, self._it + n_iters
            while it < end:
                if self._refresh and it > 0 and it % every == 0:
                    fresh = _make_consts_jit(self.net, self._prev,
                                             self.state.min_scale)
                    stopped = self._stopped
                    self._consts = jax.tree.map(
                        lambda old, new: jnp.where(stopped, old, new),
                        self._consts, fresh)
                if self._guarded:
                    self._iterate(it)
                    it += 1
                    continue
                n = min(end - it, BLOCK)
                if self._refresh:
                    n = min(n, every - it % every)
                with obs.span("sgp.block"):
                    carry, hist, _ = self._dispatch(n)
                (self._phi, self._fl, self._sigma, self._prev,
                 self._n_costs, self._n_rej, self._stopped, fs, rng) = carry
                self._fs = fs if self._faulted else self._fs
                self._rng = rng if self._use_rng else self._rng
                self._push(n, *hist)
                it += n
        self._it = end
        return self

    def _dispatch(self, n: int):
        """One `_sgp_block` dispatch of `n` iterations from the stream's
        carry, its fault state and its rng."""
        return _dispatch_block(
            self.net, self._phi, self._fl, self._consts,
            (self._sigma, self._prev, self._n_costs, self._n_rej,
             self._stopped, self._tol32), n, self._opts,
            fault_state=self._fs if self._faulted else None,
            rng=self._rng if self._use_rng else None, active=self._active)

    def _push(self, n, cost, take, live):
        self._cost_h.append(cost)
        self._take_h.append(take)
        self._live_h.append(live)
        self._n_h.append(n)

    def _iterate(self, it: int):
        """One guarded iteration: the block's candidate at n=1, then the
        guarded select on the stream's own carry (the sentinels and the
        checkpoint cadence need the host's iteration number)."""
        state = self.state
        with obs.span("sgp.step"):
            carry, _, (phi_new, fl_new, cost_new) = self._dispatch(1)
        # a stopped carry runs no pass, so the fault state and the rng
        # stay frozen too and chunked resumption past a stop stays
        # bitwise (the dead dispatches must not advance them)
        self._fs = carry[7] if self._faulted else self._fs
        rng_new = carry[8] if self._use_rng else None
        cfg = state.guard_cfg
        do_ckpt = bool(cfg.checkpoint_every
                       and it % cfg.checkpoint_every == 0)
        with obs.span("sgp.accept"):
            (self._phi, self._fl, self._sigma, self._prev,
             self._n_costs, self._n_rej, self._stopped, self._rng,
             take, live, self._gs, code, rolled, ck_cost) = \
                self._guarded_update(
                    phi_new, fl_new, cost_new, self._phi, self._fl,
                    self._sigma, self._prev, self._n_costs,
                    self._n_rej, self._stopped, rng_new, self._rng,
                    self._tol32, self._gs, state.nbrs,
                    adaptive=self._adaptive, cfg=cfg, do_ckpt=do_ckpt)
        self._code_h.append(code)
        self._roll_h.append(rolled)
        self._ck_h.append(ck_cost)
        self._push(1, cost_new, take, live)

    # -------------------------------------------------------- rebaseline
    def rebaseline(self, net_new: CECNetwork, repair=None, *,
                   fault_rng=None, rng=None, active=None) -> "FusedStream":
        """Fold one SAME-GRAPH churn event into the carry without a
        host sync: close the open segment (its boundary scalars are
        snapshotted as device refs and fetched in `finish`'s single
        device_get) and open the next one with the fresh-`RunState`
        re-baseline the replay event loop performs.

        `repair`, if given, maps the current device φ to the repaired
        one (routing events: `refeasibilize_sparse_samegraph`, device
        ops only); rate events pass None — the iterate stays
        feasible as-is.  `net_new.adj` must equal the adjacency the
        state's `Neighbors` were built from; topology events must break
        the stream instead.  `fault_rng`/`rng` re-key the per-segment
        fault and Theorem-2 async-mask streams (the same splits
        `ReplayEngine._init_state` would pass).  `active` swaps in a
        task pool's updated slot mask (TaskArrive/TaskDepart events —
        same [S] shape, so the step's compiled executable is reused;
        None leaves the mask unchanged, it never reverts to fixed-S
        mid-stream)."""
        assert not self._finished, "stream already finished"
        state = self.state
        if active is not None:
            self._active = active
            state.active = active
        phi = self._phi if repair is None else repair(self._phi)
        fl, T0 = flows_carry_and_cost_jit(
            net_new, phi, state.method, nbrs=state.nbrs,
            engine_impl=self._opts["engine_impl"], buckets=state.buckets)
        self._markers.append(dict(
            end=sum(self._n_h), it0=self._seg_it0,
            prev=self._prev, n_rej=self._n_rej, T0=T0))
        self.net = net_new
        self._phi, self._fl = phi, fl
        # the EAGER make_consts, exactly as init_run_state builds the
        # fresh segment's Eq. 16 constants (the jitted compilation need
        # not round the d2_sup chains identically — see _make_consts_jit)
        self._consts = make_consts(net_new, T0, state.min_scale)
        # `warm_sigma` on the device value: the segment's sigma carries
        # over unless it blew up
        self._sigma = jnp.where(self._sigma > SIGMA_STOP, jnp.float32(1.0),
                                self._sigma)
        # bitwise jnp.float32(float(T0)), the fresh chunk's prologue
        self._prev = T0.astype(jnp.float32)
        self._n_costs = jnp.asarray(1, jnp.int32)
        self._n_rej = jnp.asarray(0, jnp.int32)
        self._stopped = jnp.asarray(False)
        self._it = 0
        self._seg_it0 = 0
        if state.fault_plan is not None:
            self._fs = init_fault_state(
                net_new, phi, fl, state.fault_plan, rng=fault_rng,
                method=state.method, nbrs=state.nbrs,
                engine_impl=self._opts["engine_impl"], buckets=state.buckets)
        if state.guard_cfg is not None:
            from .guards import init_guard_state
            self._gs = init_guard_state(phi, fl, T0, state.guard_cfg)
        if rng is not None:
            self._rng = rng
        return self

    # ------------------------------------------------------------ finish
    def _render_guard_events(self, extra_h, cost_h, live_h, s, e, it0):
        """Host-side GuardEvent rendering for history slice [s, e), with
        per-segment iteration numbering starting at `it0` (each replay
        segment's fresh state restarts `it` at 0, so the event loop's
        GuardEvent.it is within-segment — mirrored here)."""
        if not self._guarded or extra_h is None:
            return []
        from .guards import GuardEvent, SENTINEL_NAMES
        codes, rolls, cks = extra_h
        out = []
        for i in range(s, e):
            if live_h[i] and int(codes[i]) > 0:
                out.append(GuardEvent(
                    it=it0 + (i - s), sentinel=SENTINEL_NAMES[int(codes[i])],
                    action="rollback" if bool(rolls[i]) else "stop",
                    cost=float(cost_h[i]),
                    restored_cost=float(cks[i]) if bool(rolls[i]) else None))
        return out

    def finish(self) -> list:
        """The stream's single device→host sync.

        With no rebaselines this IS `_run_chunk_fused`'s epilogue:
        append semantics on `self.state` (costs extended, `it` and
        `n_rejected` advanced) and an empty return.  With rebaselines
        it returns one dict per CLOSED segment — ``accepted`` costs,
        ``executed`` iteration count, ``cost_before``/``cost_after``
        (the event's boundary costs), per-segment ``n_rejected`` and
        rendered ``guard_events`` — plus the trailing OPEN segment's
        dict last, and leaves `self.state` as that last segment's warm
        `RunState` (replace semantics: exactly what the event loop's
        `_init_state` + `run_chunk` would have left behind)."""
        assert not self._finished, "stream already finished"
        self._finished = True
        with obs.span("sgp.finish"):
            return self._finish()

    def _finish(self) -> list:
        state = self.state
        extra = ((self._code_h, self._roll_h, self._ck_h)
                 if self._guarded else None)
        if not self._markers:
            cost_h, _, live_h, extra_h = _fold_fused_histories(
                state, self._sigma, self._n_rej, self._stopped,
                self._cost_h, self._take_h, self._live_h, extra,
                counts=self._n_h)
            if self._guarded:
                state.guard_events.extend(self._render_guard_events(
                    extra_h, cost_h, live_h, 0, len(cost_h),
                    self._seg_it0))
                state.guard_state = self._gs
            if self._faulted:
                state.fault_state = self._fs
            state.phi, state.flows, state.consts = \
                self._phi, self._fl, self._consts
            if self._use_rng:
                state.rng = self._rng
            return []
        obs.count("host_syncs")
        (sigma, n_rej, stopped, cost_h, take_h, live_h, extra_h,
         marks) = jax.device_get((
            self._sigma, self._n_rej, self._stopped, self._cost_h,
            self._take_h, self._live_h, extra,
            [(m["prev"], m["n_rej"], m["T0"]) for m in self._markers]))
        cost_h, take_h, live_h = _flat_histories((cost_h, take_h, live_h),
                                                 self._n_h)
        bounds = [0] + [m["end"] for m in self._markers] + [len(cost_h)]
        it0s = [m["it0"] for m in self._markers] + [self._seg_it0]
        segs = []
        for k in range(len(bounds) - 1):
            s, e = bounds[k], bounds[k + 1]
            acc = [float(c) for c, t, l in zip(cost_h[s:e], take_h[s:e],
                                              live_h[s:e]) if l and t]
            seg = dict(accepted=acc,
                       executed=int(np.sum(live_h[s:e])) if e > s else 0,
                       guard_events=self._render_guard_events(
                           extra_h, cost_h, live_h, s, e, it0s[k]))
            if k < len(self._markers):
                prev_k, nrej_k, T0_k = marks[k]
                seg["cost_before"] = float(prev_k)
                seg["n_rejected"] = int(nrej_k)
                seg["cost_after"] = float(T0_k)
            else:
                seg["n_rejected"] = int(n_rej)
            segs.append(seg)
        # leave `state` as the LAST segment's warm RunState — the fresh
        # state apply_event's _init_state would have built, advanced by
        # the open segment's iterations
        last = segs[-1]
        state.costs = [float(marks[-1][2])] + list(last["accepted"])
        state.sigma = float(sigma)
        state.n_rejected = int(n_rej)
        state.it = last["executed"]
        state.stopped = bool(stopped)
        state.guard_events = list(last["guard_events"])
        if self._guarded:
            state.guard_state = self._gs
        if self._faulted:
            state.fault_state = self._fs
        state.phi, state.flows, state.consts = \
            self._phi, self._fl, self._consts
        state.rng = self._rng
        return segs


def _run_chunk_fused(net: CECNetwork, state: RunState, fl, n_iters: int,
                     variant: str, beta: float, allowed_data,
                     allowed_result, async_frac: float, tol: float,
                     use_blocking: bool, refresh_every: int, scaling: str,
                     kappa: float, proj_impl: Optional[str],
                     engine_impl: Optional[str]) -> RunState:
    """The whole accept/reject loop with ZERO host syncs inside: an
    async pipeline of the SAME compiled block the python reference runs.

    One `FusedStream` segment, advanced `n_iters` and finished — on the
    plain path one `_sgp_block` dispatch per BLOCK iterations, whose
    candidate costs and accepted/executed flags come back in ONE
    `device_get` after the last dispatch, the chunk's single
    device→host sync.  Because the block executable is literally the
    host loop's jit-cache entry (dynamic iteration count) and the select
    arithmetic mirrors `accept_step`'s f32 ops, the resulting
    `costs`/sigma/rng/φ trajectory is bitwise identical to the python
    loop (locked by tests/test_fused_driver.py).  A mid-chunk stop
    (sigma blow-up / tol) ends the block's loop on device; the later
    dispatches of the chunk run no pass.
    """
    with obs.span("sgp.open"):
        stream = FusedStream(net, state, fl=fl, variant=variant, beta=beta,
                             allowed_data=allowed_data,
                             allowed_result=allowed_result,
                             async_frac=async_frac, tol=tol,
                             use_blocking=use_blocking,
                             refresh_every=refresh_every, scaling=scaling,
                             kappa=kappa, proj_impl=proj_impl,
                             engine_impl=engine_impl)
    stream.advance(n_iters)
    stream.finish()
    return state


def run_opt_keys(fn=None) -> frozenset:
    """Keyword surface a caller may forward to a driver as a `run_opts`
    dict — `run_chunk` by default, or any driver `fn`.  Positional
    driver inputs (net/state/phi0/n_iters) are excluded: wrappers own
    those."""
    import inspect
    fn = run_chunk if fn is None else fn
    return frozenset(inspect.signature(fn).parameters) - {
        "net", "state", "phi0", "n_iters"}


def validate_run_opts(opts: Optional[dict], supported, context: str,
                      reserved=()) -> dict:
    """Reject unsupported/reserved `run_opts` keys LOUDLY.

    Forwarding dicts through **kwargs turns a typo'd or unsupported
    option into silently-default behavior mid-flight (the PR-8 lesson
    from the distributed replay driver); every layer that accepts a
    run_opts dict funnels it through here instead.  `reserved` names
    keys the wrapper sets itself (passing one is a conflict, not an
    unknown).  Returns a copy of `opts` safe to ** into the driver.
    """
    opts = dict(opts or {})
    clash = set(opts) & set(reserved)
    if clash:
        raise ValueError(
            f"run_opts {sorted(clash)} are set by {context} itself — "
            "pass them through its own arguments instead")
    unknown = set(opts) - set(supported)
    if unknown:
        raise ValueError(
            f"run_opts {sorted(unknown)} are not supported by {context}; "
            f"supported keys: {sorted(set(supported) - set(reserved))}")
    return opts


def run(net: CECNetwork, phi0, n_iters: int = 200,
        variant: str = "sgp", beta: float = 1.0,
        allowed_data=None, allowed_result=None,
        min_scale: float = 0.05, method: str = "dense",
        rng: Optional[jax.Array] = None, async_frac: float = 0.0,
        tol: float = 0.0, callback=None, use_blocking: bool = True,
        refresh_every: int = 20, scaling: str = "adaptive",
        kappa: float = 0.0, proj_impl: Optional[str] = None,
        engine_impl: Optional[str] = None,
        driver: Optional[str] = None, bucketed: bool = False,
        fault_plan=None, fault_rng: Optional[jax.Array] = None,
        guards=None):
    """Driver around the jitted step.

    fault_plan (faults.FaultPlan, seeded by fault_rng) arms on-device
    asynchrony/fault injection; guards (guards.GuardConfig) arms the
    sentinel/rollback recovery layer — see those modules.  Either one
    forces the fused driver; the history then also carries
    "guard_events"/"n_corrupt".

    driver="fused" (the default when no callback is given) runs each
    chunk of iterations — accept/reject, sigma safeguard, tol exit and
    all — as an async on-device pipeline with a single host sync at the
    end; driver="host" is the per-iteration python loop, kept as the
    bitwise reference oracle (identical cost/sigma/rng trajectories on
    CPU).  See `run_chunk`.

    method="sparse" precomputes the neighbor lists once (numpy, outside
    jit), converts φ⁰ to the edge-slot `PhiSparse` layout at the
    boundary, and iterates NATIVELY in that layout — no [S, V, V+1]
    array is materialized anywhere in the loop.  bucketed=True
    additionally builds degree-bucketed `NeighborBuckets` tiles and
    runs every fixed-point recursion over them (bitwise the padded
    trajectory at ΣVb·Db per-round work — the power-law scaling mode).  The returned φ matches
    the input layout: a dense `Phi` in, a dense `Phi` back (one
    conversion after the loop); a `PhiSparse` in, a `PhiSparse` back.
    engine_impl picks the message-passing backend
    (kernels.ops.edge_rounds; None = the backend default,
    `kernels.ops.default_impl`).

    callback, if given, is invoked as ``callback(it, phi, aux, accepted)``
    where `phi` is the iterate AFTER the accept/reject decision (the new
    iterate on accepted steps, the reverted one otherwise), `accepted`
    says which happened, and `aux` (cost/flows/marginals) describes the
    iterate the step started FROM — `aux["flows"]` is the driver's
    `FlowsCarry` slice (t_data/t_result/F/G; the per-task f_data /
    f_result link flows are no longer materialized per iteration —
    recompute via `compute_flows` if a callback needs them).  Under method="sparse" the callback
    sees the edge-slot `PhiSparse` iterate (convert with
    `sparse_to_phi` if dense coordinates are needed).

    async_frac > 0 simulates Theorem-2 asynchrony: each iteration only a
    random fraction of (node, task) rows update.

    scaling="paper": Eq. 16 constants, refreshed from the CURRENT cost
    every `refresh_every` iterations.  Sound: descent is monotone
    (Theorem 2), so all future iterates stay in the T^t-sublevel set and
    A(T^t) <= A(T^0) remains a valid curvature bound.

    scaling="adaptive" (default): Eq. 16 structure with current-flow
    curvature × safety factor sigma.  Monotone descent is ENFORCED:
    an uphill step is rejected (φ reverted) and sigma ×= 4; accepted
    steps decay sigma toward 1.  Converges orders of magnitude faster on
    instances with small-capacity links, where the paper's sublevel-sup
    constants are astronomically conservative.

    The loop itself is resumable: `init_run_state` + repeated
    `run_chunk` calls walk the identical trajectory and let callers
    (core.replay's streaming churn engine) interleave events between
    chunks.

    Returns (phi_final, history dict of per-iteration costs).
    """
    with obs.span("sgp.run"):
        dense_in = not isinstance(phi0, PhiSparse)
        state = init_run_state(net, phi0, min_scale=min_scale,
                               method=method, rng=rng,
                               engine_impl=engine_impl,
                               bucketed=bucketed, fault_plan=fault_plan,
                               fault_rng=fault_rng, guards=guards)
        state = run_chunk(net, state, n_iters, variant=variant, beta=beta,
                          allowed_data=allowed_data,
                          allowed_result=allowed_result,
                          async_frac=async_frac, tol=tol, callback=callback,
                          use_blocking=use_blocking,
                          refresh_every=refresh_every, scaling=scaling,
                          kappa=kappa, proj_impl=proj_impl,
                          engine_impl=engine_impl, driver=driver)
        phi = state.phi
        if method == "sparse" and dense_in:
            # boundary: back to dense
            phi = sparse_to_phi(phi, state.nbrs, net.V)
        hist = {"costs": state.costs, "final_cost": state.costs[-1],
                "n_rejected": state.n_rejected}
        if guards is not None:
            hist["guard_events"] = state.guard_events
        if state.fault_state is not None:
            hist["n_corrupt"] = int(state.fault_state.n_corrupt)
        return phi, hist
