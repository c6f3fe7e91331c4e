"""The CEC flow model (paper §II).

State layout (fixed-shape, jit-friendly; V nodes, S tasks):

  adj        [V, V]   bool   directed edges (i -> j)
  dest       [S]      int    destination node of each task
  r          [S, V]   float  exogenous data input rates r_i(d,m)
  a          [S]      float  result-size ratio a_m of the task's type
  w          [S, V]   float  computation weight w_{i, m_s}
  task_type  [S]      int    computation type m of each task (bookkeeping)

Routing/offloading strategy phi (paper's φ), in one of two layouts:

`Phi` — the dense reference layout (public API, human-readable):

  data    [S, V, V+1]  φ⁻: columns 0..V-1 forward to neighbor j, column V
                       is the local-offload fraction φ⁻_i0 ("0" in paper)
  result  [S, V, V]    φ⁺: result forwarding fractions; row dest[s] ≡ 0

`PhiSparse` — the edge-slot layout the sparse engine iterates in
(aligned to `Neighbors.out_nbr`, see the slot convention below):

  data    [S, V, Dmax]  φ⁻ on out-edge slots: data[s, i, e] is the
                        fraction forwarded along edge i -> out_nbr[i, e]
  local   [S, V, 1]     the local-compute column φ⁻_i0 (kept as its own
                        [.., 1] tensor so the QP rows are
                        concat([data, local]) with no dense detour)
  result  [S, V, Dmax]  φ⁺ on the same out-edge slots; row dest[s] ≡ 0

Slot semantics: `data`/`result` slots with `out_mask[i, e] == False` are
PADDING — they carry no meaning, are ignored (masked to zero) by every
consumer, and may hold arbitrary garbage; `local` is always meaningful.
Conversion contract: `phi_to_sparse` / `sparse_to_phi` are mutually
inverse wherever φ is feasible — `sparse_to_phi(phi_to_sparse(p)) == p`
bitwise whenever p puts mass only on edges + the local column (any
feasible φ), and `phi_to_sparse(sparse_to_phi(q)) == q` bitwise up to
zeroed padding slots.  Under `method="sparse"` the whole SGP iteration
(flows, marginals, blocked sets, QP projection, drivers, shard_map)
consumes and produces `PhiSparse` directly, so no `[S, V, V+1]` array is
ever materialized inside the loop; `Phi` remains the reference layout at
the public boundary (scenario construction, `spt_phi`, optimality
checks, plotting).

Flow computation: with loop-free φ the supports are DAGs, so the traffic
recursions (1)-(2) are nonsingular sparse triangular-like systems

  t⁻ = r + (Φ⁻)ᵀ t⁻        (data traffic)
  t⁺ = a·g + (Φ⁺)ᵀ t⁺      (result traffic),  g = t⁻ ⊙ φ_local

with three interchangeable engines (`method=`):

  "dense"      batched ``jnp.linalg.solve`` on [S, V, V] systems —
               O(S·V³); the reference for V up to a few hundred.
  "broadcast"  |V|-round dense fixed-point iteration mirroring the
               paper's hop-by-hop broadcast — O(S·V²·V) worst case;
               what the distributed shard_map version uses.
  "sparse"     neighbor-list message passing (this module's `Neighbors`):
               edge quantities live in max-degree-padded [S, V, Dmax]
               arrays aligned to `nbr[V, Dmax]` index lists, each round
               is one gather + masked reduce, and rounds stop as soon as
               the fixed point is reached — O(S·V·Dmax·diam) total.
               This is the engine that scales to V ~ 10³⁺ arbitrary
               topologies, exactly because Algorithm 1 is distributed.
               With `buckets=` (a `NeighborBuckets` from
               `build_buckets`) the recursions run over DEGREE-BUCKETED
               tiles instead — O(S·E·diam), see below — which is what
               takes power-law topologies to V ~ 10⁴⁺.

The sparse rounds themselves dispatch through
`kernels.ops.edge_rounds(..., impl=engine_impl)`:

  engine_impl=None         backend default — the jnp reference on
                           every backend (`kernels.ops._TPU_DEFAULT`)
  engine_impl="ref"        force the jnp one-gather-per-round path
  engine_impl="pallas"     force the fused Pallas kernel (index tiles
                           resident in VMEM, the whole early-exit
                           while-loop in ONE launch; the TPU compiler
                           refuses its in-kernel gather)
  engine_impl="pallas_interpret"  kernel body through the Pallas
                           interpreter (CPU validation mode)

`compute_flows`, `compute_marginals`, `sgp_step` and `run` all thread
an `engine_impl=` argument down to this switch.

Sparse layout convention (used by marginals.py and sgp.py too): for an
edge slot (i, e) with `nbrs.out_mask[i, e]`, `nbrs.out_nbr[i, e] = j`
names the edge i -> j; padded slots point at node 0 and are masked.
`x_sp[s, i, e]` then stores the per-edge quantity (φ_ij, δ_ij, f_ij…).
`Neighbors` must be precomputed from a *concrete* adjacency (numpy,
outside jit) via `build_neighbors` and threaded through `nbrs=`.

BUCKETED edge-slot layout (`NeighborBuckets` via `build_buckets`): the
[V, Dmax] tiling pads every node to the GLOBAL max degree, so on
power-law / hub-and-spoke graphs (one hub of degree ~√V·m, a long tail
of degree ~m) nearly every lane is padding — the padded engine's
per-round work V·Dmax can exceed the edge count |E| by 50×.  The
bucketed layout groups nodes into power-of-two degree classes, each a
CSR-style [Vb, Db] tile (node list `nodes`, state-gather `nbr`, weight
-gather `wsrc`/`wslot`, `mask`), so per-round work is ΣVb·Db < 2·|E|
regardless of the degree distribution.  φ itself (PhiSparse) and every
other slot array KEEP the [S, V, Dmax] layout — buckets are a VIEW
used inside the fixed-point recursions (the tiles gather the lanes
they own), not a second φ layout, so projections, drivers, replay and
the conversion contract above are untouched.  Bitwise identity with
the padded engine is guaranteed by construction: a bucket row reads
exactly the lanes the padded row holds (out-edges pack ascending at
slots 0..deg-1), and `kernels.ref.fold_reduce` fixes a tile-width-
stable reduction order shared by both engines, so flows, marginals,
blocked sets and whole SGP trajectories agree bit-for-bit (locked by
tests/test_bucketed.py on every Table II row).  Like `Neighbors`,
buckets come from a *concrete* adjacency (`build_buckets`, LRU-
memoized) and thread through `buckets=` as a jit-dynamic pytree.
"""
from __future__ import annotations

import dataclasses
from collections import OrderedDict
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from .costs import Cost
from ..kernels import ops as kernel_ops

LOCAL = -1  # alias: phi.data[..., -1] is the local-offload column


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class CECNetwork:
    adj: jnp.ndarray        # [V, V] bool
    link_cost: Cost         # params [V, V]
    comp_cost: Cost         # params [V]
    dest: jnp.ndarray       # [S] int32
    r: jnp.ndarray          # [S, V]
    a: jnp.ndarray          # [S]
    w: jnp.ndarray          # [S, V]
    task_type: jnp.ndarray  # [S] int32

    @property
    def V(self) -> int:
        return self.adj.shape[0]

    @property
    def S(self) -> int:
        return self.dest.shape[0]


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Phi:
    """Dense reference layout of the routing strategy φ (module docstring)."""
    data: jnp.ndarray    # [S, V, V+1]
    result: jnp.ndarray  # [S, V, V]


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PhiSparse:
    """Edge-slot layout of φ, aligned to `Neighbors.out_nbr` index tiles.

    See the module docstring for slot semantics (data/result slots vs
    the local-compute column) and the `phi_to_sparse`/`sparse_to_phi`
    conversion contract.  Padding slots (out_mask False) are ignored by
    every consumer and may hold garbage.
    """
    data: jnp.ndarray    # [S, V, Dmax]  φ⁻ out-edge slots
    local: jnp.ndarray   # [S, V, 1]     φ⁻_i0 local-compute column
    result: jnp.ndarray  # [S, V, Dmax]  φ⁺ out-edge slots

    @property
    def S(self) -> int:
        return self.data.shape[0]

    @property
    def Dmax(self) -> int:
        return self.data.shape[-1]


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Neighbors:
    """Fixed max-degree padded neighbor lists of a concrete adjacency.

    Out-edges of i sit in ascending-j order at slots e < out_deg(i);
    `in_slot[j, e]` is the position of edge (in_nbr[j, e] -> j) inside
    the *sender's* out-list, so incoming messages gather straight from
    [S, V, Dmax] edge arrays without any transpose.
    """
    out_nbr: jnp.ndarray   # [V, Dmax]  int32, j of edge (i -> j); pad = 0
    out_mask: jnp.ndarray  # [V, Dmax]  bool, slot is a real edge
    in_nbr: jnp.ndarray    # [V, Dmax_in] int32, i of edge (i -> j); pad = 0
    in_slot: jnp.ndarray   # [V, Dmax_in] int32, slot of (i -> j) in i's list
    in_mask: jnp.ndarray   # [V, Dmax_in] bool

    @property
    def V(self) -> int:
        return self.out_nbr.shape[0]

    @property
    def Dmax(self) -> int:
        return self.out_nbr.shape[1]


# build_neighbors is O(V·deg) python; callers that omit `nbrs=` (one-off
# total_cost / compute_flows calls) would re-pad the same adjacency every
# call, so results are memoized on the adjacency bytes.  The cache is a
# bounded TRUE LRU (hits refresh recency): long churn-replay streams
# alternate between a handful of live adjacencies (cut -> restore ->
# cut...) far more than _NBR_CACHE_MAX distinct ones, so the working set
# stays resident instead of being evicted in insertion (FIFO) order.
_NBR_CACHE: OrderedDict = OrderedDict()
_NBR_CACHE_MAX = 32


def _to_host(x) -> np.ndarray:
    """`np.asarray(x)`, counted as a host sync when `x` is on the device."""
    if isinstance(x, jax.Array):
        obs.count("host_syncs")
    return np.asarray(x)


def _adj_key(A: np.ndarray):
    return (A.shape[0], A.tobytes())


def _lru_get(cache: OrderedDict, key):
    hit = cache.get(key)
    if hit is not None:
        cache.move_to_end(key)
    return hit


def _lru_put(cache: OrderedDict, key, value):
    cache[key] = value
    while len(cache) > _NBR_CACHE_MAX:
        cache.popitem(last=False)


def build_neighbors(adj) -> Neighbors:
    """Precompute `Neighbors` from a concrete [V, V] bool adjacency.

    Memoized per adjacency (bounded LRU on the adjacency bytes): repeat
    calls on the same (or an equal) matrix return the cached padded
    lists instead of re-building them.
    """
    if isinstance(adj, jax.core.Tracer):
        raise ValueError(
            "build_neighbors needs a concrete adjacency; precompute it "
            "outside jit and pass it through the `nbrs=` argument")
    with obs.span("seed.neighbors"):
        return _build_neighbors_impl(adj)


def _build_neighbors_impl(adj) -> Neighbors:
    A = np.asarray(_to_host(adj), dtype=bool)
    key = _adj_key(A)
    cached = _lru_get(_NBR_CACHE, key)
    if cached is not None:
        return cached
    V = A.shape[0]
    d_out = max(int(A.sum(axis=1).max()), 1)
    d_in = max(int(A.sum(axis=0).max()), 1)
    out_nbr = np.zeros((V, d_out), np.int32)
    out_mask = np.zeros((V, d_out), bool)
    slot_of = np.zeros((V, V), np.int32)  # slot of edge (i, j) in i's list
    for i in range(V):
        js = np.nonzero(A[i])[0]
        out_nbr[i, :len(js)] = js
        out_mask[i, :len(js)] = True
        slot_of[i, js] = np.arange(len(js))
    in_nbr = np.zeros((V, d_in), np.int32)
    in_slot = np.zeros((V, d_in), np.int32)
    in_mask = np.zeros((V, d_in), bool)
    for j in range(V):
        ks = np.nonzero(A[:, j])[0]
        in_nbr[j, :len(ks)] = ks
        in_slot[j, :len(ks)] = slot_of[ks, j]
        in_mask[j, :len(ks)] = True
    nbrs = Neighbors(jnp.asarray(out_nbr), jnp.asarray(out_mask),
                     jnp.asarray(in_nbr), jnp.asarray(in_slot),
                     jnp.asarray(in_mask))
    _lru_put(_NBR_CACHE, key, nbrs)
    return nbrs


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class EdgeBuckets:
    """Degree-bucketed CSR-style tiles of ONE edge direction.

    Nodes are grouped by power-of-two degree class; bucket k holds the
    (ascending-id) nodes whose degree rounds up to width Db_k, as a
    [Vb_k, Db_k] tile — so per-round message-passing work is
    ΣVb·Db ≈ |E| lanes instead of the padded engine's V·Dmax.  All
    tuples have one entry per bucket:

      nodes [Vb]       node ids, in concat order (ascending within
                       each bucket, buckets by ascending width)
      nbr   [Vb, Db]   state-gather index: x[.., nbr] reads the edge's
                       other endpoint (out: the head j; in: the tail i)
      wsrc  [Vb, Db]   weight-gather row into the [.., V, Dmax]
                       out-edge-slot weight array (out: the node
                       itself; in: the SENDER node)
      wslot [Vb, Db]   weight-gather lane (out: the slot e itself; in:
                       the edge's slot in the sender's out-list)
      mask  [Vb, Db]   slot is a real edge (padding inert, as always)
      inv   [V]        position of node v in concat(nodes): un-permutes
                       the concatenated per-bucket results back to node
                       order

    Top-bucket widths are clamped to the tile width Dmax (a hub whose
    degree rounds up past Dmax can't read lanes that don't exist);
    `kernels.ref.fold_reduce` keeps row reductions bitwise identical
    across tile widths regardless.
    """
    nodes: tuple
    nbr: tuple
    wsrc: tuple
    wslot: tuple
    mask: tuple
    inv: jnp.ndarray

    @property
    def n_buckets(self) -> int:
        return len(self.nbr)

    @property
    def V(self) -> int:
        return self.inv.shape[0]

    @property
    def lanes(self) -> int:
        """ΣVb·Db — the per-round gather/reduce work of one pass."""
        return sum(int(t.shape[0]) * int(t.shape[1]) for t in self.nbr)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class NeighborBuckets:
    """Both edge directions of `EdgeBuckets` for one adjacency.

    `out` drives the downstream/marginal recursions (ρ = b + Φ ρ) and
    the taint/path-length closures; `inn` drives the traffic solves
    (t = r + Φᵀ t), bucketed by IN-degree with the (in_nbr, in_slot)
    weight view folded into its wsrc/wslot tiles.  A separate
    side-structure (not new `Neighbors` fields) so existing positional
    `Neighbors` pytree specs — e.g. the distributed shard_map in_specs
    — keep working unchanged; thread it through the engines' optional
    `buckets=` argument (built once per concrete adjacency via
    `build_buckets`, LRU-memoized like `build_neighbors`).
    """
    out: EdgeBuckets
    inn: EdgeBuckets

    @property
    def V(self) -> int:
        return self.out.V


_BUCKET_CACHE: OrderedDict = OrderedDict()


def _pow2_widths(deg: np.ndarray, cap: int) -> np.ndarray:
    """Per-node bucket width: smallest power of two >= degree (>=1),
    clamped to the tile width `cap`."""
    d = np.maximum(deg.astype(np.int64), 1)
    w = 2 ** np.ceil(np.log2(d)).astype(np.int64)   # exact: d < 2**52
    return np.minimum(w, cap)


def _bucket_direction(deg, nbr_rows, slot_rows, mask_rows,
                      out_direction: bool) -> EdgeBuckets:
    V, D = nbr_rows.shape
    widths = _pow2_widths(deg, D)
    nodes_t, nbr_t, wsrc_t, wslot_t, mask_t, perm = [], [], [], [], [], []
    for Db in sorted(set(widths.tolist())):
        nodes = np.nonzero(widths == Db)[0].astype(np.int32)
        perm.append(nodes)
        nbr_b = np.ascontiguousarray(nbr_rows[nodes, :Db], np.int32)
        mask_b = np.ascontiguousarray(mask_rows[nodes, :Db])
        if out_direction:
            wsrc_b = np.broadcast_to(nodes[:, None], nbr_b.shape)
            wslot_b = np.broadcast_to(
                np.arange(Db, dtype=np.int32)[None], nbr_b.shape)
        else:
            wsrc_b = nbr_b                       # sender rows
            wslot_b = slot_rows[nodes, :Db]      # slot in sender's list
        nodes_t.append(jnp.asarray(nodes))
        nbr_t.append(jnp.asarray(nbr_b))
        wsrc_t.append(jnp.asarray(np.ascontiguousarray(wsrc_b, np.int32)))
        wslot_t.append(jnp.asarray(np.ascontiguousarray(wslot_b, np.int32)))
        mask_t.append(jnp.asarray(mask_b))
    perm = np.concatenate(perm)
    inv = np.empty(V, np.int32)
    inv[perm] = np.arange(V, dtype=np.int32)
    return EdgeBuckets(tuple(nodes_t), tuple(nbr_t), tuple(wsrc_t),
                       tuple(wslot_t), tuple(mask_t), jnp.asarray(inv))


def build_buckets(adj) -> NeighborBuckets:
    """Degree-bucketed tiles of a concrete adjacency (LRU-memoized).

    Isolated nodes land in the width-1 bucket with their single slot
    masked; a lone hub (a star center) gets a Vb=1 bucket of its own
    width class.  The result is a registered pytree, so it threads
    through jitted steps as a dynamic argument (shapes/bucket count are
    static per adjacency).
    """
    if isinstance(adj, jax.core.Tracer):
        raise ValueError(
            "build_buckets needs a concrete adjacency; precompute it "
            "outside jit and pass it through the `buckets=` argument")
    A = np.asarray(adj, dtype=bool)
    key = _adj_key(A)
    cached = _lru_get(_BUCKET_CACHE, key)
    if cached is not None:
        return cached
    nbrs = build_neighbors(A)
    out = _bucket_direction(A.sum(axis=1), np.asarray(nbrs.out_nbr), None,
                            np.asarray(nbrs.out_mask), out_direction=True)
    inn = _bucket_direction(A.sum(axis=0), np.asarray(nbrs.in_nbr),
                            np.asarray(nbrs.in_slot),
                            np.asarray(nbrs.in_mask), out_direction=False)
    buckets = NeighborBuckets(out=out, inn=inn)
    _lru_put(_BUCKET_CACHE, key, buckets)
    return buckets


def gather_edges(x: jnp.ndarray, nbrs: Neighbors,
                 fill: float = 0.0) -> jnp.ndarray:
    """Gather per-(i, j) values onto edge slots: [..., V, K] -> [..., V, Dmax].

    K may exceed V (e.g. Phi.data's V+1 columns); only neighbor columns
    are ever indexed.  Padded slots read `fill`, cast to x's dtype so
    low-precision (bf16) edge arrays stay low-precision.
    """
    idx_i = jnp.arange(nbrs.V)[:, None]
    g = x[..., idx_i, nbrs.out_nbr]
    return jnp.where(nbrs.out_mask, g, jnp.asarray(fill, dtype=g.dtype))


def scatter_edges(x_sp: jnp.ndarray, nbrs: Neighbors, K: int) -> jnp.ndarray:
    """Scatter-add edge-slot values back to dense: [..., V, Dmax] -> [..., V, K]."""
    idx_i = jnp.arange(nbrs.V)[:, None]
    x_sp = jnp.where(nbrs.out_mask, x_sp, jnp.zeros((), x_sp.dtype))
    out = jnp.zeros(x_sp.shape[:-2] + (nbrs.V, K), x_sp.dtype)
    return out.at[..., idx_i, nbrs.out_nbr].add(x_sp)


def mask_slots(x_sp: jnp.ndarray, nbrs: Neighbors,
               fill: float = 0.0) -> jnp.ndarray:
    """Zero (or `fill`) the padding slots of an [..., V, Dmax] edge array.

    Every consumer of `PhiSparse` slots sanitizes through this, so
    garbage (even NaN) in padded slots never leaks into flows, marginals
    or blocked sets — bitwise identical to what `gather_edges` of the
    equivalent dense array would produce.
    """
    return jnp.where(nbrs.out_mask, x_sp, jnp.asarray(fill, dtype=x_sp.dtype))


def phi_to_sparse(phi: Phi, nbrs: Neighbors) -> PhiSparse:
    """Dense `Phi` -> edge-slot `PhiSparse` (lossless for feasible φ).

    Mass on non-edge coordinates (infeasible φ only) is dropped; padding
    slots come back exactly zero.
    """
    return PhiSparse(data=gather_edges(phi.data, nbrs),
                     local=phi.data[..., -1:],
                     result=gather_edges(phi.result, nbrs))


def sparse_to_phi(phi_sp: PhiSparse, nbrs: Neighbors,
                  V: int | None = None) -> Phi:
    """Edge-slot `PhiSparse` -> dense `Phi` (always lossless).

    Each slot scatters to its unique (i, out_nbr[i, e]) column, so the
    roundtrip `phi_to_sparse(sparse_to_phi(q))` reproduces q bitwise on
    real slots (padding is zeroed).
    """
    V = nbrs.V if V is None else V
    data = jnp.concatenate(
        [scatter_edges(phi_sp.data, nbrs, V), phi_sp.local], axis=-1)
    return Phi(data, scatter_edges(phi_sp.result, nbrs, V))


def as_dense_phi(phi, net: "CECNetwork") -> Phi:
    """Coerce either φ layout to the dense reference layout."""
    if isinstance(phi, PhiSparse):
        return sparse_to_phi(phi, build_neighbors(net.adj), net.adj.shape[0])
    return phi


def _fixed_point(step, x0: jnp.ndarray, max_rounds: int,
                 with_rounds: bool = False):
    """Iterate x <- step(x) until it stops changing (exact, loop-free
    supports are nilpotent) or `max_rounds` is hit (cyclic-φ guard).

    with_rounds=True also returns the round count (int32 scalar).
    NOT reverse-mode differentiable (lax.while_loop); linear fixed
    points that need gradients go through `_solve_fp_broadcast`.
    """

    def cond(carry):
        k, x, x_prev = carry
        return jnp.logical_and(k < max_rounds, jnp.any(x != x_prev))

    def body(carry):
        k, x, _ = carry
        return k + 1, step(x), x

    k, x, _ = jax.lax.while_loop(
        cond, body, (jnp.asarray(1, jnp.int32), step(x0), x0))
    return (x, k) if with_rounds else x


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def _solve_fp_broadcast(phi_nbr: jnp.ndarray, b: jnp.ndarray,
                        transpose: bool) -> jnp.ndarray:
    """Early-exit linear fixed point x = b + contract(Φ, x), dense.

    transpose=True  solves x_j = b_j + Σ_i φ_ij x_i  (traffic, Eq. 1-2)
    transpose=False solves x_i = b_i + Σ_j φ_ij x_j  (marginals, Eq. 11-12)

    The while-loop early exit alone is not reverse-mode differentiable,
    so the VJP is supplied analytically via the implicit function
    theorem: the adjoint of a linear fixed point is the SAME recursion
    with the contraction transposed (x̄ solves the adjoint system, the
    φ cotangent is its outer product with the primal solution).
    """
    eq = "sij,si->sj" if transpose else "sij,sj->si"

    def step(x):
        return b + jnp.einsum(eq, phi_nbr, x)

    return _fixed_point(step, b, max_rounds=phi_nbr.shape[-1])


def _solve_fp_broadcast_fwd(phi_nbr, b, transpose):
    x = _solve_fp_broadcast(phi_nbr, b, transpose)
    return x, (phi_nbr, x)


def _solve_fp_broadcast_bwd(transpose, res, g):
    phi_nbr, x = res
    xbar = _solve_fp_broadcast(phi_nbr, g, not transpose)
    phi_bar = (jnp.einsum("si,sj->sij", x, xbar) if transpose
               else jnp.einsum("si,sj->sij", xbar, x))
    return phi_bar, xbar


_solve_fp_broadcast.defvjp(_solve_fp_broadcast_fwd, _solve_fp_broadcast_bwd)


def _solve_traffic_sparse(phi_sp: jnp.ndarray, inject: jnp.ndarray,
                          nbrs: Neighbors, impl: str | None = None,
                          buckets: "NeighborBuckets | None" = None
                          ) -> jnp.ndarray:
    """Solve t = inject + Φᵀ t by in-edge message passing.

    phi_sp: [S, V, Dmax] out-edge fractions; inject: [S, V].
    Each round, node j sums φ_{k->j} t_k over its in-edges.  Padded
    path: the in-edge weight view (one gather of φ at (in_nbr,
    in_slot)) is built once, then all rounds run in
    kernels.ops.edge_rounds.  Bucketed path (`buckets=`): the in-degree
    buckets' wsrc/wslot tiles perform that view gather bucket-by-bucket
    inside the kernel, so the global [S, V, Dmax_in] view is never
    materialized — bitwise identical either way.
    """
    if buckets is not None:
        return kernel_ops.edge_rounds_bucketed(
            phi_sp, inject, buckets.inn, reduce="sum",
            max_rounds=nbrs.V, impl=impl)
    phi_in = phi_sp[:, nbrs.in_nbr, nbrs.in_slot]     # [S, V, Dmax_in]
    return kernel_ops.edge_rounds(phi_in, inject, nbrs.in_nbr,
                                  nbrs.in_mask, reduce="sum",
                                  max_rounds=nbrs.V, impl=impl)


def solve_downstream_sparse(phi_sp: jnp.ndarray, b: jnp.ndarray,
                            nbrs: Neighbors, impl: str | None = None,
                            buckets: "NeighborBuckets | None" = None
                            ) -> jnp.ndarray:
    """Solve ρ = b + Φ ρ by out-edge message passing (marginal recursions)."""
    if buckets is not None:
        return kernel_ops.edge_rounds_bucketed(
            phi_sp, b, buckets.out, reduce="sum", max_rounds=nbrs.V,
            impl=impl)
    return kernel_ops.edge_rounds(phi_sp, b, nbrs.out_nbr, nbrs.out_mask,
                                  reduce="sum", max_rounds=nbrs.V,
                                  impl=impl)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Flows:
    """Per-task traffic and link flows.

    f_data / f_result are [S, V, V] dense under method="dense"/"broadcast"
    and [S, V, Dmax] edge-slot arrays (aligned to `Neighbors.out_nbr`)
    under method="sparse"; everything else is layout-independent.
    """
    t_data: jnp.ndarray    # [S, V] data traffic t⁻
    t_result: jnp.ndarray  # [S, V] result traffic t⁺
    g: jnp.ndarray         # [S, V] computational input rate
    F: jnp.ndarray         # [V, V] total link flow
    G: jnp.ndarray         # [V] computation workload
    f_data: jnp.ndarray    # [S, V, V] | [S, V, Dmax] per-task data link flow
    f_result: jnp.ndarray  # [S, V, V] | [S, V, Dmax] per-task result link flow


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class FlowsCarry:
    """The slice of `Flows` the NEXT driver iteration actually consumes.

    The SGP drivers carry an iterate's flows between iterations so each
    iterate's flow solve runs exactly once.  Marginals need the link
    flows (→ D'/C'), the Eq. 16 scaling and the zero-traffic jump need
    t_data/t_result — the per-task per-edge f_data/f_result arrays are
    NOT consumed downstream, and keeping them out of the step outputs
    lets XLA fuse them away instead of materializing ~2×[S, V, Dmax]
    buffers every iteration.

    Under the sparse driver `F` is the [V, Dmax] EDGE-SLOT total link
    flow (aligned to `Neighbors.out_nbr`, padding exactly zero) — the
    drivers never build the dense [V, V] link matrix at all; under the
    dense/broadcast drivers it is the usual [V, V].  The methods are
    static through the jitted steps, so the layout is unambiguous.
    """
    t_data: jnp.ndarray    # [S, V]
    t_result: jnp.ndarray  # [S, V]
    F: jnp.ndarray         # [V, Dmax] slots (sparse driver) | [V, V]
    G: jnp.ndarray         # [V]


def link_cost_sparse(net: "CECNetwork", nbrs: Neighbors) -> Cost:
    """The link cost with its [V, V] parameters gathered onto edge
    slots, so D(F)/D'(F)/D''(F) evaluate directly on a [V, Dmax]
    slot-layout flow array (bitwise the dense evaluation per real slot;
    padding slots produce garbage and must be masked by the caller)."""
    return Cost(net.link_cost.family,
                gather_edges(net.link_cost.params, nbrs))


def cost_of_carry(net: "CECNetwork", carry: FlowsCarry,
                  nbrs: Neighbors | None = None) -> jnp.ndarray:
    """`cost_of_flows` for a driver `FlowsCarry`: slot-domain link sum
    when `nbrs` is given (sparse driver — ~Dmax/V of the dense cost
    evaluation), dense otherwise.  The slot and dense sums differ only
    in reduction order (same per-edge values)."""
    if nbrs is None:
        link = jnp.where(net.adj, net.link_cost.value(carry.F), 0.0)
    else:
        link = mask_slots(link_cost_sparse(net, nbrs).value(carry.F), nbrs)
    return jnp.sum(link) + jnp.sum(net.comp_cost.value(carry.G))


def flows_carry_and_cost(net: "CECNetwork", phi, method: str = "dense",
                         nbrs: Neighbors | None = None,
                         engine_impl: str | None = None,
                         psum_axis: str | None = None,
                         buckets: NeighborBuckets | None = None,
                         active: jnp.ndarray | None = None):
    """(FlowsCarry, total cost) of one iterate — the drivers' flow
    evaluation, run exactly once per iterate (when it is the candidate,
    or at the boundary for φ⁰).

    The sparse path stays entirely in edge-slot domain: the total link
    flow is accumulated as [V, Dmax] slots and the cost evaluated on
    them, so no [V, V] array is materialized anywhere in the sparse
    iteration loop (completing what the PhiSparse layout did for φ).
    `psum_axis` all-reduces F/G for the shard_mapped distributed step.

    `active` ([S] bool, dynamic task-slot pools — events.TaskPool) is a
    belt-and-braces mask of inactive task rows.  The pool contract
    already keeps their r/a rows exactly zero (so their traffic, flows
    and cost contributions vanish without any masking), and the hot
    drivers therefore never pass it; it exists for padded-vs-compact
    audits where r may deliberately hold stale rates.
    """
    if active is not None:
        net = dataclasses.replace(
            net, r=net.r * active[:, None].astype(net.r.dtype),
            a=net.a * active.astype(net.a.dtype))
    if method != "sparse":
        fl = compute_flows(net, phi, method, nbrs=nbrs,
                           engine_impl=engine_impl)
        if psum_axis is not None:
            fl = psum_flows(fl, psum_axis)
        return flows_carry(fl), cost_of_flows(net, fl)
    nbrs = nbrs if nbrs is not None else build_neighbors(net.adj)
    phi_d_sp, phi_loc, phi_r_sp = _phi_edge_views(phi, nbrs)
    t_data = _solve_traffic_sparse(phi_d_sp, net.r, nbrs, engine_impl,
                                   buckets)
    g = t_data * phi_loc
    t_result = _solve_traffic_sparse(phi_r_sp, net.a[:, None] * g, nbrs,
                                     engine_impl, buckets)
    f_data = t_data[..., None] * phi_d_sp         # [S, V, Dmax]
    f_result = t_result[..., None] * phi_r_sp
    F_sp = jnp.sum(f_data + f_result, axis=0)     # [V, Dmax] slots
    G = jnp.sum(net.w * g, axis=0)
    if psum_axis is not None:
        F_sp = jax.lax.psum(F_sp, psum_axis)
        G = jax.lax.psum(G, psum_axis)
    carry = FlowsCarry(t_data, t_result, F_sp, G)
    return carry, cost_of_carry(net, carry, nbrs)


flows_carry_and_cost_jit = jax.jit(
    flows_carry_and_cost,
    static_argnames=("method", "engine_impl", "psum_axis"))


def flows_carry(fl) -> "FlowsCarry":
    """Project a full dense-F `Flows` onto the driver-carry slice."""
    return FlowsCarry(fl.t_data, fl.t_result, fl.F, fl.G)


# --------------------------------------------------------------------------
def _solve_traffic(phi_nbr: jnp.ndarray, inject: jnp.ndarray,
                   method: str = "dense") -> jnp.ndarray:
    """Solve t = inject + Φᵀ t for each task.

    phi_nbr: [S, V, V] neighbor-forwarding fractions, inject: [S, V].
    """
    S, V, _ = phi_nbr.shape
    if method == "dense":
        eye = jnp.eye(V, dtype=phi_nbr.dtype)
        A = eye[None] - jnp.swapaxes(phi_nbr, -1, -2)  # I - Φᵀ
        return jnp.linalg.solve(A, inject[..., None])[..., 0]
    elif method == "broadcast":
        # Paper-faithful hop-by-hop propagation. Loop-free Φ is nilpotent
        # with index <= V so V rounds always suffice, but the fixed-point
        # early exit stops after ~diam(support) rounds on small-diameter
        # instances instead of burning all V (differentiable through the
        # implicit-function-theorem adjoint).
        return _solve_fp_broadcast(phi_nbr, inject, True)
    raise ValueError(f"unknown method {method}")


def compute_flows(net: CECNetwork, phi, method: str = "dense",
                  nbrs: Neighbors | None = None,
                  engine_impl: str | None = None,
                  buckets: NeighborBuckets | None = None) -> Flows:
    """Forward pass of the flow model: φ -> all traffic and flows.

    `phi` is a dense `Phi` or (with method="sparse") an edge-slot
    `PhiSparse`, which is consumed directly — no gather, no dense
    [S, V, V+1] intermediate.  engine_impl selects the sparse
    message-passing backend (see the module docstring); ignored by the
    dense/broadcast engines.  `buckets=` (sparse only) routes the
    traffic solves over degree-bucketed tiles — bitwise identical,
    ΣVb·Db per-round work.
    """
    if isinstance(phi, PhiSparse) and method != "sparse":
        raise ValueError(
            f"PhiSparse requires method='sparse', got {method!r}; convert "
            "with sparse_to_phi for the dense/broadcast engines")
    if method == "sparse":
        return _compute_flows_sparse(net, phi,
                                     nbrs if nbrs is not None
                                     else build_neighbors(net.adj),
                                     engine_impl, buckets)
    adjf = net.adj.astype(phi.data.dtype)
    phi_d_nbr = phi.data[..., :-1] * adjf[None]   # mask non-edges
    phi_loc = phi.data[..., -1]                   # [S, V]
    phi_r = phi.result * adjf[None]

    t_data = _solve_traffic(phi_d_nbr, net.r, method)
    g = t_data * phi_loc
    t_result = _solve_traffic(phi_r, net.a[:, None] * g, method)

    f_data = t_data[..., None] * phi_d_nbr
    f_result = t_result[..., None] * phi_r
    F = jnp.sum(f_data + f_result, axis=0)
    G = jnp.sum(net.w * g, axis=0)
    return Flows(t_data, t_result, g, F, G, f_data, f_result)


def _phi_edge_views(phi, nbrs: Neighbors):
    """Edge-slot views (phi_d_sp, phi_loc, phi_r_sp) of either φ layout.

    `PhiSparse` slots are used in place (padding masked to zero, exactly
    like a gather of the equivalent dense φ would); dense `Phi` is
    gathered onto the slots.
    """
    if isinstance(phi, PhiSparse):
        return (mask_slots(phi.data, nbrs), phi.local[..., 0],
                mask_slots(phi.result, nbrs))
    return (gather_edges(phi.data, nbrs), phi.data[..., -1],
            gather_edges(phi.result, nbrs))


def _compute_flows_sparse(net: CECNetwork, phi, nbrs: Neighbors,
                          impl: str | None = None,
                          buckets: NeighborBuckets | None = None) -> Flows:
    """Sparse flow engine: all edge quantities in [S, V, Dmax] layout."""
    phi_d_sp, phi_loc, phi_r_sp = _phi_edge_views(phi, nbrs)

    t_data = _solve_traffic_sparse(phi_d_sp, net.r, nbrs, impl, buckets)
    g = t_data * phi_loc
    t_result = _solve_traffic_sparse(phi_r_sp, net.a[:, None] * g, nbrs,
                                     impl, buckets)

    f_data = t_data[..., None] * phi_d_sp         # [S, V, Dmax]
    f_result = t_result[..., None] * phi_r_sp
    F = scatter_edges(jnp.sum(f_data + f_result, axis=0), nbrs, net.V)
    G = jnp.sum(net.w * g, axis=0)
    return Flows(t_data, t_result, g, F, G, f_data, f_result)


def total_cost(net: CECNetwork, phi, method: str = "dense",
               nbrs: Neighbors | None = None,
               engine_impl: str | None = None,
               buckets: NeighborBuckets | None = None) -> jnp.ndarray:
    fl = compute_flows(net, phi, method, nbrs=nbrs, engine_impl=engine_impl,
                       buckets=buckets)
    return cost_of_flows(net, fl)


# jitted variant for one-off cost evaluations at the public boundary: at
# V=1000 the eager path spends ~10x the jitted time on op dispatch
total_cost_jit = jax.jit(total_cost,
                         static_argnames=("method", "engine_impl"))


def psum_flows(fl: Flows, axis: str) -> Flows:
    """All-reduce the cross-task couplings of a task-sharded `Flows`.

    Total link flow F and workload G are the only quantities that mix
    tasks (the paper's link-measurement phase); everything else is
    task-local and stays per-shard.  One psum pair per call — this is
    the single collective of the distributed SGP iteration.
    """
    return dataclasses.replace(fl, F=jax.lax.psum(fl.F, axis),
                               G=jax.lax.psum(fl.G, axis))




def cost_of_flows(net: CECNetwork, fl: Flows) -> jnp.ndarray:
    link = jnp.where(net.adj, net.link_cost.value(fl.F), 0.0)
    return jnp.sum(link) + jnp.sum(net.comp_cost.value(fl.G))


# --------------------------------------------------------------------------
def uniform_phi(net: CECNetwork) -> Phi:
    """A trivially feasible (NOT loop-free) φ — only for shape plumbing."""
    V, S = net.V, net.S
    deg = jnp.sum(net.adj, axis=1)
    data = jnp.zeros((S, V, V + 1))
    data = data.at[..., -1].set(1.0)  # all-local offload
    result = jnp.where(net.adj[None], 1.0 / jnp.maximum(deg, 1)[None, :, None],
                       0.0) * jnp.ones((S, 1, 1))
    result = result.at[jnp.arange(S), net.dest, :].set(0.0)
    return Phi(data, result)


def _floyd_warshall(adj: np.ndarray, weight: np.ndarray):
    """All-pairs (dist[i, j], next_hop[i, j]) under edge weights (numpy)."""
    V = adj.shape[0]
    INF = 1e30
    dist = np.where(adj, weight, INF).astype(np.float64)
    np.fill_diagonal(dist, 0.0)
    nxt = np.where(adj, np.arange(V)[None, :], -1)
    for k in range(V):
        alt = dist[:, k:k + 1] + dist[k:k + 1, :]
        better = alt < dist
        dist = np.where(better, alt, dist)
        nxt = np.where(better, nxt[:, k:k + 1], nxt)
    return dist, nxt


def shortest_path_tree(adj: np.ndarray, weight: np.ndarray,
                       dest: int) -> np.ndarray:
    """Next hop toward `dest` under edge weights (Floyd-Warshall, numpy).

    Returns next_hop[i] (== dest's own entry is arbitrary/self)."""
    _, nxt = _floyd_warshall(adj, weight)
    return nxt[:, dest]


# above this node count, dense O(V³)-ish algorithms stop being practical:
# spt_phi swaps Floyd-Warshall for per-destination Dijkstra (scipy
# csgraph), and scenario plumbing / benchmarks switch to the sparse
# engine (scenarios.enforce_feasibility, benchmarks.scale_sweep)
DENSE_V_LIMIT = 200


def _spt_next_hops(net: CECNetwork,
                   weight: np.ndarray | None = None) -> np.ndarray:
    """Per-task next hop toward the destination (numpy): [S, V] int,
    -1 where there is none (the destination itself, unreachable nodes).

    Small graphs share one Floyd-Warshall; past DENSE_V_LIMIT it's
    per-unique-destination Dijkstra on the reversed graph (next hop =
    argmin_j w_ij + dist(j, d); the positive weight floor makes dist
    strictly decrease along chosen edges, so the tree is a DAG).
    """
    with obs.span("seed.next_hops"):
        return _spt_next_hops_impl(net, weight)


def _spt_next_hops_impl(net: CECNetwork,
                        weight: np.ndarray | None) -> np.ndarray:
    adj = _to_host(net.adj)
    V, S = net.V, net.S
    if weight is None:
        weight = _to_host(net.link_cost.d1(jnp.zeros((V, V))))
    dests = _to_host(net.dest)
    nx_all = np.full((S, V), -1, np.int64)
    idx = np.arange(V)

    if V > DENSE_V_LIMIT:
        from scipy.sparse import csr_matrix
        from scipy.sparse.csgraph import dijkstra
        w = np.where(adj, np.maximum(weight, 1e-12), 0.0)
        uniq = np.unique(dests)
        # rows of dijkstra on the reversed graph = distances TO d
        dist_to = dijkstra(csr_matrix(w.T), indices=uniq)       # [U, V]
        for k, d in enumerate(uniq):
            cand = np.where(adj, w + dist_to[k][None, :], np.inf)
            nx = np.argmin(cand, axis=1)
            ok = (idx != d) & np.isfinite(np.min(cand, axis=1))
            row = np.where(ok, nx, -1)
            for s in np.nonzero(dests == d)[0]:
                nx_all[s] = row
        return nx_all

    # small graphs: one Floyd-Warshall shared by every task
    _, nxt = _floyd_warshall(adj, weight)
    for s in range(S):
        d = int(dests[s])
        nx = nxt[:, d]
        ok = (idx != d) & (nx >= 0)
        nx_all[s] = np.where(ok, nx, -1)
    return nx_all


def spt_phi(net: CECNetwork, weight: np.ndarray | None = None) -> Phi:
    """Feasible loop-free initial strategy φ⁰ (the paper's requirement).

    Data: fully local offload (φ⁻_i0 = 1).  Result: forwarded along the
    shortest-path tree toward each task's destination, with edge weights
    = marginal link cost at zero flow (propagation-only, no queueing).

    Dense [S, V, V] construction — at scale use `spt_phi_sparse` /
    `spt_result_slots`, which write the SAME one-hot rows straight into
    edge slots without ever materializing this layout.
    """
    V, S = net.V, net.S
    nx_all = _spt_next_hops(net, weight)
    data = np.zeros((S, V, V + 1))
    data[..., -1] = 1.0
    result = np.zeros((S, V, V))
    idx = np.arange(V)
    for s in range(S):
        ok = nx_all[s] >= 0
        result[s, idx[ok], nx_all[s][ok]] = 1.0
    return Phi(jnp.asarray(data), jnp.asarray(result))


def spt_result_slots(net: CECNetwork, nbrs: Neighbors,
                     weight: np.ndarray | None = None) -> jnp.ndarray:
    """The SPT result rows of `spt_phi`, built NATIVELY in the edge-slot
    layout: [S, V, Dmax] with 1.0 at the slot of each node's next hop.

    Bitwise identical to `gather_edges(spt_phi(net).result, nbrs)` —
    the rows are exact {0, 1} one-hots, so writing them straight into
    slots loses nothing — without the dense [S, V, V] detour (256 GB at
    S=32, V=10⁴).
    """
    nx_all = _spt_next_hops(net, weight)                        # [S, V]
    out_nbr = _to_host(nbrs.out_nbr)
    out_mask = _to_host(nbrs.out_mask)
    hit = (out_nbr[None] == nx_all[:, :, None]) \
        & out_mask[None] & (nx_all[:, :, None] >= 0)            # [S, V, D]
    return jnp.asarray(hit.astype(np.float64))


def spt_phi_sparse(net: CECNetwork, nbrs: Neighbors | None = None,
                   weight: np.ndarray | None = None) -> PhiSparse:
    """`spt_phi` delivered in the edge-slot layout (boundary helper).

    Built natively slot-by-slot (data slots zero, local column one,
    result one-hots via `spt_result_slots`) — bitwise identical to
    `phi_to_sparse(spt_phi(net), nbrs)` with no [S, V, V+1] array
    anywhere, which is what lets V=10⁴ scenarios initialize at all.
    """
    nbrs = build_neighbors(net.adj) if nbrs is None else nbrs
    S, V, D = net.S, net.V, nbrs.Dmax
    return PhiSparse(data=jnp.zeros((S, V, D)),
                     local=jnp.ones((S, V, 1)),
                     result=spt_result_slots(net, nbrs, weight))


def offload_phi(net: CECNetwork, compute_nodes, weight: np.ndarray | None = None
                ) -> Phi:
    """Feasible loop-free φ⁰ that computes only at `compute_nodes`.

    Data: each node forwards along the shortest path toward its nearest
    compute node (zero-flow marginal weights); compute nodes offload
    locally.  Result: shortest-path tree toward each destination.
    Used when some nodes (serving frontends) must not compute.
    """
    adj = np.asarray(net.adj)
    V, S = net.V, net.S
    if weight is None:
        weight = np.asarray(net.link_cost.d1(jnp.zeros((V, V))))
    dist, nxt = _floyd_warshall(adj, weight)

    compute_nodes = list(compute_nodes)
    nearest = np.asarray(compute_nodes)[
        np.argmin(dist[:, compute_nodes], axis=1)]        # [V]

    data = np.zeros((S, V, V + 1))
    for i in range(V):
        if i in compute_nodes:
            data[:, i, -1] = 1.0
        else:
            h = nxt[i, nearest[i]]
            data[:, i, h if h >= 0 else -1] = 1.0

    result = np.zeros((S, V, V))
    dests = np.asarray(net.dest)
    for s in range(S):
        for i in range(V):
            d = int(dests[s])
            if i != d and nxt[i, d] >= 0:
                result[s, i, nxt[i, d]] = 1.0
    return Phi(jnp.asarray(data), jnp.asarray(result))


# --------------------------------------------------------------------------
def support_matrices(net: CECNetwork, phi, tol: float = 0.0
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Boolean support of data / result forwarding (neighbors only)."""
    phi = as_dense_phi(phi, net)
    sup_d = (phi.data[..., :-1] > tol) & net.adj[None]
    sup_r = (phi.result > tol) & net.adj[None]
    return sup_d, sup_r


def is_loop_free(net: CECNetwork, phi, tol: float = 0.0) -> jnp.ndarray:
    """True iff both supports are DAGs for every task (boolean closure)."""
    sup_d, sup_r = support_matrices(net, phi, tol)

    def has_cycle(sup):
        V = sup.shape[-1]
        reach = sup
        n = max(1, int(np.ceil(np.log2(max(V, 2)))))
        for _ in range(n):
            reach = reach | (jnp.einsum("sik,skj->sij", reach.astype(jnp.float32),
                                        reach.astype(jnp.float32)) > 0)
        diag = jnp.diagonal(reach, axis1=-2, axis2=-1)
        return jnp.any(diag)

    return ~(has_cycle(sup_d) | has_cycle(sup_r))


def refeasibilize(net: CECNetwork, phi: Phi,
                  rebuild_tasks: jnp.ndarray | None = None) -> Phi:
    """Project φ back to feasibility after topology change (node failure).

    Zeroes mass on removed edges and renormalizes; data rows left with
    no mass fall back to local offload; result rows that LOST their mass
    to the change fall back to the shortest-path tree toward their
    destination on the NEW graph (spreading over all out-edges can close
    a loop and make the traffic solve singular).

    No node that is alive (has an exit on the new graph) and is not the
    task's destination keeps an empty result row.  A row that was
    ALREADY empty before the change — a recovered node rejoining, whose
    edges all come back as zero-mass slots — takes its shortest-path
    row on the new graph, and every other row of the task keeps its
    mass.  The SGP step does not grow such a row back: a node with an
    empty row reads as a free sink (zero result marginal), so the
    blocked-set rule blocks its exits while its neighbours' steps start
    routing results into it, and those results are dropped.  The fill
    closes no loop: no surviving row carries mass into a node whose row
    was empty (that flow would already have been lost), and the filled
    rows among themselves follow one shortest-path tree.  So a
    failure→recovery roundtrip keeps the warm iterate instead of
    resetting every task to the SPT tree.  An empty row that carries
    result traffic at once — the node locally computes restored
    exogenous input (r·φ_local > 0, a > 0), as a recovered source node
    does — still counts as damage and rebuilds its whole task.

    rebuild_tasks : optional [S] bool — tasks to force-rebuild from the
    new graph's SPT regardless of damage (e.g. a destination re-draw,
    where the surviving rows still point at the OLD destination).

    Dense layout only — edge-slot iterates go through
    `refeasibilize_sparse`, which repairs the slots in place and
    re-slots them onto the new graph's `Neighbors`.
    """
    if isinstance(phi, PhiSparse):
        raise TypeError("refeasibilize takes a dense Phi; use "
                        "refeasibilize_sparse(net, phi_sp, nbrs) for the "
                        "edge-slot layout")
    adjf = net.adj.astype(phi.data.dtype)
    data_nbr = phi.data[..., :-1] * adjf[None]
    data = jnp.concatenate([data_nbr, phi.data[..., -1:]], axis=-1)
    dsum = jnp.sum(data, axis=-1, keepdims=True)
    # missing mass goes to local offload
    data = data.at[..., -1].add(jnp.maximum(0.0, 1.0 - dsum[..., 0]))
    data = data / jnp.maximum(jnp.sum(data, axis=-1, keepdims=True), 1e-30)

    result = phi.result * adjf[None]
    rsum = jnp.sum(result, axis=-1)                       # [S, V]
    rsum_before = jnp.sum(phi.result, axis=-1)            # incl. cut edges
    S, V = net.S, net.V
    is_dest = (jnp.arange(V)[None] == net.dest[:, None])  # [S, V]
    # A task whose routing LOST mass anywhere (a row emptied by the
    # change at a node still alive) is rebuilt ENTIRELY from the
    # shortest-path tree on the new graph: mixing surviving rows with
    # repaired rows can close a loop (making the traffic solve
    # singular); per-task SPT replacement is always loop-free.
    alive = jnp.any(net.adj, axis=-1)[None] | is_dest     # nodes with exits
    # empty rows about to carry result traffic (direct source, locally
    # computed) are damage too — see the docstring
    src = (net.r * data[..., -1] > 1e-12) & (net.a[:, None] > 0.0)
    damaged = (rsum <= 1e-12) & ((rsum_before > 1e-12) | src) \
        & ~is_dest & alive
    broken = jnp.any(damaged, axis=-1)                    # [S]
    if rebuild_tasks is not None:
        broken = broken | rebuild_tasks
    spt = spt_phi(net).result
    result = result / jnp.maximum(rsum[..., None], 1e-30)
    result = jnp.where(rsum[..., None] > 1e-12, result, spt)
    result = jnp.where(broken[:, None, None], spt, result)
    result = jnp.where(is_dest[..., None], 0.0, result)
    return Phi(data, result)


def sanitize_phi_sparse(phi_sp: PhiSparse, nbrs: Neighbors) -> PhiSparse:
    """On-device repair of a damaged edge-slot iterate (jit-safe — no
    topology change, unlike `refeasibilize_sparse`): zero non-finite
    entries and padding slots, clip negatives, renormalize data rows
    with lost mass routed to local offload (a fully-emptied row becomes
    all-local), renormalize surviving result rows and leave emptied ones
    exactly empty.  The guard layer's last-resort scrub for a poisoned
    checkpoint; NOT a projection — feasible iterates pass through only
    up to renormalization, so call it on known-damaged state."""

    def scrub(x, mask):
        x = jnp.where(jnp.isfinite(x), x, 0.0)
        x = jnp.maximum(x, 0.0)
        return jnp.where(mask, x, 0.0)

    data = scrub(phi_sp.data, nbrs.out_mask[None])
    local = scrub(phi_sp.local[..., 0], True)
    dsum = jnp.sum(data, axis=-1) + local
    local = local + jnp.maximum(0.0, 1.0 - dsum)
    tot = jnp.maximum(jnp.sum(data, axis=-1) + local, 1e-30)
    data = data / tot[..., None]
    local = local / tot
    result = scrub(phi_sp.result, nbrs.out_mask[None])
    rsum = jnp.sum(result, axis=-1)
    result = result / jnp.maximum(rsum[..., None], 1e-30)
    result = jnp.where(rsum[..., None] > 1e-12, result, 0.0)
    return PhiSparse(data, local[..., None], result)


def _slot_remap(old: Neighbors, new: Neighbors):
    """Per-row map from NEW out-edge slots to the OLD slot of the same
    edge (numpy, concrete): remap[i, e'] = e with old.out_nbr[i, e] ==
    new.out_nbr[i, e'], valid[i, e'] = that edge existed in the old
    layout.  Lets a topology change re-slot [S, V, Dmax_old] arrays with
    one cheap gather instead of a dense scatter/gather roundtrip.
    """
    o_nbr = np.asarray(old.out_nbr)
    n_nbr = np.asarray(new.out_nbr)
    V = o_nbr.shape[0]
    slot_of = np.full((V, V), -1, np.int32)
    ii, ee = np.nonzero(np.asarray(old.out_mask))
    slot_of[ii, o_nbr[ii, ee]] = ee
    remap = slot_of[np.arange(V)[:, None], n_nbr]
    valid = np.asarray(new.out_mask) & (remap >= 0)
    return jnp.asarray(np.maximum(remap, 0)), jnp.asarray(valid)


def _repair_slots_impl(phi_sp: PhiSparse, old_mask, remap, valid,
                       new_mask, r, a, dest, spt_sp, rebuild_tasks):
    """The arithmetic of `refeasibilize_sparse`: `phi_sp` (aligned to
    slots `old_mask`) re-slotted by `remap`/`valid` (`_slot_remap`)
    onto the slots `new_mask`, repaired with the SPT rows `spt_sp`."""
    idx_i = jnp.arange(old_mask.shape[0])[:, None]

    def reslot(x_sp):
        moved = x_sp[:, idx_i, remap]                      # [S, V, Dmax_new]
        return jnp.where(valid, moved, jnp.zeros((), x_sp.dtype))

    def mask(x_sp):
        return jnp.where(old_mask, x_sp, jnp.zeros((), x_sp.dtype))

    data = reslot(mask(phi_sp.data))
    local = phi_sp.local[..., 0]
    dsum = jnp.sum(data, axis=-1) + local
    # missing mass goes to local offload
    local = local + jnp.maximum(0.0, 1.0 - dsum)
    tot = jnp.maximum(jnp.sum(data, axis=-1) + local, 1e-30)
    data = data / tot[..., None]
    local = local / tot

    result_masked = mask(phi_sp.result)
    result = reslot(result_masked)
    rsum = jnp.sum(result, axis=-1)                        # [S, V]
    rsum_before = jnp.sum(result_masked, axis=-1)
    V = old_mask.shape[0]
    is_dest = (jnp.arange(V)[None] == dest[:, None])       # [S, V]
    # same damaged-row policy as the dense path (see refeasibilize)
    alive = jnp.any(new_mask, axis=-1)[None] | is_dest
    src = (r * local > 1e-12) & (a[:, None] > 0.0)
    damaged = (rsum <= 1e-12) & ((rsum_before > 1e-12) | src) \
        & ~is_dest & alive
    broken = jnp.any(damaged, axis=-1)                     # [S]
    if rebuild_tasks is not None:
        broken = broken | rebuild_tasks
    result = result / jnp.maximum(rsum[..., None], 1e-30)
    result = jnp.where(rsum[..., None] > 1e-12, result, spt_sp)
    result = jnp.where(broken[:, None, None], spt_sp, result)
    result = jnp.where(is_dest[..., None], 0.0, result)
    return PhiSparse(data, local[..., None], result)


_repair_slots = jax.jit(_repair_slots_impl)


def refeasibilize_sparse(net: CECNetwork, phi_sp: PhiSparse,
                         nbrs: Neighbors,
                         rebuild_tasks: jnp.ndarray | None = None
                         ) -> Tuple[PhiSparse, Neighbors]:
    """`refeasibilize` for edge-slot iterates after a topology change.

    `nbrs` is the Neighbors the iterate is aligned to (the OLD graph);
    the repaired strategy comes back aligned to `build_neighbors` of the
    NEW `net.adj`, together with those new index tiles.  Same policy as
    the dense version (bitwise): surviving mass renormalized per row,
    missing data mass to local offload, any task whose result routing
    LOST mass rebuilt entirely from the new graph's shortest-path tree
    (partial repair can close a loop), rows that were already empty —
    recovered nodes rejoining after a failure — given their
    shortest-path row while the task's other rows keep their mass, so
    the warm iterate survives a failure→recovery roundtrip and no alive
    non-destination row is left empty to drop results (`_slot_remap`
    handles growing neighborhoods: restored edges come back as
    zero-mass slots), and a task whose empty row locally computes
    restored exogenous input rebuilt whole (see `refeasibilize`).
    `rebuild_tasks` force-rebuilds specific tasks from the SPT (see
    `refeasibilize`).  All slot-level including the SPT fallback rows
    (`spt_result_slots` writes the one-hots natively), so churn replay
    never materializes a dense [S, V, V] array even at V=10⁴; the
    arithmetic is one jitted dispatch (`_repair_slots`), where eager
    operations cost a host dispatch each.
    """
    new_nbrs = build_neighbors(net.adj)
    remap, valid = _slot_remap(nbrs, new_nbrs)
    spt_sp = spt_result_slots(net, new_nbrs)
    return _repair_slots(phi_sp, nbrs.out_mask, remap, valid,
                         new_nbrs.out_mask, net.r, net.a, net.dest, spt_sp,
                         rebuild_tasks), new_nbrs


def refeasibilize_sparse_samegraph(net: CECNetwork, phi_sp: PhiSparse,
                                   nbrs: Neighbors,
                                   rebuild_tasks: jnp.ndarray | None = None,
                                   spt_sp: jnp.ndarray | None = None
                                   ) -> PhiSparse:
    """`refeasibilize_sparse` specialized to an UNCHANGED adjacency
    (routing churn: destination/source re-draws) — bitwise the same
    repaired iterate, with the topology machinery peeled off.

    On the same graph `build_neighbors` memoizes to the identical
    `Neighbors` and `_slot_remap` is the identity permutation, so the
    repair is the same compiled arithmetic (`_repair_slots`) on the
    identity map, which is what makes it bitwise the full repair
    rather than merely close.  `spt_sp` lets the
    caller supply `spt_result_slots(net, nbrs)` precomputed host-side
    (the per-unique-destination Dijkstra is the dominant per-event host
    cost at V > DENSE_V_LIMIT, and it depends only on the adjacency,
    the zero-flow link weights and `net.dest` — not on φ — so a churn
    stream memoizes it per destination vector).  The repair is one
    device dispatch with NO host sync, which lets the fused churn
    stream (sgp.FusedStream) fold it into its dispatch pipeline without
    draining it.
    """
    if spt_sp is None:
        spt_sp = spt_result_slots(net, nbrs)
    V, D = nbrs.V, nbrs.Dmax
    identity = np.broadcast_to(np.arange(D, dtype=np.int32), (V, D))
    return _repair_slots(phi_sp, nbrs.out_mask, identity, nbrs.out_mask,
                         nbrs.out_mask, net.r, net.a, net.dest, spt_sp,
                         rebuild_tasks)

# ----------------------------------------------------- dynamic task pool
def next_pow2(n: int) -> int:
    """Smallest power of two >= max(n, 1) — the task-pool capacity
    ladder (events.TaskPool), so repeated growth settles into a
    geometric rung sequence instead of a recompile per arrival."""
    n = max(int(n), 1)
    return 1 << (n - 1).bit_length()


def pad_tasks(net: CECNetwork, S_cap: int,
              n_active: int | None = None) -> CECNetwork:
    """Pad the task axis to `S_cap` slots for a dynamic task-slot pool
    (events.TaskPool), optionally deactivating the tail at `n_active`.

    Padding/deactivated rows follow the pool's inert-slot convention —
    zero exogenous rate, zero result ratio, unit weight (dest/task_type
    of deactivated original rows are left stale on purpose; they are
    inert with r = a = 0).  Rows the flow model maps to exactly-zero
    traffic, flows and cost, so a padded pool measures the active
    system and nothing else.  Adjacency and cost families are untouched.
    """
    S, V = net.S, net.V
    S_cap = int(S_cap)
    if S_cap < S:
        raise ValueError(f"S_cap={S_cap} < S={S}: cannot drop tasks")
    n_active = S if n_active is None else int(n_active)
    if not (0 <= n_active <= S):
        raise ValueError(f"n_active={n_active} outside [0, {S}]")
    r = np.zeros((S_cap, V), dtype=np.asarray(net.r).dtype)
    r[:S] = np.asarray(net.r)
    dest = np.zeros(S_cap, dtype=np.int32)
    dest[:S] = np.asarray(net.dest)
    a = np.zeros(S_cap, dtype=np.asarray(net.a).dtype)
    a[:S] = np.asarray(net.a)
    w_np = np.asarray(net.w)
    w = np.ones((S_cap,) + w_np.shape[1:], dtype=w_np.dtype)
    w[:S] = w_np
    task_type = np.zeros(S_cap, dtype=np.int32)
    task_type[:S] = np.asarray(net.task_type)
    if n_active < S:
        r[n_active:S] = 0.0
        a[n_active:S] = 0.0
        w[n_active:S] = 1.0
    return dataclasses.replace(
        net, r=jnp.asarray(r), dest=jnp.asarray(dest), a=jnp.asarray(a),
        w=jnp.asarray(w), task_type=jnp.asarray(task_type))


def pad_phi_sparse(phi_sp: PhiSparse, S_cap: int) -> PhiSparse:
    """Pad the task axis of an edge-slot iterate to `S_cap` rows with
    inert-slot rows (all-local data, empty result — what
    `clear_task_slot` writes): feasible, zero-traffic, and frozen
    bitwise by the masked SGP step."""
    S = phi_sp.data.shape[0]
    S_cap = int(S_cap)
    if S_cap < S:
        raise ValueError(f"S_cap={S_cap} < S={S}: cannot drop tasks")
    if S_cap == S:
        return phi_sp
    pad = S_cap - S
    return PhiSparse(
        data=jnp.concatenate(
            [phi_sp.data,
             jnp.zeros((pad,) + phi_sp.data.shape[1:], phi_sp.data.dtype)]),
        local=jnp.concatenate(
            [phi_sp.local,
             jnp.ones((pad,) + phi_sp.local.shape[1:], phi_sp.local.dtype)]),
        result=jnp.concatenate(
            [phi_sp.result,
             jnp.zeros((pad,) + phi_sp.result.shape[1:],
                       phi_sp.result.dtype)]))


def seed_task_slot(phi_sp: PhiSparse, slot: int,
                   spt_rows: jnp.ndarray) -> PhiSparse:
    """Seed one recycled task slot from the SPT: all-local data routing
    plus the slot's `spt_result_slots` row — the same φ⁰ row a cold
    start gives a task.  Written with eager `.at` updates (no host
    sync), so a fused churn stream folds an arrival into its dispatch
    pipeline like any other same-graph repair."""
    return PhiSparse(
        data=phi_sp.data.at[slot].set(0.0),
        local=phi_sp.local.at[slot].set(1.0),
        result=phi_sp.result.at[slot].set(
            spt_rows[slot].astype(phi_sp.result.dtype)))


def clear_task_slot(phi_sp: PhiSparse, slot: int) -> PhiSparse:
    """Return a departed task's slot to the inert-slot convention
    (all-local data, empty result): feasible, exactly-zero traffic, and
    frozen bitwise by the masked SGP step until the slot is reused."""
    return PhiSparse(
        data=phi_sp.data.at[slot].set(0.0),
        local=phi_sp.local.at[slot].set(1.0),
        result=phi_sp.result.at[slot].set(0.0))


def mask_inactive_slots(phi_sp: PhiSparse, active: jnp.ndarray) -> PhiSparse:
    """Force every inactive slot of `phi_sp` back to the inert-slot
    convention in one vectorized pass (eager device ops, no host sync).

    The replay engine runs this after any repair that touched the whole
    iterate (`refeasibilize_sparse*`): the repair's damage rule cannot
    damage a zero-mass row, but a schedule CAN aim routing churn at an
    inert slot (e.g. a DestRedraw of a departed task), and the rebuild
    would then write SPT rows into a slot the pool considers empty.
    """
    act = active[:, None, None]
    return PhiSparse(
        data=jnp.where(act, phi_sp.data, 0.0),
        local=jnp.where(act, phi_sp.local, 1.0),
        result=jnp.where(act, phi_sp.result, 0.0))
