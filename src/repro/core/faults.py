"""Fault injection for the SGP drivers — the paper's asynchrony, measured.

The paper claims Algorithm 1 "allows asynchronous individual updating":
nodes may update from stale broadcasts, sit out iterations, or drop
control messages, and the blocked-set/accept machinery is supposed to
keep the trajectory convergent.  Every driver in this repo is bulk-
synchronous, so that claim was prose.  This module turns it into a
seeded, composable, ON-DEVICE fault model:

  bounded-staleness broadcasts   each node proposes from marginals up
                                 to `staleness_k` iterations old (a
                                 per-array ring buffer of the four
                                 marginal tensors the projection
                                 consumes, carried in the driver state)
  partial participation          a fresh Bernoulli(node) mask per
                                 iteration gates which rows of φ update
                                 — the paper's "asynchronous individual
                                 updating" (Theorem 2 row masks, drawn
                                 per node instead of per (task, node))
  control-message dropout        a node's marginal broadcast is silently
                                 LOST: consumers reuse its last
                                 effective values (a `held` copy)
  transient value corruption     with prob `corrupt_p` per iteration a
                                 random (task, node) data row of the
                                 CANDIDATE iterate is poisoned with
                                 NaN/Inf AFTER its flows/cost were
                                 measured — the cost looks healthy, so
                                 an adaptive accept lands the poison in
                                 the carry (exactly the failure mode
                                 `core.guards` exists to catch)

Faults compose as masks/selects inside the step of the SAME jitted
`sgp._sgp_block` executable both drivers dispatch (the `FaultState`
rides the block's loop carry), so an injected run never syncs the
host inside a chunk: the `FaultPlan` (static,
hashable — which injectors are armed and how hard) picks the traced
code at compile time, and the `FaultState` pytree (rng, staleness
ring, dropout hold, corruption count) rides the driver carry.  A plan
whose armed injectors are all inert (participation_p=1.0,
corrupt_p=0.0, ...) walks the fault-free trajectory up to XLA fusion
(same accept/reject decisions, costs to ulp-level reassociation noise
— arming a `jnp.where(all_true, new, old)` changes the executable, so
exact bitwise equality across the two compilations is not guaranteed;
locked at rtol=1e-5 by tests/test_faults.py), and `fault_plan=None`
compiles the IDENTICAL jaxpr as before this module existed — that
path is exactly bitwise.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .marginals import Marginals, compute_marginals
from .network import CECNetwork, Phi, PhiSparse, Neighbors


@dataclasses.dataclass(frozen=True)
class FaultPlan:
    """Which injectors are armed, and how hard (static jit argument).

    A field's None/0 default keeps that injector's code OUT of the
    traced program entirely; an armed-but-inert value (e.g.
    participation_p=1.0) traces the fault code yet reproduces the
    fault-free trajectory up to compilation (same accept/reject
    decisions, ulp-level cost noise).  Plain frozen dataclass — hashable,
    so the driver's block caches one executable per distinct plan.
    """
    participation_p: Optional[float] = None  # P(node updates) per iter
    staleness_k: int = 0                     # max marginal age (iters)
    dropout_p: Optional[float] = None        # P(node's broadcast lost)
    corrupt_p: Optional[float] = None        # P(one row poisoned) per iter
    corrupt_mode: str = "nan"                # "nan" | "inf" poison value

    def __post_init__(self):
        if self.staleness_k < 0:
            raise ValueError("staleness_k must be >= 0")
        if self.corrupt_mode not in ("nan", "inf"):
            raise ValueError(f"unknown corrupt_mode {self.corrupt_mode!r}")

    @property
    def stale_marginals(self) -> bool:
        """Marginals must be computed OUTSIDE the propose (ring/hold)."""
        return self.staleness_k > 0 or self.dropout_p is not None


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class FaultState:
    """Per-run dynamic fault state (a pytree riding the driver carry).

    `ring`/`held` hold the four marginal tensors the projection
    consumes — (rho_data, rho_result, delta_data, delta_result) — as
    [staleness_k+1, ...] stacks / last-effective copies; they are None
    exactly when the plan's corresponding injector is unarmed (the plan
    is static, so init and step always agree on the treedef).
    """
    rng: jax.Array                        # fault rng (split 5-way per step)
    ring: Optional[Tuple] = None          # 4× [k+1, S, V(, K)] stacks
    held: Optional[Tuple] = None          # 4× [S, V(, K)] last effective
    n_corrupt: jax.Array = dataclasses.field(
        default_factory=lambda: jnp.asarray(0, jnp.int32))


_MG_FIELDS = ("rho_data", "rho_result", "delta_data", "delta_result")

_marginals_jit = jax.jit(
    compute_marginals,
    static_argnames=("method", "engine_impl", "slot_F"))


def _mg_tuple(mg: Marginals) -> Tuple:
    return tuple(getattr(mg, f) for f in _MG_FIELDS)


def init_fault_state(net: CECNetwork, phi, fl, plan: FaultPlan,
                     rng: Optional[jax.Array] = None,
                     method: str = "sparse",
                     nbrs: Optional[Neighbors] = None,
                     engine_impl: Optional[str] = None,
                     buckets=None) -> FaultState:
    """Fault state for iterate `phi` with flows `fl`: the staleness ring
    (and dropout hold) start filled with φ's OWN marginals — age-0
    copies, so the first step's lag selects are well defined — and the
    rng defaults to PRNGKey(0).  `slot_F` mirrors the driver step's
    internal `compute_marginals` call (the carry F is already on the
    edge slots under method="sparse")."""
    if rng is None:
        rng = jax.random.PRNGKey(0)
    ring = held = None
    if plan.stale_marginals:
        mg = _marginals_jit(net, phi, fl, method, nbrs=nbrs,
                            engine_impl=engine_impl,
                            slot_F=(method == "sparse"), buckets=buckets)
        vals = _mg_tuple(mg)
        if plan.staleness_k > 0:
            R = plan.staleness_k + 1
            ring = tuple(jnp.stack([x] * R) for x in vals)
        if plan.dropout_p is not None:
            held = vals
    return FaultState(rng=rng, ring=ring, held=held,
                      n_corrupt=jnp.asarray(0, jnp.int32))


def fault_state_specs(plan: FaultPlan, axis: str) -> FaultState:
    """shard_map PartitionSpecs for a FaultState under the task axis:
    the rng/counter are replicated, ring stacks shard on their task dim
    (axis 1, behind the age axis), held copies on their leading task
    dim.  Treedef matches `init_fault_state` for the same plan."""
    ring = (tuple(P(None, axis) for _ in _MG_FIELDS)
            if plan.staleness_k > 0 else None)
    held = (tuple(P(axis) for _ in _MG_FIELDS)
            if plan.dropout_p is not None else None)
    return FaultState(rng=P(), ring=ring, held=held, n_corrupt=P())


# ------------------------------------------------------------- injectors
def fault_step_begin(net: CECNetwork, phi, fl, fs: FaultState,
                     plan: FaultPlan, method: str,
                     nbrs: Optional[Neighbors], engine_impl: Optional[str],
                     buckets):
    """The pre-propose injectors: staleness, dropout, participation.

    Returns (mg, pmask, k_corrupt, fs_mid):
      mg      the marginals the propose must consume (None = compute
              internally as usual — staleness/dropout unarmed),
      pmask   [1, V] bool participation row mask (None = unarmed),
      k_corrupt  the rng key reserved for `fault_step_end`,
      fs_mid  the state with rng advanced and ring/held updated.
    All draws come from fs.rng (NOT the driver's async rng), so arming
    faults never perturbs the Theorem-2 row-mask stream.
    """
    V = net.V
    rng_new, k_part, k_lag, k_drop, k_cor = jax.random.split(fs.rng, 5)
    mg = None
    ring_new, held_new = fs.ring, fs.held
    if plan.stale_marginals:
        fresh = compute_marginals(net, phi, fl, method, nbrs=nbrs,
                                  engine_impl=engine_impl,
                                  slot_F=(method == "sparse"),
                                  buckets=buckets)
        eff = _mg_tuple(fresh)
        if plan.staleness_k > 0:
            # push-front: slot 0 is this iteration's broadcast, slot l
            # is l iterations old
            ring_new = tuple(jnp.concatenate([f[None], r[:-1]], axis=0)
                             for f, r in zip(eff, fs.ring))
            lag = jax.random.randint(k_lag, (V,), 0, plan.staleness_k + 1)

            def at_lag(ring):
                out = ring[0]
                for age in range(1, plan.staleness_k + 1):
                    m = (lag == age).reshape((1, V) + (1,) * (out.ndim - 2))
                    out = jnp.where(m, ring[age], out)
                return out

            eff = tuple(at_lag(r) for r in ring_new)
        if plan.dropout_p is not None:
            drop = jax.random.bernoulli(k_drop, plan.dropout_p, (V,))

            def held_or(cur, held):
                m = drop.reshape((1, V) + (1,) * (cur.ndim - 2))
                return jnp.where(m, held, cur)

            eff = tuple(held_or(c, h) for c, h in zip(eff, fs.held))
            held_new = eff   # dropped nodes keep re-broadcasting the hold
        # Dp/Cp ride along fresh: the projection/blocked sets only read
        # the four rho/delta tensors (the per-node broadcast payload)
        mg = Marginals(eff[0], eff[1], eff[2], eff[3], fresh.Dp, fresh.Cp)
    pmask = None
    if plan.participation_p is not None:
        pmask = jax.random.bernoulli(k_part, plan.participation_p, (1, V))
    fs_mid = FaultState(rng=rng_new, ring=ring_new, held=held_new,
                        n_corrupt=fs.n_corrupt)
    return mg, pmask, k_cor, fs_mid


def fault_step_end(net: CECNetwork, phi_new, k_cor, plan: FaultPlan,
                   fs_mid: FaultState, nbrs: Optional[Neighbors] = None,
                   psum_axis: Optional[str] = None):
    """The post-measurement injector: transient value corruption.

    With prob `corrupt_p`, poison the data row (real out-edge slots +
    the local column; padding slots stay untouched — consumers mask
    them and the replay invariants pin them to exactly 0) of ONE
    uniformly drawn (task, node) of the CANDIDATE iterate.  Runs AFTER
    `flows_carry_and_cost`, so the measured cost is the healthy
    candidate's: an accepting driver lands the poison in its carry.
    Under `psum_axis` the (replicated-rng) task draw is GLOBAL across
    shards; exactly one shard applies it.
    """
    if plan.corrupt_p is None:
        return phi_new, fs_mid
    kf, ks, kv = jax.random.split(k_cor, 3)
    fire = jax.random.bernoulli(kf, plan.corrupt_p)
    dtype = phi_new.data.dtype
    poison = jnp.asarray(
        jnp.nan if plan.corrupt_mode == "nan" else jnp.inf, dtype)
    S_local = phi_new.data.shape[0]
    V = net.V
    u_s = jax.random.uniform(ks)
    u_v = jax.random.uniform(kv)
    v_idx = jnp.minimum((u_v * V).astype(jnp.int32), V - 1)
    if psum_axis is not None:
        # global task index from the replicated draw: uniform → [0, S·n)
        # (randint cannot take the traced shard count as a bound)
        n_sh = jax.lax.psum(jnp.asarray(1, jnp.int32), psum_axis)
        S_g = S_local * n_sh
        g = jnp.minimum((u_s * S_g).astype(jnp.int32), S_g - 1)
        s_idx = g - jax.lax.axis_index(psum_axis) * S_local
        hit = (s_idx >= 0) & (s_idx < S_local)
        s_idx = jnp.clip(s_idx, 0, S_local - 1)
    else:
        s_idx = jnp.minimum((u_s * S_local).astype(jnp.int32), S_local - 1)
        hit = jnp.asarray(True)
    sel = ((jnp.arange(S_local) == s_idx)[:, None]
           & (jnp.arange(V) == v_idx)[None, :]
           & fire & hit)                                        # [S, V]
    if isinstance(phi_new, PhiSparse):
        data = jnp.where(sel[..., None] & nbrs.out_mask[None],
                         poison, phi_new.data)
        local = jnp.where(sel[..., None], poison, phi_new.local)
        phi_out = PhiSparse(data, local, phi_new.result)
    else:
        colmask = jnp.concatenate(
            [net.adj, jnp.ones((V, 1), dtype=bool)], axis=1)    # [V, V+1]
        data = jnp.where(sel[..., None] & colmask[None],
                         poison, phi_new.data)
        phi_out = Phi(data, phi_new.result)
    # count FIRINGS (replicated across shards), not shard-local hits
    n_corrupt = fs_mid.n_corrupt + fire.astype(jnp.int32)
    return phi_out, dataclasses.replace(fs_mid, n_corrupt=n_corrupt)
