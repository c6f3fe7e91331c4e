"""On-device sentinels, checkpoint ring, and rollback for the drivers.

`core.faults` makes the solver LOSE things (stale marginals, skipped
nodes, poisoned rows); this module makes it NOTICE and RECOVER, on
device, without breaking the fused chunk's one-sync contract.

Per iteration, on the candidate of a one-iteration `sgp._sgp_block`
dispatch, `_guarded_update_impl` runs the exact accept/reject carry
update (`sgp._accept_update_impl`, op-for-op — the update the block's
own loop runs, so a guarded fault-free run is bitwise the unguarded
one) and then checks the POST-accept carry against four sentinels:

  1 nonfinite_cost    the carried best cost went NaN/Inf
  2 nonfinite_phi     any φ leaf holds a non-finite value (the landing
                      point of `corrupt_p` poison: the candidate's cost
                      was measured BEFORE the poison, so accept cannot
                      catch it)
  3 mass_drift        a simplex row's mass drifted > `mass_eps` from 1
                      (data rows; result rows may also be exactly empty)
  4 cost_explosion    carried cost > `explode_factor` × the min of a
                      trailing window of accepted costs (inert under
                      adaptive SGP, which enforces monotone descent;
                      guards the paper/GP accept paths)

On a trip the carry rolls back to the newest LIVE slot of a periodic
checkpoint ring (φ, flows, cost, σ — written every `checkpoint_every`
accepted-and-clean iterations), σ backs off ×`sigma_backoff` from the
larger of (current, checkpoint) so the retried steps are more
conservative, and a retry budget (`max_retries`) latches `stopped`
when recovery keeps failing — restoring the checkpoint even on the
final dying trip, so a stopped guarded run never hands back a poisoned
iterate.  If the checkpoint itself fails a health check (it was
poisoned before the write cadence caught it), the sparse iterate is
re-feasibilized on device by `network.sanitize_phi_sparse` first.
Everything is branchless selects folded into the fused carry: the
drivers still make one `device_get` per chunk, and the per-iteration
sentinel codes come back in that same sync to be rendered as host-side
`GuardEvent` records.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from .network import Neighbors, PhiSparse, sanitize_phi_sparse
from .sgp import _accept_update_impl


@dataclasses.dataclass(frozen=True)
class GuardConfig:
    """Sentinel thresholds + recovery policy (static jit argument)."""
    mass_eps: float = 1e-3        # simplex row mass drift tolerance
    explode_factor: float = 10.0  # trip when cost > factor * window min
    window: int = 8               # trailing accepted-cost window length
    checkpoint_every: int = 8     # ring write cadence (iterations)
    ring: int = 4                 # checkpoint slots
    max_retries: int = 8          # rollbacks before latching stopped
    sigma_backoff: float = 4.0    # σ multiplier applied on rollback


@dataclasses.dataclass
class GuardEvent:
    """One sentinel trip, rendered host-side from the fused histories."""
    it: int                       # global driver iteration
    sentinel: str                 # SENTINEL_NAMES value
    action: str                   # "rollback" | "stop"
    cost: float                   # the iteration's candidate cost
    restored_cost: Optional[float] = None  # checkpoint cost (rollbacks)


SENTINEL_NAMES = {1: "nonfinite_cost", 2: "nonfinite_phi",
                  3: "mass_drift", 4: "cost_explosion"}


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class GuardState:
    """Device-resident guard carry: the checkpoint ring ([ring]-stacked
    copies of the φ/flows pytrees + their cost/σ scalars), the trailing
    accepted-cost window, and the trip/retry counters."""
    ckpt_phi: object              # [R]-stacked φ pytree
    ckpt_fl: object               # [R]-stacked FlowsCarry pytree
    ckpt_cost: jax.Array          # [R] f32 (inf = never written)
    ckpt_sigma: jax.Array         # [R] f32
    valid: jax.Array              # [R] bool
    ptr: jax.Array                # next ring slot to write
    window: jax.Array             # [W] f32 trailing accepted costs (inf pad)
    wptr: jax.Array               # next window slot
    retries: jax.Array            # rollbacks consumed (cumulative)
    n_trips: jax.Array            # total sentinel trips


def _stack_ring(tree, R: int):
    return jax.tree.map(
        lambda x: jnp.zeros((R,) + x.shape, x.dtype).at[0].set(x), tree)


def init_guard_state(phi, fl, T0, cfg: GuardConfig) -> GuardState:
    """Guard carry anchored at the entry iterate: ring slot 0 holds
    (φ, flows, T0, σ=1) — the guaranteed-good rollback target — and the
    window starts [T0, inf, ...]."""
    R, W = cfg.ring, cfg.window
    i32 = lambda v: jnp.asarray(v, jnp.int32)
    return GuardState(
        ckpt_phi=_stack_ring(phi, R),
        ckpt_fl=_stack_ring(fl, R),
        ckpt_cost=jnp.full((R,), jnp.inf, jnp.float32).at[0].set(
            jnp.float32(T0)),
        ckpt_sigma=jnp.ones((R,), jnp.float32),
        valid=jnp.zeros((R,), bool).at[0].set(True),
        ptr=i32(1 % R if R > 1 else 0),
        window=jnp.full((W,), jnp.inf, jnp.float32).at[0].set(
            jnp.float32(T0)),
        wptr=i32(1 % W if W > 1 else 0),
        retries=i32(0), n_trips=i32(0))


def _tree_finite(tree) -> jax.Array:
    leaves = [jnp.all(jnp.isfinite(x)) for x in jax.tree.leaves(tree)]
    out = leaves[0]
    for flag in leaves[1:]:
        out = out & flag
    return out


def _mass_err(phi) -> jax.Array:
    """Worst simplex-row mass drift of a φ: data rows must sum to 1,
    result rows to 1 or exactly 0 (tasks terminated locally).  NaN rows
    propagate into the max and fail the `<= eps` compare."""
    if isinstance(phi, PhiSparse):
        dsum = jnp.sum(phi.data, axis=-1) + phi.local[..., 0]
        rsum = jnp.sum(phi.result, axis=-1)
    else:
        dsum = jnp.sum(phi.data, axis=-1)
        rsum = jnp.sum(phi.result, axis=-1)
    derr = jnp.max(jnp.abs(dsum - 1.0))
    rerr = jnp.max(jnp.minimum(jnp.abs(rsum - 1.0), jnp.abs(rsum)))
    return jnp.maximum(derr, rerr)


def _phi_healthy(phi, eps: float) -> jax.Array:
    err = _mass_err(phi)
    return _tree_finite(phi) & ~(err > eps)


def _guarded_update_impl(phi_new, fl_new, cost_new, phi, fl, sigma, prev,
                         n_costs, n_rej, stopped, rng_new, rng, tol, gs,
                         nbrs: Optional[Neighbors] = None,
                         adaptive: bool = True,
                         cfg: GuardConfig = GuardConfig(),
                         do_ckpt: bool = False):
    """One guarded driver iteration: the exact `_accept_update_impl`
    carry update, then sentinels / rollback / checkpoint as branchless
    selects.  `do_ckpt` is decided host-side from the global iteration
    (it costs a ring write, so it is a static trace branch).

    Returns the accept-update tuple extended with the guard outputs:
    (phi, fl, sigma, prev, n_costs, n_rej, stopped, rng, take, live,
     gs, code, rolled, ckpt_cost) — `code` is this iteration's sentinel
    (0 = clean), `rolled` whether the carry was restored, `ckpt_cost`
    the restored cost (for the host-side GuardEvent render).
    """
    R = cfg.ring
    stopped_pre = stopped
    sigma_pre, prev_pre, n_costs_pre = sigma, prev, n_costs
    window_pre, wptr_pre = gs.window, gs.wptr

    (phi_a, fl_a, sigma_a, prev_a, n_costs_a, n_rej_a, stopped_a, rng_a,
     take, live) = _accept_update_impl(
        phi_new, fl_new, cost_new, phi, fl, sigma, prev, n_costs, n_rej,
        stopped, rng_new, rng, tol, adaptive)

    # --- sentinels on the POST-accept carry ----------------------------
    cost_bad = ~jnp.isfinite(prev_a)
    phi_bad = ~_tree_finite(phi_a)
    mass_bad = _mass_err(phi_a) > cfg.mass_eps
    explode = prev_a > jnp.float32(cfg.explode_factor) * jnp.min(window_pre)
    # successive selects, most specific sentinel LAST so it wins the code
    code = jnp.asarray(0, jnp.int32)
    code = jnp.where(explode, 4, code)
    code = jnp.where(mass_bad, 3, code)
    code = jnp.where(phi_bad, 2, code)
    code = jnp.where(cost_bad, 1, code)
    trip = live & (code > 0)

    # --- rollback target: newest valid ring slot -----------------------
    idx = (gs.ptr + (R - 1)) % R
    ck_valid = jax.lax.dynamic_index_in_dim(gs.valid, idx, 0,
                                            keepdims=False)
    ck_cost = jax.lax.dynamic_index_in_dim(gs.ckpt_cost, idx, 0,
                                           keepdims=False)
    ck_sigma = jax.lax.dynamic_index_in_dim(gs.ckpt_sigma, idx, 0,
                                            keepdims=False)
    ck_phi = jax.tree.map(
        lambda x: jax.lax.dynamic_index_in_dim(x, idx, 0, keepdims=False),
        gs.ckpt_phi)
    ck_fl = jax.tree.map(
        lambda x: jax.lax.dynamic_index_in_dim(x, idx, 0, keepdims=False),
        gs.ckpt_fl)
    # the ring slot itself might have been written from a state the
    # cadence never sentinel-checked at write time in a prior chunk —
    # re-feasibilize a sparse checkpoint that fails its health check
    if isinstance(ck_phi, PhiSparse) and nbrs is not None:
        ck_ok = _phi_healthy(ck_phi, cfg.mass_eps) & jnp.isfinite(ck_cost)
        clean = sanitize_phi_sparse(ck_phi, nbrs)
        ck_phi = jax.tree.map(
            lambda a, b: jnp.where(ck_ok, a, b), ck_phi, clean)

    restore = trip & ck_valid
    exhausted = trip & (gs.retries >= cfg.max_retries)
    die = trip & (~ck_valid | exhausted)

    def roll(restored, accepted):
        return jax.tree.map(
            lambda a, b: jnp.where(restore, a, b), restored, accepted)

    phi_out = roll(ck_phi, phi_a)
    fl_out = roll(ck_fl, fl_a)
    prev_out = jnp.where(restore, ck_cost, prev_a)
    sigma_out = jnp.where(
        restore,
        jnp.maximum(sigma_pre, ck_sigma) * jnp.float32(cfg.sigma_backoff),
        sigma_a)
    n_costs_out = jnp.where(restore, n_costs_pre, n_costs_a)
    take2 = take & ~trip        # a rolled-back accept never reaches costs
    stopped_out = jnp.where(restore, stopped_pre, stopped_a) | die

    # --- trailing accepted-cost window ---------------------------------
    W = cfg.window
    win_push = jax.lax.dynamic_update_index_in_dim(
        window_pre, prev_a, wptr_pre % W, 0)
    window_out = jnp.where(take2, win_push, window_pre)
    wptr_out = jnp.where(take2, wptr_pre + 1, wptr_pre)
    # a restore re-anchors the window at the checkpoint cost: comparing
    # retried steps against the pre-trip window would re-trip instantly
    win_reset = jnp.full((W,), jnp.inf, jnp.float32).at[0].set(ck_cost)
    window_out = jnp.where(restore, win_reset, window_out)
    wptr_out = jnp.where(restore, jnp.asarray(1 % W if W > 1 else 0,
                                              jnp.int32), wptr_out)

    # --- periodic checkpoint write (clean live iterations only) --------
    ckpt_phi, ckpt_fl = gs.ckpt_phi, gs.ckpt_fl
    ckpt_cost, ckpt_sigma = gs.ckpt_cost, gs.ckpt_sigma
    valid, ptr = gs.valid, gs.ptr
    if do_ckpt:
        write = live & (code == 0)

        def ring_write(ring, val):
            return jax.tree.map(
                lambda r, v: jnp.where(
                    write,
                    jax.lax.dynamic_update_index_in_dim(r, v, ptr, 0),
                    r),
                ring, val)

        ckpt_phi = ring_write(ckpt_phi, phi_out)
        ckpt_fl = ring_write(ckpt_fl, fl_out)
        ckpt_cost = ring_write(ckpt_cost, prev_out)
        ckpt_sigma = ring_write(ckpt_sigma, sigma_out)
        valid = ring_write(valid, jnp.asarray(True))
        ptr = jnp.where(write, (ptr + 1) % R, ptr)

    gs_out = GuardState(
        ckpt_phi=ckpt_phi, ckpt_fl=ckpt_fl, ckpt_cost=ckpt_cost,
        ckpt_sigma=ckpt_sigma, valid=valid, ptr=ptr,
        window=window_out, wptr=wptr_out,
        retries=gs.retries + restore.astype(jnp.int32),
        n_trips=gs.n_trips + trip.astype(jnp.int32))
    code_out = jnp.where(trip, code, 0)
    # a dying trip still restores the checkpoint (never hand back a
    # poisoned iterate) but renders as action="stop", not "rollback"
    return (phi_out, fl_out, sigma_out, prev_out, n_costs_out, n_rej_a,
            stopped_out, rng_a, take2, live, gs_out, code_out,
            restore & ~die, ck_cost)


_guarded_update = jax.jit(
    _guarded_update_impl,
    static_argnames=("adaptive", "cfg", "do_ckpt"))
