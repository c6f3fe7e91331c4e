"""The paper's per-(node, task) QP (Eq. 15) as a batched Pallas kernel.

Each row solves  min_v δ·(v-φ) + (v-φ)ᵀ diag(M)(v-φ)  over the simplex
with blocked coordinates pinned to 0, via bisection on the simplex dual.
This is the inner-loop hot-spot of Algorithm 1 (one QP per node × task ×
{data, result} per iteration); the paper §IV suggests a commercial QP
solver per node — here the whole batch is one kernel launch with rows
tiled into VMEM, the TPU-native adaptation.

Layout: the rows lie along the lanes.  XLA stores the [R, K] rows with
R along the lanes already, so the wrapper hands the kernel [K, R] (a
layout change XLA folds into the rows' producers and consumers) in
[K, bc] blocks, the last one partial; rows never mix, so a partial
block's spare lanes touch no real row.  The kernel relays each slot's bc
rows into a dense [bc/128, 128] slab, so whatever K is, no lane of the
60 rounds is padding and a row's sum over its K slots is K-1
element-wise vreg adds, not a cross-lane reduce.

Each round is the jnp oracle's (core.sgp.project_rows) hoisted
slope-intercept form, v_j(λ) = max(q_j - λ w_j, 0) with w = 1/(2M),
q = φ - d/(2M) on permitted slots and (q, w) = (-BIG, 0) on blocked
ones: one multiply-subtract, max and add per slot, no division.  The
oracle exits once no row's bracket moves; a round that moves no bracket
of a row leaves it fixed for every later round, so the fixed 60 rounds
end on the oracle's bracket with no branch.  The two differ only in the
order of the float32 sum over K (kernel tests lock 1e-4), not bitwise.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BIG = 1e12
SNAP_TOL = 1e-12
LANES = 128
SUBLANES = 8
# Slot-rows of one block, K·bs for bc = 128·bs rows: about 512 keeps a
# float32 input tile near 256 KiB at the cells' K of 4 to 10, and a block
# is never thinner than one vreg per slot, bs = 8 (K = 278, ba_10000).
_BLOCK_SLOT_ROWS = 512
# [K, bc] float32 tiles of VMEM a block may use: the double-buffered four
# inputs and output, and the body's slabs (the v5e compiler asks 15 at
# K = 278, past the default 16 MiB; 32 MiB covers the cells' K)
_TILES_IN_VMEM = 20


def _kernel(phi_ref, delta_ref, M_ref, perm_ref, out_ref, *, n_iter: int):
    K, bc = out_ref.shape

    def slabs(ref):   # [K, bc] -> [K, bc/128, 128]: axis 0 the slot
        return ref[...].reshape(K, bc // LANES, LANES)

    perm = slabs(perm_ref) != 0
    Msafe = jnp.where(perm, jnp.maximum(slabs(M_ref).astype(jnp.float32),
                                        1e-12), 1.0)
    phi0 = jnp.where(perm, slabs(phi_ref).astype(jnp.float32), 0.0)
    d = jnp.where(perm, slabs(delta_ref).astype(jnp.float32), BIG)

    lam_lo = jnp.min(jnp.where(perm, -d - 2.0 * Msafe * (1.0 - phi0), BIG),
                     axis=0, keepdims=True)
    lam_hi = jnp.max(jnp.where(perm, -d + 2.0 * Msafe * phi0, -BIG),
                     axis=0, keepdims=True)
    w = jnp.where(perm, 1.0 / (2.0 * Msafe), 0.0)
    q = jnp.where(perm, phi0 - d / (2.0 * Msafe), -BIG)

    def v_of(lam):
        return jnp.maximum(q - lam * w, 0.0)

    def body(_, carry):
        lo, hi = carry
        mid = 0.5 * (lo + hi)
        above = jnp.sum(v_of(mid), axis=0, keepdims=True) > 1.0
        return jnp.where(above, mid, lo), jnp.where(above, hi, mid)

    lo, hi = jax.lax.fori_loop(0, n_iter, body, (lam_lo, lam_hi))
    v = v_of(0.5 * (lo + hi))
    v = jnp.where(v > SNAP_TOL, v, 0.0)
    s = jnp.sum(v, axis=0, keepdims=True)
    # argmin-δ one-hot (the first minimal slot, as jnp.argmin picks)
    k = jax.lax.broadcasted_iota(jnp.int32, d.shape, 0)
    dmin = jnp.min(d, axis=0, keepdims=True)
    first = jnp.min(jnp.where(d == dmin, k, K), axis=0, keepdims=True)
    onehot = (k == first).astype(jnp.float32)
    v = jnp.where(s > 0.0, v / jnp.maximum(s, 1e-30), onehot)
    # fully-blocked rows: all-zero, matching the core.sgp.project_rows
    # oracle — never a one-hot on a blocked coord.
    v = jnp.where(jnp.any(perm, axis=0, keepdims=True), v, 0.0)
    out_ref[...] = v.reshape(K, bc).astype(out_ref.dtype)


def _block_rows(K: int, R: int) -> int:
    """Rows of one [K, bc] block: bc = 128·bs, bs a multiple of 8 with
    K·bs near `_BLOCK_SLOT_ROWS`, split evenly over the blocks R needs so
    the last block's spare lanes stay under 8·128."""
    lane_rows = pl.cdiv(R, LANES)
    cap = max(SUBLANES, _BLOCK_SLOT_ROWS // K // SUBLANES * SUBLANES)
    nb = pl.cdiv(lane_rows, cap)
    return pl.cdiv(pl.cdiv(lane_rows, nb), SUBLANES) * SUBLANES * LANES


@functools.partial(jax.jit, static_argnames=("n_iter", "interpret"))
def simplex_project(phi: jnp.ndarray, delta: jnp.ndarray, M: jnp.ndarray,
                    permitted: jnp.ndarray, n_iter: int = 60,
                    interpret: bool = False) -> jnp.ndarray:
    """All inputs [R, K] (permitted is bool); returns projected rows."""
    R, K = phi.shape
    bc = _block_rows(K, R)
    spec = pl.BlockSpec((K, bc), lambda i: (0, i))
    out = pl.pallas_call(
        functools.partial(_kernel, n_iter=n_iter),
        grid=(pl.cdiv(R, bc),),
        in_specs=[spec] * 4,
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((K, R), phi.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=max(
            32 << 20, _TILES_IN_VMEM * K * bc * 4)),
        name="simplex_project",
    )(phi.T, delta.T, M.T, permitted.T.astype(jnp.int32))
    return out.T
