"""Public jit'd wrappers with backend dispatch.

Off TPU every op runs its pure-jnp reference (CPU CI, the 512-host-device
dry-run).  On TPU an op runs its Pallas kernel unless `_TPU_DEFAULT`
names another implementation for it.  Pass `impl="pallas_interpret"` to
force the kernel body through the Pallas interpreter (the CPU
validation mode used by the kernel tests); `impl="pallas"` always means
the compiled kernel, and on TPU it compiles or raises.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import ref as _ref
from .decode_attention import decode_attention as _decode_pallas
from .edge_rounds import edge_rounds as _rounds_pallas
from .edge_rounds import edge_rounds_bucketed as _rounds_bucketed_pallas
from .flash_attention import flash_attention as _flash_pallas
from .moe_gmm import moe_gmm as _gmm_pallas
from .simplex_project import simplex_project as _proj_pallas
from .ssd_scan import ssd_scan as _ssd_pallas


# TPU defaults that are not the Pallas kernel.  The edge_rounds kernels
# gather with `jnp.take` over a VMEM-resident state, which the TPU
# compiler refuses, and their weight block does not fit VMEM on
# power-law graphs (ba_10000: 8 tasks x 10^4 x 277 f32 = 89 MB), so the
# sparse engine's fixed points run on the XLA reference there.
_TPU_DEFAULT = {"edge_rounds": "ref", "edge_rounds_bucketed": "ref"}


def default_impl(op: str, platform: Optional[str] = None) -> str:
    """The implementation `op` runs when the caller passes impl=None, on
    `platform` (default: the backend JAX runs on)."""
    if (platform or jax.default_backend()) != "tpu":
        return "ref"
    return _TPU_DEFAULT.get(op, "pallas")


def _pick(impl: Optional[str], op: str) -> str:
    return impl if impl is not None else default_impl(op)


def flash_attention(q, k, v, causal: bool = True,
                    impl: Optional[str] = None, **kw):
    """q [B,H,S,hd]; k,v [B,KV,S,hd] -> [B,H,S,hd]."""
    mode = _pick(impl, "flash_attention")
    if mode == "ref":
        return _ref.flash_attention_ref(q, k, v, causal=causal)
    return _flash_pallas(q, k, v, causal=causal,
                         interpret=(mode == "pallas_interpret"), **kw)


def decode_attention(q, k_cache, v_cache, lengths,
                     impl: Optional[str] = None, **kw):
    """q [B,KV,G,hd]; caches [B,KV,S,hd]; lengths [B]."""
    mode = _pick(impl, "decode_attention")
    if mode == "ref":
        B, KV, G, hd = q.shape
        out = _ref.decode_attention_ref(
            q.reshape(B, KV * G, hd),
            jnp.swapaxes(k_cache, 1, 2), jnp.swapaxes(v_cache, 1, 2),
            lengths)
        return out.reshape(B, KV, G, hd)
    return _decode_pallas(q, k_cache, v_cache, lengths,
                          interpret=(mode == "pallas_interpret"), **kw)


def ssd_scan(x, dt, A, Bm, Cm, impl: Optional[str] = None, **kw):
    """x [B,L,H,P], dt [B,L,H], A [H], Bm/Cm [B,L,N] -> [B,L,H,P]."""
    mode = _pick(impl, "ssd_scan")
    if mode == "ref":
        y, _ = _ref.ssd_scan_ref(x, dt, A, Bm, Cm)
        return y
    return _ssd_pallas(x, dt, A, Bm, Cm,
                       interpret=(mode == "pallas_interpret"), **kw)


def moe_gmm(x, w, impl: Optional[str] = None, **kw):
    """x [E,C,D] @ w [E,D,F] -> [E,C,F]."""
    mode = _pick(impl, "moe_gmm")
    if mode == "ref":
        return _ref.moe_gmm_ref(x, w)
    return _gmm_pallas(x, w, interpret=(mode == "pallas_interpret"), **kw)


def edge_rounds(w_sp, inject, nbr, mask, reduce: str = "sum",
                shift: float = 0.0, max_rounds: Optional[int] = None,
                impl: Optional[str] = None, return_rounds: bool = False,
                **kw):
    """Sparse message-passing fixed point: w_sp [S, V, Dmax] edge
    weights, inject [S, V], padded neighbor lists nbr/mask [V, Dmax].

    The Pallas path fuses gather + multiply + masked-reduce per round
    and runs the whole early-exit while-loop in one launch with the
    index tiles resident in VMEM; the jnp reference dispatches one
    gather per round and is the default on every backend (see
    `_TPU_DEFAULT`).  Edge-slot φ
    (core.network.PhiSparse) feeds this directly — both backends mask
    padded weight slots internally, so slot garbage never propagates.
    """
    if w_sp.shape[-2:] != nbr.shape or nbr.shape != mask.shape:
        raise ValueError(
            f"edge weights {w_sp.shape} are not aligned to the neighbor "
            f"tiles nbr{nbr.shape}/mask{mask.shape}; slot arrays must "
            "share the [V, Dmax] trailing layout of their Neighbors")
    mode = _pick(impl, "edge_rounds")
    if mode == "ref":
        return _ref.edge_rounds_ref(w_sp, inject, nbr, mask, reduce=reduce,
                                    shift=shift, max_rounds=max_rounds,
                                    return_rounds=return_rounds)
    return _rounds_pallas(w_sp, inject, nbr, mask, reduce=reduce,
                          shift=shift, max_rounds=max_rounds,
                          interpret=(mode == "pallas_interpret"),
                          return_rounds=return_rounds, **kw)


def edge_rounds_bucketed(w_sp, inject, buckets, reduce: str = "sum",
                         shift: float = 0.0,
                         max_rounds: Optional[int] = None,
                         impl: Optional[str] = None,
                         return_rounds: bool = False, **kw):
    """`edge_rounds` over degree-bucketed tiles (core.network
    `EdgeBuckets`): same fixed point, ΣVb·Db per-round work instead of
    V·Dmax, bitwise identical per row (both paths reduce rows through
    `kernels.ref.fold_reduce`, whose fold order is tile-width-stable).

    w_sp is ALWAYS the [S, V, Dmax] out-edge-slot weight array; the
    bucket tiles' (wsrc, wslot) indices express both the out-direction
    (identity rows) and the in-direction ((in_nbr, in_slot) view)
    weight gathers, so in-edge recursions skip the global
    [S, V, Dmax_in] weight-view materialization entirely.
    """
    if w_sp.shape[-2] != buckets.inv.shape[0]:
        raise ValueError(
            f"edge weights {w_sp.shape} are not aligned to the bucket "
            f"tiles (V={buckets.inv.shape[0]}); slot arrays must share "
            "the [V, Dmax] trailing layout of the Neighbors the buckets "
            "were built from")
    mode = _pick(impl, "edge_rounds_bucketed")
    if mode == "ref":
        return _ref.edge_rounds_bucketed_ref(
            w_sp, inject, buckets, reduce=reduce, shift=shift,
            max_rounds=max_rounds, return_rounds=return_rounds)
    return _rounds_bucketed_pallas(
        w_sp, inject, buckets, reduce=reduce, shift=shift,
        max_rounds=max_rounds, interpret=(mode == "pallas_interpret"),
        return_rounds=return_rounds, **kw)


def edge_rounds_stacked(problems, nbr, mask, reduce: str = "sum",
                        shift: float = 0.0, max_rounds: Optional[int] = None,
                        impl: Optional[str] = None, buckets=None):
    """Several independent `edge_rounds` fixed points sharing one
    neighbor tiling, solved in ONE launch.

    `problems` is a sequence of `(w_sp, inject)` pairs (each shaped like
    a single `edge_rounds` problem over the same `nbr`/`mask` tiles);
    they are stacked along the leading batch (task) axis, iterated
    together, and split back.  Because the early-exit fixed point is
    EXACT (rounds past a sub-problem's own fixed point reproduce it
    bitwise — `step(x) == x` there), the stacked solve is bitwise
    identical to dispatching the pairs one by one while paying 1/len
    of the launches: this is how the SGP step batches its data+result
    taint and path-length recursions (core.sgp).
    """
    w = jnp.concatenate([w for w, _ in problems], axis=0)
    b = jnp.concatenate([inj for _, inj in problems], axis=0)
    if buckets is not None:
        out = edge_rounds_bucketed(w, b, buckets, reduce=reduce,
                                   shift=shift, max_rounds=max_rounds,
                                   impl=impl)
    else:
        out = edge_rounds(w, b, nbr, mask, reduce=reduce, shift=shift,
                          max_rounds=max_rounds, impl=impl)
    splits = np.cumsum([w.shape[0] for w, _ in problems])[:-1]
    return jnp.split(out, splits, axis=0)


def simplex_project(phi, delta, M, permitted, impl: Optional[str] = None,
                    **kw):
    """Batched Eq. 15 QP rows [R, K].

    The kernel paths take K as-is: the kernel lays the rows along the
    lanes (kernels/simplex_project.py), so no slot is padded to the lane
    width.
    """
    mode = _pick(impl, "simplex_project")
    if mode == "ref":
        return _ref.simplex_project_ref(phi, delta, M, permitted)
    return _proj_pallas(phi, delta, M, permitted,
                        interpret=(mode == "pallas_interpret"), **kw)
