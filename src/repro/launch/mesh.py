"""Production mesh + per-architecture sharding rules.

Mesh: (16, 16) "data"x"model" per pod (256 chips, TPU v5e), with an
outer "pod" axis for multi-pod (2, 16, 16) = 512 chips.  Data
parallelism runs over ("pod", "data") — cross-pod traffic is gradient
all-reduce only; "model" carries TP/EP inside a pod where ICI is fast.

`rules_for(cfg, mesh)` adapts the logical->mesh mapping per arch:
  * vocab -> model when the vocab divides the axis, else the embedding
    shards its d_model dim instead (granite 49155, whisper 51865,
    mamba2 50280 are not 16-divisible);
  * heads/kv_heads -> model when divisible (phi4 24H, yi 56H, whisper
    8H, qwen2-vl 28H are not) — attention TP then falls back to
    sharding head_dim (contracting-dim TP, one psum per projection);
  * experts -> model (EP) for MoE archs;
  * batch -> ("pod", "data") when the global batch divides it.
"""
from __future__ import annotations

from typing import Optional

import jax
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from ..models.config import ModelConfig


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_test_mesh(shape=(1, 1), axes=("data", "model")) -> Mesh:
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def _axis_size(mesh: Mesh, name: str) -> int:
    return mesh.shape[name] if name in mesh.shape else 1


def dp_axes(mesh: Mesh):
    axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    return axes if axes else None


def dp_size(mesh: Mesh) -> int:
    return int(np.prod([_axis_size(mesh, a) for a in ("pod", "data")]))


def rules_for(cfg: ModelConfig, mesh: Mesh, global_batch: int = 0) -> dict:
    m = _axis_size(mesh, "model")

    def fits(n):
        return n > 0 and n % m == 0

    dp = dp_axes(mesh)
    dpn = dp_size(mesh)
    rules = {
        # FSDP: parameters shard their d_model dim over the data axes
        # (ZeRO-3 style; XLA all-gathers weights per layer on use).
        "embed": dp if (dp and cfg.d_model % dpn == 0) else None,
        # flag-gated embedding-table layout (cfg.embed_tbl_shard):
        "vocab_off": None,
        "embed_tbl_d": "model" if fits(cfg.d_model) else None,
        "embed_tbl": None,
        "layers": None,
        "mlp": "model",
        "experts": "model" if fits(cfg.n_experts) else None,
        "vocab": "model" if fits(cfg.vocab) else None,
        "heads": None,
        "kv_heads": None,
        "head_dim": None,
        "cache_seq": None,
        "batch": None,
    }

    n_heads = cfg.n_heads
    ssm_heads = (cfg.ssm_expand * cfg.d_model // cfg.ssm_head_dim
                 if cfg.ssm_state else 0)
    if fits(n_heads) and (not cfg.ssm_state or fits(ssm_heads)):
        rules["heads"] = "model"
    if fits(cfg.n_kv_heads):
        rules["kv_heads"] = "model"
    if rules["kv_heads"] is None and fits(cfg.hd):
        # shard head_dim whenever kv heads can't shard — otherwise the
        # KV cache only shards on batch (decode_32k blew past HBM for
        # every kv=8 arch before this)
        rules["head_dim"] = "model"

    if dp is not None and global_batch and global_batch % dpn == 0:
        rules["batch"] = dp
    elif dp is not None:
        # batch not shardable (e.g. long-context decode at batch=1):
        # shard the KV-cache sequence dim instead; XLA partitions the
        # decode-attention reductions over it (flash-decode style psum).
        rules["cache_seq"] = dp
    return rules


def moe_groups_for(cfg: ModelConfig, mesh: Mesh, global_batch: int) -> int:
    """Dispatch-group count for MoE layers: one group per DP shard keeps
    the [groups, E, capacity, D] buffers fully sharded and the dispatch
    scatter local to each shard."""
    if not cfg.n_experts:
        return 1
    g = dp_size(mesh)
    return g if global_batch % g == 0 else 1


def batch_specs(mesh: Mesh, global_batch: int) -> P:
    """PartitionSpec for the leading batch dim of data arrays."""
    dp = dp_axes(mesh)
    if dp is None or global_batch % dp_size(mesh) != 0:
        return P()
    return P(dp)


def data_shardings(mesh: Mesh, batch: dict, global_batch: int) -> dict:
    bspec = batch_specs(mesh, global_batch)
    dp = bspec[0] if len(bspec) else None

    def one(key, x):
        nd = x.ndim if hasattr(x, "ndim") else 0
        if key == "positions" and nd == 3:     # [3, B, S] M-RoPE
            return NamedSharding(mesh, P(None, dp, None))
        if nd == 0 or dp is None:
            return NamedSharding(mesh, P())
        return NamedSharding(mesh, P(dp, *([None] * (nd - 1))))

    return {k: one(k, v) for k, v in batch.items()}
