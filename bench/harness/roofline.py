"""Operations and bytes of the kernels a per-layer metric rooflines,
computed from shapes alone."""
from __future__ import annotations

F32 = 4
BOOL = 1


def qp_bytes_per_iteration(S: int, V: int, D: int) -> int:
    """Least HBM traffic of one SGP iteration's QP projections
    (`simplex_project`): the data rows [S*V, D+1] (out-edge slots and the
    local column) and the result rows [S*V, D], each reading φ, δ and the
    scaling M (float32) and the permitted mask (one byte), and writing φ.
    The count is the same whatever implements the projection."""
    rows_elems = S * V * (D + 1) + S * V * D
    return rows_elems * (3 * F32 + BOOL + F32)
