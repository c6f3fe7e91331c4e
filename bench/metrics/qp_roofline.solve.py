"""Roofline share of the QP projection (`kernels/simplex_project.py`):
the least time its bytes need at the chip's HBM bandwidth over the
device time of the kernel (the custom call named `simplex_project`), in
%."""
import numpy as np

from harness import trace
from harness.roofline import qp_bytes_per_iteration


def read(run):
    if run.trace is None or run.peaks is None or not run.window.iterations:
        return None
    t = trace.named_time_s(run.trace, "simplex_project")
    if t <= 0.0:
        return None
    dep = run.dep
    D = max(1, int(np.bincount(dep.src, minlength=dep.V).max()))
    need = qp_bytes_per_iteration(dep.S, dep.V, D) * run.window.iterations
    return 100.0 * need / run.peaks["hbm_bytes_per_s"] / t
