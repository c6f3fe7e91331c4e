"""Host milliseconds per cold solve in seeding: the benchmark's own span
around `core.build_neighbors` and `core.spt_phi_sparse`."""
import numpy as np


def read(run):
    s = run.spans.get("seed")
    return 1e3 * float(np.mean(s)) if s else None
