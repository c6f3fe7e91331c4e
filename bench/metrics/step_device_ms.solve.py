"""Device-busy milliseconds per SGP iteration: the busy time of the
traced window over the iterations executed in it (the flows fixed
points, marginals, blocked sets, projection and accept of
`core/sgp.py`, `core/marginals.py`, `kernels/ops.py`)."""
from harness import trace


def read(run):
    if run.trace is None or not run.trace.devices or not run.window.iterations:
        return None
    return 1e3 * trace.busy_s(run.trace) / run.window.iterations
