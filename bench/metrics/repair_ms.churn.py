"""Host milliseconds per call of the program's `churn.repair` span
(`refeasibilize_sparse` with its shortest-path rows, on topology and
routing events); read from the program's spans of a traced run, None
without them."""


def read(run):
    span = (getattr(run.window, "program", None) or {}).get(
        "spans", {}).get("churn.repair")
    return 1e3 * span["total_s"] / span["calls"] if span else None
