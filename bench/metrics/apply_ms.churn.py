"""Host milliseconds per churn event in the program's `churn.apply` span
(`ReplayEngine.apply_event`: churn state, repair, warm re-baseline);
read from the program's spans of a traced run, None without them."""


def read(run):
    span = (getattr(run.window, "program", None) or {}).get(
        "spans", {}).get("churn.apply")
    return 1e3 * span["total_s"] / span["calls"] if span else None
