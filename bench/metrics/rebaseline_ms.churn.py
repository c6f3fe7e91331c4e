"""Host milliseconds per call of the program's `churn.rebaseline` span
(the warm re-baseline after every event: `init_run_state` on the
repaired iterate); read from the program's spans of a traced run, None
without them."""


def read(run):
    span = (getattr(run.window, "program", None) or {}).get(
        "spans", {}).get("churn.rebaseline")
    return 1e3 * span["total_s"] / span["calls"] if span else None
