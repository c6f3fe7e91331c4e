"""Device-to-host fetches of the program per churn event: its
`host_syncs` counter over its `churn.events` counter in a traced run,
None without them."""


def read(run):
    counters = (getattr(run.window, "program", None) or {}).get(
        "counters", {})
    events = counters.get("churn.events")
    return counters.get("host_syncs", 0) / events if events else None
