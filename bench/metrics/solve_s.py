"""Seconds per cold solve: the whole window over the solves completed in
it (the window ends at a solve boundary)."""


def read(run):
    return run.window.seconds / len(run.window.latencies)
