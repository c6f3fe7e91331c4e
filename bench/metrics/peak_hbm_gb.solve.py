"""Device memory peak after the window (`memory_stats`
peak_bytes_in_use), in GB."""


def read(run):
    if not run.memory_peak_bytes:
        return None
    return run.memory_peak_bytes / 1e9
