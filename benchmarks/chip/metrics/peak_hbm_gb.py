"""Peak device memory in use over the run, ``memory_stats()`` after the
window, in GB (1e9 bytes)."""


def read(run):
    if run.memory_peak_bytes is None:
        return None
    return run.memory_peak_bytes / 1e9
