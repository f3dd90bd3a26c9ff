"""Peak device memory of the run (``peak_bytes_in_use`` after the
window, fullest chip), in GB."""


def read(r):
    return r.memory_peak_bytes / 1e9 if r.memory_peak_bytes else None
