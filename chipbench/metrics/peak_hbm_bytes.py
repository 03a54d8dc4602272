"""memory_stats()["peak_bytes_in_use"] of the fullest chip, read when the
window closes and before any reference computation."""


def read(run):
    return run.get("peak_bytes") or None
