"""``peak_bytes_in_use`` after the window, in GiB (the largest over ranks)."""


def read(run):
    return run.memory_peak_bytes / 2 ** 30 if run.memory_peak_bytes else None
