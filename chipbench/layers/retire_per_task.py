"""device issue: the program's own ``dev.retire`` span (the epilog: version
bumps, unpin, release of successors and their scheduling), mean microseconds
per executed task from the ``tpudev.retire_ns`` histogram. Process-lifetime
totals, read after the run: the warm-up solve and the window's solves
alike."""


def read(run):
    from parsec_tpu.utils.hist import histograms

    hist = histograms.snapshot().get("tpudev.retire_ns")
    if not hist or not hist["count"]:
        return None
    return hist["sum_ns"] / hist["count"] / 1e3
