"""residency: the program's own ``dev.writeback`` span (the dirty branch of
an eviction: the device array fetched into a numpy array under the data's
lock, on the thread that asked for room), mean microseconds per tile
written back from the ``tpudev.writeback_ns`` histogram. Unlike a stage-in's
span it holds the whole transfer: the fetch is synchronous. Process-lifetime
totals, read after the run: the warm-up solve and the window's solves alike.
A program without the histogram, or a run that wrote nothing back, gives
nothing to read."""


def read(run):
    from parsec_tpu.utils.hist import histograms

    hist = histograms.snapshot().get("tpudev.writeback_ns")
    if not hist or not hist["count"]:
        return None
    return hist["sum_ns"] / hist["count"] / 1e3
