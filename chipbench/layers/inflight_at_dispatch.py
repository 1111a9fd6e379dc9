"""device issue: the device programs already in flight when the ``ptdev``
manager is handed the next ready ones: ``ptdev.inflight`` sum over count (one
record a ``dispatch`` callback). It is the depth of the device's queue as the
host keeps it: near 0 in a chain of dependent regions, each released only
when the host has seen its predecessor complete; the queue's depth where a
pool's regions are all ready at once. Process-lifetime totals, read after
the run. A program without the histogram gives nothing to read."""


def read(run):
    from parsec_tpu.utils.hist import histograms

    hist = histograms.snapshot().get("ptdev.inflight")
    if not hist or not hist["count"]:
        return None
    return hist["sum_ns"] / hist["count"]
