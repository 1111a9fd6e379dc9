"""residency: what a stage-in miss of the ``ptdev`` lane's push phase costs
the host, microseconds per tile that moved bytes: ``ptdev.stage_in_ns`` sum
over count (one record per ``device_put`` issued; a hit or an adoption is
not recorded). It times the issue, not the transfer. Process-lifetime
totals, read after the run. A program without the histogram, or a window
that staged nothing in, gives nothing to read."""


def read(run):
    from parsec_tpu.utils.hist import histograms

    hist = histograms.snapshot().get("ptdev.stage_in_ns")
    if not hist or not hist["count"]:
        return None
    return hist["sum_ns"] / hist["count"] / 1e3
