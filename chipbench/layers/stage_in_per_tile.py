"""residency: the program's own ``dev.stage_in`` span (the host cost of
issuing one H2D: ``device_put`` and the residency bookkeeping; hits are not
recorded), mean microseconds per staged tile from the ``tpudev.stage_in_ns``
histogram. Process-lifetime totals, read after the run: the warm-up solve and
the window's solves alike."""


def read(run):
    from parsec_tpu.utils.hist import histograms

    hist = histograms.snapshot().get("tpudev.stage_in_ns")
    if not hist or not hist["count"]:
        return None
    return hist["sum_ns"] / hist["count"] / 1e3
