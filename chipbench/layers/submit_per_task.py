"""device issue: the program's own ``dev.submit`` span (residency decision,
pins and stage-in issue of the inputs, then the jitted call's dispatch),
microseconds per executed task: ``tpudev.submit_ns`` sum over
``tpudev.retire_ns`` count. Process-lifetime totals, read after the run: the
warm-up solve (with its executable loads) and the window's solves alike."""


def read(run):
    from parsec_tpu.utils.hist import histograms

    snap = histograms.snapshot()
    span, done = snap.get("tpudev.submit_ns"), snap.get("tpudev.retire_ns")
    if not span or not span["count"] or not done or not done["count"]:
        return None
    return span["sum_ns"] / done["count"] / 1e3
