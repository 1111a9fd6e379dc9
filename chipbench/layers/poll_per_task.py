"""device issue: the program's own ``dev.poll`` span (``is_ready`` over the
in-flight programs, one record per manager pass, epilogs subtracted),
microseconds per executed task: ``tpudev.poll_ns`` sum over
``tpudev.retire_ns`` count. Process-lifetime totals, read after the run: the
warm-up solve and the window's solves alike."""


def read(run):
    from parsec_tpu.utils.hist import histograms

    snap = histograms.snapshot()
    span, done = snap.get("tpudev.poll_ns"), snap.get("tpudev.retire_ns")
    if not span or not span["count"] or not done or not done["count"]:
        return None
    return span["sum_ns"] / done["count"] / 1e3
