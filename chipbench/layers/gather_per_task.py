"""device issue: the ``dev.gather`` span inside ``dev.submit`` (the inputs
of a program made resident and pinned: the residency decision, a stage-in
where a tile misses, an eviction and its write-back where the device is
full), microseconds per executed task: ``tpudev.gather_ns`` sum over
``tpudev.retire_ns`` count, as ``submit_per_task``. Process-lifetime
totals, read after the run. A program without the span gives nothing to
read."""


def read(run):
    from parsec_tpu.utils.hist import histograms

    snap = histograms.snapshot()
    span, done = snap.get("tpudev.gather_ns"), snap.get("tpudev.retire_ns")
    if not span or not span["count"] or not done or not done["count"]:
        return None
    return span["sum_ns"] / done["count"] / 1e3
