"""device issue: the ``dev.call`` span inside ``dev.submit`` (the program's
call: one task's jitted body, or a group's flat program at a member's
share), microseconds per executed task: ``tpudev.call_ns`` sum over
``tpudev.retire_ns`` count, as ``submit_per_task``; with
``gather_per_task`` it makes up ``submit_per_task``. Process-lifetime
totals, read after the run. A program without the span gives nothing to
read."""


def read(run):
    from parsec_tpu.utils.hist import histograms

    snap = histograms.snapshot()
    span, done = snap.get("tpudev.call_ns"), snap.get("tpudev.retire_ns")
    if not span or not span["count"] or not done or not done["count"]:
        return None
    return span["sum_ns"] / done["count"] / 1e3
