"""device issue: the share of executed tasks that left the host inside a
program of several tasks, in percent: ``tpudev.group_tasks`` sum (one record
per multi-task program, its size) over ``tpudev.retire_ns`` count.
Process-lifetime totals, read after the run, like the span readers beside
it. A program without the histogram (before the device manager issued groups
by observation) gives nothing to read."""


def read(run):
    from parsec_tpu.utils.hist import histograms

    snap = histograms.snapshot()
    groups, done = snap.get("tpudev.group_tasks"), snap.get("tpudev.retire_ns")
    if groups is None or not done or not done["count"]:
        return None
    return 100.0 * groups["sum_ns"] / done["count"]
