"""device issue: the ``ptdev`` manager's ``ptdev.poll`` span (one record a
pass over the programs in flight, ``is_ready`` on each, the retirements'
own spans subtracted), microseconds per retired device program:
``ptdev.poll_ns`` sum over ``ptdev.retire_ns`` count. Process-lifetime
totals, read after the run."""


def read(run):
    from parsec_tpu.utils.hist import histograms

    snap = histograms.snapshot()
    span, done = snap.get("ptdev.poll_ns"), snap.get("ptdev.retire_ns")
    if not span or not span["count"] or not done or not done["count"]:
        return None
    return span["sum_ns"] / done["count"] / 1e3
