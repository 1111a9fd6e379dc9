"""device issue: the ``ptdev`` manager's ``ptdev.retire`` span (write-backs,
version bumps and unpins of a completed program), microseconds per device
program: ``ptdev.retire_ns`` sum over count. Process-lifetime totals, read
after the run."""


def read(run):
    from parsec_tpu.utils.hist import histograms

    span = histograms.snapshot().get("ptdev.retire_ns")
    if not span or not span["count"]:
        return None
    return span["sum_ns"] / span["count"] / 1e3
