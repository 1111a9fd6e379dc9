"""device issue: the ``ptdev`` manager's ``ptdev.dispatch`` span (stage-in
issue and pins of a region's operands, then the jitted call's dispatch),
microseconds per device program: ``ptdev.dispatch_ns`` sum over count (one
record a program). Process-lifetime totals, read after the run. A program
without the span gives nothing to read."""


def read(run):
    from parsec_tpu.utils.hist import histograms

    span = histograms.snapshot().get("ptdev.dispatch_ns")
    if not span or not span["count"]:
        return None
    return span["sum_ns"] / span["count"] / 1e3
