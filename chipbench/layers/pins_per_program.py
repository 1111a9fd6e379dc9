"""device issue: the pins the ``ptdev`` manager takes in the residency table
per device program: ``ptdev.pins`` sum (one record a ``dispatch`` callback,
the table pins it took, its stage-ins' included) over ``ptdev.dispatch_ns``
count (one record a program). Process-lifetime totals, read after the run,
like the span readers beside it. A program without the histogram (pins taken
per program and operand, uncounted) gives nothing to read."""


def read(run):
    from parsec_tpu.utils.hist import histograms

    snap = histograms.snapshot()
    pins, programs = snap.get("ptdev.pins"), snap.get("ptdev.dispatch_ns")
    if pins is None or not programs or not programs["count"]:
        return None
    return pins["sum_ns"] / programs["count"]
