"""PTG lowering: the program's own ``ptg.lower`` span, one record an
instantiation (``PTGProgram.instantiate`` to the lanes bound to the device:
task classes and bodies, the native graph from the cached flatten, the
regions' operands), milliseconds per solve: ``ptg.lower_ns`` sum over count.
Process-lifetime totals, read after the run: the warm-up solve, which
flattens the DAG and plans the regions, and the window's solves alike. A
program without the span gives nothing to read."""


def read(run):
    from parsec_tpu.utils.hist import histograms

    span = histograms.snapshot().get("ptg.lower_ns")
    if not span or not span["count"]:
        return None
    return span["sum_ns"] / span["count"] / 1e6
