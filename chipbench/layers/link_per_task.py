"""DTD insert/link: the program's own ``dtd.link`` span (the locked insert:
class lookup, tile chains, engine link, ready buffering; window stalls
excluded), mean microseconds per insert from the ``dtd.link_ns`` histogram.
Process-lifetime totals, read after the run: the warm-up solve and the
window's solves alike (``run.py`` takes a window delta of
``ptdtd.ready_wait_ns`` only)."""


def read(run):
    from parsec_tpu.utils.hist import histograms

    hist = histograms.snapshot().get("dtd.link_ns")
    if not hist or not hist["count"]:
        return None
    return hist["sum_ns"] / hist["count"] / 1e3
