"""residency: bytes of the flows written without being read that took room
on the device and moved no byte (the device module's ``write_alloc_bytes``)
over the window, per solve, in GiB. Nothing to read where the graph driver
does not count it."""


def read(run):
    good = sum(s["ok"] for s in run.solves)
    if not good or "write_alloc_bytes" not in run.counters:
        return None
    return run.counters["write_alloc_bytes"] / 2 ** 30 / good
