"""residency: bytes written back from the device to the host
(``transfer_out_bytes`` of the device module: the dirty tiles the residency
layer evicted) over the window, per solve, in GiB. 0.0 where a solve wrote
nothing back; nothing to read where the graph driver does not count it."""


def read(run):
    good = sum(s["ok"] for s in run.solves)
    if not good or "transfer_out_bytes" not in run.counters:
        return None
    return run.counters["transfer_out_bytes"] / 2 ** 30 / good
