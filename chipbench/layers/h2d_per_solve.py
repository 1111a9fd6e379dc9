"""residency: bytes staged onto the device (``transfer_in_bytes`` of the
device module) over the window, per solve, in GiB."""


def read(run):
    good = sum(s["ok"] for s in run.solves)
    return run.counters["transfer_in_bytes"] / 2 ** 30 / good if good \
        else None
