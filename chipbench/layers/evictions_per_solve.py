"""residency: device copies evicted (``TPUDevice.evictions``) over the
window, per solve."""


def read(run):
    good = sum(s["ok"] for s in run.solves)
    return run.counters["evictions"] / good if good else None
