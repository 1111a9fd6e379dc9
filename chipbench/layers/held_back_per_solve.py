"""residency: device programs that surfaced ready and waited in their pool's
backlog until the residency budget could pin their operands
(``PTDEV_STATS["held_back"]``, served as ``ptdev.held_back``, each program
counted once), over the window, per good solve. 0 where every batch fit; a
program without the counter (one that pins a whole batch whatever the
budget) gives nothing to read."""


def read(run):
    good = sum(s["ok"] for s in run.solves)
    if not good or "ptdev.held_back" not in run.counters:
        return None
    return run.counters["ptdev.held_back"] / good
