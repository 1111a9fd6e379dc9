"""comm: active messages sent (``sent_msgs`` of the TCP engine) over the
window, per solve, summed over ranks. The engine counts messages, not
bytes."""

RANKS = "sum"


def read(run):
    good = sum(s["ok"] for s in run.solves)
    if "sent_msgs" not in run.counters or not good:
        return None
    return run.counters["sent_msgs"] / good
