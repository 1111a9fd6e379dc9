"""device issue: what share of the device programs the lane's closures
dispatched were released to the engine at dispatch, their successors
surfacing while they still ran: 100 x ``PTDEV_STATS["released_early"]``
over ``PTDEV_STATS["programs"]``. A program is released early when it has
successors and every one of them is a device node of the same lane (the
plan's finding, from the graph's structure alone); a sink, and a node with
one host-bodied successor, retires when the host has seen it complete. 46
of the 47 regions of the NT = 32 Cholesky; 0 where no region has a
successor (a k-chain GEMM). Process-lifetime totals, read after the run,
like the readers beside it. A program without the counters (every node
retires at its observed completion) gives nothing to read."""


def read(run):
    from parsec_tpu.device.native import PTDEV_STATS

    programs = PTDEV_STATS.get("programs")
    if not programs or "released_early" not in PTDEV_STATS:
        return None
    return 100.0 * PTDEV_STATS["released_early"] / programs
