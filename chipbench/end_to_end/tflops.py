"""Algorithmic FLOP of a solve (the graph driver's ``flops``: 2N^3 for GEMM,
N^3/3 + N^2/2 for POTRF) over the median solve seconds, in TFLOP/s."""

import statistics


def read(run):
    secs = run.solve_seconds()
    return run.flops_per_solve / statistics.median(secs) / 1e12 \
        if secs else None
