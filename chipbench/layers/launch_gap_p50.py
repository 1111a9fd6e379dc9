"""device issue: median gap between consecutive device programs in the
traced part of the window, in microseconds."""


def read(run):
    return run.trace and run.trace["launch_gap_p50_us"]
