"""Tasks of the DAG over the median solve seconds (all ranks' tasks, the
slowest rank's seconds, where the cell spans chips)."""

import statistics


def read(run):
    secs = run.solve_seconds()
    return run.tasks_per_solve / statistics.median(secs) if secs else None
