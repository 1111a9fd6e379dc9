"""entry: CPU seconds of the process (``time.process_time``, every thread)
inside the solves, per task, in microseconds, over the solves the profiler
left alone."""


def read(run):
    per = run.per_task("cpu")
    return None if per is None else per * 1e6
