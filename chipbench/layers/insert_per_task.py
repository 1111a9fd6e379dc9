"""DTD insert/link: host clock around ``insert_*_tasks`` (window stalls
included) per task, in microseconds, over the solves the profiler left
alone."""


def read(run):
    per = run.per_task("insert")
    return None if per is None else per * 1e6
