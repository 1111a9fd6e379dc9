"""ready/sched: p99 of the native ``ptdtd.ready_wait_ns`` histogram over
the window, in microseconds — only if the histogram counted exactly the
window's tasks (DTD pools on a TPU context take the per-task lane, where
the histogram is unproven; a sampled or partial count is not a p99)."""


def read(run):
    from parsec_tpu.utils.hist import percentile

    tasks = sum(s["local_tasks"] for s in run.solves if s["ok"])
    hist = run.ready_wait
    if not hist or not tasks or hist["count"] != tasks:
        return None
    return percentile(hist["buckets"], 0.99) / 1e3
