"""DTD insert/link: times the inserter blocked on the task window
(``tp.window_stalls``, ``dtd_window_size`` 2048), per solve."""


def read(run):
    good = [s for s in run.solves if s["ok"]]
    return sum(s["window_stalls"] for s in good) / len(good) if good else None
