"""kernels: device seconds of the graph's tile programs (XLA modules
``jit_<body>``, as the graph driver lists them) in the traced solves, per
solve."""


def read(run):
    if not run.trace or not run.trace["solves"]:
        return None
    secs = sum(run.trace["modules"].get(name, {"seconds": 0.0})["seconds"]
               for name in run.graph.KERNEL_MODULES)
    return secs / run.trace["solves"]
