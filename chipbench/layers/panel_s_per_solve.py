"""kernels: device seconds of the Householder panels (the XLA modules the
graph driver lists as ``PANEL_MODULES``: ``jit_tile_geqrt`` and
``jit_tile_tsqrt``) in the traced solves, per solve. Nothing to read where
the graph has no panel or the trace holds none of its modules."""


def read(run):
    names = getattr(run.graph, "PANEL_MODULES", ())
    if not run.trace or not run.trace["solves"]:
        return None
    found = [run.trace["modules"][n] for n in names
             if n in run.trace["modules"]]
    if not found:
        return None
    return sum(m["seconds"] for m in found) / run.trace["solves"]
