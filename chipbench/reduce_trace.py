"""From a ``jax.profiler`` trace to the numbers the per-layer metrics read.

One ``.xplane.pb`` holds a plane per device (``/device:TPU:0``) and one for
the host's threads (``/host:CPU``). On a device plane the line
``XLA Modules`` has one event per device program, named
``jit_<body>(<fingerprint>)``, and ``XLA Ops`` one per operation inside it.
The host plane holds this benchmark's own spans
(``jax.profiler.TraceAnnotation``: ``refill``, ``insert``, ``wait``,
``check``). Both are on the profiler's clock, in nanoseconds.

The reduction is kept as code with the benchmark, and checked on a recorded
trace in ``chipbench/tests``, so that every PR computes the same number the
same way.
"""

import glob
import os
import re
import statistics

#: this benchmark's host spans; an idle gap is named by the one it lies in
SPANS = ("refill", "insert", "wait", "check")
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"


def union(intervals):
    """Merge [(start, end)] into disjoint sorted intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def module_name(event_name):
    """``jit_tile_gemm(1234567)`` -> ``jit_tile_gemm``."""
    return re.sub(r"\(\d+\)$", "", event_name)


def span_at(spans, t):
    """Name of the innermost host span that holds time ``t``, else
    ``outside``. ``spans`` is [(name, start, end)]."""
    best = None
    for name, s, e in spans:
        if s <= t < e and (best is None or s >= best[1]):
            best = (name, s)
    return best[0] if best else "outside"


def reduce_events(programs, ops, spans):
    """The reduction proper, on plain tuples in nanoseconds.

    ``programs``: [(module name, start, duration)] of one device;
    ``ops``: [(start, duration)] of its operations (may be empty: the
    programs then stand for them); ``spans``: [(name, start, end)] of the
    host. The window runs from the first host span's start to the last
    one's end (the whole trace where there is none).
    """
    busy_src = [(s, s + d) for s, d in ops] or \
        [(s, s + d) for _n, s, d in programs]
    if spans:
        lo, hi = min(s for _n, s, _e in spans), max(e for _n, _s, e in spans)
    elif busy_src:
        lo, hi = min(s for s, _e in busy_src), max(e for _s, e in busy_src)
    else:
        lo = hi = 0
    busy = union(clip(busy_src, lo, hi))
    busy_ns = sum(e - s for s, e in busy)

    modules = {}
    for name, s, d in programs:
        if lo <= s < hi:
            m = modules.setdefault(name, {"count": 0, "seconds": 0.0})
            m["count"] += 1
            m["seconds"] += d / 1e9

    # gaps between consecutive device programs (launch gaps) and between
    # busy intervals (idle gaps, named by the host span at their middle)
    progs = union(clip([(s, s + d) for _n, s, d in programs], lo, hi))
    launch_gaps = [b[0] - a[1] for a, b in zip(progs, progs[1:])]
    edges = [lo] + [t for iv in busy for t in iv] + [hi]
    idle_by_span = {}
    for s, e in zip(edges[0::2], edges[1::2]):
        if e > s:
            name = span_at(spans, (s + e) // 2)
            idle_by_span[name] = idle_by_span.get(name, 0.0) + (e - s) / 1e9

    def ranked(d):
        return sorted(([k, v] for k, v in d.items()), key=lambda kv: -kv[1])

    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_ns / 1e9,
        "programs": sum(m["count"] for m in modules.values()),
        "modules": modules,
        "launch_gap_p50_us": statistics.median(launch_gaps) / 1e3
        if launch_gaps else None,
        "device_ops": ranked({k: m["seconds"] for k, m in modules.items()}),
        "idle_gaps": ranked(idle_by_span),
    }


def load(path):
    """(programs, ops, spans) of the first device plane of an xplane file.
    A trace with no device plane (a CPU rehearsal) gives empty lists."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    programs, ops, spans = [], [], []
    device = None
    for plane in data.planes:
        if plane.name.startswith("/device:") and device is None and \
                any(line.name == MODULE_LINE for line in plane.lines):
            device = plane
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in SPANS:
                        spans.append((ev.name, int(ev.start_ns),
                                      int(ev.start_ns + ev.duration_ns)))
    if device is not None:
        for line in device.lines:
            if line.name == MODULE_LINE:
                programs = [(module_name(ev.name), int(ev.start_ns),
                             int(ev.duration_ns)) for ev in line.events]
            elif line.name == OP_LINE:
                ops = [(int(ev.start_ns), int(ev.duration_ns))
                       for ev in line.events]
    return programs, ops, spans


def reduce_file(path):
    return reduce_events(*load(path))


def reduce_dir(trace_dir):
    """Reduce the newest ``.xplane.pb`` under ``trace_dir``."""
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return reduce_file(found[-1])


def describe(path, limit=12):
    """The planes, lines and first events of a trace, to read by hand."""
    from jax.profiler import ProfileData

    rows = []
    for plane in ProfileData.from_file(path).planes:
        rows.append(f"plane {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            rows.append(f"  line {line.name!r}: {len(events)} events")
            for ev in events[:limit]:
                rows.append(f"    {ev.name!r} start={ev.start_ns:.0f} "
                            f"dur={ev.duration_ns:.0f}")
    return "\n".join(rows)


if __name__ == "__main__":
    import sys
    print(describe(sys.argv[1]))
