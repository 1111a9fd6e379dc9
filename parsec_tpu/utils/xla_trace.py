"""XLA/HLO-level tracing bridge.

The role profiling_nvtx.c plays in the reference (annotating runtime spans
for the vendor profiler) maps on TPU to ``jax.profiler``: device-side HLO
timelines captured into TensorBoard/Perfetto format, with runtime task spans
annotated via TraceAnnotation so kernel activity lines up with task names
(BASELINE.json: "swap profiling_nvtx for XLA HLO tracing").

Usage::

    with xla_trace("/tmp/tb"):            # device + host timeline
        ... run taskpools ...

or annotate spans manually through :class:`TaskAnnotator` (a PINS module).

:class:`Spans` is the runtime's own instrumentation of the device paths:
seven named spans on the per-task path (``device/tpu.py``, ``dsl/dtd.py``;
``dev.writeback``, the dirty branch of an eviction, is both managers')
and four on the PTG path (``dsl/ptg/compiler.py``: the lowering of one
instantiation, and the ``ptdev`` manager's dispatch, poll and retire),
each a ``TraceAnnotation`` on the profiler's host plane and a duration in
a ``utils/hist.py`` histogram, plus two intervals that are histograms
alone: the ready-wait, and ``ptdev.stage_in_ns`` (a miss of the lane's
push phase, whose annotation is the ``dev.stage_in`` it nests), and four
counts filed the same way: ``tpudev.group_tasks``, ``ptdev.pins``,
``ptdev.inflight`` and ``ptexec.region_tasks``.
One object per ``Context``, ``None`` when off, so a site is
``sp = self._spans`` / ``if sp is not None:``.
"""

from __future__ import annotations

import contextlib
from time import perf_counter_ns
from typing import Iterator, List, Optional, Tuple

from ..core import pins as P
from . import mca, output
from .hist import HIST_NAMES, HistCell, PyHistograms

mca.register("profile_xla_dir", "", "Capture a jax.profiler trace into this dir")


@contextlib.contextmanager
def xla_trace(logdir: Optional[str] = None) -> Iterator[None]:
    """Capture a jax.profiler trace around a region (no-op without a dir)."""
    logdir = logdir or mca.get("profile_xla_dir", "")
    if not logdir:
        yield
        return
    import jax
    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
        output.inform(f"XLA trace captured to {logdir}")


#: span names as they stand on the profiler's host plane
DTD_LINK, DTD_STALL = "dtd.link", "dtd.stall"
DEV_SUBMIT, DEV_STAGE_IN = "dev.submit", "dev.stage_in"
DEV_POLL, DEV_RETIRE = "dev.poll", "dev.retire"
DEV_WRITEBACK = "dev.writeback"
PTG_LOWER = "ptg.lower"
PTDEV_DISPATCH = "ptdev.dispatch"
PTDEV_POLL, PTDEV_RETIRE = "ptdev.poll", "ptdev.retire"


class Spans:
    """The spans of one context. ``tok = sp.begin(NAME)`` ...
    ``sp.end(tok, sp.<cell>)``, entered and left on one thread in strict
    nesting (legal TraceMe nesting). Outside a profiler session no
    annotation is made and only the histogram records."""

    def __init__(self) -> None:
        from jax.profiler import TraceAnnotation

        from ..dsl.dtd import DTDTask
        self._annotation = TraceAnnotation
        self._tracing = TraceAnnotation.is_enabled
        self._dtd_task = DTDTask
        tpudev = PyHistograms(HIST_NAMES["tpudev"])
        dtd = PyHistograms(HIST_NAMES["dtd"])
        # DTD pools' ready -> issued wait joins the native engine's
        # histogram of the same name under the registry key
        # ``ptdtd.ready_wait_ns`` (the engine arms its own on the batched
        # lane only, which refuses contexts with an accelerator)
        ready = PyHistograms(("ready_wait_ns",))
        self.submit = tpudev.cell("submit_ns")
        self.stage_in = tpudev.cell("stage_in_ns")
        self.poll = tpudev.cell("poll_ns")
        self.retire = tpudev.cell("retire_ns")
        self.group_tasks = tpudev.cell("group_tasks")
        self.writeback = tpudev.cell("writeback_ns")
        self.link = dtd.cell("link_ns")
        self.stall = dtd.cell("stall_ns")
        self._ready = ready.cell("ready_wait_ns")
        ptg = PyHistograms(HIST_NAMES["ptg"])
        ptdev = PyHistograms(HIST_NAMES["ptdev"])
        self.lower = ptg.cell("lower_ns")
        self.pt_dispatch = ptdev.cell("dispatch_ns")
        self.pt_stage_in = ptdev.cell("stage_in_ns")
        self.pt_poll = ptdev.cell("poll_ns")
        self.pt_retire = ptdev.cell("retire_ns")
        self.pt_pins = ptdev.cell("pins")
        self.pt_inflight = ptdev.cell("inflight")
        # a fused region's members, one record a region a pool binds:
        # filed beside the ptexec lane's own histograms, as the ready-wait
        # is beside ptdtd's
        regions = PyHistograms(("region_tasks",))
        self.region_tasks = regions.cell("region_tasks")
        #: (registry kind, object) for Context._hist_attach/_hist_detach
        self.hists: List[Tuple[str, PyHistograms]] = [
            ("tpudev", tpudev), ("dtd", dtd), ("ptdtd", ready),
            ("ptg", ptg), ("ptdev", ptdev), ("ptexec", regions)]

    def begin(self, name: str):
        ann = None
        if self._tracing():
            ann = self._annotation(name)
            ann.__enter__()
        return ann, perf_counter_ns()

    def end(self, tok, cell: Optional[HistCell], less: int = 0,
            n: int = 1) -> int:
        """Close the span and record its nanoseconds, ``less`` what nested
        spans already recorded, as ``n`` equal observations (a batched
        dispatch counts once per member; ``cell=None`` records nothing).
        Returns the whole duration."""
        dt = perf_counter_ns() - tok[1]
        if tok[0] is not None:
            tok[0].__exit__(None, None, None)
        if cell is not None:
            cell.record((dt - less) // n, n)
        return dt

    def stamp(self, tasks) -> None:
        """``Context.schedule``: the moment these tasks became ready. A
        task scheduled again (an OOM bounce) keeps its first stamp."""
        now = perf_counter_ns()
        for t in tasks:
            if t.prof_info is None:
                t.prof_info = now

    def ready_wait(self, task) -> None:
        """The task's first successful submit: ready -> issued, once per
        executed task, for DTD pools."""
        t0 = task.prof_info
        if t0 is not None and isinstance(task, self._dtd_task):
            task.prof_info = None
            self._ready.record(perf_counter_ns() - t0)


class TaskAnnotator:
    """PINS module: wrap task execution in jax.profiler.TraceAnnotation so
    device kernels group under their task names in the timeline (the NVTX
    range push/pop role). The interval is ``EXEC_BEGIN..EXEC_END``, fired
    once per task on every lane that has Python task objects (the native
    engine's per-task lane included). For a device task that is the
    *enqueue* (the hook returns ``HOOK_ASYNC``), not the submit:
    :data:`DEV_SUBMIT` marks the dispatch."""

    name = "xla_annotator"

    def __init__(self) -> None:
        self._open = {}

    def enable(self, context) -> None:
        self.context = context
        context.pins.register(P.EXEC_BEGIN, self._begin)
        context.pins.register(P.EXEC_END, self._end)

    def disable(self, context) -> None:
        context.pins.unregister(P.EXEC_BEGIN, self._begin)
        context.pins.unregister(P.EXEC_END, self._end)

    def _begin(self, stream, task, extra) -> None:
        import jax
        ann = jax.profiler.TraceAnnotation(
            f"{task.taskpool.name}::{task.task_class.name}")
        ann.__enter__()
        self._open[id(task)] = ann

    def _end(self, stream, task, extra) -> None:
        ann = self._open.pop(id(task), None)
        if ann is not None:
            ann.__exit__(None, None, None)
