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
nine named spans on the per-task path (``device/tpu.py``, ``dsl/dtd.py``;
``dev.gather`` and ``dev.call`` are the two halves of ``dev.submit``;
``dev.writeback``, the dirty branch of an eviction, is both managers')
and seven on the PTG path (``dsl/ptg/compiler.py``: the lowering of one
instantiation; ``device/lane_pool.py``: the ``ptdev`` manager's dispatch,
with its push phase and each program's call inside it, poll and retire;
``device/tpu.py``: inside the push phase, a miss's room made),
each a ``TraceAnnotation`` on the profiler's host plane and a duration in
a ``utils/hist.py`` histogram, plus two intervals that are histograms
alone: the ready-wait, and ``ptdev.stage_in_ns`` (the misses of the
lane's push phase, each at its share of the batch's one ``device_put``,
whose annotation is the ``dev.stage_in`` the push nests), and four
counts filed the same way: ``tpudev.group_tasks``, ``ptdev.pins``,
``ptdev.inflight`` and ``ptexec.region_tasks``.
One object per ``Context``, ``None`` when off, so a site is
``sp = self._spans`` / ``if sp is not None:``.

With the spans on, a pool of the ``ptdev`` lane also keeps an account of
the manager thread's time and files it at its end
(:func:`file_pool_account`, :data:`POOL_ACCOUNTS`; docs/observability.md,
"A pool's account").
"""

from __future__ import annotations

import collections
import contextlib
from time import perf_counter_ns
from typing import Deque, Dict, Iterator, List, Optional, Tuple

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
DEV_GATHER, DEV_CALL = "dev.gather", "dev.call"     # inside dev.submit
PTG_LOWER = "ptg.lower"
PTDEV_DISPATCH = "ptdev.dispatch"
PTDEV_PUSH, PTDEV_CALL = "ptdev.push", "ptdev.call"  # inside ptdev.dispatch
PTDEV_ROOM = "ptdev.room"                           # inside ptdev.push
PTDEV_POLL, PTDEV_RETIRE = "ptdev.poll", "ptdev.retire"

#: the fields of a pool's account, in the order they are told: nanoseconds
#: of the lane's manager thread that add up to ``life_ns`` (``head_ns`` is
#: the part of ``life_ns`` before the first program's call, ``room_ns`` the
#: part of ``push_ns`` that made room), then counts
POOL_ACCOUNT_FIELDS = ("life_ns", "head_ns", "push_ns", "room_ns", "call_ns",
                       "own_ns", "poll_ns", "retire_ns", "away_ns",
                       "programs", "callbacks", "passes", "tasks")
#: the accounts of the last pools that ended on a ``ptdev`` lane with the
#: spans on, oldest first. Process-wide, so it outlives ``ctx.fini()`` as
#: the folded histograms do; appended on a lane's manager thread, read by
#: anyone (the registry serves the newest as ``ptdev.pool.<field>``)
POOL_ACCOUNTS: Deque[Dict[str, int]] = collections.deque(maxlen=64)


def file_pool_account(bound: int, first_call: int, end: int, *,
                      dispatch_ns: int, push_ns: int, call_ns: int,
                      poll_ns: int, retire_ns: int, programs: int,
                      callbacks: int, passes: int, tasks: int,
                      room_ns: int = 0) -> Dict[str, int]:
    """File the account of a pool that was bound at the clock ``bound``,
    entered its first ``ptdev.call`` at ``first_call`` (0: it never did)
    and ended at ``end``, from the nanoseconds of its callbacks' spans
    (``poll_ns`` less the retirements, as ``ptdev.poll_ns`` records it).
    ``own_ns`` is what a ``dispatch`` callback spends outside its push
    phase and its calls; ``away_ns`` the manager thread outside this
    pool's callbacks: the engine's release walk, the C lane's wake-ups and
    waits, other pools. ``push + call + own + poll + retire + away`` is
    ``life`` to the nanosecond; ``room_ns``, the ``ptdev.room`` spans, is
    a part of ``push_ns``."""
    life = end - bound
    account = {
        "life_ns": life,
        "head_ns": first_call - bound if first_call else life,
        "push_ns": push_ns, "room_ns": room_ns, "call_ns": call_ns,
        "own_ns": dispatch_ns - push_ns - call_ns,
        "poll_ns": poll_ns, "retire_ns": retire_ns,
        "away_ns": life - dispatch_ns - poll_ns - retire_ns,
        "programs": programs, "callbacks": callbacks, "passes": passes,
        "tasks": tasks}
    POOL_ACCOUNTS.append(account)
    return account


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
        self.gather = tpudev.cell("gather_ns")
        self.call = tpudev.cell("call_ns")
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
        self.pt_push = ptdev.cell("push_ns")
        self.pt_call = ptdev.cell("call_ns")
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
