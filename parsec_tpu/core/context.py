"""Runtime context, execution streams, and the scheduling state machine.

Re-design of parsec/parsec.c (parsec_init, :405) + parsec/scheduling.c:

* :class:`ExecutionStream` — one per worker thread (ref:
  parsec_execution_stream_t, parsec/include/parsec/execution_stream.h:36-76).
* :class:`Context` — process-wide state (ref: parsec_context_t,
  execution_stream.h:117-174), with ``add_taskpool / start / wait / test``
  mirroring parsec/runtime.h:174-388.
* The per-thread hot loop re-creates ``__parsec_context_wait``
  (scheduling.c:727, hot loop :789-818) including exponential backoff and
  master-thread communication progress.
* ``_task_progress`` re-creates ``__parsec_task_progress`` (scheduling.c:507)
  and ``__parsec_execute`` (scheduling.c:126): prepare_input → best-device
  selection → chore evaluate/hook → return-code dispatch
  (DONE/AGAIN/ASYNC/NEXT/DISABLE, scheduling.c:518-566).
* ``generic_release_deps`` re-creates the dependency-release engine
  (parsec_release_dep_fct parsec.c:1837, parsec_release_local_OUT_dependencies
  parsec.c:1750, parsec_update_deps_with_mask parsec.c:1657).

TPU-first deviation: device chores dispatch pre-compiled XLA/Pallas
executables asynchronously and return ``HOOK_ASYNC``; the progress loop polls
device modules (the analogue of the reference's GPU manager thread,
device_gpu.c:3376+) so a single host thread can keep the chip saturated —
important because host cores are scarce relative to TPU throughput.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

from ..utils import mca, output
from . import pins as pins_mod
from . import scheduler as sched_mod
from . import termdet as termdet_mod
from .datarepo import DataRepo
from .task import (
    DEV_ALL, DEV_CPU, FLOW_ACCESS_CTL, FLOW_ACCESS_WRITE,
    HOOK_AGAIN, HOOK_ASYNC, HOOK_DISABLE, HOOK_DONE, HOOK_ERROR, HOOK_NEXT,
    Task, TaskClass, Taskpool,
    TASK_STATUS_COMPLETE, TASK_STATUS_HOOK, TASK_STATUS_PREPARE_INPUT,
)

mca.register("runtime_nb_cores", 0, "Worker threads (0 = autodetect)", type=int)
mca.register("runtime_backoff_max_us", 1000, "Max starvation backoff (µs)", type=int)
mca.register("runtime_gc_defer", True,
             "Stretch Python cyclic-GC thresholds while taskpools are in "
             "flight (the mempool discipline of the reference: no "
             "allocator churn in the hot path). Task/tile graphs are "
             "cyclic and mostly LIVE mid-DAG, so frequent young-gen scans "
             "only promote them and full collections walk the whole heap "
             "— measured ~2x EP task throughput. Fully disabling GC "
             "instead would leak jax buffer cycles and force a costly "
             "whole-heap collect at quiescence (measured 3x on tiled "
             "POTRF), so thresholds are stretched, not switched off",
             type=bool)
mca.register("debug_paranoid", 0,
             "Assertion tier (ref: PARSEC_DEBUG_PARANOID): >0 adds runtime "
             "invariant checks in the scheduling hot path (not-ready or "
             "completed tasks entering the queues, double completion)",
             type=int)


# process-wide refcount for the GC-stretch window (several rank contexts
# can live in one process; gc thresholds are global)
_gc_defer_lock = threading.Lock()
_gc_defer_count = 0
_gc_saved_thresholds = None
_GC_STRETCHED = (50_000, 20, 20)    # vs the (700, 10, 10) default


def _gc_defer_acquire() -> None:
    global _gc_defer_count, _gc_saved_thresholds
    import gc
    with _gc_defer_lock:
        _gc_defer_count += 1
        if _gc_defer_count == 1:
            _gc_saved_thresholds = gc.get_threshold()
            gc.set_threshold(*_GC_STRETCHED)


def _gc_defer_release() -> None:
    global _gc_defer_count, _gc_saved_thresholds
    import gc
    with _gc_defer_lock:
        if _gc_defer_count == 0:
            return
        _gc_defer_count -= 1
        if _gc_defer_count == 0 and _gc_saved_thresholds is not None:
            gc.set_threshold(*_gc_saved_thresholds)
            _gc_saved_thresholds = None


class ExecutionStream:
    """One worker's view of the runtime (ref: execution_stream.h:36-76)."""

    __slots__ = ("th_id", "vp_id", "context", "next_task", "nb_selects",
                 "nb_executed", "prof", "rng_state")

    def __init__(self, th_id: int, context: "Context", vp_id: int = 0) -> None:
        self.th_id = th_id
        self.vp_id = vp_id
        self.context = context
        self.next_task: Optional[Task] = None   # es->next_task locality slot
        self.nb_selects = 0
        self.nb_executed = 0
        self.prof = None
        self.rng_state = (th_id * 2654435761) & 0xFFFFFFFF

    @property
    def is_master(self) -> bool:
        return self.th_id == 0  # ref: PARSEC_THREAD_IS_MASTER


class Context:
    """Process-wide runtime (ref: parsec_context_t + parsec_init parsec.c:405)."""

    def __init__(
        self,
        nb_cores: Optional[int] = None,
        scheduler: Optional[str] = None,
        argv: Optional[List[str]] = None,
        my_rank: int = 0,
        nb_ranks: int = 1,
    ) -> None:
        if argv:
            mca.parse_cmdline(argv)
        if nb_cores is None:
            nb_cores = mca.get("runtime_nb_cores", 0) or (os.cpu_count() or 1)
        self.nb_cores = max(1, nb_cores)
        self.my_rank = my_rank
        self.nb_ranks = nb_ranks
        self.pins = pins_mod.PinsManager()
        self.paranoid = mca.get("debug_paranoid", 0)
        from .vpmap import VPMap
        self.vpmap = VPMap(nb_threads=self.nb_cores)
        self.streams: List[ExecutionStream] = [
            ExecutionStream(i, self, vp_id=self.vpmap.thread_to_vp(i))
            for i in range(self.nb_cores)
        ]
        #: True when the user picked a scheduler policy explicitly (ctor
        #: arg or --mca sched): execution-order policy then matters to
        #: them, and order-bypassing fast lanes (the DTD batched drain,
        #: which backfills outside the scheduler queues) must not engage
        self.sched_explicit = scheduler is not None or \
            mca.get("sched", "lfq") != "lfq"
        self.sched = sched_mod.create(scheduler)
        self.sched.install(self)
        for s in self.streams:
            self.sched.flow_init(s)
        #: native multi-pool scheduler plane (core/sched_plane.py, ISSUE
        #: 9): the shared ready plane the ptexec/ptdtd lanes drain
        #: through — per-worker hot queues, work stealing, weighted DRR
        #: across taskpools, admission windows. None when --mca
        #: sched_native 0, the native module is missing, or the selected
        #: scheduler policy has no native flavor (counted fallback)
        from .sched_plane import SchedPlane
        self.sched_plane = SchedPlane.maybe_create(self)
        #: the per-task device path's spans (utils/xla_trace.py Spans);
        #: None when off, armed below once the histograms' verdict is in
        self._spans = None
        # device registry (lazy import to avoid cycles)
        from ..device.device import DeviceRegistry
        self.devices = DeviceRegistry(self)
        self.comm = None            # set by parsec_tpu.comm when distributed
        #: process tracer: attach one directly (``ctx.profiling =
        #: Profiling()``) or let ``--mca profile_enabled 1`` create it —
        #: mca-created tracers dump to ``--mca profile_filename`` at fini
        #: (the reference's parsec_fini dbp write)
        self.profiling = None
        self._prof_auto = False
        if mca.get("profile_enabled", False):
            from ..utils.trace import Profiling
            self.profiling = Profiling()
            self._prof_auto = True
        self._taskpools: Dict[int, Taskpool] = {}
        self._active = 0
        self._cv = threading.Condition()
        self._started = False
        self._finalized = False
        self._workers: List[threading.Thread] = []
        self._work_event = threading.Event()
        self._error: Optional[BaseException] = None
        self._prio_seen = False   # any nonzero-priority task ever scheduled
        #: weak bound-method refs invoked when a progress loop starts or
        #: starves — producers holding amortization buffers (the DTD ready
        #: batch) drain here so direct _progress_loop users see their
        #: tasks. WEAK on purpose: a dropped taskpool must not be pinned
        #: alive (or keep costing a call per starved iteration) just
        #: because it once registered a hook
        self._drain_hooks: List = []
        # per-thread stream binding (was a thread-NAME parse on every
        # schedule() — the single hottest line of the EP profile)
        self._tls = threading.local()
        self._tls.stream = self.streams[0]
        #: serializes progress loops on the MASTER stream: every
        #: non-worker thread (wait()/wait_taskpool()/fini drain/DTD
        #: window stall/direct _progress_loop users) drives streams[0],
        #: and two concurrent drivers race on streams[0].next_task (the
        #: read-then-clear hand-off can execute a task twice or drop it).
        #: REENTRANT: nested loops on one thread (wait inside a drain)
        #: are legal
        self._master_loop_lock = threading.RLock()
        # schedule() only needs to wake anyone when parked workers or a
        # comm thread exist; single-core local runs skip the Event syscall
        # (RemoteDepEngine flips this when it attaches)
        self._need_wake = self.nb_cores > 1
        self._gc_held = False
        #: native PTG execution lanes awaiting drain: [(taskpool, lane)].
        #: Every stream's hot loop joins the front graph's run() (the C
        #: walk is GIL-free, so in-process workers scale on real cores)
        self._ptexec_q: List = []
        self._ptexec_lock = threading.Lock()
        #: the native DEVICE lane (device/native.py, ISSUE 10): one per
        #: context, created lazily the first time a TPU-bodied pool
        #: prepares for the execution lane (None = not yet tried, False =
        #: tried and unavailable). Its manager thread feeds completions
        #: back into the graphs GIL-free; fini tears it down BEFORE the
        #: device modules.
        self._ptdev: Any = None
        #: count of DEVICE-BOUND lane graphs in flight — same backoff
        #: treatment as comm-bound graphs: the next ready task comes from
        #: the lane's manager thread, not from this process's walk
        self._ptexec_dev_live = 0
        #: count of COMM-BOUND lane graphs in flight: while one lives,
        #: starvation backoff is capped near the wire latency — the comm
        #: progress thread ingests remote releases GIL-free at any
        #: moment, and a millisecond-scale sleep between lane polls would
        #: put the hot loop (not the wire) on the critical path of every
        #: cross-rank dependency chain
        self._ptexec_comm_live = 0
        #: the per-context native DTD engine (set by DTDTaskpool) and the
        #: count of LIVE batched-lane pools: while any pool has the
        #: batched insert lane armed, every stream's hot loop drains the
        #: engine's internal ready structure (drain_ready) the way it
        #: drains ptexec graphs. A count, not a sticky flag: each pool's
        #: final completion decrements it, so later non-batch pools (e.g.
        #: the bench's per-task-engine baseline reps) don't pay an empty
        #: engine drain every idle iteration
        self._dtd_neng = None
        self._dtd_batch_pools = 0
        #: bridge landing the native lanes' in-lane ring events into
        #: self.profiling (utils/native_trace.py); created lazily when a
        #: lane arms while profiling is attached — zero cost otherwise
        self._ntrace = None
        #: per-rank metrics endpoint (tools/metrics_server.py): the
        #: counter registry + latency percentiles over HTTP/UDS JSON,
        #: up for the context's whole life (--mca metrics_port / _uds)
        from ..tools.metrics_server import MetricsServer
        self.metrics = MetricsServer.maybe_start(my_rank, nb_ranks)
        #: native latency histograms (utils/hist.py): armed on every
        #: lane the context enqueues when requested explicitly or
        #: implied by a live metrics endpoint (/metrics serves live
        #: percentiles); off = one null branch per lane event site
        self._hist_on = bool(mca.get("hist_enabled", False)) or \
            self.metrics is not None
        if self._hist_on or mca.get("profile_xla_dir", ""):
            from ..utils.xla_trace import Spans
            self._spans = Spans()
            for kind, obj in self._spans.hists:
                self._hist_attach(kind, obj)
            for dev in self.devices.devices:
                dev.attach(self)    # the TPU modules pick the spans up
        #: lane stall watchdog (core/watchdog.py): armed by --mca
        #: watchdog_stall_ms; reads existing counters only (the PR 13
        #: no-new-hot-path contract), degrades /health on a latched
        #: stall and triggers the flight recorder
        self.watchdog = None
        wd_ms = mca.get("watchdog_stall_ms", 0)
        if wd_ms > 0:
            from .watchdog import StallWatchdog
            self.watchdog = StallWatchdog(self, stall_ms=wd_ms).start()
        if self.sched_plane is not None:
            # sched.queue_ns (push->pop wait) joins the lane histograms
            self._hist_attach("sched", self.sched_plane.plane)
        output.debug_verbose(2, "runtime",
                             f"context up: {self.nb_cores} streams, sched={self.sched.name}")

    # ------------------------------------------------------- in-lane tracing
    def _native_trace(self):
        """The native-lane trace bridge, or None when neither profiling
        (``ctx.profiling``, set by tests/users or --mca profile_enabled)
        nor PINS instrumentation is active. With PINS but no tracer the
        bridge runs marker-only (coarse NativeDrainMarker events, nothing
        landed) so instrumented pools can stay on the native lanes
        without PINS consumers seeing a silent, idle machine. Lazily
        constructed and registered as a drain hook so starving progress
        loops land pending ring events."""
        prof = self.profiling
        if prof is not None and not getattr(prof, "enabled", True):
            prof = None
        if prof is None and not self.pins.enabled:
            return None
        if self._ntrace is None:
            from ..utils.native_trace import NativeTraceBridge
            self._ntrace = NativeTraceBridge(prof, self.pins)
            self.register_drain_hook(self._ntrace.drain_all)
        elif self._ntrace.prof is None and prof is not None:
            # a tracer attached after a marker-only bridge armed: upgrade
            self._ntrace.prof = prof
        return self._ntrace

    def _ntrace_attach(self, kind: str, obj, tpid: int = 0) -> None:
        nt = self._native_trace()
        if nt is not None:
            nt.attach(kind, obj, tpid)

    def _ntrace_detach(self, obj) -> None:
        if self._ntrace is not None:
            self._ntrace.detach(obj)

    # --------------------------------------------------- latency histograms
    def _hist_attach(self, kind: str, obj) -> None:
        """Arm ``obj``'s native latency histograms (pthist.h) when the
        context wants them; called from the same lifecycle points as
        :meth:`_ntrace_attach`."""
        if self._hist_on:
            from ..utils.hist import histograms
            histograms.attach(kind, obj)

    def _hist_detach(self, obj) -> None:
        """Fold a finishing lane object's buckets into the process
        accumulator so /metrics keeps reporting completed pools."""
        if self._hist_on:
            from ..utils.hist import histograms
            histograms.detach(obj)

    # ----------------------------------------------------- online cost model
    def _cost_fold(self, lane: Dict[str, Any]) -> None:
        """Fold a finishing lane's cost observations into the online cost
        model (ISSUE 18) — the SAME lifecycle moment as the histogram
        registry's detach, and idempotent the same way the abandon path
        needs: every exiting stream of an errored graph attempts this,
        the pop()s make only the first one fold."""
        meta = lane.pop("cost_meta", None)
        obs = lane.pop("cost_dev", None)
        if meta is None and not obs:
            return
        from .costmodel import fold_cost_rows, model
        if meta is not None:
            try:
                fold_cost_rows(meta, lane["graph"].cost_snapshot())
            except Exception:  # noqa: BLE001 — folding is advisory
                pass
        if obs:
            # the device lane's dispatch/poll observations (manager-thread
            # local dict: (cls, bucket, dev) -> [count, sum_ns])
            model.fold_pairs((k, v[0], v[1]) for k, v in obs.items())

    def register_drain_hook(self, bound_method) -> None:
        import weakref
        self._drain_hooks.append(weakref.WeakMethod(bound_method))

    def unregister_drain_hook(self, bound_method) -> None:
        self._drain_hooks = [r for r in self._drain_hooks
                             if r() is not None and r() != bound_method]

    def _run_drain_hooks(self) -> None:
        dead = False
        for ref in tuple(self._drain_hooks):
            fn = ref()
            if fn is None:
                dead = True
                continue
            fn()
        if dead:
            self._drain_hooks = [r for r in self._drain_hooks
                                 if r() is not None]

    # ------------------------------------------------------------------ setup
    def add_taskpool(self, tp: Taskpool) -> None:
        """parsec_context_add_taskpool (ref: scheduling.c:865-923)."""
        if self._finalized:
            output.fatal("context already finalized")
        tp.context = self
        if tp.termdet is None:
            termdet_mod.LocalTermdet().monitor_taskpool(tp)  # ref: scheduling.c:879-884
        with self._cv:
            self._taskpools[tp.taskpool_id] = tp
            self._active += 1
            first = self._active == 1
        if first and mca.get("runtime_gc_defer", True):
            # the hold + finalizer transition under _cv: racing a
            # concurrent quiesce-release outside the lock could detach the
            # WRONG finalizer and lose the crash-safety net
            with self._cv:
                if not self._gc_held:
                    self._gc_held = True
                    _gc_defer_acquire()
                    # crash-safety (VERDICT r4 weak #6): a context
                    # abandoned without fini() must not leave process-wide
                    # GC thresholds stretched forever — the finalizer
                    # releases this context's hold when it is collected
                    import weakref
                    self._gc_finalizer = weakref.finalize(
                        self, _gc_defer_release)
        # taskpool keeps one pending action for the enqueue itself
        tp.addto_nb_pending_actions(1)
        if tp.on_enqueue is not None:
            tp.on_enqueue(tp)
        if tp.startup_hook is not None:
            startup = tp.startup_hook(self.streams[0], tp)
            if startup:
                self.schedule(startup, self.streams[0])
        tp.termdet.taskpool_ready(tp)
        tp.addto_nb_pending_actions(-1)
        self._work_event.set()

    def _taskpool_completed(self, tp: Taskpool) -> None:
        with self._cv:
            if tp.taskpool_id in self._taskpools:
                del self._taskpools[tp.taskpool_id]
                self._active -= 1
            quiesced = self._active == 0
            self._cv.notify_all()
        if quiesced:
            self._release_gc_hold()

    def _release_gc_hold(self) -> None:
        with self._cv:
            if not self._gc_held:
                return
            self._gc_held = False
            fin = getattr(self, "_gc_finalizer", None)
            self._gc_finalizer = None
            if fin is not None:
                fin.detach()     # normal release: the safety net must not
        _gc_defer_release()      # double-decrement the process refcount

    # ------------------------------------------------------------------ start/wait
    def start(self) -> None:
        """parsec_context_start (ref: scheduling.c:968): spawn workers, wake comm."""
        if self._started:
            return
        self._started = True
        if self.comm is not None:
            self.comm.enable()
        for s in self.streams[1:]:
            t = threading.Thread(target=self._worker_main, args=(s,),
                                 name=f"parsec-tpu-worker-{s.th_id}", daemon=True)
            self._workers.append(t)
            t.start()

    def test(self) -> bool:
        """parsec_context_test: True when no active taskpool remains."""
        with self._cv:
            return self._active == 0

    def wait(self, timeout: Optional[float] = None) -> int:
        """parsec_context_wait (ref: scheduling.c:994): master joins the hot loop."""
        self.start()
        self._progress_loop(self.streams[0],
                            until=lambda: self._active == 0,
                            timeout=timeout)
        return 0

    def wait_taskpool(self, tp: Taskpool, timeout: Optional[float] = None) -> bool:
        """parsec_taskpool_wait (ref: scheduling.c:1028)."""
        self.start()
        self._progress_loop(self.streams[0],
                            until=lambda: tp.completed,
                            timeout=timeout)
        return tp.completed

    def fini(self, timeout: Optional[float] = None) -> None:
        """parsec_fini: drain and join workers; report statistics
        (the per-thread usage + device statistics reports the reference
        prints at shutdown, scheduling.c:47-90 / device.c). After a body
        error the context is poisoned: fini skips the drain and tears down
        cleanly instead of re-raising. With ``timeout``, a drain that cannot
        finish (e.g. a peer rank died mid-graph) degrades to a warned
        teardown instead of hanging forever."""
        if self._finalized:
            return
        if self._error is None:
            try:
                self.wait(timeout=timeout)
            except TimeoutError:
                output.warning("fini: drain timed out with work outstanding; "
                               "tearing down anyway")
        self._finalized = True
        if self._ntrace is not None:
            # fini: land straggler ring events (blocking final drain)
            self._ntrace.drain_all(wait=True)
        if self.comm is not None and self.profiling is not None and \
                hasattr(self.comm, "stamp_clock_meta"):
            # the per-rank clock-offset metadata must land BEFORE any
            # dump: the multi-rank trace merge reads it to rebase this
            # rank's timestamps onto rank 0's clock. Finalize the ladder
            # first (bounded, collective — rank 0 answers the peers'
            # remaining pings here; only traced runs pay this), THEN
            # stamp, so the pump's result is what actually gets dumped
            try:
                if hasattr(self.comm, "clock_sync_finalize"):
                    self.comm.clock_sync_finalize(timeout=2.0)
                self.comm.stamp_clock_meta()
            except Exception:  # noqa: BLE001 — merge degrades to raw clocks
                pass
        if self._prof_auto and self.profiling is not None:
            try:
                self.profiling.dump()
            except OSError as e:
                output.warning(f"fini: trace dump failed: {e}")
        for s in self.streams:
            if s.nb_executed:
                output.debug_verbose(1, "stats",
                                     f"es{s.th_id} (vp{s.vp_id}): "
                                     f"{s.nb_executed} tasks, "
                                     f"{s.nb_selects} selects")
        for name, st in self.devices.statistics().items():
            if st["executed_tasks"]:
                output.debug_verbose(1, "stats", f"device {name}: {st}")
        self._work_event.set()
        for t in self._workers:
            t.join(timeout=5.0)
        if self._ptdev:
            # device lane down BEFORE the device modules: its manager
            # thread dispatches through them under the GIL
            self._ptdev.fini()
            self._ptdev = False
        self.devices.fini()
        if self.comm is not None:
            self.comm.fini()
        if self._dtd_neng is not None:
            # the per-context DTD engine never hits a per-pool detach
            # point: fold its buckets here so the process-wide registry
            # does not pin one engine per finished context forever
            self._hist_detach(self._dtd_neng)
        if self.sched_plane is not None:
            # same lifecycle for the plane's queue-wait histogram
            self._hist_detach(self.sched_plane.plane)
        if self._spans is not None:
            # and for the span histograms: folded, so /metrics and the
            # benchmark's readers still see them after fini
            for _kind, obj in self._spans.hists:
                self._hist_detach(obj)
        # persist the online cost model (ISSUE 18) alongside the warm-
        # executable cache's lifecycle: a restarted serving process loads
        # it back at its first placement decision and starts warm
        from .costmodel import model as _cost_model
        _cost_model.maybe_save()
        if self.watchdog is not None:
            # watchdog before the endpoint: a dying context must not be
            # reported as a stall, and /health must answer to the end
            self.watchdog.stop()
            self.watchdog = None
        if self.metrics is not None:
            # endpoint down LAST: ops dashboards may scrape through the
            # drain, and the fini counter aggregation itself is scrapeable
            self.metrics.stop()
            self.metrics = None
        self._release_gc_hold()  # error paths can finalize w/ pools active

    # ------------------------------------------------------------------ scheduling
    def schedule(self, tasks, stream: Optional[ExecutionStream] = None,
                 distance: int = 0) -> None:
        """__parsec_schedule (ref: scheduling.c:287)."""
        if isinstance(tasks, Task):
            tasks = [tasks]
        tasks = list(tasks)
        if not tasks:
            return
        if self.paranoid:
            # PARANOID tier 1+ (ref: PARSEC_DEBUG_PARANOID build flavor):
            # a task entering the ready queues must actually be ready, and
            # must not already be completed/queued
            for t in tasks:
                # DTD tasks carry an explicit deps_remaining counter; PTG
                # readiness lives in the repo goal tables (base Task has no
                # such field)
                unmet = getattr(t, "deps_remaining", 0)
                if unmet > 0:
                    output.fatal(f"PARANOID: {t!r} scheduled with "
                                 f"{unmet} unmet dependencies")
                if t.status == TASK_STATUS_COMPLETE:
                    output.fatal(f"PARANOID: completed task {t!r} "
                                 f"re-scheduled")
        if not self._prio_seen:
            # burst selection is only policy-sound while every live task
            # has equal priority: the first prioritized task flips the hot
            # loop to task-at-a-time selects so releases preempt promptly
            for t in tasks:
                if t.priority:
                    self._prio_seen = True
                    break
        sp = self._spans
        if sp is not None:
            sp.stamp(tasks)     # ready-wait starts here
        stream = stream or self._current_stream()
        if self.pins.enabled:
            self.pins.fire(pins_mod.SCHEDULE_BEGIN, stream, tasks)
            self.sched.schedule(stream, tasks, distance)
            self.pins.fire(pins_mod.SCHEDULE_END, stream, tasks)
        else:
            self.sched.schedule(stream, tasks, distance)
        if self._need_wake:
            self._work_event.set()

    def _current_stream(self) -> ExecutionStream:
        # threadlocal binding (workers bind in _worker_main); unknown
        # threads (user code, comm thread) act as the master stream
        return getattr(self._tls, "stream", None) or self.streams[0]

    # ------------------------------------------------------------ device lane
    def _ptdev_lane(self):
        """The context's native device lane (device/native.py), created
        lazily on the first TPU-bodied lane pool, or None when it cannot
        engage (no accelerator device, --mca device_native 0, module
        missing). The verdict is memoized — probing it per pool would
        retry a failed module load on every instantiation."""
        if self._ptdev is not None:
            return self._ptdev or None
        from ..device.native import NativeDeviceLane
        lane = NativeDeviceLane.maybe_create(self)
        self._ptdev = lane if lane is not None else False
        return lane

    # ------------------------------------------------------------ native lane
    def _ptexec_enqueue(self, tp: Taskpool, lane: Dict[str, Any]) -> None:
        """A PTG taskpool handed its whole FSM to the native execution
        lane (dsl/ptg/compiler.py _ptexec_prepare); every stream's hot
        loop drains it."""
        # ring lifecycle (enable): arm in-lane tracing before the first
        # burst so no lane event predates its rings
        self._ntrace_attach("ptexec", lane["graph"], tp.taskpool_id)
        self._hist_attach("ptexec", lane["graph"])
        if lane.get("dev_pool") is not None:
            # the device lane outlives pools; re-attach per enqueue
            # (idempotent) so a tracer attached AFTER the lane's creation
            # still lands this pool's EV_DEV_* events
            self._ntrace_attach("ptdev", lane["dev"].clane)
        with self._ptexec_lock:
            self._ptexec_q.append((tp, lane))
            if lane.get("pool_id") is not None:
                self._ptexec_comm_live += 1
            if lane.get("dev_pool") is not None:
                self._ptexec_dev_live += 1
            # scheduler plane, LAZY arming (the one-pool fast path): a
            # lone lane graph keeps its private allocation-free ready
            # vector — zero plane crossings on the 10M/s chain walk. The
            # moment a SECOND pool runs concurrently (or a pool carries
            # explicit QoS config), every queued lane binds: ready
            # structures migrate into the plane mid-run (safe hand-off,
            # see ptexec.cpp sched_bind) and the drain arbitrates by DRR
            if self.sched_plane is not None and (
                    len(self._ptexec_q) > 1
                    or getattr(tp, "qos_weight", None)
                    or getattr(tp, "admission_window", None)
                    or mca.get("sched_admission_window", 0)):
                for tp_i, lane_i in self._ptexec_q:
                    self._sched_pool_bind(tp_i, lane_i)
        self._work_event.set()

    def _sched_pool_bind(self, tp: Taskpool, lane: Dict[str, Any]) -> None:
        """Register ``tp`` on the scheduler plane and move its lane
        graph's ready structure there (idempotent; declines — full pool
        table, bind refusal — keep the private vector: engagement is
        unchanged, only cross-pool arbitration is lost)."""
        plane = self.sched_plane
        if plane is None or lane.get("sched_pool") is not None \
                or lane.get("finalized"):
            return
        h = plane.register_pool(tp.name, plane.KIND_PTEXEC,
                                weight=getattr(tp, "qos_weight", None),
                                window=getattr(tp, "admission_window",
                                               None))
        if h < 0:
            return
        try:
            lane["graph"].sched_bind(plane.capsule, h)
        except Exception:  # noqa: BLE001 — keep the private structure
            plane.unregister_pool(h)
            return
        lane["sched_pool"] = h

    def _ptexec_drain(self, stream: ExecutionStream) -> bool:
        """One burst through the front lane graph. The burst budget shrinks
        when this stream's scheduler queues hold work so a live lane cannot
        starve concurrently-active taskpools; the graph's run() never
        blocks, so a starved call returns straight to the hot loop.

        For data-flow pools the callback IS the data path: each batched
        dispatch reads its inputs from the lane's slot array, runs the
        bodies, lands outputs back into slots, and clears the slot ids the
        engine retired (the datarepo usagelmt/usagecnt protocol, kept in C)
        — generic_prepare_input / generic_release_deps never run for lane
        tasks. One callback per ~256 ready tasks amortizes the
        lane-crossing cost the per-task FSM used to pay on every task.

        With the scheduler plane armed and SEVERAL lane graphs queued,
        the pool to serve is picked by the plane's weighted DRR
        (next_ptexec) instead of always the FRONT graph — N concurrent
        taskpools then share the workers by QoS weight with a structural
        starvation bound, and the burst budget is capped by the pool's
        DRR quantum so one heavy pool cannot monopolize a worker between
        arbitration points (charge() spends the credits back)."""
        plane = self.sched_plane
        quantum = None
        pool_h = None
        with self._ptexec_lock:
            if not self._ptexec_q:
                return False
            tp, lane = self._ptexec_q[0]
            if plane is not None and len(self._ptexec_q) > 1:
                pick = plane.next_ptexec()
                if pick is not None:
                    h, quantum = pick
                    for tp_i, lane_i in self._ptexec_q:
                        if lane_i.get("sched_pool") == h:
                            tp, lane = tp_i, lane_i
                            pool_h = h
                            break
                    else:
                        quantum = None   # pool already retired: front graph
        graph = lane["graph"]
        # short bursts whenever (a) ordinary queues hold work, or (b) the
        # lane dispatches Python bodies (eager CTL callbacks or the
        # data-flow slot dispatcher) — a body-callback burst is bounded in
        # TASK count, not time, so a long budget would blind this stream
        # to newly scheduled tasks and peer errors for the whole burst.
        # Empty-body walks run >10M tasks/s, so the long budget still
        # returns within ~0.5s
        if lane["callback"] is not None or self.sched.has_local_work(stream):
            budget = 4096
        else:
            budget = 1 << 22
        if quantum is not None:
            # multi-pool arbitration: the burst spends this pool's DRR
            # credits, then returns to the arbiter for the next pick
            budget = max(256, min(budget, quantum))
        try:
            dv = lane.get("dev")
            if dv is not None:
                msg = dv.failed()
                if msg is not None:
                    # a device dispatch/poll callback raised on the lane's
                    # manager thread (which has no caller to propagate
                    # to): surface it here as the pool's error
                    raise RuntimeError(
                        f"native device lane callback failed: {msg}")
            mine = graph.run(lane["callback"], 256, budget, stream.th_id)
            if mine == 0 and (lane.get("pool_id") is not None
                              or lane.get("dev_pool") is not None) \
                    and not graph.failed() and not graph.done():
                # comm- or device-bound lane starved mid-graph: the next
                # ready task arrives from the comm progress thread or the
                # device manager thread (both GIL-free/GIL-taking off
                # this loop), not from this process's walk — micro-poll
                # briefly instead of paying a full hot-loop iteration per
                # hop (bounded: ~1ms, then the outer loop resumes its
                # usual error/deadline/device servicing)
                for spin in range(224):
                    # yield-spin first (the GIL is free: the comm thread
                    # runs without it), then ease into short naps
                    time.sleep(0 if spin < 200 else 2e-5)
                    mine = graph.run(lane["callback"], 256, budget,
                                     stream.th_id)
                    if mine or graph.failed() or graph.done():
                        break
        except BaseException as e:  # noqa: BLE001 — a body raised
            with self._ptexec_lock:
                self._ptexec_retire_locked(lane)
            self._ptexec_abandon(lane)
            if self._error is None:
                self._error = e
            self._work_event.set()
            if stream.is_master:
                raise           # workers park; the master surfaces the error
            return True
        stream.nb_executed += mine
        if pool_h is not None and mine:
            plane.charge(pool_h, mine)
        if graph.failed():
            # poisoned by another stream's body exception: that stream
            # owns the propagation; just retire the queue entry
            with self._ptexec_lock:
                self._ptexec_retire_locked(lane)
            self._ptexec_abandon(lane)
            return True
        if graph.done():
            fin = False
            with self._ptexec_lock:
                if not lane.get("finalized"):
                    lane["finalized"] = True
                    fin = True
                self._ptexec_retire_locked(lane)
            if fin:
                tp._ptexec_finalize(lane)
                # ring lifecycle (quiescence): land the finished graph's
                # events and stop pinning it
                self._ntrace_detach(lane["graph"])
                self._hist_detach(lane["graph"])
                self._cost_fold(lane)
                self._sched_pool_retire(lane)
            return True
        return mine > 0

    def _ptexec_retire_locked(self, lane: Dict[str, Any]) -> None:
        """Drop ``lane`` from the drain queue wherever it sits (the DRR
        arbiter serves graphs out of front order). _ptexec_lock held."""
        for i, (_tp, l_) in enumerate(self._ptexec_q):
            if l_ is lane:
                self._ptexec_q.pop(i)
                if lane.get("pool_id") is not None:
                    self._ptexec_comm_live -= 1
                if lane.get("dev_pool") is not None:
                    self._ptexec_dev_live -= 1
                return

    def _sched_pool_retire(self, lane: Dict[str, Any]) -> None:
        """Free a finished/errored lane graph's scheduler-plane pool slot
        (idempotent: sched_unbind on an unbound graph is a no-op)."""
        h = lane.get("sched_pool")
        if h is None or self.sched_plane is None:
            return
        try:
            # the GRAPH owns its slot (sched_unbind frees it natively);
            # the wrapper only forgets the name mapping — a second free
            # here could kill an unrelated pool that reused the slot
            lane["graph"].sched_unbind()
        except Exception:  # noqa: BLE001 — a peer is still mid-batch
            return      # (poisoned graph): keep the handle so the next
                        # stream's abandon retries; dealloc frees anyway
        lane.pop("sched_pool", None)
        self.sched_plane.forget_pool(h)

    def _dtd_drain(self, stream: ExecutionStream) -> bool:
        """One burst through the DTD engine's batched ready-drain (the
        in-lane execute of the batched insert lane, ISSUE 4): pops ready
        batch-lane tasks, runs their bodies through per-class batched
        callbacks, and feeds completions straight back into the release
        walk without surfacing intermediate ids. Only newly-ready
        PER-TASK-lane successors come back (`surfaced`) and enter the
        ordinary scheduler. Body exceptions poison the engine lane and
        propagate through the usual error machinery."""
        eng = self._dtd_neng
        if eng is None:
            return False
        try:
            nexec, surfaced = eng.drain_ready(256, 4096, stream.th_id)
        except BaseException as e:  # noqa: BLE001 — a batched body raised
            if self._error is None:
                self._error = e
            self._work_event.set()
            if stream.is_master:
                raise
            return True
        if nexec:
            stream.nb_executed += nexec
        if surfaced:
            ntasks = self._dtd_ntasks
            rtasks = []
            for rid in surfaced:
                t = ntasks[rid]
                t.deps_remaining = 0    # paranoid-check coherence
                rtasks.append(t)
            self.schedule(rtasks, stream)
        return nexec > 0 or bool(surfaced)

    def _ptexec_abandon(self, lane: Dict[str, Any]) -> None:
        """Drop an errored data-mode lane's slot payloads. Each stream
        that exits the poisoned graph attempts this; the LAST one out
        (graph idle — after a poison no worker can claim a new batch, so
        idleness is stable) clears the payload list. Clearing earlier
        would yank inputs out from under a peer still mid-callback;
        leaking instead would pin every produced payload for the
        taskpool's remaining lifetime."""
        self._ntrace_detach(lane["graph"])   # final drain of an errored lane
        self._hist_detach(lane["graph"])
        self._cost_fold(lane)                # idempotent (pop-guarded)
        self._sched_pool_retire(lane)        # free the plane pool slot
        if lane.get("dev_pool") is not None:
            # stop routing the poisoned pool's device completions (in-
            # flight retires for it count late_retires, never land)
            lane["dev"].unbind_pool(lane.pop("dev_pool"))
        slots = lane.get("slots")
        if not slots:
            return
        with self._ptexec_lock:
            if lane.get("finalized") or not lane["graph"].idle():
                return
            lane["finalized"] = True
        slots.clear()


    # ------------------------------------------------------------------ hot loop
    def _worker_main(self, stream: ExecutionStream) -> None:
        self._tls.stream = stream
        if mca.get("runtime_bind_threads", False):
            from .vpmap import bind_current_thread
            bind_current_thread(self.vpmap.core_of(stream.th_id))
        while not self._finalized:
            self._progress_loop(stream, until=lambda: self._active == 0)
            # park until new work shows up
            self._work_event.wait(timeout=0.05)
            self._work_event.clear()

    def in_progress_loop(self) -> bool:
        """True when the CALLING thread is inside a progress loop — i.e. a
        task body may be on its call stack. Flow-control blocking (the DTD
        window stall) consults this: blocking mid-body can deadlock the
        pool (the unfinished task's successors may be the only drainable
        work). THREAD-local on purpose — all user threads share the master
        stream object, so stream-level state would let one thread's
        wait() mask another thread's top-level inserts (and the unlocked
        += on a shared counter could corrupt it permanently)."""
        return getattr(self._tls, "loop_depth", 0) > 0

    def _progress_loop(self, stream: ExecutionStream, until, timeout=None) -> None:
        """The hot loop (ref: __parsec_context_wait scheduling.c:789-818).

        Master-stream loops are serialized (one driving thread at a time,
        see ``_master_loop_lock``). A contender must NOT block on the
        lock unconditionally — the holder's exit condition may require
        the contender to make progress elsewhere (e.g. wait() holds while
        a window-stalled inserter contends: the pool cannot complete
        until the inserter resumes) — so contenders poll their OWN
        ``until`` (and the error flag, and their deadline) between short
        acquire attempts; the holder is draining the same work anyway."""
        tls = self._tls
        depth = getattr(tls, "loop_depth", 0)
        tls.loop_depth = depth + 1
        try:
            if stream.th_id != 0:
                self._progress_loop_inner(stream, until, timeout)
                return
            deadline = None if timeout is None \
                else time.monotonic() + timeout
            while True:
                if until():
                    return
                if self._error is not None:
                    raise self._error
                if deadline is not None:
                    left = deadline - time.monotonic()
                    if left <= 0:
                        return
                    slice_ = min(0.02, left)
                else:
                    slice_ = 0.02
                if self._master_loop_lock.acquire(timeout=slice_):
                    try:
                        self._progress_loop_inner(
                            stream, until,
                            None if deadline is None
                            else max(0.0, deadline - time.monotonic()))
                    finally:
                        self._master_loop_lock.release()
                    return
        finally:
            tls.loop_depth = depth

    def _progress_loop_inner(self, stream: ExecutionStream, until,
                             timeout=None) -> None:
        misses = 0
        deadline = None if timeout is None else time.monotonic() + timeout
        backoff_max = mca.get("runtime_backoff_max_us", 1000) / 1e6
        self._run_drain_hooks()
        while not until():
            if self._error is not None:
                if stream.is_master:
                    raise self._error
                return  # workers park quietly; the master surfaces the error
            did_something = False
            # master progresses communications inline (ref: scheduling.c:790-798)
            if stream.is_master and self.comm is not None:
                did_something |= bool(self.comm.progress())
            # poll device modules (our analogue of the GPU manager thread)
            did_something |= bool(self.devices.progress(stream))
            # native PTG execution lane: join the front graph's batched C
            # walk (returns promptly when starved — see _ptexec_drain)
            if self._ptexec_q:
                did_something |= self._ptexec_drain(stream)
            task = stream.next_task
            stream.next_task = None
            distance = 0
            if task is None:
                if self.pins.enabled:
                    self.pins.fire(pins_mod.SELECT_BEGIN, stream, None)
                    task, distance = self.sched.select(stream)
                    self.pins.fire(pins_mod.SELECT_END, stream, task)
                else:
                    task, distance = self.sched.select(stream)
                stream.nb_selects += 1
            if task is None and self._dtd_batch_pools:
                # native DTD batched lane: drain the engine's internal
                # ready structure through per-class batched callbacks.
                # AFTER the scheduler select on purpose: batched tasks all
                # carry priority 0 (prioritized inserts ride the per-task
                # lane), so scheduler-queued work — which includes every
                # prioritized task — must preempt the batch backfill, the
                # same policy order the interpreted FSM's priority-sorted
                # queues give
                did_something |= self._dtd_drain(stream)
            if task is not None:
                misses = 0
                # drain a burst before re-checking the loop conditions: the
                # per-iteration overhead (until, error, comm, device polls)
                # is pure cost for fine-grain tasks, and the scheduler pops
                # the whole burst under ONE lock (select_burst). Bursts
                # skip the SELECT pins events, so instrumentation keeps the
                # task-at-a-time shape
                budget = 1 if self.pins.enabled else 32
                use_burst = not (self.pins.enabled or self._prio_seen)
                batch: List[Task] = []
                bi = 0
                try:
                    while True:
                        self._task_progress(stream, task, distance)
                        budget -= 1
                        task = stream.next_task
                        if task is not None:
                            if budget <= 0:
                                # outer loop consumes next_task; un-run
                                # burst tasks go back to the queues
                                if bi < len(batch):
                                    self.sched.schedule(stream, batch[bi:], 0)
                                break
                            stream.next_task = None
                            distance = 0
                            continue
                        if bi < len(batch):
                            task = batch[bi]
                            bi += 1
                            distance = 0
                            continue
                        if budget <= 0:
                            break
                        if use_burst:
                            batch = self.sched.select_burst(stream, budget)
                            stream.nb_selects += 1
                            bi = 0
                            if not batch:
                                break
                            task = batch[0]
                            bi = 1
                        else:
                            # prioritized workload: task-at-a-time selects
                            # keep just-released high-priority work first
                            task, distance = self.sched.select(stream)
                            stream.nb_selects += 1
                            if task is None:
                                break
                            continue
                        distance = 0
                except BaseException as e:  # noqa: BLE001
                    # a failing body must surface to every waiter, not die
                    # silently with one worker thread (ref: hook errors are
                    # fatal, scheduling.c:541-548)
                    if self._error is None:
                        self._error = e
                    if bi < len(batch):     # un-run burst tasks stay queued
                        try:
                            self.sched.schedule(stream, batch[bi:], 0)
                        except Exception:
                            pass
                    self._work_event.set()
                    if stream.is_master:
                        raise
                    return
                did_something = True
            if not did_something:
                misses += 1
                self._run_drain_hooks()   # starving: drain buffers
                if deadline is not None and time.monotonic() > deadline:
                    return
                # exponential backoff while starving (ref: scheduling.c:801-804)
                # — capped near the wire latency while a comm-bound lane
                # graph is in flight: its next ready task arrives from
                # the comm progress thread, not from this process, and a
                # ms-scale sleep would dominate every cross-rank hop
                cap = 2e-5 if (self._ptexec_comm_live
                               or self._ptexec_dev_live) else backoff_max
                if cap == backoff_max and self.sched_plane is not None \
                        and (self._ptexec_q or self._dtd_batch_pools) \
                        and self.sched_plane.queued_total() > 0:
                    # "no local work" is NOT global with multiple pools:
                    # this stream's last pick starved, but the plane holds
                    # queued work (another pool's overflow spill) a fresh
                    # arbitration round will hand out — stay hot instead
                    # of parking a worker against a non-empty plane
                    cap = 2e-5
                time.sleep(min(cap, 1e-6 * (1 << min(misses, 10))))

    # ------------------------------------------------------------------ task FSM
    def _task_progress(self, stream: ExecutionStream, task: Task,
                       distance: int = 0) -> int:
        """__parsec_task_progress (ref: scheduling.c:507)."""
        tc = task.task_class
        if getattr(task, "nid", -1) >= 0 and not self.pins.paranoid \
                and not self.paranoid and tc.fast_inline and not tc.jit_ok:
            # DTD native fast lane: eager CPU body, synchronous completion
            # — one fused call replaces the prepare/execute/complete FSM.
            # Profiling no longer ejects tasks from this lane (the PR 5
            # observer-effect removal): with PINS enabled the lean cycle
            # fires the core lifecycle events itself, and only --mca
            # pins_paranoid 1 restores the full per-task FSM
            task.taskpool._lean_cycle(stream, task)
            return HOOK_DONE
        if task.status < TASK_STATUS_PREPARE_INPUT:
            task.status = TASK_STATUS_PREPARE_INPUT
            pins_on = self.pins.enabled
            if tc.prepare_input is None and not tc.flows and not pins_on:
                # nothing to resolve — but only skip the PREPARE pins
                # events when instrumentation is off (trace consumers pair
                # intervals and must see symmetric streams per task)
                return self._execute(stream, task)
            if pins_on:
                self.pins.fire(pins_mod.PREPARE_INPUT_BEGIN, stream, task)
            if tc.prepare_input is not None:
                rc = tc.prepare_input(stream, task)
            else:
                rc = self.generic_prepare_input(stream, task)
            if pins_on:
                self.pins.fire(pins_mod.PREPARE_INPUT_END, stream, task)
            if rc == HOOK_AGAIN:
                self.schedule([task], stream, distance)
                return rc
        return self._execute(stream, task)

    def _execute(self, stream: ExecutionStream, task: Task) -> int:
        """__parsec_execute (ref: scheduling.c:126)."""
        tc = task.task_class
        task.status = TASK_STATUS_HOOK
        device = self.devices.select_best_device(task)  # ref: device.c:100
        task.selected_device = device
        for chore in tc.incarnations:
            if not (chore.device_type & task.chore_mask):
                continue
            if device is not None and not (chore.device_type & device.type):
                continue
            if chore.evaluate is not None:
                ev = chore.evaluate(stream, task)
                if ev == HOOK_NEXT:
                    continue
                if ev == HOOK_DISABLE:
                    task.chore_mask &= ~chore.device_type
                    continue
            task.selected_chore = chore
            pins_on = self.pins.enabled
            if pins_on:
                self.pins.fire(pins_mod.EXEC_BEGIN, stream, task)
            rc = chore.hook(stream, task)
            stream.nb_executed += 1
            # return-code dispatch (ref: scheduling.c:518-566)
            if rc == HOOK_DONE:
                if pins_on:
                    self.pins.fire(pins_mod.EXEC_END, stream, task)
                if device is not None:
                    device.executed_tasks += 1  # async devices count in epilog
                self.complete_task_execution(stream, task)
                return rc
            if rc == HOOK_ASYNC:
                # completion arrives via complete_task_execution from a
                # device; the EXEC interval closes here (it measures host
                # dispatch — device execution shows on the device's own
                # profiling stream)
                if pins_on:
                    self.pins.fire(pins_mod.EXEC_END, stream, task)
                return rc
            if rc == HOOK_AGAIN:
                if pins_on:
                    self.pins.fire(pins_mod.EXEC_END, stream, task)
                self.schedule([task], stream, distance=1)  # __parsec_reschedule :445
                return rc
            if rc == HOOK_NEXT:
                continue
            if rc == HOOK_DISABLE:
                task.chore_mask &= ~chore.device_type
                continue
            if rc == HOOK_ERROR:
                output.fatal(f"task {task!r} hook failed")  # ref: scheduling.c:541-548
        output.fatal(f"no runnable chore for task {task!r} "
                     f"(chore_mask={task.chore_mask:#x})")
        return HOOK_ERROR

    def complete_task_execution(self, stream: ExecutionStream, task: Task) -> None:
        """__parsec_complete_execution (ref: scheduling.c:469)."""
        tc = task.task_class
        if self.paranoid and task.status == TASK_STATUS_COMPLETE:
            output.fatal(f"PARANOID: {task!r} completed twice")
        task.status = TASK_STATUS_COMPLETE
        pins_on = self.pins.enabled
        if pins_on:
            self.pins.fire(pins_mod.COMPLETE_EXEC_BEGIN, stream, task)
        if tc.prepare_output is not None:
            tc.prepare_output(stream, task)
        if tc.complete_execution is not None:
            tc.complete_execution(stream, task)
        if pins_on:
            self.pins.fire(pins_mod.RELEASE_DEPS_BEGIN, stream, task)
        if tc.release_deps is not None:
            tc.release_deps(stream, task)
        else:
            self.generic_release_deps(stream, task)
        if pins_on:
            self.pins.fire(pins_mod.RELEASE_DEPS_END, stream, task)
            self.pins.fire(pins_mod.COMPLETE_EXEC_END, stream, task)
        if task.on_complete is not None:
            task.on_complete(task)
        task.taskpool.addto_nb_tasks(-1)
        if tc.release_task is not None:
            tc.release_task(stream, task)

    # ------------------------------------------------------------------ deps engine
    def generic_prepare_input(self, stream: ExecutionStream, task: Task) -> int:
        """Generic data_lookup: resolve input copies from repos / collections
        (the role of the generated data_lookup, ref: jdf2c.c:45)."""
        tp = task.taskpool
        for flow in task.task_class.flows:
            slot = task.data[flow.flow_index]
            if slot.data_in is not None or flow.access & FLOW_ACCESS_CTL:
                continue
            for dep in flow.deps_in:
                if dep.cond is not None and not dep.cond(task.locals):
                    continue
                if dep.task_class is None:
                    # direct read from a data collection (JDF: "A <- A(k)")
                    if dep.data_ref is not None:
                        data = dep.data_ref(task.locals)
                        slot.data_in = data.get_copy() if hasattr(data, "get_copy") else data
                else:
                    plocals_seq = dep.target_locals(task.locals) if dep.target_locals else [task.locals]
                    plocals = plocals_seq[0] if not isinstance(plocals_seq, dict) else plocals_seq
                    pkey = dep.task_class.make_key(tp, plocals)
                    repo = tp.repos[dep.task_class.task_class_id]
                    entry = repo.lookup_entry(pkey) if repo is not None else None
                    if entry is None:
                        output.fatal(f"missing repo entry {pkey} for {task!r} flow {flow.name}")
                    slot.data_in = entry.data[dep.flow_index]
                    slot.source_repo_entry = entry
                break
        return HOOK_DONE

    def generic_release_deps(self, stream: ExecutionStream, task: Task) -> None:
        """Generic release-deps (ref: parsec_release_dep_fct parsec.c:1837).

        Walks output deps, updates successor dependency masks/counters
        (parsec.c:1657), collects newly-ready tasks into a ring and schedules
        it (scheduling keeps the highest-priority task as ``next_task``,
        ref: __parsec_schedule_vp scheduling.c:360).
        """
        tp = task.taskpool
        tc = task.task_class
        ready: List[Task] = []
        # publish produced copies into this class's repo for local successors
        repo = tp.repos[tc.task_class_id]
        # publish every flow that local successors will consume — written
        # flows and forwarded reads alike (count_deps_fct role, parsec.c:1448)
        wants_repo = repo is not None and any(
            any(d.task_class is not None for d in f.deps_out)
            for f in tc.flows if not (f.access & FLOW_ACCESS_CTL))
        entry = None
        nb_uses = 0
        if wants_repo:
            entry = repo.lookup_entry_and_create(task.key)
            for f in tc.flows:
                if f.deps_out and not (f.access & FLOW_ACCESS_CTL):
                    slot = task.data[f.flow_index]
                    out = slot.data_out if slot.data_out is not None else slot.data_in
                    entry.data[f.flow_index] = out

        distributed = self.comm is not None and self.nb_ranks > 1

        def visit(dep, succ_locals: Dict[str, int]) -> bool:
            succ_tc = dep.task_class
            key = succ_tc.make_key(tp, succ_locals)
            contribution = 1 if succ_tc.count_mode else (1 << dep.dep_index)
            goal = (succ_tc.dependencies_goal_fn(succ_locals)
                    if succ_tc.dependencies_goal_fn is not None else None)
            if tp.update_deps(succ_tc, key, contribution, goal):
                t = self.make_task(tp, succ_tc, dict(succ_locals))
                ready.append(t)
            return True

        for flow in tc.flows:
            # remote destinations grouped by the out-dep's named datatype:
            # each type is reshaped ONCE before the wire and packed once per
            # destination set (pre-send remote reshape, parsec/remote_dep.h:117;
            # remote_multiple_outs_same_pred_flow.jdf)
            remote_by_dtt: Dict[Optional[str], set] = {}
            null_checked = False
            for dep in flow.deps_out:
                if dep.cond is not None and not dep.cond(task.locals):
                    continue
                if dep.task_class is None:
                    continue  # write-back to memory handled by the body/copy model
                if not null_checked and not (flow.access & FLOW_ACCESS_CTL):
                    # forwarding no-data on a data flow is a program bug the
                    # runtime must catch at the source (ref: "A NULL is
                    # forwarded", parsec.c:1879; ptgpp forward_*_NULL tests)
                    null_checked = True
                    slot = task.data[flow.flow_index]
                    out = slot.data_out if slot.data_out is not None \
                        else slot.data_in
                    if (out.payload if hasattr(out, "payload") else out) is None:
                        output.fatal(
                            f"A NULL is forwarded\n"
                            f"\tfrom: {tc.name}{task.key} flow {flow.name}\n"
                            f"\tto:   {dep.task_class.name}")
                targets = dep.target_locals(task.locals) if dep.target_locals else [task.locals]
                if isinstance(targets, dict):
                    targets = [targets]
                for tl in targets:
                    if distributed:
                        r = tp.task_rank_of(dep.task_class, tl)
                        if r != self.my_rank:
                            # remote successor: ship this flow's output once
                            # per destination (the remote activation fork of
                            # parsec_release_dep_fct); [type_remote]
                            # overrides [type] on the wire
                            wire = getattr(dep, "wire_datatype", dep.datatype)
                            remote_by_dtt.setdefault(wire, set()).add(r)
                            continue
                    visit(dep, tl)
                    if not (flow.access & FLOW_ACCESS_CTL):
                        # CTL consumers never look the entry up (their
                        # prepare_input skips data resolution), so counting
                        # them in the usage limit would make the entry
                        # unretirable
                        nb_uses += 1
            if remote_by_dtt:
                slot = task.data[flow.flow_index]
                out = slot.data_out if slot.data_out is not None else slot.data_in
                payload = out.payload if hasattr(out, "payload") else out
                dtt_of = getattr(tp, "_dtt", None)
                ck = getattr(tc, "_ptg_canonical_key", None)
                wire_key = ck(task) if ck is not None else task.key
                for dtt_name, ranks in remote_by_dtt.items():
                    wire_payload = payload
                    if dtt_name is not None and dtt_of is not None:
                        dtt = dtt_of(dtt_name)
                        if dtt is not None and not dtt.identity:
                            wire_payload = dtt.extract(payload)
                    self.comm.ptg_send(tp, tc, wire_key, flow.flow_index,
                                       wire_payload, sorted(ranks),
                                       dtt=dtt_name)
        if entry is not None:
            repo.entry_addto_usage_limit(task.key, max(nb_uses, 1))
        # consume source repo entries (one use each)
        for flow in tc.flows:
            slot = task.data[flow.flow_index]
            if slot.source_repo_entry is not None:
                slot.source_repo_entry._repo.entry_used_once(slot.source_repo_entry.key)
        if ready:
            ready.sort(key=lambda t: -t.priority)
            # only claim the hot-path slot when it is free: device epilogs can
            # release several tasks on the same stream within one progress
            # sweep, and overwriting a pending next_task would lose it forever
            # (mirrors __parsec_schedule_vp pushing the displaced task back)
            if stream.next_task is None:
                stream.next_task, rest = ready[0], ready[1:]
            else:
                rest = ready
            if rest:
                self.schedule(rest, stream)

    def make_task(self, tp: Taskpool, tc: TaskClass,
                  locals_: Dict[str, int], priority: Optional[int] = None) -> Task:
        if priority is None:
            prio = tc.properties.get("priority", 0)
            priority = prio(locals_) if callable(prio) else prio
        return Task(tp, tc, locals_, priority)


# ---------------------------------------------------------------------------
# module-level convenience mirroring parsec_init/parsec_fini
# ---------------------------------------------------------------------------
_default_context: Optional[Context] = None


def init(nb_cores: Optional[int] = None, argv: Optional[List[str]] = None,
         **kw) -> Context:
    """parsec_init equivalent (ref: parsec/parsec.c:405)."""
    global _default_context
    if _default_context is None or _default_context._finalized:
        _default_context = Context(nb_cores=nb_cores, argv=argv, **kw)
    return _default_context


def fini() -> None:
    global _default_context
    if _default_context is not None:
        _default_context.fini()
        _default_context = None
