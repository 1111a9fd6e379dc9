"""DTD: dynamic task discovery — the insert-task frontend.

Re-design of parsec/interfaces/dtd (insert_function.c, insert_function.h,
insert_function_internal.h). The user (on every rank, in the same order)
inserts tasks against *tiles*; the runtime builds the DAG on the fly from each
tile's access chain and executes tasks as their dependencies retire:

* :class:`DTDTile` — ref: parsec_dtd_tile_t (insert_function_internal.h:174-196)
  with ``last_writer`` / reader lists driving RAW/WAR/WAW chaining
  (WAR strategy per overlap_strategies.c: a writer waits on all readers since
  the previous write; readers wait on the last writer).
* :class:`DTDTaskpool` — ref: parsec_dtd_taskpool_new (insert_function.c:1513);
  task classes are auto-created per body function + parameter profile
  (the reference's function_h_table); flow-control **window/threshold**
  (insert_function.h:149-157): the inserter blocks past the window and helps
  execute until the executed count catches up.
* ``insert_task`` — ref: parsec_dtd_insert_task (insert_function.c:3617) →
  create/initialize (:2801), param linking (:2896), schedule-if-ready (:2963).
* distributed mode: every rank runs the same insert sequence; tasks filtered
  by the affinity tile's rank (owner-computes); remote edges are forwarded to
  the comm layer (rank_sent_to bitmaps, delayed release — wired in
  :mod:`parsec_tpu.comm.remote_dep`).

TPU-first shape: bodies are *functional* — ``fn(*args) -> outputs`` returns
fresh arrays for its WRITE flows instead of mutating in place. The same body
runs as the CPU chore (eager, host arrays) or the TPU chore (jitted once per
task class, dispatched asynchronously to the chip). This keeps bodies jittable
and makes version-tracked copies natural (every write is a new buffer).
"""

from __future__ import annotations

import threading
import time
import weakref
import zlib
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..core import pins as pins_mod
from ..core.context import Context
from ..core.task import (
    Chore, DEV_ALL, DEV_CPU, DEV_TPU, Flow, FLOW_ACCESS_READ, FLOW_ACCESS_RW,
    FLOW_ACCESS_WRITE, HOOK_DONE, TASK_STATUS_COMPLETE, Task, TaskClass,
    Taskpool,
)
from ..data.collection import DataCollection
from ..data.data import COHERENCY_OWNED, Data, data_from_array
from ..device.tpu import TPUDevice, make_tpu_hook
from ..utils import mca, output
from ..utils.xla_trace import DTD_LINK, DTD_STALL

# access flags for insert_task args (ref: PARSEC_INPUT/OUTPUT/INOUT | AFFINITY)
READ = FLOW_ACCESS_READ
WRITE = FLOW_ACCESS_WRITE
RW = FLOW_ACCESS_RW
AFFINITY = 0x100          # ref: PARSEC_AFFINITY bit on a dtd param
NOTRACK = 0x200           # ref: PARSEC_DONT_TRACK (dtd_test_flag_dont_track.c):
                          # the tile's VALUE flows to the body, but the access
                          # creates no RAW/WAR/WAW edges and no distributed
                          # version bookkeeping — ordering w.r.t. tracked
                          # accesses of the same tile is the caller's problem.
                          # Rank-local by contract (like tile_new scratch).

mca.register("dtd_window_size", 2048,
             "Max in-flight inserted-but-not-executed tasks", type=int)
mca.register("dtd_audit", False,
             "Replay auditor: digest every rank's (tile, version, rank) "
             "link decisions and compare across ranks at wait() (the DTD "
             "analogue of the PTG iterators_checker)", type=bool)
mca.register("dtd_threshold_size", 1024,
             "Catch-up target once the window is hit", type=int)
mca.register("dtd_batch_insert", True,
             "Batched native insert lane: buffer eligible insert_task calls "
             "and link them in the engine N at a time under one GIL drop; "
             "ready tasks execute through in-engine batched drains "
             "(drain_ready) instead of per-task scheduler cycles", type=bool)

#: engagement counters for the batched DTD lane (the DTD analogue of
#: dsl/ptg/compiler.py PTEXEC_STATS — the ci.sh gate watches ENGAGEMENT,
#: not throughput, through the LaneStats snapshot()/delta() helpers).
#: ``tasks_batched`` counts inserts that rode the batch buffer;
#: ``tasks_per_task`` counts inserts on batch-enabled pools that fell
#: back to the per-task engine path (first insert of a class, shape
#: mismatch, priority/where/NOTRACK/AFFINITY, jittable bodies with
#: by-value args); ``pools_batch`` counts pools that enabled the lane.
#: utils/counters.install_native_counters exports these under ``ptdtd.*``
from ..utils.counters import LaneStats as _LaneStats

PTDTD_STATS = _LaneStats(pools_batch=0, tasks_batched=0, tasks_per_task=0,
                         batches=0, classes_ineligible=0,
                         capture_windows_deferred=0,
                         # ISSUE 12: deferred-window region fusion —
                         # capturable runs of a deferred capture window
                         # replay as ONE fused super-task insert each
                         capture_regions_fused=0, capture_tasks_fused=0)

#: "batch registration not yet attempted" marker for the one-entry class
#: cache (None means attempted-and-ineligible, which must not retry)
_BINFO_UNSET = object()


class AdmissionBackpressure(RuntimeError):
    """insert_task(nowait=True) on a pool past its scheduler-plane
    admission window (--mca sched_admission_window / tp.admission_window):
    the ready plane is protecting itself from a runaway inserter. Retry
    later, drop the request, or insert blocking (the default) — the
    serving-tier choice, not the runtime's."""


def _flush_body(arr):
    """data_flush task body: force device->host materialization."""
    return np.asarray(arr)


#: serializes Context._dtd_batch_pools updates (pools arming/retiring from
#: different threads; a torn read-modify-write would wedge the count and
#: either stall the drains or run them forever)
_BATCH_POOLS_LOCK = threading.Lock()


def _pool_sync_on_complete(tp: "DTDTaskpool") -> None:
    """Taskpool.on_complete hook for batch-lane pools: sync the engine's
    tile payload slots into tile.data even when the user never calls
    tp.wait() (close + ctx.wait drains through termination detection),
    then hand the pool's engine-side state back (termdet fires this
    exactly once, after close() — no further inserts can arrive)."""
    tp._sync_slots()
    tp._retire_batch_lane()


class DTDTile:
    """Ref: parsec_dtd_tile_t (insert_function_internal.h:174-196)."""

    __slots__ = ("data", "key", "dc", "lock", "last_writer", "readers",
                 "rank", "new_tile", "wcount", "writer_rank",
                 "last_writer_version", "compact_at", "nid")

    def __init__(self, data: Data, key: Any, dc: Optional[DataCollection],
                 rank: int = 0, new_tile: bool = False) -> None:
        self.data = data
        self.key = key
        self.dc = dc
        self.lock = threading.Lock()
        self.last_writer: Optional["DTDTask"] = None
        self.readers: List["DTDTask"] = []
        self.rank = rank
        self.new_tile = new_tile
        self.compact_at = 32      # next reader-list compaction watermark
        #: logical write sequence number, identical on every rank because all
        #: ranks replay the same insert sequence (the basis remote transfers
        #: are keyed on, standing for the reference's output version tracking)
        self.wcount = 0
        self.writer_rank = rank      # rank holding the newest version
        self.last_writer_version = 0
        #: native-engine tile id (dsl chains in native/src/ptdtd.cpp);
        #: assigned on first native-mode link. Tiles are POOL-local, so a
        #: tile's chain lives entirely in one engine mode.
        self.nid: Optional[int] = None

    def __repr__(self) -> str:  # pragma: no cover
        return f"<DTDTile {self.key}>"


class DTDTask(Task):
    """Task with runtime-discovered deps (ref: parsec_dtd_task_t)."""

    __slots__ = ("deps_remaining", "successors", "completed", "lock",
                 "arg_spec", "tiles", "rank", "pending_inputs",
                 "remote_sends", "ident", "nid")

    def __init__(self, taskpool, task_class, priority=0) -> None:
        super().__init__(taskpool, task_class, None, priority)
        self.ident = 0          # insertion index (repr/debug identity)
        self.nid = -1           # native-engine task id (-1: Python engine)
        # starts at 1: the insertion-in-progress guard (dropped at the end of
        # insert_task, mirroring the count-then-activate protocol of
        # parsec_dtd_schedule_task_if_ready, insert_function.c:2963)
        self.deps_remaining = 1
        self.completed = False
        # Python-engine pools assign a real lock + successor list at insert
        # (pred linking / release walk); the native lane never touches
        # either (GIL-serialized engine), so allocation would be pure
        # insert-path cost
        self.successors: Optional[List[DTDTask]] = None
        self.lock = None
        self.arg_spec: List[Tuple[str, Any]] = []  # ('flow', i) | ('value', v)
        self.tiles: List[Optional[DTDTile]] = []
        self.rank = 0
        #: flow_index -> payload delivered by the comm engine (exact-version
        #: remote inputs override newest_copy resolution). Lazily allocated:
        #: only distributed consumers need it, and a per-task dict is
        #: GC-tracked churn on the insert hot path
        self.pending_inputs: Optional[Dict[int, Any]] = None
        #: id(tile) -> (tile, version, {dst ranks}) — the rank_sent_to
        #: bitmap; lazily allocated for the same reason
        self.remote_sends: Optional[Dict[int, Tuple]] = None

    def dep_satisfied(self) -> bool:
        with self.lock:
            self.deps_remaining -= 1
            return self.deps_remaining == 0

    def __repr__(self) -> str:  # pragma: no cover
        return f"{self.task_class.name}(#{self.ident})"


#: process-wide jit cache keyed by the body function object, so the same body
#: used across many taskpools compiles exactly once (jax.jit caches traces on
#: the wrapper object — a fresh wrapper per task class would retrace).
_jit_cache: Dict[Any, Any] = {}
_jit_cache_lock = threading.Lock()


def _grouped(fn: Callable, k: int):
    """One flat program for ``k`` independent tasks of the body ``fn``
    (the device manager's group dispatch), cached per ``(fn, k)``: the
    operands of the tasks side by side in, one output per task out, each
    the same function of the same operands as the task's own program.
    The wrapper carries the body's name, so the XLA module is named
    ``jit_<body>`` whichever program runs the body."""
    key = (fn, k)
    j = _jit_cache.get(key)
    if j is None:
        with _jit_cache_lock:
            j = _jit_cache.get(key)
            if j is None:
                import jax

                def group(*flat):
                    a = len(flat) // k
                    return tuple(fn(*flat[i * a:(i + 1) * a])
                                 for i in range(k))
                group.__name__ = group.__qualname__ = \
                    getattr(fn, "__name__", "dtd_task")
                j = jax.jit(group)
                _jit_cache[key] = j
    return j


#: (body, operand signature) -> True once its ladder of group programs is
#: compiled; until then a weak reference to the pool that first ran it on the
#: device, the only pool that may still compile it: a later pool builds no
#: program (``DTDTaskpool._may_group``)
_ladders: Dict[Tuple, Any] = {}


def _signature(vals: Sequence[Any]) -> Tuple:
    """(shape, dtype) of each operand of a device program; ``None`` (the
    device lane's value for a flow written without being read) is its own."""
    return tuple(None if v is None else (v.shape, v.dtype) for v in vals)


_host_dev_cache = [False, None]   # [resolved, device]


def _host_device():
    """The host jax device, resolved once (a per-task jax.local_devices()
    lookup showed up in the benchmark profile). Only a successful lookup is
    cached: a transient backend failure (flaky accelerator discovery) must
    not latch None for the process lifetime."""
    if not _host_dev_cache[0]:
        try:
            import jax
            _host_dev_cache[1] = jax.local_devices(backend="cpu")[0]
            _host_dev_cache[0] = True
        except Exception:
            return None
    return _host_dev_cache[1]


def _jitted(fn: Callable):
    j = _jit_cache.get(fn)
    if j is None:
        with _jit_cache_lock:
            j = _jit_cache.get(fn)
            if j is None:
                import jax
                j = jax.jit(fn)
                _jit_cache[fn] = j
    return j


class DTDTaskClass(TaskClass):
    """Auto-created per (body fn, param profile)
    (ref: function_h_table, insert_function_internal.h:206-224)."""

    def __init__(self, name: str, fn: Callable, flow_accesses: Tuple[int, ...],
                 nb_values: int, jit_ok: bool = True,
                 batchable: bool = False) -> None:
        super().__init__(name, nb_flows=len(flow_accesses))
        self.fn = fn
        self.count_mode = True
        self.lazy_data = True     # fused lane retires tasks slot-free
        self.flow_accesses = flow_accesses
        #: False for side-effectful bodies (callbacks, host I/O): run eagerly
        self.jit_ok = jit_ok
        #: True: pending device tasks of the class are issued together as
        #: one program whatever the device manager observes (ref: dtd GPU
        #: batching flag on task-class chores)
        self.batchable = batchable
        #: may the device manager issue the class in groups? Unknown until
        #: its first program in this pool (``DTDTaskpool._may_group``)
        self.groups: Optional[bool] = None
        for i, acc in enumerate(flow_accesses):
            self.add_flow(Flow(f"f{i}", acc))

    def jitted(self):
        return _jitted(self.fn)

    @property
    def fast_inline(self) -> bool:
        """True when this class can take the fused inline cycle: exactly
        one synchronous CPU chore, no evaluate gate — completion is
        immediate, so insert can run prepare->hook->complete in place."""
        fi = getattr(self, "_fast_inline", None)
        if fi is None:
            fi = self._fast_inline = (
                len(self.incarnations) == 1
                and self.incarnations[0].device_type == DEV_CPU
                and self.incarnations[0].evaluate is None)
        return fi


class DTDTaskpool(Taskpool):
    """Ref: parsec_dtd_taskpool_new (insert_function.c:1513)."""

    def __init__(self, context: Context, name: str = "dtd",
                 capture=False) -> None:
        # per-context (i.e. per-rank) sequence number per base name: every
        # rank constructs its taskpools in the same order, so "dtd#3" means
        # the same pool on all ranks while two concurrently-live pools can
        # never collide in the remote-dep registry
        seqs = getattr(context, "_dtd_name_seq", None)
        if seqs is None:
            seqs = context._dtd_name_seq = {}
        seq = seqs.get(name, 0)
        seqs[name] = seq + 1
        if seq:
            name = f"{name}#{seq}"
        super().__init__(name)
        self.ctx = context
        self._classes: Dict[Any, DTDTaskClass] = {}
        self._tiles: Dict[Any, DTDTile] = {}
        self._tiles_lock = threading.Lock()
        self.window_size = mca.get("dtd_window_size", 2048)
        self.threshold_size = mca.get("dtd_threshold_size", 1024)
        #: serializes the WHOLE insert path (ADVICE r5 medium): concurrent
        #: user-thread inserts are an advertised contract, but the ready
        #: buffer was the only locked piece — the tile.nid check-then-create
        #: could mint two engine chains for one shared tile (silently
        #: dropping RAW/WAR edges), the inserted/local_inserted RMWs could
        #: undercount (wait() then targets too few tasks), and two
        #: concurrently stalling inserters both drove
        #: _progress_loop(streams[0]), racing on stream.next_task.
        #: REENTRANT on purpose: a window-stalled inserter executes tasks
        #: inline, and a body may itself insert (recursive task insertion).
        #: NOT held across the window stall (see _window_stall) — blocking
        #: a worker-thread body's insert on a stalled user thread would
        #: deadlock; _stall_lock elects the one user thread that drives
        #: the master stream's drain loop
        self._insert_lock = threading.RLock()
        self._stall_lock = threading.Lock()
        #: the context's span object (utils/xla_trace.py Spans), None when
        #: off: dtd.link around the locked insert, dtd.stall around the
        #: window stall
        self._spans = context._spans
        self.inserted = 0
        self.local_inserted = 0   # tasks this rank actually executes
        self.window_stalls = 0    # inserter blocked on the task window
        self._executed = 0
        self._exec_lock = threading.Lock()
        self._open = False
        self._touched_tiles: List[DTDTile] = []
        self._new_tile_count = 0
        self._audit = mca.get("dtd_audit", False)
        self._audit_digest = 0      # zlib.crc32 chain: process-independent
        self._audit_count = 0
        #: native dependency engine (native/src/ptdtd.cpp) — the insert/
        #: release hot path as a C extension. Decided at first insert:
        #: single-rank, no comm engine, no audit (those stay on the Python
        #: engine, which owns the distributed protocol bookkeeping)
        self._neng = None
        self._neng_decided = False
        #: batched native insert lane (ISSUE 4): eligible repeat inserts of
        #: one class buffer their specs here (plain list: append is
        #: GIL-atomic, so the fast path takes NO lock; flushers serialize
        #: on the insert lock and drain a snapshot prefix with del-slice,
        #: which can never race a concurrent tail append) and link in the
        #: engine N at a time under one GIL drop (engine.insert_many).
        #: Batched tasks have NO Python task object: the engine owns the
        #: whole insert->link->ready->execute->release cycle; bodies run
        #: through per-class batched callbacks at the drain points
        #: (Context._dtd_drain in every stream's hot loop)
        self._batch_on = False
        self._batch_retired = False   # final-completion hand-back ran
        self._slots_stale = False     # quiescence sync emptied the slots
        #: scheduler-plane pool handle (core/sched_plane.py): set when the
        #: batch lane arms on a plane-carrying context; batch classes
        #: register with it so their ready tasks drain by QoS weight, and
        #: the admission window (tp.admission_window / --mca
        #: sched_admission_window) backpressures insert_task through it
        self._sched_pool: Optional[int] = None
        self._bbuf: List[tuple] = []
        self._batch_flush_n = max(1, min(256, self.window_size // 2))
        #: one-entry FAST-PATH cache: (fn, jit, batch, kinds|k0, cls_nid,
        #: bbuf, flush_n, DTDTile) — everything the native try_buffer
        #: fast path needs in one tuple. kinds collapses to the bare acc
        #: int for the dominant single-flow shape. Rebound wherever
        #: _last_class gains a batch registration; cleared on close()
        self._fast: Optional[tuple] = None
        self._tbuf = None        # native try_buffer (set with _batch_on)
        #: ready-at-insert batch (native lane only): single-stream contexts
        #: gain nothing from per-task scheduler pushes, so ready tasks
        #: buffer here and enter the scheduler in BULK at the drain points
        #: (window stall, wait, close) — one push lock + one priority sort
        #: per batch instead of per task
        self._ready_buf: List[DTDTask] = []
        self._last_class = None   # (fn, accs, nvals, jit, batch, tc)
        if context.comm is not None:
            # distributed: global termination detection + name-keyed registry
            context.comm.fourcounter.monitor_taskpool(self)
            context.comm.register_taskpool(self)
        # hold the "user may still insert" action BEFORE attaching, so the
        # termdet can never observe transiently-zero counters at enqueue time
        # (the reference keeps the taskpool's own nb_pending_actions pinned
        # while attached)
        #: True while the CURRENT insert window is deferred to the
        #: scheduler (a non-capturable insert poisoned it); wait() resets
        #: it so the next window captures again (per-region auto-defer)
        self._capture_deferred = False
        # whole-DAG capture mode (dsl/capture.py): record inserts, execute
        # the entire pool as ONE jitted XLA program at wait()
        self._capture = None
        if capture:
            if context.nb_ranks > 1:
                output.fatal("graph capture is single-rank "
                             "(a captured pool never leaves the chip)")
            from .capture import GraphCapture
            # capture=True -> "auto"; or an explicit "inline"/"scan" strategy
            self._capture = GraphCapture(self, mode=capture)
        self.addto_nb_pending_actions(1)
        self._open = True
        context.add_taskpool(self)

    # ------------------------------------------------------------- tiles
    def tile_of(self, dc: DataCollection, *indices) -> DTDTile:
        """PARSEC_DTD_TILE_OF (ref: parsec_dtd_tile_of, insert_function.c:1403)."""
        key = (dc.name, dc.data_key(*indices))
        with self._tiles_lock:
            t = self._tiles.get(key)
            if t is None:
                data = dc.data_of(*indices)
                t = DTDTile(data, key, dc, rank=dc.rank_of(*indices))
                self._tiles[key] = t
                self._touched_tiles.append(t)
            return t

    def tile_of_key(self, dc: DataCollection, key: Any) -> DTDTile:
        tkey = (dc.name, key)
        with self._tiles_lock:
            t = self._tiles.get(tkey)
            if t is None:
                data = dc.data_of_key(key)
                t = DTDTile(data, tkey, dc, rank=dc.rank_of_key(key))
                self._tiles[tkey] = t
                self._touched_tiles.append(t)
            return t

    def tile_new(self, array_or_shape, dtype=np.float32, key: Any = None) -> DTDTile:
        """parsec_dtd_tile_new (ref: insert_function.h:239): a taskpool-lifetime
        scratch tile not backed by any collection."""
        if hasattr(array_or_shape, "shape"):
            arr = np.asarray(array_or_shape)
        else:
            arr = np.zeros(array_or_shape, dtype=dtype)
        data = data_from_array(arr)
        self._new_tile_count += 1
        t = DTDTile(data, ("new", self.name, self._new_tile_count), None,
                    rank=self.ctx.my_rank, new_tile=True)
        with self._tiles_lock:
            self._tiles[t.key] = t
            self._touched_tiles.append(t)
        return t

    # ------------------------------------------------------------- classes
    def _class_of(self, fn: Callable, flow_accesses: Tuple[int, ...],
                  nb_values: int, name: Optional[str],
                  jit_ok: bool = True, batchable: bool = False) -> DTDTaskClass:
        key = (fn, flow_accesses, nb_values, jit_ok, batchable)
        tc = self._classes.get(key)
        if tc is None:
            tc = DTDTaskClass(name or getattr(fn, "__name__", "dtd_task"),
                              fn, flow_accesses, nb_values, jit_ok=jit_ok,
                              batchable=batchable)
            tc.prepare_input = self._prepare_input
            tc.release_deps = self._release_deps
            tc.complete_execution = self._complete_execution
            # the TPU chore only exists where a TPU device does — on
            # CPU-only contexts every task would walk (and fail) it first.
            # Non-jittable bodies never get one: they would ride the whole
            # async device pipeline (stage-in/events/epilog) only to run
            # raw Python anyway — pure per-task overhead
            if jit_ok and any(d.type & DEV_TPU
                              for d in self.ctx.devices.devices):
                tc.add_chore(Chore(DEV_TPU, self._tpu_hook))
            tc.add_chore(Chore(DEV_CPU, self._cpu_hook))
            self.add_task_class(tc)
            self._classes[key] = tc
        return tc

    # ------------------------------------------------------------- insert
    def _native_engine(self):
        """The per-context native DTD engine, or None (gated)."""
        if self._neng_decided:
            return self._neng
        self._neng_decided = True
        ctx = self.ctx
        # PINS no longer ejects pools from the native engine (PR 5): the
        # per-task lane keeps firing the full event cycle through the
        # Python FSM (successor lists mirrored on demand from the engine,
        # see _complete_execution), the batched lane records in-lane ring
        # events (utils/native_trace.py). Only --mca pins_paranoid 1
        # restores the all-Python engine for full-fidelity debugging
        if ctx.comm is not None or ctx.nb_ranks > 1 or self._audit \
                or ctx.pins.paranoid or not mca.get("native_enabled", True):
            return None
        eng = getattr(ctx, "_dtd_neng", None)
        if eng is None and not getattr(ctx, "_dtd_neng_failed", False):
            # serialized: two pools first-inserting from different client
            # threads must not BOTH mint an engine (the loser's tasks
            # would link into a chain state nobody drains)
            with _BATCH_POOLS_LOCK:
                eng = getattr(ctx, "_dtd_neng", None)
                if eng is None and \
                        not getattr(ctx, "_dtd_neng_failed", False):
                    from .. import native as native_mod
                    mod = native_mod.load_ptdtd()
                    if mod is None:
                        ctx._dtd_neng_failed = True
                    else:
                        ctx._dtd_ntasks = {}
                        eng = ctx._dtd_neng = mod.Engine()
        if eng is not None:
            # progress loops drain our ready buffer even when the user
            # drives the context directly (no tp.wait()); weakly bound so
            # a dropped pool unregisters itself
            ctx.register_drain_hook(self._flush_ready)
            # batched insert lane: engine v2 (insert_many/drain_ready)
            # on a CPU-only context with the DEFAULT scheduler. TPU
            # contexts stay per-task — device selection / async epilogs
            # are policy the in-engine drain bypasses, and a TPU epilog
            # writing a tile behind the engine's payload slot would break
            # slot coherence. An explicitly-chosen scheduler module also
            # refuses the lane: batched tasks never enter the scheduler
            # queues, so a user-selected ordering policy (FIFO, priority
            # heap, ...) could not see them
            if mca.get("dtd_batch_insert", True) \
                    and hasattr(eng, "insert_many") \
                    and not getattr(ctx, "sched_explicit", False) \
                    and not any(d.type & DEV_TPU
                                for d in ctx.devices.devices):
                # an explicitly-chosen scheduler still refuses the batch
                # lane even with the scheduler plane up: a DTD pool mixes
                # batch-lane tasks (plane-ordered) with per-task-lane
                # tasks (Python-queue-ordered — every prioritized or
                # shape-ineligible insert), and the user's policy spans
                # BOTH, which no per-lane ordering can honor
                # (test_scheduler_policy_separation is the contract).
                # PTG lanes are whole-pool native, so THEY honor an
                # explicit policy through the plane's flavor instead
                self._batch_on = True
                from .. import native as _nm     # memoized load
                self._tbuf = _nm.load_ptdtd().try_buffer
                # ring lifecycle (enable): the batched lane's insert/exec
                # cycle never surfaces per-task pins events, so its
                # observability is the in-lane rings (no-op when no
                # profiling is attached). The engine is per-CONTEXT and
                # outlives pools, so its events carry taskpool id 0
                ctx._ntrace_attach("ptdtd", eng)
                ctx._hist_attach("ptdtd", eng)
                # open-batch-pool count gates the stream hot loops' engine
                # drain; decremented at final completion so pools running
                # AFTER this one (e.g. with the batch lane mca-disabled)
                # don't pay an empty drain_ready every idle iteration
                with _BATCH_POOLS_LOCK:
                    ctx._dtd_batch_pools += 1
                PTDTD_STATS["pools_batch"] += 1
                # scheduler plane (ISSUE 9): bind the engine (idempotent —
                # one plane per context) and register this pool's QoS
                # identity; batch classes then route ready tasks through
                # the shared plane, so N concurrent DTD pools drain by
                # DRR weight and the admission window gains teeth
                plane = getattr(ctx, "sched_plane", None)
                if plane is not None:
                    try:
                        eng.sched_bind(plane.capsule)
                        h = plane.register_pool(
                            self.name, plane.KIND_PTDTD,
                            weight=getattr(self, "qos_weight", None),
                            window=getattr(self, "admission_window", None))
                        self._sched_pool = h if h >= 0 else None
                    except Exception:  # noqa: BLE001 — private ready path
                        self._sched_pool = None
                # tile payload slots sync back into tile.data when the
                # pool completes, even when the user never calls wait().
                # CHAIN any prior hook — compound stages and recursive
                # device pools set on_complete BEFORE their first insert,
                # and must see the synced tile.data values when they fire
                prev = self.on_complete
                if prev is None:
                    self.on_complete = _pool_sync_on_complete
                else:
                    def _chained(tp, _prev=prev):
                        _pool_sync_on_complete(tp)
                        _prev(tp)
                    self.on_complete = _chained
        self._neng = eng
        return eng

    # ------------------------------------------------------- batched lane
    def _tile_nid(self, tile: DTDTile) -> int:
        """The tile's engine chain id, created (and its payload slot
        seeded) on first native touch. The check-then-create runs under
        the insert lock: two threads racing here must not mint two engine
        chains for one shared tile (the PR 2 concurrent-inserter bug)."""
        nid = tile.nid
        if nid is None:
            with self._insert_lock:
                nid = tile.nid
                if nid is None:
                    neng = self._neng
                    nid = neng.tile()
                    if self._batch_on:
                        copy = tile.data.newest_copy()
                        if copy is not None:
                            neng.slot_set(nid, copy.payload)
                    tile.nid = nid
        return nid

    def _slot_payload(self, tile: DTDTile):
        """Newest payload of a tile on a batch-lane pool: the engine slot
        is authoritative while batched writers are in flight (tile.data
        syncs at wait/complete); falls back to newest_copy."""
        if self._batch_on and tile.nid is not None:
            p = self._neng.slot_get(tile.nid)
            if p is not None:
                return p
        copy = tile.data.newest_copy()
        return None if copy is None else copy.payload

    def _mk_batch_callback(self, tc: "DTDTaskClass", argmap: Tuple[int, ...]):
        """The per-class batched dispatch the engine's drain_ready invokes
        once per (class, batch): run every body on its gathered args and
        hand WRITE-flow outputs back for native slot landing. Execution
        accounting does NOT happen here — the engine invokes
        ``_batch_retire`` only after phase 3 has landed the outputs, so a
        wait()er can never observe the counters ahead of the payloads."""
        fn = tc.fn
        use_jit = tc.jit_ok
        wflows = [i for i, a in enumerate(tc.flow_accesses) if a & WRITE]
        nw = len(wflows)
        # arg position each write flow's input payload sits at (a body
        # returning fewer outputs keeps the old payload, like _run_lean)
        wpos = [argmap.index(i) for i in wflows]

        def _batch_cb(args_list):
            f = _jitted(fn) if use_jit else fn
            if nw:
                outs_list = []
                ap = outs_list.append
                for vals in args_list:
                    o = f(*vals)
                    if o is None:
                        o = ()
                    elif type(o) is not tuple:
                        o = tuple(o) if isinstance(o, list) else (o,)
                    if len(o) < nw:
                        o = tuple(o[k] if k < len(o) else vals[wpos[k]]
                                  for k in range(nw))
                    ap(o)
            else:
                for vals in args_list:
                    f(*vals)
                outs_list = None
            return outs_list

        return _batch_cb

    def _batch_retire(self, ne: int) -> None:
        """Engine-invoked AFTER a batch's outputs have landed in the tile
        slots and its release walk has run (drain_ready phase 3): retire
        the batch's execution accounting in bulk (one _exec_lock acquire
        and one nb_tasks update per BATCH instead of per task). Ordering
        matters: retiring inside the batch callback — before the landing —
        would let a concurrent wait() see ``executed >= target`` and
        _sync_slots() the PRE-batch payloads, silently dropping the final
        batch's writes."""
        with self._exec_lock:
            self._executed += ne
        self.addto_nb_tasks(-ne)

    def _mk_batch_info(self, tc: "DTDTaskClass", flow_accesses,
                       arg_spec) -> Optional[tuple]:
        """Register an engine batch class for (tc, arg interleaving), or
        None when ineligible. Eligibility (honest-fallback contract, the
        ptexec pattern — refusals ride the per-task lane and count in
        PTDTD_STATS):
          * plain READ/WRITE/RW flows only (NOTRACK snapshots the value at
            insert time, which a deferred batch cannot honor; AFFINITY is
            placement policy);
          * jittable bodies take no by-value args (the batched dispatch
            calls the class's jitted fn on payloads only);
          * TPU contexts never reach here (pool-level gate)."""
        if not self._batch_on:
            return None
        for acc in flow_accesses:
            if acc & ~0x3:
                PTDTD_STATS["classes_ineligible"] += 1
                return None
        if tc.jit_ok and any(kind != "flow" for kind, _ in arg_spec):
            PTDTD_STATS["classes_ineligible"] += 1
            return None
        kinds: List[Optional[int]] = []
        argmap: List[int] = []
        for kind, v in arg_spec:
            if kind == "flow":
                kinds.append(flow_accesses[v])
                argmap.append(v)
            else:
                kinds.append(None)
                argmap.append(-1)
        reg = getattr(tc, "_breg", None)
        if reg is None:
            reg = tc._breg = {}
        key = tuple(argmap)
        nid = reg.get(key)
        if nid is None:
            cb = self._mk_batch_callback(tc, key)
            nid = self._neng.register_class(
                cb, key, [a & 0x3 for a in flow_accesses],
                self._batch_retire,
                -1 if self._sched_pool is None else self._sched_pool)
            reg[key] = nid
        return (nid, tuple(kinds))

    def _flush_batch(self) -> None:
        """Hand the buffered insert specs to the engine in one call.
        Flushers serialize on the insert lock; the del-slice prefix drain
        cannot race concurrent tail appends (both are GIL-atomic and the
        fast path only ever appends)."""
        if not self._bbuf:
            return
        with self._insert_lock:
            self._flush_batch_locked()

    def _flush_batch_locked(self) -> None:
        lst = self._bbuf
        n = len(lst)
        if not n:
            return
        if self._slots_stale:
            # a quiescence sync emptied the slots (tile.data became
            # authoritative again, honoring any user reseed since); the
            # next batch gathers args from the slots, so refill them from
            # the host copies before linking
            self._slots_stale = False
            neng = self._neng
            with self._tiles_lock:
                tiles = list(self._touched_tiles)
            for t in tiles:
                if t.nid is not None:
                    copy = t.data.newest_copy()
                    if copy is not None:
                        neng.slot_set(t.nid, copy.payload)
        chunk = lst[:n]
        del lst[:n]
        # count BEFORE linking: a linked task may be drained by a worker
        # immediately, and its -1 must never underflow the counter
        self.addto_nb_tasks(n)
        self.inserted += n
        self.local_inserted += n
        PTDTD_STATS["tasks_batched"] += n
        PTDTD_STATS["batches"] += 1
        try:
            self._neng.insert_many(chunk)
        except BaseException:
            # insert_many validates the WHOLE batch before linking any of
            # it, so a raise means nothing linked: roll the counters back
            # or the pool could never quiesce (wait() would spin to its
            # timeout on tasks that do not exist)
            self.addto_nb_tasks(-n)
            self.inserted -= n
            self.local_inserted -= n
            PTDTD_STATS["tasks_batched"] -= n
            PTDTD_STATS["batches"] -= 1
            raise

    def _sync_slots(self) -> None:
        """Land the engine's tile payload slots back into tile.data (the
        slot-ownership hand-off: C owned the values while batched writers
        were in flight; Python re-takes them at quiescence points). The
        version delta equals the number of batched writes, keeping
        tile.data.version in parity with the per-task lanes. slot_sync
        also EMPTIES each slot, making tile.data authoritative until the
        next flush re-seeds — so a user reseeding a tile's host copy
        between waits is honored exactly like on the per-task lanes.

        Runs under the insert lock (RLock — callers already holding it
        are fine): a concurrent inserter thread's flush must never link a
        batch against slots this sync is mid-way through emptying (the
        drained bodies would gather None payloads), and the stale flag
        must be set before any later flush can read it."""
        if not self._batch_on:
            return
        neng = self._neng
        with self._insert_lock:
            with self._tiles_lock:
                tiles = list(self._touched_tiles)
            synced = False
            for t in tiles:
                nid = t.nid
                if nid is None:
                    continue
                payload, writes = neng.slot_sync(nid)
                synced = True
                if not writes:
                    continue
                data = t.data
                host = data.get_copy(0)
                if host is None:
                    data.create_copy(0, payload, COHERENCY_OWNED)
                else:
                    host.payload = payload
                data.bump_version(0, writes)
                t.wcount += writes
                t.last_writer_version = t.wcount
            if synced:
                self._slots_stale = True

    def _retire_batch_lane(self) -> None:
        """Final-completion hand-back for batch-lane pools (fires once,
        from on_complete): drop this pool from the context's open-batch
        count (stream hot loops stop paying the engine drain once no
        batch pool is live) and release the engine-side state the pool
        pinned."""
        if not self._batch_on or self._batch_retired:
            return
        self._batch_retired = True
        with _BATCH_POOLS_LOCK:
            self.ctx._dtd_batch_pools -= 1
        self._release_native()
        if self._sched_pool is not None:
            # free the plane slot AFTER release_pool cleared the classes'
            # pool routing (a released class must never route to a slot
            # another pool may reuse)
            plane = getattr(self.ctx, "sched_plane", None)
            if plane is not None:
                plane.unregister_pool(self._sched_pool)
            self._sched_pool = None
        if self.ctx._ntrace is not None:
            # ring lifecycle (quiescence): land this pool's in-lane events
            # now — the engine outlives the pool, but a dumped trace must
            # not be missing a completed pool's tail
            self.ctx._ntrace.drain_all(wait=True)

    def _release_native(self) -> None:
        """Hand the pool's engine-side references back: tile payload slots
        and batch-class callbacks. The Engine is per-CONTEXT while pools
        come and go — without this, every dead pool's payloads (and the
        pool object itself, through the callback closures) stay pinned
        until context teardown. Only called once the pool is fully drained
        (no task of a released class can ever be ready again)."""
        rel = getattr(self._neng, "release_pool", None)
        if rel is None:
            return
        with self._tiles_lock:
            nids = [t.nid for t in self._touched_tiles if t.nid is not None]
        cls_ids: List[int] = []
        for tc in self._classes.values():
            reg = getattr(tc, "_breg", None)
            if reg:
                cls_ids.extend(reg.values())
        if nids or cls_ids:
            rel(nids, cls_ids)
        self._fast = None

    def _run_lean(self, task: "DTDTask", tc: "DTDTaskClass",
                  tiles, arg_spec) -> None:
        """Non-jittable fused body: resolve payloads straight from the
        tiles, run eagerly, write WRITE flows back — the _cpu_hook eager
        branch without TaskData slot churn (fused-inline path only)."""
        pend = task.pending_inputs
        batch_on = self._batch_on
        payloads = []
        for i, tile in enumerate(tiles):
            p = pend.pop(i, None) if pend else None
            if p is None and batch_on and tile.nid is not None:
                # batch-lane coherence: the engine slot holds the newest
                # payload while batched writers are in flight
                p = self._neng.slot_get(tile.nid)
            if p is None:
                copy = tile.data.newest_copy()
                if copy is None:
                    output.fatal(f"tile {tile!r} has no valid copy "
                                 f"for {task!r}")
                p = copy.payload
            payloads.append(p)
        vals = [payloads[v] if kind == "flow" else v for kind, v in arg_spec]
        outs = tc.fn(*vals)
        if outs is None:
            outs = ()
        elif not isinstance(outs, (tuple, list)):
            outs = (outs,)
        oi = 0
        for i, acc in enumerate(tc.flow_accesses):
            if acc & WRITE:
                new = outs[oi] if oi < len(outs) else payloads[i]
                oi += 1
                tile = tiles[i]
                data = tile.data
                host = data.get_copy(0)
                if host is None:
                    data.create_copy(0, new, COHERENCY_OWNED)
                else:
                    host.payload = new
                data.bump_version(0)
                if batch_on and tile.nid is not None:
                    # mirror into the engine slot so batched readers see
                    # this write (slot_set bumps no batch-write counter:
                    # the version was bumped Python-side above)
                    self._neng.slot_set(tile.nid, new)

    def _lean_cycle(self, stream, task: "DTDTask") -> None:
        """The fused select-side task cycle for native-lane eager bodies:
        run, land outputs, retire, release successors — one call from the
        progress loop instead of the generic prepare/execute/complete FSM
        (the machinery a C runtime pays ~0 for; fusing it is how the
        interpreted runtime stays in the reference's rate class).

        Profiling no longer ejects tasks from this lane (PR 5): with PINS
        enabled the fused cycle fires the core lifecycle events itself —
        EXEC and COMPLETE/RELEASE pairs plus the engine-successor mirror —
        so TaskProfiler/ALPerf/grapher consumers keep their contract at
        near-lean cost; ``--mca pins_paranoid 1`` restores the full FSM
        (which additionally fires the PREPARE_INPUT pair)."""
        tc = task.task_class
        pins = self.ctx.pins
        pins_on = pins.enabled
        if pins_on:
            pins.fire(pins_mod.EXEC_BEGIN, stream, task)
        self._run_lean(task, tc, task.tiles, task.arg_spec)
        stream.nb_executed += 1
        if pins_on:
            pins.fire(pins_mod.EXEC_END, stream, task)
            pins.fire(pins_mod.COMPLETE_EXEC_BEGIN, stream, task)
            # engine-successor mirror for RELEASE consumers (the grapher);
            # complete() below moves the engine's list out
            ntasks = self.ctx._dtd_ntasks
            task.successors = [ntasks[s]
                               for s in self._neng.successors(task.nid)
                               if s in ntasks]
            pins.fire(pins_mod.RELEASE_DEPS_BEGIN, stream, task)
        task.status = TASK_STATUS_COMPLETE
        task.completed = True
        with self._exec_lock:
            self._executed += 1
        ready_ids = self._neng.complete(task.nid)
        self.ctx._dtd_ntasks.pop(task.nid, None)
        task.tiles = ()
        task.arg_spec = ()
        task.data = ()
        task.pending_inputs = None
        if ready_ids:
            self._schedule_native_ready(ready_ids, stream)
        if pins_on:
            task.successors = None
            pins.fire(pins_mod.RELEASE_DEPS_END, stream, task)
            pins.fire(pins_mod.COMPLETE_EXEC_END, stream, task)
        self.addto_nb_tasks(-1)

    def _schedule_native_ready(self, ready_ids, stream=None) -> None:
        """Map newly-ready native task ids to their Python tasks and queue
        them (shared by the release path and the fused-inline complete)."""
        ntasks = self.ctx._dtd_ntasks
        rtasks = []
        for rid in ready_ids:
            rt = ntasks[rid]
            rt.deps_remaining = 0   # paranoid-check coherence
            rtasks.append(rt)
        self.ctx.schedule(rtasks, stream)

    def _flush_ready(self) -> None:
        """Hand the buffered ready-at-insert batch to the scheduler (and
        flush the batch-lane insert buffer: this doubles as the pool's
        progress-loop drain hook, so starving loops always see buffered
        work)."""
        if self._bbuf:
            self._flush_batch()
        if not self._ready_buf:
            return
        with self._exec_lock:
            buf = self._ready_buf
            self._ready_buf = []
        if buf:
            self.ctx.schedule(buf)

    def _window_stall(self) -> None:
        """Window flow control (ref: insert_function.h:149-157).

        Runs OUTSIDE the insert lock — a stalling inserter must never
        block another thread's (in particular a worker-thread body's)
        insert fast path, or a mid-body recursive insert would deadlock
        the pool. Flow control NEVER blocks inside a task body (a thread
        currently driving a progress loop, ``ctx.in_progress_loop()`` —
        thread-local, so one thread's wait()/stall cannot mask another
        thread's top-level inserts): the unfinished task's successors may
        be the only drainable work, so waiting there can never converge —
        recursive inserts overshoot the window instead, bounded by the
        DAG's recursive fan-out (the reference's window also only ever
        throttles the user-side inserter). Top-level user threads elect
        ONE drainer via a try-lock — the loser waits for the window to
        drain instead of racing the winner on streams[0].next_task
        (ADVICE r5)."""
        if self.local_inserted - self.executed <= self.window_size:
            return
        if self.ctx.in_progress_loop():
            return              # mid-body insert: never block flow control
        sp = self._spans
        if sp is not None:
            # dtd.stall: the inserter draining tasks or sleeping; every
            # device span the drain runs nests inside
            tok = sp.begin(DTD_STALL)
        try:
            self._flush_ready()
            self.window_stalls += 1
            self.ctx.start()
            while self.local_inserted - self.executed > self.window_size:
                if self.ctx._error is not None:
                    return
                if self._stall_lock.acquire(blocking=False):
                    try:
                        target = self.local_inserted - self.threshold_size
                        self.ctx._progress_loop(
                            self.ctx.streams[0],
                            until=lambda: self.executed >= target)
                    finally:
                        self._stall_lock.release()
                    return
                time.sleep(50e-6)   # another user thread is draining
        finally:
            if sp is not None:
                sp.end(tok, sp.stall)

    def _admission_stall(self) -> None:
        """Admission backpressure (ISSUE 9): the scheduler plane reported
        this pool past its admission window (in-flight inserted-but-not-
        completed tasks > --mca sched_admission_window / tp.admission_
        window), so the inserting thread HELPS DRAIN until the pool is
        back under — a runaway client thread saturates the ingest budget
        instead of OOMing the ready plane. Same discipline as
        _window_stall: never blocks inside a task body (recursive inserts
        overshoot, bounded by the DAG's fan-out), one elected drainer."""
        h = self._sched_pool
        if h is None:
            return
        plane = self.ctx.sched_plane
        if plane is None or not plane.over_window(h):
            return
        if self.ctx.in_progress_loop():
            return              # mid-body insert: never block flow control
        self._flush_ready()
        plane.count_stall(h)
        self.ctx.start()
        while plane.over_window(h):
            if self.ctx._error is not None or self._batch_retired:
                return
            if self._stall_lock.acquire(blocking=False):
                try:
                    self.ctx._progress_loop(
                        self.ctx.streams[0],
                        until=lambda: not plane.over_window(h))
                finally:
                    self._stall_lock.release()
                return
            time.sleep(50e-6)   # another user thread is draining

    def insert_task(self, fn: Callable, *args, priority: int = 0,
                    where: int = DEV_ALL, name: Optional[str] = None,
                    jit: bool = True, batch: bool = False,
                    nowait: bool = False) -> Optional[DTDTask]:
        """parsec_dtd_insert_task (ref: insert_function.c:3617).

        ``args``: ``(tile, access)`` tuples become data flows; anything else
        is a by-value parameter. ``access`` may carry the AFFINITY bit to pick
        the task's rank (default: first WRITE tile's rank) and/or the
        NOTRACK bit to pass the tile's value without dependency tracking
        (ref PARSEC_DONT_TRACK).

        Thread-safe: concurrent user threads may insert into one pool —
        the whole linking path (tile chain check-then-create, engine
        calls, counters, ready buffering) runs under the taskpool insert
        lock, so shared-tile chains stay exact; window flow control runs
        AFTER the lock drops (one drainer elected, see _window_stall).

        Batched native lane: on a single-rank CPU context, repeat inserts
        of an eligible class (same body fn, same flow shape — the one-
        entry class cache) buffer their specs and link in the engine N at
        a time; such inserts return ``None`` (no per-task Python object
        exists — like capture mode, the handle-free contract of the
        batched lane). The FIRST insert of a class, and any ineligible
        insert (priority, NOTRACK/AFFINITY, device restriction, jittable
        body with by-value args), takes the per-task path and returns the
        task. Buffered inserts flush at window boundaries, at wait/close,
        and whenever a progress loop starves.

        Admission backpressure: past the scheduler plane's per-pool
        window the insert BLOCKS (helping drain) — or raises
        :class:`AdmissionBackpressure` with ``nowait=True``, the
        serving-tier "shed load instead of queueing" contract. The window
        is a soft limit: buffered-but-unflushed specs (at most the flush
        threshold) do not count against it.
        """
        if nowait and self._sched_pool is not None:
            plane = self.ctx.sched_plane
            if plane is not None and plane.over_window(self._sched_pool):
                from ..core.sched_plane import SCHED_STATS
                SCHED_STATS["admission_rejects"] += 1
                raise AdmissionBackpressure(
                    f"taskpool {self.name!r} over its admission window "
                    f"(in-flight tasks > configured "
                    f"sched_admission_window)")
        # batch-lane fast path: NO lock — the whole validate+spec-build+
        # buffer-append collapses into one C call (native try_buffer); the
        # list append it performs is GIL-atomic. A 0 return (unknown fn,
        # shape mismatch, priority, device restriction, un-entered tile)
        # falls through to the per-task slow path
        fi = self._fast
        if fi is not None:
            r = self._tbuf(fi, fn, args, priority, where, jit, batch)
            if r:
                if r == 2:      # flush threshold reached
                    self._flush_batch()
                    self._window_stall()
                    if not nowait:
                        self._admission_stall()
                return None
        with self._insert_lock:
            sp = self._spans
            if sp is None:
                task = self._insert_task_locked(fn, args, priority, where,
                                                name, jit, batch)
            else:
                # dtd.link: the whole locked insert (class lookup, tile
                # chains, engine link, ready buffering), no window stall
                tok = sp.begin(DTD_LINK)
                try:
                    task = self._insert_task_locked(fn, args, priority,
                                                    where, name, jit, batch)
                finally:
                    sp.end(tok, sp.link)
        self._window_stall()
        if not nowait:
            self._admission_stall()
        return task

    def _insert_task_locked(self, fn: Callable, args, priority: int,
                            where: int, name: Optional[str],
                            jit: bool, batch: bool) -> Optional[DTDTask]:
        if not self._open:
            output.fatal("insert_task on a closed DTD taskpool")
        if self._bbuf:
            # chain-order guarantee: buffered batch specs precede this
            # task in program order, so they must link first
            self._flush_batch_locked()
        if self._capture is not None and not self._capture_deferred:
            from .capture import CaptureDeferred
            try:
                self._capture.record(fn, args, jit=jit, name=name or "",
                                     priority=priority, where=where)
                self.inserted += 1
                return None
            except CaptureDeferred as e:
                # per-region auto-defer (ISSUE 10): this wait()-delimited
                # window holds a non-capturable insert — replay the
                # recorded prefix through the scheduler in program order
                # (device bodies then ride the device module / ptdev
                # lane) and run the REST of the window interpreted too;
                # capture re-arms at the next window, so capture wins
                # where it applies instead of losing globally
                output.debug_verbose(1, "capture",
                                     f"{self.name}: window deferred to "
                                     f"the scheduler ({e})")
                self._capture_deferred = True
                PTDTD_STATS["capture_windows_deferred"] += 1
                n_rec = len(self._capture.ops)
                # region fusion (ISSUE 12): capturable RUNS of the
                # deferred window collapse into one super-task insert
                # each — capture still wins where it applies, the
                # scheduler handles only the seams
                replays = self._capture.take_ops(
                    fuse=bool(mca.get("region_fusion", True)))
                self.inserted -= n_rec          # re-counted by the replay
                for rfn, rargs, rprio, rwhere, rname in replays:
                    nf = getattr(rfn, "_ptdtd_fused", 0)
                    if nf:
                        PTDTD_STATS["capture_regions_fused"] += 1
                        PTDTD_STATS["capture_tasks_fused"] += nf
                    self._insert_task_locked(rfn, rargs, rprio,
                                             DEV_ALL if rwhere is None
                                             else rwhere, rname or None,
                                             True, False)
                # fall through: THIS task inserts normally below
        flow_accesses: List[int] = []
        arg_spec: List[Tuple[str, Any]] = []
        tiles: List[DTDTile] = []
        affinity_tile: Optional[DTDTile] = None
        for a in args:
            if isinstance(a, tuple) and len(a) == 2 and isinstance(a[0], DTDTile):
                tile, acc = a
                if acc & AFFINITY:
                    affinity_tile = tile
                acc &= ~AFFINITY
                arg_spec.append(("flow", len(flow_accesses)))
                flow_accesses.append(acc)
                tiles.append(tile)
            elif isinstance(a, DTDTile):
                arg_spec.append(("flow", len(flow_accesses)))
                flow_accesses.append(RW)
                tiles.append(a)
            else:
                arg_spec.append(("value", a))
        # one-entry class cache: the dominant pattern is a loop inserting
        # the same body with the same flow shape (the reference's task
        # class reuse), so the 5-tuple dict key is usually redundant.
        # Entry 6 is the batch-lane registration (engine class id + arg
        # kind pattern) the insert_task fast path matches against
        lc = self._last_class
        if lc is not None and lc[0] is fn and lc[1] == flow_accesses \
                and lc[2] == len(arg_spec) and lc[3] == jit and lc[4] == batch:
            tc = lc[5]
            binfo = lc[6]
        else:
            tc = self._class_of(fn, tuple(flow_accesses), len(arg_spec),
                                name, jit_ok=jit, batchable=batch)
            binfo = _BINFO_UNSET
            self._last_class = (fn, list(flow_accesses), len(arg_spec),
                                jit, batch, tc, None)
        task = DTDTask(self, tc, priority)
        task.arg_spec = arg_spec
        task.tiles = tiles
        task.ident = self.inserted
        self.inserted += 1

        neng = self._neng if self._neng_decided else self._native_engine()
        if neng is not None:
            if self._batch_on:
                if binfo is _BINFO_UNSET:
                    # register (or refuse) the batch-lane class for this
                    # arg interleaving so the NEXT insert can take the
                    # lock-free buffered fast path
                    binfo = self._mk_batch_info(tc, flow_accesses, arg_spec)
                    self._last_class = (fn, list(flow_accesses),
                                        len(arg_spec), jit, batch, tc, binfo)
                    if binfo is not None:
                        kinds = binfo[1]
                        if len(kinds) == 1 and kinds[0] is not None:
                            kinds = kinds[0]    # single-flow collapse
                        self._fast = (fn, jit, batch, kinds, binfo[0],
                                      self._bbuf, self._batch_flush_n,
                                      DTDTile)
                PTDTD_STATS["tasks_per_task"] += 1
            # single-rank: owner-computes placement is the identity — the
            # affinity scan below would always land on my_rank
            task.rank = self.ctx.my_rank
            # native fast lane (single-rank): per-tile chain linking, pred
            # discovery, and the insertion-guard drop happen in ONE
            # C-extension call; Python keeps the id->task map plus a cheap
            # chain MIRROR (last_writer/readers/wcount) so tile
            # introspection keeps its documented meaning
            nids, naccs = [], []
            for fi, (tile, acc) in enumerate(zip(tiles, flow_accesses)):
                if acc & NOTRACK:
                    p = self._slot_payload(tile)
                    if p is not None:
                        if task.pending_inputs is None:
                            task.pending_inputs = {}
                        task.pending_inputs[fi] = p
                    continue
                nid = tile.nid
                if nid is None:
                    nid = self._tile_nid(tile)
                nids.append(nid)
                naccs.append(acc & 0x3)
                if acc & WRITE:
                    tile.last_writer = task
                    tile.readers = []
                    tile.compact_at = 32
                    tile.wcount += 1
                    tile.last_writer_version = tile.wcount
                else:
                    readers = tile.readers
                    if len(readers) >= tile.compact_at:
                        live = [r for r in readers if not r.completed]
                        live.append(task)
                        tile.readers = live
                        tile.compact_at = max(32, 2 * len(live))
                    else:
                        readers.append(task)
            # count-then-activate (ref: parsec_dtd_schedule_task_if_ready,
            # insert_function.c:2963): insert() links the chains but KEEPS
            # the insertion guard held, so a fast predecessor completing on
            # a worker thread cannot surface this id from complete() before
            # the id->task map below is populated (the round-5 activation
            # race, ADVICE.md). activate() drops the guard only after the
            # task is findable.
            tid, _held = neng.insert(nids, naccs)
            task.nid = tid
            self.ctx._dtd_ntasks[tid] = task
            self.addto_nb_tasks(1)
            li = self.local_inserted = self.local_inserted + 1
            ndeps = neng.activate(tid)
            if ndeps == 0:
                task.deps_remaining = 0
                # ready now — but insert_task is ASYNCHRONOUS by contract
                # (bodies run at the window stall / wait drain, never at
                # insert): batch toward the scheduler so priorities stay
                # policy-visible while the push cost amortizes. The lock
                # pairs the append with the flusher's swap — two USER
                # threads may insert concurrently regardless of stream
                # count, and an append racing the swap would land in an
                # already-scheduled list
                with self._exec_lock:
                    buf = self._ready_buf
                    buf.append(task)
                if len(buf) >= 1024:
                    self._flush_ready()
            return task     # window stall runs after the insert lock drops

        task.lock = threading.Lock()      # Python engine: preds/release lock
        task.successors = []
        # owner-computes rank (ref: rank from affinity tile's rank_of_key);
        # untracked flows don't steer placement
        if affinity_tile is None:
            for t, acc in zip(tiles, flow_accesses):
                if acc & WRITE and not acc & NOTRACK:
                    affinity_tile = t
                    break
            if affinity_tile is None:
                # fallback prefers tracked flows too: an untracked scratch
                # tile is rank-local and would diverge owner-computes
                # placement across the distributed replay
                tracked = [t for t, acc in zip(tiles, flow_accesses)
                           if not acc & NOTRACK]
                if tracked:
                    affinity_tile = tracked[0]
                elif tiles:
                    affinity_tile = tiles[0]
        task.rank = affinity_tile.rank if affinity_tile is not None \
            else self.ctx.my_rank

        distributed = self.ctx.comm is not None and self.ctx.nb_ranks > 1
        remote = distributed and task.rank != self.ctx.my_rank
        # link against each tile's chain (ref: parsec_dtd_set_params_of_task
        # insert_function.c:2896; WAR via overlap_strategies.c). In
        # distributed mode every rank replays the same sequence, so the
        # version bookkeeping below is globally consistent without messages.
        for fi, (tile, acc) in enumerate(zip(tiles, flow_accesses)):
            self._link_tile(task, tile, acc, fi, remote, distributed)
        if remote:
            # shadow task: executes elsewhere; local role is only data routing
            self.ctx.comm.dtd_remote_task(self, task)
            self._drop_insertion_guard(task, schedule=False)
            return task
        self.addto_nb_tasks(1)
        self.local_inserted += 1
        self._drop_insertion_guard(task, schedule=True)
        return task     # window stall runs after the insert lock drops

    def _link_tile(self, task: DTDTask, tile: DTDTile, acc: int,
                   flow_index: int, remote: bool, distributed: bool) -> None:
        if acc & NOTRACK:
            # untracked access: no chaining, no version bump, no comm
            # bookkeeping, no audit entry — and the VALUE is snapshotted NOW
            # (ref: insert_function.c:3038 captures tile->data_copy at insert
            # time): an untracked flow has no ordering edges, so resolving
            # newest_copy at execution would let the body observe a tracked
            # write that landed after this insertion
            copy = tile.data.newest_copy()
            if copy is not None:
                if task.pending_inputs is None:
                    task.pending_inputs = {}
                task.pending_inputs[flow_index] = copy.payload
            return
        my = self.ctx.my_rank
        preds: List[DTDTask] = []
        with tile.lock:
            read_version = tile.wcount
            src_rank = tile.writer_rank
            # the producer of read_version — captured BEFORE the write side
            # below replaces last_writer (the consumer must attach its send
            # to the task that PRODUCES the version it reads, not to itself)
            prev_writer = tile.last_writer
            if acc & READ or not (acc & WRITE):
                # RAW: predecessor is the last writer (local chain) or a
                # remote version expectation / outbound send
                if tile.last_writer is not None and \
                        (not distributed or tile.last_writer.rank == my):
                    preds.append(tile.last_writer)
                if not remote:
                    readers = tile.readers
                    if len(readers) >= tile.compact_at:
                        # amortized compaction: completed readers are
                        # already-satisfied WAR predecessors — pruning them
                        # keeps long read-chains (and the live object
                        # graph) from growing unboundedly between writes.
                        # The watermark doubles past the survivors so a
                        # burst of never-retiring readers costs O(n log n)
                        # total, not a full rescan per insert
                        live = [r for r in readers if not r.completed]
                        live.append(task)
                        tile.readers = live
                        tile.compact_at = max(32, 2 * len(live))
                    else:
                        readers.append(task)
            if acc & WRITE:
                # WAR: wait on local readers since the previous write; WAW on
                # the local last writer (remote ones are covered by the
                # version expectation on the READ side of RW, or need no
                # local ordering at all)
                for r in tile.readers:
                    if not distributed or r.rank == my:
                        preds.append(r)
                if tile.last_writer is not None and \
                        (not distributed or tile.last_writer.rank == my) and \
                        tile.last_writer not in preds:
                    preds.append(tile.last_writer)
                tile.last_writer = task
                tile.readers = []
                tile.compact_at = 32
                tile.wcount += 1
                tile.last_writer_version = tile.wcount
                tile.writer_rank = task.rank
        if self._audit and not tile.new_tile:
            # deterministic digest of this link decision (crc32: stable
            # across processes, unlike str hash under PYTHONHASHSEED): all
            # ranks replay the same COLLECTION-BACKED inserts, so the
            # chains must agree (tile_new scratch tiles are rank-local by
            # contract and excluded). The digest item avoids a repr()
            # round-trip where the key is already bytes-able: collection
            # keys are (dc.name, data_key) with int/str/tuple-of-int parts,
            # so a %-format over the scalar fields byte-compiles the same
            # decision without building the intermediate repr string of a
            # nested tuple (the link-path profile showed repr+encode as
            # the audit branch's dominant cost)
            key = tile.key
            if type(key) is tuple and len(key) == 2 and \
                    isinstance(key[1], (int, str)):
                item = b"%s\x00%a\x00%d\x00%d\x00%d\x00%d" % (
                    key[0].encode(), key[1], acc & 0x3, read_version,
                    src_rank, task.rank)
            else:
                item = repr((key, acc & 0x3, read_version, src_rank,
                             task.rank)).encode()
            self._audit_digest = zlib.crc32(item, self._audit_digest)
            self._audit_count += 1
        if distributed:
            comm = self.ctx.comm
            needs_data = bool(acc & READ)   # pure WRITE flows ship nothing
            if not remote and needs_data and src_rank != my:
                # local consumer of a remotely-produced version
                comm.expect(self, task, tile, read_version, src_rank,
                            flow_index)
            elif remote and needs_data and src_rank == my:
                # remote consumer of a locally-held/produced version
                comm.note_send(self, tile, read_version, task.rank,
                               writer=prev_writer)
        if remote:
            return
        seen = set()
        for p in preds:
            if id(p) in seen or p is task:
                continue
            seen.add(id(p))
            with p.lock:
                if not p.completed:
                    p.successors.append(task)
                    with task.lock:
                        task.deps_remaining += 1

    def _drop_insertion_guard(self, task: DTDTask, schedule: bool) -> None:
        if task.dep_satisfied() and schedule:
            # ref: parsec_dtd_schedule_task_if_ready (insert_function.c:2963)
            self.ctx.schedule([task])

    # ------------------------------------------------------------- hooks
    def _prepare_input(self, stream, task: DTDTask) -> int:
        if task.data is None:     # lazy_data: first touch allocates
            from ..core.task import TaskData
            task.data = [TaskData()
                         for _ in range(task.task_class.nb_flows)]
        pending = task.pending_inputs
        batch_on = self._batch_on
        for i, tile in enumerate(task.tiles):
            pend = pending.pop(i, None) if pending else None
            if pend is None and batch_on and tile.nid is not None:
                # batch-lane coherence: in-flight batched writes live in
                # the engine slot, not yet in tile.data (synced at wait)
                p = self._neng.slot_get(tile.nid)
                copy = tile.data.newest_copy()
                if p is not None and (copy is None or p is not copy.payload):
                    pend = p
            if pend is not None:
                # remote exact-version payload (may differ from newest_copy
                # when versions raced in through the network out of order);
                # an unattached copy: carries the right Data for write-back
                # without perturbing newest_copy resolution
                from ..data.data import DataCopy
                task.data[i].data_in = DataCopy(tile.data, 0, pend)
                continue
            copy = tile.data.newest_copy()
            if copy is None:
                output.fatal(f"tile {tile!r} has no valid copy for {task!r}")
            task.data[i].data_in = copy
        return HOOK_DONE

    def _gather_args(self, task: DTDTask, flow_payloads: Sequence[Any]) -> List[Any]:
        vals = []
        for kind, v in task.arg_spec:
            if kind == "flow":
                vals.append(flow_payloads[v])
            else:
                vals.append(v)
        return vals

    def _apply_outputs(self, task: DTDTask, outs) -> List[Any]:
        if outs is None:
            outs = ()
        elif not isinstance(outs, (tuple, list)):
            outs = (outs,)
        return list(outs)

    def _jittable(self, task: DTDTask) -> bool:
        if not task.task_class.jit_ok:
            return False
        return all(kind != "value" or isinstance(v, (int, float, np.number, np.ndarray))
                   for kind, v in task.arg_spec)

    def _cpu_hook(self, stream, task: DTDTask) -> int:
        tc: DTDTaskClass = task.task_class
        payloads = [s.data_in.payload if s.data_in is not None else None
                    for s in task.data]
        vals = self._gather_args(task, payloads)
        # jit the body on the host backend too: eager per-op dispatch is the
        # dominant cost for jax-expressed bodies (compiled once per class)
        if self._jittable(task):
            fn = tc.jitted()
            cpu = _host_device()
            import jax
            conv = []
            for v in vals:
                if isinstance(v, (int, float)):
                    v = np.asarray(v)
                elif cpu is not None and isinstance(v, np.ndarray):
                    v = jax.device_put(v, cpu)
                conv.append(v)
            # persist converted flow payloads on their copies: each tile
            # crosses into the backend ONCE per DAG instead of on every
            # consuming task (the dominant re-copy cost for READ panels).
            # Only when the conversion is lossless — device_put canonicalizes
            # 64-bit dtypes under default x64-disabled jax, and that must
            # stay confined to the jitted computation, not the stored copy
            for (kind, fi), cv in zip(task.arg_spec, conv):
                if kind == "flow":
                    slot = task.data[fi]
                    if slot.data_in is not None and \
                            isinstance(slot.data_in.payload, np.ndarray) and \
                            getattr(cv, "dtype", None) == slot.data_in.payload.dtype:
                        slot.data_in.payload = cv
            if cpu is not None:
                with jax.default_device(cpu):
                    outs = self._apply_outputs(task, fn(*conv))
            else:
                outs = self._apply_outputs(task, fn(*conv))
        else:
            outs = self._apply_outputs(task, tc.fn(*vals))
        oi = 0
        for i, acc in enumerate(tc.flow_accesses):
            if acc & WRITE:
                tile = task.tiles[i]
                new = outs[oi] if oi < len(outs) else payloads[i]
                oi += 1
                copy = task.data[i].data_in
                host = tile.data.get_copy(0)
                if host is None:
                    host = tile.data.create_copy(0, new, COHERENCY_OWNED)
                else:
                    host.payload = new
                tile.data.bump_version(0)
                if self._batch_on and tile.nid is not None:
                    # keep the engine slot coherent for batched readers
                    # (no batch-write count: version bumped above)
                    self._neng.slot_set(tile.nid, new)
                task.data[i].data_out = host
        return HOOK_DONE

    def _tpu_hook(self, stream, task: "DTDTask") -> int:
        """TPU chore: enqueue on the selected device, with the group hook of
        every jittable task (plays the generated GPU hook role,
        jdf2c.c:6613). Whether tasks are grouped is the manager's call."""
        from ..device.tpu import TPUTask, _run_inline
        dev = task.selected_device
        if dev is None or not isinstance(dev, TPUDevice):
            return _run_inline(stream, task, self._tpu_submit)
        tc: DTDTaskClass = task.task_class
        jittable = self._jittable(task)
        groups = jittable and tc.groups is not False
        gt = TPUTask(task, self._tpu_submit,
                     batchable=tc.batchable and groups,
                     batch_submit=self._tpu_batch_submit if groups else None)
        return dev.kernel_scheduler(stream, task, tpu_task=gt)

    def _may_group(self, key: Tuple) -> bool:
        """May this pool issue the (body, operand signature) ``key`` in
        groups? The ladder of group programs is compiled in the first pool
        that runs the key on the device, at its first group, or not at all
        in this process: a pool that comes later finds the programs there
        or issues the class a program a task, so nothing is first built
        inside a later pool (a later solve of a benchmark, a later request
        of a server) whatever the timing of the first."""
        state = _ladders.setdefault(key, weakref.ref(self))
        return state is True or state() is self

    def _tpu_batch_submit(self, device: TPUDevice, tasks: List["DTDTask"],
                          inputs_list: List[List[Any]]):
        """One flat program over a group of independent tasks of one class
        (mutually independent by construction: only dependency-free tasks
        sit in the device queue). A group whose members differ in operand
        shapes raises, and the manager issues its tasks one by one."""
        fn = tasks[0].task_class.fn
        flat: List[Any] = []
        for t, inp in zip(tasks, inputs_list):
            flat += [np.asarray(v) if isinstance(v, (int, float)) else v
                     for v in self._gather_args(t, inp)]
        a = len(flat) // len(tasks)
        sigs = _signature(flat)
        if any(sigs[i] != sigs[i % a] for i in range(a, len(flat))):
            raise ValueError(f"ragged group of {tasks[0].task_class.name}")
        sig = sigs[:a]
        if _ladders.get((fn, sig)) is not True:
            if not self._may_group((fn, sig)):
                tasks[0].task_class.groups = False
                raise ValueError(f"no group programs of "
                                 f"{tasks[0].task_class.name}: the first "
                                 f"pool that ran it built none")
            # the class's first group: build every size a later group may
            # come in, on this group's first operands, so that which
            # programs exist never depends on the timing of a run
            for k in device.group_sizes():
                _grouped(fn, k)(*flat[:a] * k)
            _ladders[(fn, sig)] = True
        outs = _grouped(fn, len(tasks))(*flat)
        return [tuple(self._apply_outputs(t, o)) for t, o in zip(tasks, outs)]

    def _tpu_submit(self, device: TPUDevice, task: DTDTask, inputs: List[Any]):
        """TPU chore body: call the jitted class function on device arrays.

        Non-jittable bodies (non-numeric by-value args) fall back to eager;
        JAX still dispatches the ops asynchronously.
        """
        tc: DTDTaskClass = task.task_class
        vals = self._gather_args(task, inputs)
        jittable = self._jittable(task)
        fn = tc.jitted() if jittable else tc.fn
        if jittable:
            vals = [np.asarray(v) if isinstance(v, (int, float)) else v
                    for v in vals]
            if tc.groups is None:   # the class's first program in this pool
                tc.groups = self._may_group((tc.fn, _signature(vals)))
        outs = self._apply_outputs(task, fn(*vals))
        # order outputs by WRITE flows (contract shared with device epilog)
        return tuple(outs)

    def _complete_execution(self, stream, task: DTDTask) -> int:
        with self._exec_lock:
            self._executed += 1
        if task.nid >= 0 and self.ctx.pins.enabled:
            # instrumentation mirror: the native engine owns the successor
            # lists, but PINS consumers (the DOT grapher) read
            # task.successors at RELEASE_DEPS_BEGIN — which fires after
            # this hook and before _release_deps moves the engine's list.
            # Only per-task-lane successors have Python task objects;
            # batch-lane ids stay engine-internal
            ntasks = self.ctx._dtd_ntasks
            task.successors = [ntasks[s]
                               for s in self._neng.successors(task.nid)
                               if s in ntasks]
        return HOOK_DONE

    @property
    def executed(self) -> int:
        return self._executed

    def _release_deps(self, stream, task: DTDTask) -> None:
        """DTD successor release (ref: parsec_dtd_ordering_correctly,
        insert_function_internal.h:277): flip completed, wake successors."""
        if task.nid >= 0:
            # native fast lane: the successor walk + newly-ready collection
            # is one C-extension call (no per-successor locks — the GIL
            # already serializes engine access)
            task.completed = True
            ready_ids = self._neng.complete(task.nid)
            self.ctx._dtd_ntasks.pop(task.nid, None)
            task.tiles = ()
            task.arg_spec = ()
            task.data = ()
            task.pending_inputs = None
            task.successors = None   # drop the instrumentation mirror
            if ready_ids:
                self._schedule_native_ready(ready_ids, stream)
            return
        with task.lock:
            task.completed = True
            succs = task.successors
            task.successors = []
        # ship remote sends FIRST: the payload references must be captured
        # before any released successor can rebind the tile's host copy
        if self.ctx.comm is not None:
            self.ctx.comm.dtd_task_completed(self, task)
        # retire the task's object graph (the mempool-return moment of
        # parsec_dtd_release_task): dropping the tile/copy references here
        # lets refcounting reclaim payload buffers immediately and keeps
        # the completed shell acyclic, so deferred GC at quiescence walks
        # shells, not the whole DAG
        task.tiles = ()
        task.arg_spec = ()
        task.data = ()
        task.pending_inputs = None
        ready = [s for s in succs if s.dep_satisfied()]
        if ready:
            self.ctx.schedule(ready, stream)

    # ------------------------------------------------------------- flush/wait
    def data_flush(self, tile: DTDTile) -> None:
        """parsec_dtd_data_flush (ref: parsec_dtd_data_flush.c): insert a task
        that writes the tile's newest version back home (host copy of the
        owner)."""
        self.insert_task(_flush_body, (tile, RW), name="dtd_flush", jit=False)

    def data_flush_all(self, dc: DataCollection) -> None:
        """parsec_dtd_data_flush_all: flush every tile of ``dc`` seen so far."""
        with self._tiles_lock:
            tiles = [t for t in self._touched_tiles if t.dc is dc]
        for t in tiles:
            self.data_flush(t)

    def wait_mesh(self, mesh, axis_names=None) -> bool:
        """Capture-mode only: execute the recorded DAG as ONE GSPMD program
        over ``mesh`` — collection tiles become slices of sharded global
        arrays, XLA partitions the work and inserts the ICI transfers
        (see dsl/capture.py:execute_mesh)."""
        if self._capture is None:
            output.fatal("wait_mesh requires DTDTaskpool(capture=True)")
        self._capture.execute_mesh(mesh, axis_names)
        return True

    def wait(self, timeout: Optional[float] = None) -> bool:
        """parsec_dtd_taskpool_wait: drain everything this rank executes."""
        if self._capture is not None:
            if not self._capture_deferred:
                self._capture.execute()
                return True
            # deferred window: the region's tasks went through the
            # scheduler — drain them like an uncaptured pool, then re-arm
            # capture for the next window
            self._capture_deferred = False
        if self._audit and self.ctx.comm is not None and self.ctx.nb_ranks > 1:
            # replay audit BEFORE blocking on completion: a divergent insert
            # sequence surfaces as a fatal here instead of a silent hang
            self.ctx.comm.audit_check(self, self._audit_digest,
                                      self._audit_count)
        self._flush_ready()
        self.ctx.start()
        target = self.local_inserted
        self.ctx._progress_loop(self.ctx.streams[0],
                                until=lambda: self.executed >= target and
                                self.nb_tasks == 0,
                                timeout=timeout)
        done = self.executed >= target
        if done:
            # slot-ownership hand-off: batched writes land back in
            # tile.data now that the pool is drained
            self._sync_slots()
        return done

    def close(self) -> None:
        """End of insertion: drop the open action so termination can fire."""
        self._fast = None     # closed pools must fatal via the slow path
        if self._capture is not None and self._capture.ops:
            # scheduler-mode inserts execute without an explicit wait();
            # captured ops must not be silently dropped on close
            self._capture.execute()
        self._flush_ready()
        if self._neng is not None:
            self.ctx.unregister_drain_hook(self._flush_ready)
        if self._open:
            self._open = False
            self.addto_nb_pending_actions(-1)

    def __enter__(self) -> "DTDTaskpool":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if exc_type is None:
            self.wait()
        self.close()
