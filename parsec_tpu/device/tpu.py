"""TPU device module: async kernel dispatch, HBM tile heap, stage in/out.

This module stands where parsec/mca/device/cuda + the generic GPU runtime
(parsec/mca/device/device_gpu.c) stand in the reference, re-designed for the
XLA/PJRT execution model:

* ``kernel_scheduler`` mirrors parsec_device_kernel_scheduler
  (device_gpu.c:3376): the calling worker enqueues and returns ``HOOK_ASYNC``;
  whichever thread wins the manager try-lock drives the device (the CAS
  owner/manager model of device_gpu.c:3398-3424).
* The push/exec/pop pipeline (streams[0]=H2D, [1]=D2H, [2+]=exec,
  device_gpu.c:3438-3515) collapses naturally: JAX dispatch is asynchronous
  and XLA orders transfers and compute on the device's streams, so the
  manager's job is issuing work early and polling completion *events* — here
  ``jax.Array.is_ready()`` plays cudaEventQuery
  (ref: parsec_device_progress_stream, device_gpu.c:2593).
* Stage-in re-creates parsec_device_data_stage_in (device_gpu.c:1800):
  version-checked transfer from the newest copy (host numpy or another
  device's jax.Array) via ``jax.device_put``.
* The HBM tile heap re-creates the LRU zone-malloc management
  (parsec_device_data_reserve_space, device_gpu.c:1210): resident copies are
  tracked in an LRU; exceeding the byte budget evicts clean (non-owned) copies
  first, then writes back owned ones (the w2r task role, transfer_gpu.c).
* Task batching (parsec_gpu_task_collect_batch, device_gpu.c:2229,
  docs/doxygen/task-batching.md): pending tasks of one class are handed to
  the class's group hook as ONE program when the class opted in
  (``batchable``) or when the manager observes that the host, not the chip,
  paces the class (:meth:`TPUDevice._observe`).
"""

from __future__ import annotations

import collections
import itertools
import os
import threading
import weakref
from typing import Any, Callable, Deque, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..core.task import (DEV_TPU, FLOW_ACCESS_CTL, FLOW_ACCESS_WRITE,
                         HOOK_ASYNC, HOOK_DONE, Task)
from ..data.data import COHERENCY_INVALID, COHERENCY_OWNED, COHERENCY_SHARED, Data, DataCopy
from ..utils import mca, output
from ..utils.xla_trace import (DEV_CALL, DEV_GATHER, DEV_POLL, DEV_RETIRE,
                               DEV_STAGE_IN, DEV_SUBMIT,
                               DEV_WRITEBACK, PTDEV_ROOM)
from .device import DeviceModule

#: the sizes a multi-task program comes in (capped by
#: ``device_tpu_batch_max``): a pending run of 7 goes as 4 + 2 + 1, so a
#: class compiles at most these programs whatever the timing of a run
GROUP_LADDER = (16, 8, 4, 2)
#: programs of a class found complete one host cycle after their submit, in
#: a row, before the class is taken as paced by the host. On the v5e a
#: program shows complete ~0.4 ms after its call returns however short it
#: is, about the host's cycle per task, so two in three singles pass and a
#: longer streak would rarely form; a program behind a backlog never passes
PACED_STREAK = 4
#: dirty copies at the LRU's end whose D2H is under way before they are
#: evicted (:meth:`TPUDevice._fetch_ahead_locked`): a burst of evictions (a
#: DTD window refilling: ~57 k-chains' C tiles in the out-of-core GEMM) then
#: finds its write-backs on the host already; 1 GiB of host memory at 16 MiB
WRITEBACK_AHEAD = 64
#: the table keys of :meth:`TPUDevice.lane_reserve`: the top bit set, which
#: no residency key (an object's address) has, and a count of their own
_RESERVED = 1 << 63
_reservations = itertools.count(1)

mca.register("device_tpu_max_bytes", 0,
             "HBM tile-heap budget in bytes (0 = 75% of the device's "
             "reported bytes_limit)", type=int)
mca.register("device_tpu_max_inflight", 64,
             "Max concurrently dispatched device tasks", type=int)
mca.register("device_tpu_batch_max", 16,
             "Max tasks of one class issued as one program", type=int)
mca.register("device_tpu_over_cpu", False,
             "TEST MODE: register the device module over a host jax device",
             type=bool)
mca.register("device_tpu_over_cpu_index", 0,
             "TEST MODE: which host jax device to register over (lets each "
             "in-process rank bind a distinct virtual device)", type=int)


class NoRoom(RuntimeError):
    """A lane stage-in for which the budget has no room without evicting a
    pinned copy: what the copies pinned now hold and the operand's bytes
    pass the budget (:meth:`TPUDevice._stage_in_decide`)."""


class TPUTask:
    """Device-side task descriptor (ref: parsec_gpu_task_t, device_gpu.h:117-155)."""

    __slots__ = ("task", "submit", "stage_in", "stage_out", "pushout",
                 "batchable", "batch_submit", "load", "out_arrays",
                 "complete_cb", "oom_retries", "pinned", "issued")

    def __init__(self, task: Task, submit: Callable, stage_in=None,
                 stage_out=None, pushout: int = 0, batchable: bool = False,
                 batch_submit: Optional[Callable] = None) -> None:
        self.task = task
        self.submit = submit          # submit(device, task, inputs)->outputs
        self.stage_in = stage_in      # optional override (ref: custom stage, stage_custom.jdf)
        self.stage_out = stage_out
        self.pushout = pushout        # bitmask of flows to push back to host now
        #: the class opted in: grouped whatever the manager observes
        self.batchable = batchable
        #: batch_submit(device, tasks, inputs_list) -> list of output tuples,
        #: ONE program for the whole group; None = never grouped
        #: (ref: parsec_gpu_task_collect_batch, device_gpu.c:2229)
        self.batch_submit = batch_submit
        #: the manager pass that issued the program this task leads,
        #: until the program is judged (:meth:`TPUDevice._observe`)
        self.issued = 0
        self.load = 0.0
        self.out_arrays: Optional[Sequence[Any]] = None
        self.complete_cb: Optional[Callable] = None
        self.oom_retries = 0
        #: device copies whose ``readers`` count this inflight task holds
        #: (pinned against eviction between stage-in and epilog, ref:
        #: the readers guard of parsec_device_data_stage_in/epilog,
        #: device_gpu.c:1210,1800)
        self.pinned: List[Any] = []


class TPUDevice(DeviceModule):
    """One TPU chip as a PaRSEC-style device module."""

    def __init__(self, jax_device) -> None:
        super().__init__(f"tpu({jax_device.id})", DEV_TPU)
        self.jax_device = jax_device
        import jax
        self._jax = jax
        # crude per-chip speed for ETA selection; real estimates come from
        # task-class time_estimate properties
        self.gflops = 100_000.0
        self._pending: Deque[TPUTask] = collections.deque()
        #: issued programs, oldest first: the tasks each one carries
        self._inflight: Deque[List[TPUTask]] = collections.deque()
        self._inflight_tasks = 0
        self._pass = 0              # manager passes, for _observe
        #: the last pass whose issue phase evicted: no program issued up to
        #: it is judged complete (:meth:`_observe`)
        self._evicted_pass = 0
        self._manager_lock = threading.Lock()  # the CAS mutex (device_gpu.c:3408)
        self._fifo_lock = threading.Lock()
        #: task class -> its programs judged complete in a row
        #: (:meth:`_observe`); weak, so a closed pool's classes leave
        self._paced: "weakref.WeakKeyDictionary[Any, int]" = \
            weakref.WeakKeyDictionary()
        # LRU tile heap bookkeeping (ref: gpu_mem_lru / gpu_mem_owned_lru)
        self._prof_stream = None
        self._prof_keys = None
        #: the context's span object (utils/xla_trace.py Spans): None when
        #: off, so every site is one attribute load and one branch
        self._spans = None
        self._retired_ns = 0        # dev.retire total, for dev.poll to subtract
        self._lru: "collections.OrderedDict[Any, DataCopy]" = collections.OrderedDict()
        self._lru_sizes: Dict[Any, int] = {}   # accounted bytes per key
        self._lru_segs: Dict[Any, Any] = {}    # key -> pt_zone segment
        self._resident_bytes = 0
        #: bytes of the resident copies that some reader pins (``readers``
        #: above 0): what no victim walk may free, so the budget less this
        #: is the room a new pin can still have (:meth:`lane_room`)
        self._pinned_bytes = 0
        self.evictions = 0          # copies evicted (budget pressure stat)
        #: of those, copies that left in OWNED state: the device had written
        #: them since they were staged, so each owes the host a write-back
        self.owned_evictions = 0
        #: residency key -> id of the device array whose D2H was started
        #: ahead of its eviction (an id only: no array is kept alive)
        self._fetching: Dict[Any, int] = {}
        self.pinned_skips = 0       # eviction walks that skipped a pinned copy
        self._budget = mca.get("device_tpu_max_bytes", 0) or \
            int(_device_bytes_limit(jax_device) * 0.75)
        # the device heap ledger: every resident tile owns a pt_zone segment
        # (offset + size), so occupancy/fragmentation are first-class stats
        # (ref: the GPU zone_malloc heap, parsec/utils/zone_malloc.c; native
        # allocator: native/src/ptcore.cpp pt_zone) — XLA still owns the
        # physical bytes, the zone is the runtime's own accounting
        from ..utils.zone_malloc import ZoneMalloc
        # 64KB units keep the ledger granularity close to the byte-exact
        # eviction accounting even for small tiles (a 1MB default unit would
        # fill the zone ~100x faster than _resident_bytes and desync them)
        self._zone = ZoneMalloc(self._budget, unit=65536)
        # the NATIVE coherency/residency table (ISSUE 10): when _ptdev is
        # available, C owns residency and eviction POLICY — the LRU order,
        # the byte budget, the stage-in version check, victim selection —
        # while Python keeps owning the payloads, the write-back mechanism
        # and the `_lru`/`_zone` mirror the tests inspect. One authority
        # instead of the two unsynchronized views (this LRU vs data.py
        # coherency) that the eviction/reader race grew from.
        from .native import make_coh_table
        self._ncoh = make_coh_table(self._budget)
        # serializes the Python residency MIRROR (_lru/_lru_sizes/_zone/
        # _resident_bytes): the interpreted path mutates it from worker
        # threads (under _manager_lock) while the ptdev manager thread
        # mutates it from lane stage-ins — compound updates like the
        # resident-bytes delta are not GIL-atomic across both
        self._heap_lock = threading.RLock()

    def attach(self, context) -> None:
        super().attach(context)
        self._spans = context._spans

    # ------------------------------------------------- native coherency map
    @staticmethod
    def res_key(data: Data) -> int:
        """The canonical residency key for BOTH the Python LRU mirror and
        the C coherency table. ``data.key`` is only unique per collection
        (A(0,0)/B(0,0)/C(0,0) all carry key 0 — the aliasing the table
        exposed), so the Data object's identity is the key: a resident
        entry's DataCopy pins its Data, so the id cannot be reused while
        the entry lives; a dead Data's stale table entry can only cause a
        spurious re-stage (version mismatch), never a wrong hit on a live
        payload."""
        return id(data)

    def _coh_pin(self, data: Data) -> None:
        if self._ncoh is not None and data is not None:
            self._ncoh.pin(self.res_key(data))

    def _coh_unpin(self, data: Data) -> None:
        if self._ncoh is not None and data is not None:
            self._ncoh.unpin(self.res_key(data))

    def _coh_mark_owned(self, data: Data, copy: DataCopy) -> None:
        """Writer completed on this device: the table's entry becomes the
        OWNER at the new version (the epilog bump); growth past the
        budget returns eviction victims to apply."""
        if self._ncoh is None:
            return
        victims = self._ncoh.mark_owned(self.res_key(data),
                                        data.version & 0xFFFFFFFF,
                                        _nbytes(copy.payload))
        if victims:
            self._apply_victims(victims)

    def _apply_victims(self, victims) -> None:
        """Commit the C table's eviction decisions: write back +
        invalidate each victim ATOMICALLY with its version check
        (Data.evict_copy), then update the Python mirror (sizes, zone
        ledger, counters). Policy came from C; this is pure mechanism."""
        with self._heap_lock:
            self._apply_victims_locked(victims)

    def _apply_victims_locked(self, victims) -> None:
        for key, _owned in victims:
            copy = self._lru.get(key)
            if copy is None:
                continue
            if copy.readers > 0:
                # a Python-side pin the table could not see (a custom
                # stage hook pins only after its stage-in returns): veto
                # the eviction — the table already dropped its entry, so
                # the next stage-in simply re-reserves, and the inflight
                # reader keeps its payload
                self.pinned_skips += 1
                continue
            self._lru.pop(key)
            self._evict_key_locked(key, copy, drop_table=False)

    def _evict_key_locked(self, key: Any, copy: DataCopy,
                          drop_table: bool) -> None:
        """The ONE eviction mechanism (heap lock held, `key` already out
        of ``_lru``): mirror bookkeeping, the atomic write-back +
        invalidate (Data.evict_copy), and the counters. ``drop_table``
        removes the C entry too (the Python-LRU fallback path decided the
        victim itself; C-decided victims already left the table)."""
        freed = self._lru_sizes.pop(key, 0)
        self._resident_bytes -= freed
        seg = self._lru_segs.pop(key, None)
        if seg is not None:
            seg.free()
        data = copy.original
        wrote = False
        if copy.coherency_state == COHERENCY_OWNED:
            self.owned_evictions += 1
        self._fetching.pop(key, None)
        self._fetch_ahead_locked()  # before this victim's own fetch blocks
        if data is not None:
            _evicted, wrote = data.evict_copy(self.device_index,
                                              self._write_back)
        else:
            copy.coherency_state = COHERENCY_INVALID
            copy.payload = None
        if wrote:
            self.transfer_out_bytes += freed
            if self._ncoh is not None:
                self._ncoh.count_writeback(freed)
        if drop_table and self._ncoh is not None:
            self._ncoh.drop(key)
        self.evictions += 1
        self._trace_mem(-freed)

    def _fetch_ahead_locked(self) -> None:
        """Start the D2H of the dirty copies next in line for eviction
        (heap lock held): where one copy leaves, more follow, and a
        write-back begun only at its eviction is milliseconds of the
        manager's own time (2.3 ms for 16 MiB on the v5e) with the chip's
        queue running dry behind it. The fetch is the array's own
        (``copy_to_host_async``: it waits for the producing program on the
        device's transfer engine, not on this thread); the eviction stays
        the one mechanism and finds the bytes on the host. A copy touched
        again before it leaves costs one wasted transfer; its array is
        replaced by the write that touches it, and the fetched bytes go
        with it."""
        ahead, fetching = 0, self._fetching
        for key, copy in itertools.islice(self._lru.items(),
                                          4 * WRITEBACK_AHEAD):
            if copy.coherency_state != COHERENCY_OWNED or copy.readers > 0:
                continue
            arr = copy.payload
            if fetching.get(key) != id(arr):
                fetching[key] = id(arr)
                start = getattr(arr, "copy_to_host_async", None)
                if start is not None:
                    start()
            ahead += 1
            if ahead == WRITEBACK_AHEAD:
                break

    def _write_back(self, payload: Any) -> np.ndarray:
        """The D2H of an eviction's dirty branch (``Data.evict_copy`` calls
        it under the data's lock): the fetch blocks until the bytes are on
        the host (at once, where :meth:`_fetch_ahead_locked` started it in
        time), so the span holds what the manager waited, one record a
        tile."""
        sp = self._spans
        if sp is not None:
            tok = sp.begin(DEV_WRITEBACK)
        try:
            return np.asarray(payload)
        finally:
            if sp is not None:
                sp.end(tok, sp.writeback)

    def coh_stats(self) -> Optional[Dict[str, int]]:
        """The native coherency/residency counters, or None when the
        table is unavailable (Python-LRU fallback mode)."""
        return None if self._ncoh is None else self._ncoh.stats()

    # ------------------------------------------------------------- dispatch API
    def kernel_scheduler(self, stream, task: Task, tpu_task: Optional[TPUTask] = None,
                         submit: Optional[Callable] = None) -> int:
        """Enqueue a device task; ref: parsec_device_kernel_scheduler
        (device_gpu.c:3376). Returns HOOK_ASYNC immediately."""
        if tpu_task is None:
            tpu_task = TPUTask(task, submit)
        tpu_task.load = self.time_estimate(task)
        self.load_add(tpu_task.load)
        with self._fifo_lock:
            self._pending.append(tpu_task)
        # a task that waits for companions stays in _pending: the progress
        # loop this thread runs polls the device after its burst of hooks
        # and issues what the burst enqueued, grouped. Anyone else
        # opportunistically becomes the manager right away
        if not (self._groups(tpu_task) and self.context.in_progress_loop()):
            self.progress(stream)
        return HOOK_ASYNC

    # ------------------------------------------------------------- batching
    def _groups(self, gt: TPUTask) -> bool:
        """Is ``gt`` issued with companions of its class? The one batching
        policy: the class opted in, or the host paces it."""
        return gt.batch_submit is not None and (
            gt.batchable or
            self._paced.get(gt.task.task_class, 0) >= PACED_STREAK)

    def _observe(self, gt: TPUTask, complete: bool) -> None:
        """Judge a program once, at the poll of the first later pass that
        issued something: the host has come round with the next program.
        Complete by then: the program was shorter than the host's cycle,
        the chip sat idle through the call just made, and a call saved is
        time saved; a streak of those and the class is issued in groups.
        Not complete: the chip has work queued, waiting for companions
        would only delay it, and the class goes back to a program a task.
        A program found complete after a host cycle that evicted (in its
        own pass or the judging one) is not judged: the cycle was long by
        the victim walk and a write-back of milliseconds, which no saved
        call shortens, and a group would pin its members' operands against
        the evictor all at once; such a cycle also ends every streak (ISSUE
        35: under memory pressure a class of 0.6-ms dots was taken as paced
        at every burst of evictions, in four runs of six, and ran slower and
        less evenly in groups of 16)."""
        gt.issued = 0
        if gt.batch_submit is None:
            return
        tc = gt.task.task_class
        if complete:
            self._paced[tc] = self._paced.get(tc, 0) + 1
        elif tc in self._paced:
            del self._paced[tc]

    def group_sizes(self) -> List[int]:
        """The sizes a multi-task program may come in here, largest first."""
        cap = mca.get("device_tpu_batch_max", 16)
        return [k for k in GROUP_LADDER if k <= cap]

    def _collect(self, gt: TPUTask) -> List[TPUTask]:
        """``gt`` and the pending tasks of its class, in arrival order, up to
        the largest ladder size they fill (fifo lock held). Pending tasks
        are mutually independent (dependencies release at epilog), so
        taking a class across the pending window reorders nothing that
        matters (ref: parsec_gpu_task_collect_batch)."""
        tc, hook = gt.task.task_class, gt.batch_submit
        mates = [p for p in self._pending
                 if p.task.task_class is tc and p.batch_submit == hook]
        size = next((k for k in self.group_sizes() if k <= 1 + len(mates)), 1)
        if size == 1:
            return [gt]
        del mates[size - 1:]
        taken = set(map(id, mates))
        rest = [p for p in self._pending if id(p) not in taken]
        self._pending.clear()
        self._pending.extend(rest)
        return [gt] + mates

    # ------------------------------------------------------------- progress
    def progress(self, stream) -> int:
        """Manager drive: submit pending, poll events, run epilogs.

        Only one thread at a time is the manager (try-lock = the CAS in
        device_gpu.c:3398-3424); others return immediately after enqueueing.
        """
        if not self._pending and not self._inflight:
            # idle fast-path: this poll sits in every hot-loop iteration,
            # and CPU-chore-only workloads must not pay the manager lock +
            # MCA lookups per loop (an enqueue racing this check is picked
            # up on the very next iteration — the enqueue sets work_event)
            return 0
        if not self._manager_lock.acquire(blocking=False):
            return 0
        try:
            # kernel_push + kernel_exec phases (device_gpu.c:2746,2874)
            self._pass += 1
            issued = False
            evicted = self.evictions
            max_inflight = mca.get("device_tpu_max_inflight", 64)
            while self._inflight_tasks < max_inflight:
                with self._fifo_lock:
                    if not self._pending:
                        break
                    gt = self._pending.popleft()
                    group = self._collect(gt) if self._groups(gt) else [gt]
                if len(group) > 1:
                    programs = self._submit_group(group)
                else:
                    programs = [group] if self._submit_one_retry(gt) else []
                for program in programs:
                    program[0].issued = self._pass
                    self._inflight_tasks += len(program)
                self._inflight.extend(programs)
                issued |= bool(programs)
            if self.evictions != evicted:
                # under memory pressure every class goes a program a task
                self._evicted_pass = self._pass
                self._paced.clear()
            # event polling + kernel_pop/epilog: poll each program's events
            # independently — inflight programs are mutually independent
            # (their deps only release at epilog), so one slow kernel must
            # not head-of-line block completed peers behind it (ref:
            # per-stream event polls, device_gpu.c:2593,2944,3179). The
            # outputs of one program complete together: its first task's
            # decide for all it carries
            completed = 0
            still: Deque[List[TPUTask]] = collections.deque()
            sp = self._spans if self._inflight else None
            if sp is not None:
                # one record per pass that polls, the epilogs' own spans
                # (nested inside on the timeline) subtracted
                tok = sp.begin(DEV_POLL)
                retired0 = self._retired_ns
            while self._inflight:
                program = self._inflight.popleft()
                head = program[0]
                done = not head.out_arrays or \
                    all(a.is_ready() for a in head.out_arrays)
                if issued and head.issued and \
                        (done or head.issued != self._pass):
                    if head.issued > self._evicted_pass or not done:
                        self._observe(head, done)
                    else:
                        # the host's cycle since the program's call held an
                        # eviction: complete by now says nothing of calls
                        head.issued = 0
                if not done:
                    still.append(program)
                    continue
                for gt in program:
                    self._epilog(stream, gt)
                self._inflight_tasks -= len(program)
                completed += len(program)
            self._inflight = still
            if sp is not None:
                sp.end(tok, sp.poll, less=self._retired_ns - retired0)
            return completed
        finally:
            self._manager_lock.release()

    # ------------------------------------------------------------- internals
    def _stage_in_copy(self, data: Data, access: int,
                       pin: bool = False) -> DataCopy:
        """Version-checked stage-in of one datum (ref:
        parsec_device_data_stage_in device_gpu.c:1800): the decision
        (:meth:`_stage_in_decide`), then the transfer if it said so
        (:meth:`_transfer`, a list of one). Returns the device-resident
        copy, pinned when ``pin`` — release with :meth:`unpin_copy`."""
        copy, src = self._stage_in_decide(data, pin)
        if src is None:
            return copy
        return self._transfer([(data, copy, src)], pin)[0][0]

    def _stage_in_decide(self, data: Data, pin: bool,
                         room: Optional[List[int]] = None
                         ) -> Tuple[Optional[DataCopy], Optional[DataCopy]]:
        """One operand's residency decision, and everything about it that
        moves no byte. With the native table up the decision is C's — is a
        copy of exactly this version resident, and which victims must
        leave to make room (CohTable.stage_in issues the early reserve of
        the push stage) — and the victims are applied here, a dirty one
        written back, before the caller transfers anything. ``pin=True``
        takes the eviction pin INSIDE the table's reserve critical section
        (a concurrent stage-in on another thread could otherwise evict
        this entry between the reserve and the caller's pin).

        ``room`` is the lane's (:meth:`lane_stage_in_batch`): a miss whose
        bytes, beside what is pinned now, pass the budget has no room
        short of a pinned victim, and the decision says so (:class:`NoRoom`,
        before the table is asked) where the DTD manager's would run the
        table over its budget; with the spans on, a miss's reservation (the
        table's victim choice, the victims applied, a dirty one's
        write-back) is the ``ptdev.room`` span, its nanoseconds added to
        ``room[0]``.

        Returns ``(copy, None)`` where the operand is resident as it
        stands, the Python reader count bumped to match the table's pin: a
        hit (LRU touched), or newest bytes that already live on this
        device, adopted. Else ``(copy, newest)``: the device copy to
        refill (or None) and the copy that holds the bytes to transfer,
        for :meth:`_install` to finish."""
        copy = data.get_copy(self.device_index)
        newest = data.newest_copy()
        if newest is None:
            raise RuntimeError(f"no valid copy to stage in for {data!r}")
        hit = copy is not None and copy.version == newest.version and \
            copy.coherency_state != COHERENCY_INVALID
        if self._ncoh is not None:
            nbytes, tok = _nbytes(newest.payload), None
            if room is not None and not hit:
                if (copy is None or not copy.readers) and \
                        self._pinned_bytes + nbytes > self._budget:
                    raise NoRoom(
                        f"no room for {data!r} ({nbytes} bytes) beside the "
                        f"{self._pinned_bytes} pinned: the budget is "
                        f"{self._budget}")
                sp = self._spans
                if sp is not None:
                    tok = sp.begin(PTDEV_ROOM)
            try:
                need, victims = self._ncoh.stage_in(
                    self.res_key(data), nbytes,
                    newest.version & 0xFFFFFFFF, 0, 1 if pin else 0)
                if victims:
                    self._apply_victims(victims)
            finally:
                if tok is not None:
                    room[0] += sp.end(tok, None)
            # the table said transfer, or the mirror lost the payload (the
            # stale table entry was already replaced by stage_in)
            hit = hit and not need
        if hit:
            self._lru_touch(self.res_key(data), copy)
            if pin:
                with self._heap_lock:
                    self._pin_locked(copy)  # the table half was pinned above
            return copy, None
        arr = newest.payload
        if isinstance(arr, self._jax.Array) and arr.committed and \
                arr.devices() == {self.jax_device}:
            # adoption: the newest bytes already live on this device (a
            # write-back that kept the device array as the host copy's
            # payload, a tile reset on the device), so the device copy takes
            # that array as it is: no call into the client, no byte moved.
            # The two copies then share one buffer, as device_put onto the
            # same device left them too. Safe because no program is ever
            # given such a buffer for good: a table copy is the residency
            # table's and a written-back array is its Data's; a fused PTG
            # region donates only slot values its own pool wrote and
            # nothing else reads (docs/device_lane.md, "Who owns what")
            self.adopted += 1
            return self._install(data, copy, arr, newest.version, pin,
                                 moved=False), None
        return copy, newest

    def _transfer(self, misses: List[Tuple[Data, Optional[DataCopy],
                                           DataCopy]], pin: bool
                  ) -> Tuple[List[DataCopy], int]:
        """Move the bytes of operands :meth:`_stage_in_decide` said to
        transfer, each a ``(data, device copy or None, source copy)``, in
        ONE ``device_put`` of the list (the TPU client's price is per
        call: 254 us a 1-MiB tile alone, 183 in a list) and install the
        arrays under one hold of the heap lock. Returns the device copies
        in order and the nanoseconds of the put and its installs (0 with
        the spans off), recorded as one observation a tile. If it raises,
        the pins these operands took at their decision are given back."""
        done: List[DataCopy] = []
        put_ns = 0
        sp = self._spans
        if sp is not None:
            tok = sp.begin(DEV_STAGE_IN)    # the host cost of one H2D call
        try:
            arrs = self._jax.device_put(    # async H2D/D2D
                [src.payload for _data, _copy, src in misses],
                self.jax_device)
            with self._heap_lock:
                for (data, copy, src), arr in zip(misses, arrs):
                    done.append(self._install(data, copy, arr, src.version,
                                              pin, moved=True))
        except BaseException:
            if pin:
                for copy in done:
                    self.unpin_copy(copy)
                for data, _copy, _src in misses[len(done):]:
                    self._coh_unpin(data)   # decided, never installed
            raise
        finally:
            if sp is not None:
                put_ns = sp.end(tok, sp.stage_in, n=len(misses))
        return done, put_ns

    def _install(self, data: Data, copy: Optional[DataCopy], arr: Any,
                 version: int, pin: bool, moved: bool) -> DataCopy:
        """The miss path's bookkeeping: ``arr`` becomes the device copy of
        ``data`` at ``version`` (reserve, LRU touch, the Python half of the
        pin); ``moved`` says whether a transfer brought it."""
        nbytes = _nbytes(arr)
        if self._ncoh is None:
            self._reserve(nbytes)   # native mode: stage_in reserved above
        if copy is None:
            copy = data.create_copy(self.device_index, arr, COHERENCY_SHARED)
        else:
            copy.payload = arr
            copy.coherency_state = COHERENCY_SHARED
        copy.version = version
        if moved:
            self.transfer_in_bytes += nbytes
        self._lru_touch(self.res_key(data), copy)
        if pin:
            with self._heap_lock:
                self._pin_locked(copy)  # the table half: pinned in stage_in
        return copy

    def lane_room(self) -> int:
        """Bytes a new pin can still have: the budget less what pinned
        copies hold. Where the operands a program would pin beyond those
        fit in it, every one of them has room short of a pinned victim."""
        return self._budget - self._pinned_bytes

    def lane_bytes(self, data: Data) -> int:
        """The bytes ``data`` takes here: its resident copy's, or its newest
        copy's where none is resident."""
        nbytes = self._lru_sizes.get(id(data))
        if nbytes is None:
            newest = data.newest_copy()
            nbytes = 0 if newest is None else _nbytes(newest.payload)
        return nbytes

    def lane_pin_bytes(self, data: Data) -> int:
        """What pinning ``data`` here adds to the pinned bytes: 0 where its
        copy is pinned already, else :meth:`lane_bytes`."""
        copy = data.copies.get(self.device_index)
        return 0 if copy is not None and copy.readers else \
            self.lane_bytes(data)

    def lane_reserve(self, nbytes: int) -> int:
        """Room in the table for the outputs of a lane program from its
        admission to its end: the buffers it allocates when it is called,
        beside the copies it reads, until its write-backs replace them. A
        pinned entry of its own (a key no datum has), victims applied as
        a stage-in's; among the pinned bytes. Returns the key for
        :meth:`lane_release`."""
        key = _RESERVED | next(_reservations)
        with self._heap_lock:
            if self._ncoh is not None:
                _need, victims = self._ncoh.stage_in(key, nbytes, 0, 0, 1)
                if victims:
                    self._apply_victims_locked(victims)
            self._pinned_bytes += nbytes
        return key

    def lane_release(self, key: int, nbytes: int) -> None:
        """The program of :meth:`lane_reserve`'s ``key`` has ended: its
        room goes back."""
        with self._heap_lock:
            if self._ncoh is not None:
                self._ncoh.unpin(key)
                self._ncoh.drop(key)
            self._pinned_bytes -= nbytes

    def lane_write_back(self, data: Data, arr: Any) -> None:
        """A lane program's output ``arr`` becomes ``data``'s newest
        version as this device's copy: OWNED, tracked in the table at its
        bytes, the room reserved as a DTD epilog's output is
        (:meth:`_coh_mark_owned`, which may evict). An eviction then writes
        it back to the host at its version, as it does a DTD output. A
        value that is no array of the device's client goes to the host copy
        (:meth:`Data.write_host`)."""
        if not isinstance(arr, self._jax.Array):
            data.write_host(arr)
            return
        with self._heap_lock:
            copy = data.get_copy(self.device_index)
            if copy is None:
                copy = data.create_copy(self.device_index, arr,
                                        COHERENCY_OWNED)
            else:
                copy.payload = arr
            data.bump_version(self.device_index)
            self._lru_touch_locked(self.res_key(data), copy)
            self._coh_mark_owned(data, copy)

    def lane_stage_in(self, data: Data, pin: bool = False) -> DataCopy:
        """One datum staged in through the lane's entry: version-checked
        through the C table, asynchronous, returns the device copy —
        pinned atomically with the reserve when ``pin``."""
        return self._stage_in_copy(data, 0, pin=pin)

    def lane_stage_in_batch(self, datas: Sequence[Data]
                            ) -> Tuple[List[DataCopy], int, int, int]:
        """Stage-in entry for the native device lane's dispatch callback
        (the push phase of ptdev): the distinct memory operands of a whole
        batch at once. The decision is taken key by key and in order
        (:meth:`_stage_in_decide`), each operand pinned atomically with
        its reserve and its victims applied before a byte moves, so a
        batch never evicts a tile of its own; a miss with no room short of
        a pinned victim raises :class:`NoRoom`. The misses then move in
        one transfer (:meth:`_transfer`).

        Returns the copies in order, each pinned once, the tiles that
        moved, the nanoseconds of the put and its installs and those of
        making room (both 0 with the spans off). When anything raises,
        every pin the batch took is given back."""
        copies: List[Optional[DataCopy]] = []
        misses, at = [], []
        room = [0]
        try:
            with self._heap_lock:
                for data in datas:
                    copy, src = self._stage_in_decide(data, True, room)
                    if src is not None:
                        at.append(len(copies))
                        misses.append((data, copy, src))
                        copy = None
                    copies.append(copy)
        except BaseException:
            for data, _copy, _src in misses:
                self._coh_unpin(data)
            self._unpin_all(copies)
            raise
        if not misses:
            return copies, 0, 0, room[0]
        try:
            moved, put_ns = self._transfer(misses, True)
        except BaseException:
            self._unpin_all(copies)     # the hits and the adoptions
            raise
        for i, copy in zip(at, moved):
            copies[i] = copy
        return copies, len(misses), put_ns, room[0]

    def _unpin_all(self, copies: Sequence[Optional[DataCopy]]) -> None:
        for copy in copies:
            if copy is not None:
                self.unpin_copy(copy)

    def _prof(self):
        """Per-device profiling stream (ref: per-GPU-stream profiling
        streams, profiling.h:146-440), lazily bound to ctx.profiling."""
        prof = getattr(self.context, "profiling", None)
        if prof is None:
            return None
        if getattr(self, "_prof_stream", None) is None:
            self._prof_stream = prof.stream(self.name)
            self._prof_keys = prof.add_dictionary_keyword(f"{self.name}::exec")
            # memory-ledger events (the dbp2mem surface, tools/profiling/
            # dbp2mem.c): every residency change is a POINT event carrying
            # the post-change occupancy, rendered over time by
            # parsec_tpu.tools.mem_view
            self._mem_key = prof.add_dictionary_keyword(
                f"{self.name}::mem", info_desc="resident{q};delta{q}")[0]
            self._prof_ref = prof
            self._mem_seq = 0
        return self._prof_stream

    def _trace_mem(self, delta: int) -> None:
        """Record a residency change (bytes) on the device's trace stream."""
        ps = self._prof()
        if ps is None or delta == 0:
            return
        from ..utils.trace import EVENT_FLAG_POINT
        self._mem_seq += 1
        ps.trace(self._mem_key, self._mem_seq, 0, EVENT_FLAG_POINT,
                 self._prof_ref.pack_info(f"{self.name}::mem",
                                          resident=self._resident_bytes,
                                          delta=delta))

    def _submit_one(self, gt: TPUTask) -> None:
        task = gt.task
        ps = self._prof()
        if ps is not None:
            from ..utils.trace import EVENT_FLAG_START
            ps.trace(self._prof_keys[0], hash(task.key) & 0x7FFFFFFF,
                     task.taskpool.taskpool_id, EVENT_FLAG_START)
        sp = self._spans
        if sp is not None:
            # dev.submit is dev.gather, then dev.call
            tok, sub, cell = sp.begin(DEV_SUBMIT), sp.begin(DEV_GATHER), \
                sp.gather
        try:
            inputs = self._gather_inputs(gt)
            if sp is not None:
                sp.end(sub, cell)
                sub, cell = sp.begin(DEV_CALL), sp.call
            outs = gt.submit(self, task, inputs)
        finally:
            if sp is not None:
                sp.end(sub, cell)           # a failed attempt's cost too
                sp.end(tok, sp.submit)
        if sp is not None:
            sp.ready_wait(task)     # issued: ready-wait ends, once per task
        if outs is None:
            outs = ()
        elif not isinstance(outs, (tuple, list)):
            outs = (outs,)
        gt.out_arrays = outs

    def _default_stage_in(self, data: Data, access: int) -> DataCopy:
        return self._stage_in_copy(data, access)

    def _gather_inputs(self, gt: TPUTask) -> List[Any]:
        task = gt.task
        inputs: List[Any] = []
        for flow in task.task_class.flows:
            slot = task.data[flow.flow_index]
            if flow.access & FLOW_ACCESS_CTL or slot.data_in is None:
                inputs.append(None)
                continue
            copy_in = slot.data_in
            # PTG intermediates may ride as raw arrays (no backing Data);
            # they bypass the LRU heap and just get placed on-device
            data = getattr(copy_in, "original", None)
            if data is not None:
                # pin between stage-in and epilog: the eviction walks skip
                # copies with readers > 0, so an inflight task's inputs
                # can never be evicted under it (device_gpu.c:1210). The
                # default path pins INSIDE the table's reserve critical
                # section; custom stage hooks pin right after
                if gt.stage_in is None:
                    dev_copy = self._stage_in_copy(data, flow.access,
                                                   pin=True)
                else:
                    dev_copy = gt.stage_in(data, flow.access)
                    self.pin_copy(dev_copy)
                slot.data_in = dev_copy
                gt.pinned.append(dev_copy)
                inputs.append(dev_copy.payload)
            else:
                payload = getattr(copy_in, "payload", copy_in)
                inputs.append(self._jax.device_put(payload, self.jax_device))
        return inputs

    def _unpin(self, gt: TPUTask) -> None:
        """Drop this task's reader pins (epilog or failed submit)."""
        for copy in gt.pinned:
            self.unpin_copy(copy)
        gt.pinned.clear()

    def _submit_one_retry(self, gt: TPUTask) -> bool:
        """Submit with the OOM -> evict -> retry -> HOOK_AGAIN discipline of
        device_gpu.c. Returns True when dispatched; False when the task was
        bounced back to the scheduler."""
        try:
            self._submit_one(gt)
            return True
        except Exception as e:  # noqa: BLE001
            self._unpin(gt)     # the retry re-gathers (and re-pins) inputs
            if not _is_oom(e):
                self.load_sub(gt.load)
                output.fatal(f"TPU submit failed for {gt.task!r}: {e}")
            freed = self.evict_bytes(max(self._resident_bytes // 2, 1))
            try:
                self._submit_one(gt)
                return True
            except Exception as e2:  # noqa: BLE001
                self._unpin(gt)
                if not _is_oom(e2):
                    self.load_sub(gt.load)
                    output.fatal(f"TPU submit failed for {gt.task!r}: {e2}")
                gt.oom_retries += 1
                if freed == 0 or gt.oom_retries > 8:
                    output.fatal(
                        f"task {gt.task!r} does not fit in device memory "
                        f"(resident={self._resident_bytes}, "
                        f"retries={gt.oom_retries})")
                self.load_sub(gt.load)
                self.context.schedule([gt.task])
                return False

    def _submit_group(self, group: List[TPUTask]) -> List[List[TPUTask]]:
        """One program for a group of independent tasks of one class; a
        ragged group (e.g. boundary tiles of a different shape) or an OOM
        falls back to per-task submission. Returns the programs actually
        dispatched, each as the tasks it carries."""
        sp = self._spans
        if sp is not None:
            tok, sub = sp.begin(DEV_SUBMIT), sp.begin(DEV_GATHER)
        try:
            inputs_list = [self._gather_inputs(g) for g in group]
            if sp is not None:
                gather_ns = sp.end(sub, None)
                sub = sp.begin(DEV_CALL)
            outs_list = group[0].batch_submit(self, [g.task for g in group],
                                              inputs_list)
        except Exception as e:  # noqa: BLE001 - ragged shapes, stage-in OOM
            if sp is not None:
                sp.end(sub, None)   # the per-task retries record their own
                sp.end(tok, None)
            output.debug_verbose(2, "device",
                                 f"group of {len(group)} fell back: {e}")
            # unpin EVERY member (a stage-in failure mid-gather leaves
            # earlier members pinned); per-task retries re-gather + re-pin
            for g in group:
                self._unpin(g)
            return [[g] for g in group if self._submit_one_retry(g)]
        if sp is not None:
            # one dispatch, recorded once per member at its share
            n = len(group)
            sp.end(sub, sp.call, n=n)
            sp.gather.record(gather_ns // n, n)
            sp.end(tok, sp.submit, n=n)
            sp.group_tasks.record(n)
            for g in group:
                sp.ready_wait(g.task)
        self.batched_dispatches += 1
        self.batched_tasks += len(group)
        for g, outs in zip(group, outs_list):
            if outs is None:
                outs = ()
            elif not isinstance(outs, (tuple, list)):
                outs = (outs,)
            g.out_arrays = tuple(outs)
        return [group]

    def _epilog(self, stream, gt: TPUTask) -> None:
        """parsec_device_kernel_epilog (device_gpu.c:3179): attach outputs,
        bump versions, OWNED->SHARED transitions, then complete the task."""
        task = gt.task
        tc = task.task_class
        sp = self._spans
        if sp is not None:
            tok = sp.begin(DEV_RETIRE)
        outs = list(gt.out_arrays or ())
        oi = 0
        for flow in tc.flows:
            if not (flow.access & FLOW_ACCESS_WRITE) or flow.access & FLOW_ACCESS_CTL:
                continue
            if oi >= len(outs):
                break
            arr = outs[oi]
            oi += 1
            slot = task.data[flow.flow_index]
            src = slot.data_in
            data = getattr(src, "original", None)
            if data is not None:
                copy = data.get_copy(self.device_index)
                if copy is None:
                    copy = data.create_copy(self.device_index, arr, COHERENCY_OWNED)
                else:
                    copy.payload = arr
                data.bump_version(self.device_index)
                slot.data_out = copy
                self._lru_touch(self.res_key(data), copy)
                self._coh_mark_owned(data, copy)
                if gt.pushout & (1 << flow.flow_index):
                    self._stage_out(data, copy)
            else:
                slot.data_out = arr
        ps = self._prof()
        if ps is not None:
            from ..utils.trace import EVENT_FLAG_END
            ps.trace(self._prof_keys[1], hash(task.key) & 0x7FFFFFFF,
                     task.taskpool.taskpool_id, EVENT_FLAG_END)
        self._unpin(gt)     # inputs consumed: copies evictable again
        self.executed_tasks += 1
        self.load_sub(gt.load)
        if gt.complete_cb is not None:
            gt.complete_cb(gt)
        self.context and self.context.complete_task_execution(stream, task)
        if sp is not None:
            self._retired_ns += sp.end(tok, sp.retire)

    def _stage_out(self, data: Data, copy: DataCopy) -> None:
        """D2H write-back (ref: stage_out device_gpu.c:1674 + w2r task)."""
        host = np.asarray(copy.payload)
        hcopy = data.get_copy(0)
        if hcopy is None:
            hcopy = data.create_copy(0, host, COHERENCY_SHARED)
        else:
            hcopy.payload = host
            hcopy.coherency_state = COHERENCY_SHARED
        hcopy.version = copy.version
        self.transfer_out_bytes += _nbytes(copy.payload)

    # ------------------------------------------------------------- LRU heap
    def _lru_touch(self, key: Any, copy: DataCopy) -> None:
        # account by the size actually resident under this key: an epilog may
        # rebind the copy's payload to a different-sized array, and the budget
        # must follow (the eviction math drifts otherwise)
        with self._heap_lock:
            self._lru_touch_locked(key, copy)

    def _lru_touch_locked(self, key: Any, copy: DataCopy) -> None:
        self._lru.pop(key, None)
        new_size = _nbytes(copy.payload)
        old_size = self._lru_sizes.get(key, 0)
        self._resident_bytes += new_size - old_size
        if copy.readers:
            self._pinned_bytes += new_size - old_size
        self._lru_sizes[key] = new_size
        self._lru[key] = copy
        self._trace_mem(new_size - old_size)
        if new_size != old_size or key not in self._lru_segs:
            # re-register on size change AND whenever the key has no live
            # segment (a past allocate() miss under pressure must not
            # permanently drop the tile from the ledger)
            seg = self._lru_segs.pop(key, None)
            if seg is not None:
                seg.free()
            seg = self._zone.allocate(new_size)
            if seg is not None:
                self._lru_segs[key] = seg

    def _evict_one(self) -> bool:
        """Evict the least-recently-used unpinned copy; an OWNED copy
        writes back AND downgrades atomically with the version check
        (Data.evict_copy — one critical section, so a reader racing the
        eviction can never see the newest version without a valid
        payload). Python-LRU fallback path; with the native table up,
        victim selection comes from C (:meth:`_apply_victims`)."""
        with self._heap_lock:
            return self._evict_one_locked()

    def _evict_one_locked(self) -> bool:
        for key in list(self._lru):
            copy = self._lru[key]
            if copy.readers > 0:
                self.pinned_skips += 1
                continue
            self._lru.pop(key)
            self._evict_key_locked(key, copy, drop_table=True)
            return True
        return False

    def evict_bytes(self, nbytes: int) -> int:
        """Force eviction of about ``nbytes`` of resident clean/dirty copies
        (the explicit half of the OOM retry path). With the native table
        up, the victim set is C's decision."""
        freed0 = self._resident_bytes
        if self._ncoh is not None:
            victims, skips = self._ncoh.evict(nbytes)
            self._apply_victims(victims)
            self.pinned_skips += skips
            return freed0 - self._resident_bytes
        target = max(0, self._resident_bytes - nbytes)
        while self._resident_bytes > target and self._lru:
            if not self._evict_one():
                break
        return freed0 - self._resident_bytes

    def pin_copy(self, copy: DataCopy) -> None:
        """Pin a device copy against eviction (the inflight-task reader
        guard): bumps the Python reader count AND the native table's pin
        so C's victim selection honors it. The reader count mutates from
        interpreted-path workers AND the ptdev manager thread — the
        non-atomic ``+=`` goes under the heap lock so no update is lost."""
        with self._heap_lock:
            self._pin_locked(copy)
        self._coh_pin(copy.original)

    def unpin_copy(self, copy: DataCopy) -> None:
        with self._heap_lock:
            copy.readers -= 1
            if not copy.readers:
                self._pinned_bytes -= self._lru_sizes.get(
                    id(copy.original), 0)
        self._coh_unpin(copy.original)

    def _pin_locked(self, copy: DataCopy) -> None:
        """One more reader of ``copy`` (heap lock held); the first puts its
        resident bytes among the pinned ones."""
        if not copy.readers:
            self._pinned_bytes += self._lru_sizes.get(id(copy.original), 0)
        copy.readers += 1

    def _reserve(self, nbytes: int) -> None:
        """Evict LRU copies until ``nbytes`` fits the budget
        (ref: parsec_device_data_reserve_space device_gpu.c:1210)."""
        while self._resident_bytes + nbytes > self._budget and self._lru:
            if not self._evict_one():
                break  # everything pinned; rely on XLA allocator

    def zone_stats(self) -> Dict[str, int]:
        """Device-heap ledger stats (occupancy, fragmentation, high-water
        mark) — the zonemalloc_benchmark surface of the reference."""
        return self._zone.stats()

    def set_budget(self, nbytes: int, unit: Optional[int] = None) -> None:
        """Resize the HBM tile budget (tests / MCA reconfiguration): the
        zone ledger is rebuilt and current residents re-registered."""
        from ..utils.zone_malloc import ZoneMalloc
        with self._heap_lock:
            self._budget = nbytes
            if self._ncoh is not None:
                # C applies the new budget first (victims leave both views)
                self._apply_victims_locked(self._ncoh.set_budget(nbytes))
            self._zone = ZoneMalloc(nbytes, unit)
            self._lru_segs = {}
            for key, sz in self._lru_sizes.items():
                seg = self._zone.allocate(sz)
                if seg is not None:
                    self._lru_segs[key] = seg

    def fini(self) -> None:
        self._lru.clear()
        self._lru_sizes.clear()
        for seg in self._lru_segs.values():
            seg.free()
        self._lru_segs.clear()
        self._resident_bytes = 0
        self._pinned_bytes = 0
        self._fetching.clear()
        self._pending.clear()


def _is_oom(e: Exception) -> bool:
    msg = str(e).upper()
    return "RESOURCE_EXHAUSTED" in msg or "OUT OF MEMORY" in msg or "OOM" in msg


def _nbytes(arr) -> int:
    try:
        return int(arr.nbytes)
    except Exception:
        return int(np.prod(getattr(arr, "shape", (1,))) * 4)


# the launcher's --virtual-devices rehearsal: which virtual host device
# this rank registers over (test mode only — a rank bound to a real chip
# sees exactly that chip in jax.devices(), so there is nothing to index)
ENV_LOCAL_DEVICE = "PARSEC_TPU_LOCAL_DEVICE"


def _device_bytes_limit(jax_device) -> int:
    """Memory behind ``jax_device``: what the accelerator reports, or the
    host's physical RAM for the over-cpu test mode. An accelerator that
    reports no limit is an error — a guessed budget would let the tile
    heap overrun (or idle most of) a chip it knows nothing about."""
    limit = (jax_device.memory_stats() or {}).get("bytes_limit")
    if limit:
        return int(limit)
    if jax_device.platform == "cpu":
        return os.sysconf("SC_PHYS_PAGES") * os.sysconf("SC_PAGE_SIZE")
    raise RuntimeError(
        f"{jax_device} reports no bytes_limit in memory_stats(); set "
        f"--mca device_tpu_max_bytes to size its tile heap")


def discover_tpu_devices() -> List[TPUDevice]:
    """One device module per accelerator chip of THIS process's JAX
    backend (ref: device discovery, device_cuda_module.c:45). The backend
    is whatever ``jax.devices()`` yields in-process: a CPU pin
    (``JAX_PLATFORMS=cpu``) is the caller's to set before starting, and a
    backend that fails to initialise raises here. Non-TPU accelerators
    (gpu) are accepted too; a CPU-only backend registers nothing unless
    the ``device_tpu_over_cpu`` test mode asks for a host device."""
    import jax
    devs = jax.devices()
    accels = [d for d in devs if d.platform in ("tpu", "gpu")]
    if accels:
        return [TPUDevice(d) for d in accels]
    if not mca.get("device_tpu_over_cpu", False):
        return []
    # test mode: drive the full async device pipeline (stage-in, LRU,
    # events, batching) over one host device — selectable so
    # oversubscribed ranks can spread over a virtual device mesh
    cpus = [d for d in devs if d.platform == "cpu"]
    bind = os.environ.get(ENV_LOCAL_DEVICE)
    idx = int(bind) if bind is not None \
        else mca.get("device_tpu_over_cpu_index", 0)
    return [TPUDevice(cpus[idx % len(cpus)])]


def make_tpu_hook(submit: Callable) -> Callable:
    """Build a chore hook dispatching ``submit`` on the selected TPU device.

    Plays the role of the generated GPU hook (jdf2c.c:6613) wrapping the body
    into a gpu_task and invoking the kernel scheduler.
    ``submit(device, task, inputs)`` must return the output arrays for WRITE
    flows in flow order; typically it calls a pre-compiled jitted function.
    """
    def hook(stream, task: Task) -> int:
        dev = task.selected_device
        if dev is None or not isinstance(dev, TPUDevice):
            return HOOK_DONE if submit is None else _run_inline(stream, task, submit)
        return dev.kernel_scheduler(stream, task, submit=submit)
    return hook


def _run_inline(stream, task, submit) -> int:
    """CPU fallback: run the body synchronously on host copies."""
    inputs = []
    for flow in task.task_class.flows:
        slot = task.data[flow.flow_index]
        inputs.append(None if slot.data_in is None else slot.data_in.payload)
    outs = submit(None, task, inputs)
    if outs is not None and not isinstance(outs, (tuple, list)):
        outs = (outs,)
    oi = 0
    for flow in task.task_class.flows:
        if flow.access & FLOW_ACCESS_WRITE and outs and oi < len(outs):
            slot = task.data[flow.flow_index]
            if slot.data_in is not None and slot.data_in.original is not None:
                data = slot.data_in.original
                slot.data_in.payload = outs[oi]
                data.bump_version(slot.data_in.device_index)
                slot.data_out = slot.data_in
            else:
                slot.data_out = outs[oi]
            oi += 1
    return HOOK_DONE
