"""A pool on the native device lane: how a ready device task's operands
become resident, pinned, read and released, decided here and nowhere
else. :func:`bind` takes a pool as plain data and an engine; it knows no
DSL (the PTG compiler is its first caller). docs/device_lane.md.
"""

from __future__ import annotations

import collections
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from ..utils.xla_trace import (PTDEV_CALL, PTDEV_DISPATCH, PTDEV_POLL,
                               PTDEV_PUSH, PTDEV_RETIRE, file_pool_account)
from .native import PTDEV_STATS


def bind(devlane, engine, *, bases: Sequence[int], params, slot_base,
         in_refs, ndflows, cls_of, fns: Sequence[Optional[Callable]],
         written: Sequence[Tuple[int, ...]], names: Sequence[str],
         slots: List[Any], mem_datas: Sequence[Any],
         writebacks: Dict[int, List], dev_mask: Sequence[int],
         ndev_tasks: int, early: Optional[Sequence[int]] = None,
         fusion: Optional[Dict[str, Any]] = None, bucket: int = 0,
         cost_obs: Optional[Dict] = None) -> Tuple[int, Dict[int, List]]:
    """Bind a flattened data pool to ``devlane`` (device/native.py): from
    then on a task of ``engine`` (``dev_bind``, ``dev_retire_capsule``,
    ``trace_mark``: the ``ptexec`` ``Graph``) whose ``dev_mask`` entry is
    set surfaces onto the lane's pending queue when it becomes ready, in
    place of the ready structure, and retires through the engine's
    GIL-free capsule.

    Task ``i`` is instance ``i - bases[k]`` of class ``k = cls_of[i]``
    with parameters ``params[k][i - bases[k]]``; its ``ndflows[k]`` flows
    own the slots from ``slot_base[i]`` on, and ``in_refs[slot]`` names a
    flow's input: a producer's slot (>= 0), nothing (-1), or
    ``mem_datas[-2 - ref]``. Class ``k`` runs ``fns[k](*params,
    *inputs)`` (None: inputs are forwarded) and returns the flows at
    positions ``written[k]``; ``names[k]`` keys its entries of
    ``cost_obs`` ((name, bucket, dev) -> [count, sum_ns]; None:
    unobserved). ``writebacks[i]``: the (flow position, ``Data``) pairs
    task ``i`` writes to memory. ``ndev_tasks``: the tasks under the mask.
    ``early[i]`` set (the caller's finding, from the graph's structure
    alone): node ``i`` has successors and every one of them is under the
    mask, so it is released when it is dispatched (:func:`_closures`).

    ``fusion``, for an engine whose nodes are regions and seams:
    ``orig_of`` maps a node to its task, ``dev_regions`` a region's node
    to its program: ``jitted(donated, kept)`` over the operands ``ext``
    (the leading ones are the slots ``given``, which the program keeps,
    see below; the memory operands are also ``ext_mems``) returns two
    tuples, ``outs``
    and ``wb_pairs`` say which of them (flattened) lands in which slot
    and in which ``Data``; ``ntasks``, ``cls``, ``cold``. ``marks`` is
    the (event, start, end) of ``engine.trace_mark``.

    Call it LAST: ``dev_bind`` surfaces zero-dependency device tasks at
    once and the manager may dispatch them before this returns. Returns
    the lane's pool id (for ``unbind_pool``) and ``held``.
    """
    dispatch, poll, drop, held = _closures(
        devlane, engine, bases, params, slot_base, in_refs, ndflows, cls_of,
        fns, written, names, slots, mem_datas, writebacks, fusion, bucket,
        cost_obs, early, ndev_tasks)
    pid = devlane.bind_pool(engine, dispatch, poll, drop)
    PTDEV_STATS["pools_engaged"] += 1
    PTDEV_STATS["tasks_engaged"] += ndev_tasks
    engine.dev_bind(devlane.submit_capsule(), pid, dev_mask)
    devlane.clane.notify()
    return pid, held


def _closures(devlane, engine, bases, params, slot_base, in_refs, ndflows,
              cls_of, fns, written, names, slots, mem_datas, writebacks,
              fusion, bucket, cost_obs, early, ndev_tasks):
    """The pool's dispatch/poll pair, both run on the lane's manager
    thread with the GIL held:

    * ``dispatch(ids)`` — the push+exec phases of the reference's stream
      pipeline (device_gpu.c:3438) on XLA's async runtime: FIRST every
      memory operand of the whole batch stages in (version-checked key
      by key through the C coherency table, the misses moved by ONE
      ``device_put`` of the list, which is asynchronous, so the transfer
      overlaps compute already in flight), THEN each
      program dispatches (async) and its future outputs land in the
      slots at once. A node under ``early`` is RELEASED here: its
      write-backs land (future arrays: ``Data.write_host`` blocks on
      nothing) and the next ``poll()`` reports its id, so the engine's
      release walk surfaces its successors, device nodes of this lane
      all, while it still runs; XLA queues each behind the producers
      of its operands, donated ones included;
    * ``poll()`` — the event queue: ``jax.Array.is_ready`` over each
      inflight program's outputs (cudaEventQuery, device_gpu.c:2593).
      Completed tasks write back to memory, give up their reads and
      return their ids; the C side then calls the engine's ``dev_retire``.
      A released node stays in flight until it is seen complete and
      then SETTLES: its reads, ``dev.executed_tasks``, the cost
      observation and the one ``ptdev.retire`` record. Its witness is
      one output that is left (a successor may have been given some
      for good, and a deleted array is never asked), or the order of
      the queue: one device runs its programs in dispatch order, so a
      released node is complete once a node dispatched after it is.

    Residency is touched once per distinct memory operand of a BATCH:
    the push phase's one ``lane_stage_in_batch`` takes the operand's one
    pin (table + ``readers``; all given back if it raises, always-on
    counts ``PTDEV_STATS["staged_tiles"]`` moved by ``["stage_in_puts"]``
    calls), ``held`` counts the programs in flight that read it,
    and the pin is given back when the last of them retires or settles
    (or at the end of ``dispatch``, where no program of the batch reads
    it). Returns ``(dispatch, poll, drop, held)``; ``held`` is empty
    whenever nothing is in flight: a node that is no sink of the device
    part of the graph retires only when seen complete, every released
    one has such a node dispatched after it, and the pass that retires
    it settles what precedes it. ``drop()`` gives up what an aborted
    pool leaves in flight (``unbind_pool``).

    Ownership: a table copy is the residency table's and a written-back
    array its ``Data``'s; a slot value is the pool's until its last
    reader takes it. A fused region's leading operands, ``given``, are
    slots it is the one reader of (the plan's finding): its program is
    jitted with them donated, so ``dispatch`` clears those slots after
    the call (``PTDEV_STATS["donated"]``, of ``["region_outputs"]``
    arrays returned).

    With the context's spans on, the pair comes back wrapped: the
    ``ptdev.*`` spans, and the pool's account of the manager thread's
    time, filed once, in the pass that retires or settles the last of
    the ``ndev_tasks`` task weights, or at ``drop()``
    (``utils/xla_trace.py file_pool_account``; docs/observability.md).
    """
    dev = devlane.device
    # (id, events, write-backs, values, reads, weight, cost key, released),
    # in dispatch order
    inflight: "collections.deque" = collections.deque()
    # released at dispatch, for the next poll() to report to the engine
    released: List[int] = []
    early = frozenset(i for i, e in enumerate(early or ()) if e)
    # device-side cost observation (ISSUE 18): each inflight entry is
    # stamped at dispatch and observed at retire — the elapsed window
    # covers the async compute, the output-ready wait, AND the lane's
    # poll cadence, i.e. the throughput a task actually experiences
    # on this path (what placement must compare against the CPU
    # lane's batch-amortized cost). Stage-ins time separately into
    # the __stage_in__ pseudo-class. All writes happen on the
    # manager thread; the fold reads after unbind.
    _pc = time.perf_counter_ns
    dev_clock = [0]      # batch-amortization mark (see poll)
    if cost_obs is not None:
        from ..core.costmodel import STAGE_IN as _STG, shape_bucket

        def _obs(key, w, ns):
            e = cost_obs.get(key)
            if e is None:
                cost_obs[key] = [w, ns]
            else:
                e[0] += w
                e[1] += ns
    else:
        _obs = None
    sp = devlane.ctx._spans
    if sp is not None:
        pinned = [0]     # table pins taken so far, for ptdev.pins
        # the pool's account: the clock now and at the first ptdev.call
        # entered, what its spans add up to, and whether it is filed
        bound, first_call, filed = _pc(), [0], []
        acct = {"dispatch_ns": 0, "push_ns": 0, "call_ns": 0, "poll_ns": 0,
                "programs": 0, "callbacks": 0, "passes": 0, "tasks": 0}

        def _called(tok):
            if not first_call[0]:
                first_call[0] = tok[1]
            acct["call_ns"] += sp.end(tok, sp.pt_call)
    # mi -> [device copy, programs in flight that read it, pins held]:
    # owned by the manager thread (dispatch and poll both run there
    # with the GIL, as _obs relies on), so no lock and no table call
    # per (program, operand). An operand staged again by a later batch
    # while an earlier reader still flies joins the same entry; its
    # pins nest in the table as they always did.
    held: Dict[int, List[Any]] = {}

    def _release(mi, h):
        del held[mi]
        for _ in range(h[2]):
            dev.unpin_copy(h[0])
    if fusion is not None:
        # fused pool (ISSUE 12): a device REGION dispatches as one
        # region-sized async program; its inflight/retire id is the
        # COMPACT node id (what the C release walk expects), while
        # slot/param arrays index by original id via orig_of
        _forig = fusion["orig_of"]
        _dregs = fusion["dev_regions"]
        _graph = engine
        _evr, _fs, _fe = fusion["marks"]
    else:
        _forig = _dregs = _graph = None

    def _fly(i, events, wbs, vals, reads, w, ckey2):
        # one more program is on the device. Released at dispatch, it owes
        # no write-back later: they land now, BEFORE the engine hears of
        # it, so that a successor reading the ``Data`` from memory stages
        # (adopts) the new version
        PTDEV_STATS["programs"] += 1
        rel = i in early
        if rel:
            if wbs:
                for dj, dref in wbs:
                    dref.write_host(vals[dj])
                wbs = None
            released.append(i)
            PTDEV_STATS["released_early"] += 1
        inflight.append((i, events, wbs, vals, reads, w, ckey2, rel))

    def push(ids):
        # PUSH phase: every distinct memory operand of the whole batch is
        # asked for at once, before any compute dispatch. The device
        # decides key by key, pinning each operand INSIDE the table's
        # reserve critical section (no peer thread's stage-in can evict it
        # first, and staging tile k+1 of this very batch cannot evict tile
        # k before the exec phase reads it: "dot got NoneType", found by
        # the verify drive), and moves the misses in one device_put. If it
        # raises it has given its pins back, and nothing is held here yet
        staged: Dict[int, Any] = {}
        if _obs is not None and not inflight:
            # idle -> active: restart the amortization clock so idle
            # gaps between batches never land in any task's cost
            dev_clock[0] = _pc()
        for i in ids:
            if _dregs is not None:
                r = _dregs.get(i)
                if r is not None:
                    for mi in r["ext_mems"]:
                        staged[mi] = None
                    continue
                i = _forig[i]
            base = slot_base[i]
            for dj in range(ndflows[cls_of[i]]):
                r = in_refs[base + dj]
                if r < -1:
                    staged[-2 - r] = None
        if not staged:
            return staged
        t0 = _pc()
        copies, moved, put_ns = dev.lane_stage_in_batch(
            [mem_datas[mi] for mi in staged])
        if moved:
            PTDEV_STATS["staged_tiles"] += moved
            PTDEV_STATS["stage_in_puts"] += 1
        if sp is not None:
            pinned[0] += len(copies)    # every pin here is a stage-in's
            if moved:
                # ptdev.stage_in: the misses only (a hit moves no bytes),
                # each at its share of the one put; on the timeline the
                # dev.stage_in annotation of TPUDevice._transfer
                sp.pt_stage_in.record(put_ns // moved, moved)
        if _obs is not None:
            # one observation an operand, hit or miss, at its share
            share = (_pc() - t0) / len(copies)
            sizes = collections.Counter(
                getattr(c.payload, "nbytes", 0) for c in copies)
            for nb, n in sizes.items():
                _obs((_STG, shape_bucket(nb), "tpu"), n, share * n)
        for mi, copy in zip(staged, copies):
            h = held.get(mi)
            if h is None:
                h = held[mi] = [copy, 0, 0]
            h[2] += 1           # one table pin an operand a batch
            staged[mi] = h
        return staged

    def issue(ids, staged):
        # EXEC phase: dispatch each ready device task asynchronously
        for i in ids:
            oi = i
            if _dregs is not None:
                r = _dregs.get(i)
                if r is not None:
                    # region-sized dispatch: ONE jitted program for
                    # the whole fused region, async like any task;
                    # the retire id stays the compact node id
                    ev: List[Any] = []
                    for kk, v in r["ext"]:
                        if kk == "slot":
                            ev.append(slots[v])
                        else:
                            h = staged[v]
                            h[1] += 1       # one more reader in flight
                            ev.append(h[0].payload)
                    # the operands this region is the last reader of
                    # lead, and the program keeps them: each is a slot
                    # value the pool owns, so the slot retires here
                    given = r["given"]
                    nd = len(given)
                    _graph.trace_mark(_evr, i, _fs)
                    if sp is None:
                        first, rest = r["jitted"](tuple(ev[:nd]),
                                                  tuple(ev[nd:]))
                    else:
                        tok = sp.begin(PTDEV_CALL)
                        try:
                            first, rest = r["jitted"](tuple(ev[:nd]),
                                                      tuple(ev[nd:]))
                        finally:
                            _called(tok)
                    _graph.trace_mark(_evr, i, _fe)
                    vals = first + rest
                    for s, p in r["outs"]:
                        slots[s] = vals[p]
                    if nd:
                        for s in given:
                            slots[s] = None
                        if ev[0].is_deleted():
                            PTDEV_STATS["donated"] += nd
                    PTDEV_STATS["region_outputs"] += len(vals)
                    events = tuple(v for v in vals
                                   if hasattr(v, "is_ready"))
                    _fly(i, events, r["wb_pairs"], vals,
                         r["ext_mems"], r["ntasks"],
                         None if (_obs is None or r.get("cold")) else
                         (names[r["cls"]], bucket, "tpu_fused"))
                    continue
                oi = _forig[i]
            k = cls_of[oi]
            base = slot_base[oi]
            nd = ndflows[k]
            vals: List[Any] = []
            reads: List[int] = []
            for dj in range(nd):
                r = in_refs[base + dj]
                if r >= 0:
                    vals.append(slots[r])
                elif r == -1:
                    vals.append(None)
                else:
                    h = staged[-2 - r]
                    h[1] += 1               # one more reader in flight
                    reads.append(-2 - r)
                    vals.append(h[0].payload)
            fn = fns[k]
            events = ()
            if fn is not None:
                if sp is None:
                    outs = fn(*params[k][oi - bases[k]], *vals)
                else:
                    tok = sp.begin(PTDEV_CALL)
                    try:
                        outs = fn(*params[k][oi - bases[k]], *vals)
                    finally:
                        _called(tok)
                for oj, dj in enumerate(written[k]):
                    vals[dj] = outs[oj]
                events = tuple(v for v in outs
                               if hasattr(v, "is_ready"))
            for dj in range(nd):
                slots[base + dj] = vals[dj]
            _fly(i, events, writebacks.get(oi), vals, reads, 1,
                 None if _obs is None else (names[k], bucket, "tpu"))
        for mi, h in staged.items():
            if not h[1]:            # staged, and no program reads it
                _release(mi, h)
        return len(ids)

    def dispatch(ids):
        return issue(ids, push(ids))

    def _complete(events, was_released):
        if not was_released:
            return not events or all(a.is_ready() for a in events)
        # settling gates the accounting only, so one witness will do: every
        # output of a program is a buffer that program wrote, and they
        # turn ready together. A successor in flight may have been given
        # some for good: a deleted array tells nothing and is never asked
        for a in reversed(events):
            if not a.is_deleted():
                return a.is_ready()
        return not events

    def poll():
        done = released[:]
        del released[:]
        if not inflight:
            return done
        # newest first: a released program is complete once one dispatched
        # after it is (one device, in dispatch order), so the pass that
        # sees a completion settles every released one before it
        complete: List[Tuple] = []
        flying: List[Tuple] = []
        later = False
        for ent in reversed(inflight):
            if (later and ent[7]) or _complete(ent[1], ent[7]):
                later = True
                complete.append(ent)
            else:
                flying.append(ent)
        if not complete:
            return done
        inflight.clear()
        inflight.extend(reversed(flying))
        retired: List[Tuple] = []
        for i, _events, wbs, vals, reads, w, ckey2, was_released in \
                reversed(complete):
            if sp is not None:
                tok = sp.begin(PTDEV_RETIRE)
            if wbs:
                for dj, dref in wbs:
                    dref.write_host(vals[dj])
            for mi in reads:
                h = held.get(mi)
                if h is None:       # dropped with an aborted pool
                    continue
                h[1] -= 1
                if not h[1]:        # its last reader in flight retired
                    _release(mi, h)
            dev.executed_tasks += w
            retired.append((ckey2, w))
            if not was_released:    # the engine heard of it at dispatch
                done.append(i)
            if sp is not None:
                retired_ns[0] += sp.end(tok, sp.pt_retire)
                acct["tasks"] += w
        if _obs is not None:
            # batch amortization, the SAME semantics as the C lane's
            # exec bump: the wall window since the last retire sweep
            # (or the idle->active mark) divides across every task
            # weight retired in it. Per-entry dispatch->retire spans
            # overlap under pipelining, so summing them would bill
            # the same wall clock N-inflight times over and make the
            # device look slower than the wall it actually consumed
            # — placement would then mis-compare against the CPU
            # lane's throughput-denominated cost. Keyless entries
            # (cold regions) still weigh in the denominator: they
            # consumed part of the window.
            now = _pc()
            total_w = sum(w for _, w in retired)
            per = (now - dev_clock[0]) / max(total_w, 1)
            for ckey2, w in retired:
                if ckey2 is not None:
                    _obs(ckey2, w, per * w)
            dev_clock[0] = now
        return done

    def drop():
        # what an aborted pool leaves: nobody will ask after it again
        inflight.clear()
        del released[:]
        while held:
            _mi, h = held.popitem()
            for _ in range(h[2]):
                dev.unpin_copy(h[0])

    if sp is None:
        return dispatch, poll, drop, held
    retired_ns = [0]     # ptdev.retire total, for ptdev.poll to subtract

    def _file(end):
        if not filed:
            filed.append(file_pool_account(
                bound, first_call[0], end, retire_ns=retired_ns[0], **acct))

    def traced_dispatch(ids):
        # one span a callback, recorded once per device program, its push
        # phase a span inside it; the table pins the callback took and
        # the programs it found in flight (the depth of the device's
        # queue as the host left it), one record each
        sp.pt_inflight.record(len(inflight))
        tok, before = sp.begin(PTDEV_DISPATCH), pinned[0]
        try:
            sub = sp.begin(PTDEV_PUSH)
            try:
                staged = push(ids)
            finally:
                acct["push_ns"] += sp.end(sub, sp.pt_push)
            return issue(ids, staged)
        finally:
            acct["dispatch_ns"] += sp.end(tok, sp.pt_dispatch, n=len(ids))
            sp.pt_pins.record(pinned[0] - before)
            acct["programs"] += len(ids)
            acct["callbacks"] += 1

    def traced_poll():
        # one record a pass, the retirements' own spans subtracted; the
        # pool's end is the end of the pass that retires its last task
        tok, before = sp.begin(PTDEV_POLL), retired_ns[0]
        try:
            return poll()
        finally:
            less = retired_ns[0] - before
            whole = sp.end(tok, sp.pt_poll, less=less)
            acct["poll_ns"] += whole - less
            acct["passes"] += 1
            if acct["tasks"] >= ndev_tasks:
                _file(tok[1] + whole)

    def traced_drop():
        drop()
        _file(_pc())

    return traced_dispatch, traced_poll, traced_drop, held
