"""PTG compiler: ProgramSpec → runtime task classes.

Stands where the reference's jdf2c.c code generator stands (SURVEY §2.5:
structure/symbols/flows/deps/startup/init/ctor/keys/hooks/data_lookup/
release_deps/iterate_successors), but instead of emitting C against the
task-class contract it *builds* :class:`parsec_tpu.core.task.TaskClass`
objects directly:

* parameter ranges → the startup enumerator counting the task space and
  seeding ready tasks (the generated startup/internal_init, jdf2c.c:3047,3455)
* guarded in-deps → ``prepare_input`` (the generated data_lookup, jdf2c.c:45)
  + per-task dependency goals (count mode — the DYNAMIC_HASH_TABLE dep mode)
* guarded out-deps → ``Dep`` descriptors consumed by the generic
  release-deps engine (iterate_successors, jdf2c.c:47)
* BODY blocks → chores: the body text becomes a Python function of
  (params..., flows...) returning its written flows, jitted once per class —
  a PTG body IS an XLA executable on TPU (the BODY[type=TPU] goal of
  BASELINE.json)
* memory out-deps → write-back to the data collection at completion

Python expressions are compiled once at class-build time and evaluated
against task locals + user globals.
"""

from __future__ import annotations

import ast
import collections
import textwrap
import threading
import time
import types
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ...core.context import Context
from ...core.datarepo import DataRepo
from ...core.task import (
    Chore, DEV_CPU, DEV_TPU, Dep, Flow, FLOW_ACCESS_CTL, FLOW_ACCESS_READ,
    FLOW_ACCESS_RW, FLOW_ACCESS_WRITE, HOOK_DONE, Task, TaskClass, Taskpool,
)
from ...core.futures import DataCopyFuture
from ...data.data import COHERENCY_OWNED, DataCopy
from ...data.reshape import NamedDatatype, default_datatype
from ...device import native as dev_native
from ...device.tpu import make_tpu_hook
from ...utils import mca, output
from ...utils.xla_trace import PTG_LOWER
from . import parser as P

mca.register("ptg_agglomerate", True,
             "Execute statically-independent flowless PTG classes "
             "as one fused sweep at startup (no per-task "
             "scheduling cycle)", type=bool)
mca.register("ptg_native_exec", True,
             "Drain eligible PTG taskpools (CTL and DATA-flow classes "
             "with single ungated CPU chores, incl. priorities) through "
             "the native execution lane (native/src/ptexec.cpp): the "
             "full dependency FSM — dep decrement, ready heap, data-slot "
             "retire — runs batched in C with the GIL dropped. "
             "Ineligible pools (named datatypes/reshapes, distributed "
             "ranks, PINS, multi-chore classes) fall back to the Python "
             "FSM (docs/native_exec.md)",
             type=bool)

#: lane-engagement accounting (consumed by ci.sh's perf smoke gate and the
#: bench — through the LaneStats snapshot()/delta() helpers, not raw key
#: pokes). ``pools_fallback`` counts pools whose classes were ALL eligible
#: yet the lane still declined (flatten refusal, native module missing) —
#: the silent perf regression no throughput number reliably catches on a
#: noisy host. ``pools_ineligible`` counts pools declined by DESIGN
#: (ineligible class features or pool-level gates: distributed/
#: pins-paranoid/debug-paranoid/mca-off) — expected fallbacks, never a CI
#: failure. utils/counters.install_native_counters exports these under
#: ``ptexec.*`` for live_view and the SDE-style snapshot
from ...utils.counters import LaneStats as _LaneStats
from ..fusion import (
    ExecCache, adaptive_fusion_limits, device_fingerprint,
    pack_source_regions, partition_regions,
)

PTEXEC_STATS = _LaneStats(pools_engaged=0, tasks_engaged=0,
                          pools_fallback=0, pools_ineligible=0,
                          pools_device=0, tasks_device=0,
                          # region fusion (ISSUE 12): original tasks
                          # collapsed into fused super-tasks vs tasks the
                          # scheduler still handles per-task (the seams)
                          fused_regions=0, fused_tasks=0, seam_tasks=0,
                          # region executables BUILT (ISSUE 29): one per
                          # distinct shape of region a program's pools
                          # have shown, not one per region
                          region_programs=0,
                          # regions of the partition that share a program
                          # with a sibling (ISSUE 32, pack_source_regions)
                          packed_regions=0,
                          # fused regions whose members are of more than
                          # one class (ISSUE 33): a factorization's, where
                          # a one-class graph has none
                          mixed_regions=0)

_ACCESS_MAP = {
    P.FLOW_READ: FLOW_ACCESS_READ,
    P.FLOW_WRITE: FLOW_ACCESS_WRITE,
    P.FLOW_RW: FLOW_ACCESS_RW,
    P.FLOW_CTL: FLOW_ACCESS_CTL,
}


def _payload_of(v: Any) -> Any:
    return v.payload if isinstance(v, DataCopy) else v


class _Expr:
    """One compiled Python expression evaluated against task locals."""

    __slots__ = ("code", "src")
    is_range = False

    def __init__(self, src: str) -> None:
        self.src = src = src.strip()
        try:
            self.code = compile(src, f"<ptg:{src}>", "eval")
        except SyntaxError as e:
            raise P.PTGSyntaxError(f"bad expression {src!r}: {e}") from e

    def __call__(self, env: Dict[str, Any]) -> Any:
        return eval(self.code, env)  # noqa: S307 - the DSL is code by design

    def values(self, env: Dict[str, Any]) -> List[int]:
        return [int(self(env))]


class _RangeExpr:
    """A JDF range endpoint index ``lo .. hi`` — broadcast/gather fan-out
    (e.g. ``-> Y WORK(0 .. W-1)`` multicasts one output to many tasks)."""

    __slots__ = ("lo", "hi")
    is_range = True

    def __init__(self, lo: str, hi: str) -> None:
        self.lo = _Expr(lo)
        self.hi = _Expr(hi)

    def values(self, env: Dict[str, Any]) -> List[int]:
        return list(range(int(self.lo(env)), int(self.hi(env)) + 1))


def _index_expr(src: str):
    # top-level '..' only (not inside parens/brackets)
    depth = 0
    for i, c in enumerate(src):
        if c in "([":
            depth += 1
        elif c in ")]":
            depth -= 1
        elif c == "." and depth == 0 and src[i:i+2] == ".." and src[i:i+3] != "...":
            return _RangeExpr(src[:i], src[i+2:])
    return _Expr(src)


class _DonationRefused(Exception):
    """Raised inside a donating region program's trace: its outputs cannot
    take every donated buffer, shape by shape and dtype by dtype."""


def _jit_region_program(shape: Dict[str, Any], fns, written_by_class, scopes):
    """jit the program of a region shape (:func:`_mk_region_program`). A
    shape that donates (ISSUE 34) is jitted with its first argument
    donated. The plan finds an output for every donated operand by
    structure and cannot see a tile's shape, so such a program counts in
    its own trace: where a body returns its RW flow in another shape or
    dtype and the outputs cannot take every donated buffer, the trace
    raises before anything runs (no buffer is given up), and the shape
    runs undonated from then on, where JAX would have warned of unusable
    donations at each trace."""
    import jax
    if not shape["n_donated"]:
        return jax.jit(_mk_region_program(shape, fns, written_by_class,
                                          scopes))
    fn = [jax.jit(_mk_region_program(shape, fns, written_by_class, scopes,
                                     check=True), donate_argnums=(0,))]

    def call(donated, kept):
        try:
            return fn[0](donated, kept)
        except _DonationRefused:
            fn[0] = jax.jit(_mk_region_program(shape, fns, written_by_class,
                                               scopes))
            return fn[0](donated, kept)
    return call


def _timed_region_program(fn, n_members: int):
    """Wrap a jitted region program so its FIRST call — the one paying
    the XLA trace+compile — feeds the cost model's ``__region_trace__``
    pseudo-class (per-member cost by region-size band; ISSUE 18). The
    wrapper, not the bare jit, is what the executable cache stores: a
    warm cache hit reuses it with the first call already burned, so only
    real traces are ever observed. Steady-state calls pay one dict-free
    boolean check."""
    state = [True]

    def call(donated, kept):
        if state[0]:
            state[0] = False
            t0 = time.perf_counter_ns()
            out = fn(donated, kept)
            from ...core.costmodel import model
            model.note_region_trace("cpu", n_members,
                                    time.perf_counter_ns() - t0)
            return out
        return fn(donated, kept)
    return call


def _mk_region_program(rp: Dict[str, Any], fns, written_by_class, scopes,
                       check: bool = False):
    """The fused super-task's body (ISSUE 12): ONE traceable program
    replaying the region's members in serialization order (topo order of
    the member subgraph — a valid serialization, the DTD-capture
    soundness argument applied to a PTG region). Internal dataflow rides
    a trace-time slot env (XLA recovers the DAG from the value
    dependencies and re-fuses across task boundaries); member memory
    WRITES feed later members' memory READS of the same (collection,
    index) through a trace-time mem env, matching the per-task path's
    release-edge ordering. Takes the external operands as (donated, kept)
    and returns the slot values ``rp["ret"]`` names, two tuples: the
    externally-consumed slots then the members' write-backs in emission
    order, or, for a shape that donates (ISSUE 34), first the outputs
    the donated operands' buffers go to, in the operands' order (JAX
    aliases the i-th donated argument to the i-th output of its shape
    and dtype), then the rest. ``check``: refuse the trace where the
    outputs cannot take every donated buffer, per shape and dtype
    (:func:`_jit_region_program`). Pure
    w.r.t. its inputs — safe to jit once and reuse across pool
    instantiations, and across every region of one shape: ``rp`` is then
    the shape's canonical plan (:func:`_region_shape`), whose slot and
    memory ids are the region's own numbering. ``rp["name"]`` names the
    program, hence its XLA module (``jit_ptg_region_<classes>``);
    ``scopes[ci]`` is class ``ci``'s name, the ``jax.named_scope`` of its
    members' bodies."""
    import jax
    steps, (ret_a, ret_b) = rp["steps"], rp["ret"]

    def region_program(donated, kept):
        ext_vals = donated + kept
        env: Dict[int, Any] = {}
        menv: Dict[Tuple, Any] = {}
        for ci, key, srcs, base, nd, wbs in steps:
            vals: List[Any] = []
            for kk, v in srcs:
                if kk == "int":
                    vals.append(env[v])
                elif kk == "ext":
                    vals.append(ext_vals[v])
                elif kk == "intm":
                    vals.append(menv[v])
                else:                      # "none": NEW/no input
                    vals.append(None)
            fn = fns[ci]
            if fn is not None:
                # the class's name on the member's operations, so a device
                # trace of a mixed region says whose time it is
                with jax.named_scope(scopes[ci]):
                    outs = fn(*key, *vals)
                for oj, dj in enumerate(written_by_class[ci]):
                    vals[dj] = outs[oj]
            for dj in range(nd):
                env[base + dj] = vals[dj]
            for dj, mk in wbs:
                menv[mk] = vals[dj]
        out = (tuple(env[s] for s in ret_a), tuple(env[s] for s in ret_b))
        if check:
            # what is donated is capped at what the outputs can take, per
            # shape and dtype: JAX warns of a donated buffer it cannot use
            room = collections.Counter(
                (v.shape, v.dtype) for v in out[0] + out[1])
            room.subtract((d.shape, d.dtype) for d in donated)
            if min(room.values()) < 0:
                raise _DonationRefused()
        return out
    if rp.get("name"):
        region_program.__name__ = region_program.__qualname__ = rp["name"]
    return region_program


def _released_at_dispatch(dev_mask: Sequence[int], off: Sequence[int],
                          succs: Sequence[int]) -> List[int]:
    """Per node of a graph (the CSR ``off`` / ``succs``), 1 where the device
    lane may release it the moment it is dispatched (ISSUE 36): a device
    node (``dev_mask``) with at least one successor, every one of them a
    device node of the same mask. Its successors are then programs of the
    one device behind it, which XLA's runtime queues behind the producers
    of their operands; a sink, and a node with one host-bodied or CTL
    successor, retires when the host has seen it complete, as ever.
    Structure alone: it is no part of a region's shape nor of any
    executable's key."""
    return [1 if d and off[i] < off[i + 1]
            and all(dev_mask[t] for t in succs[off[i]:off[i + 1]]) else 0
            for i, d in enumerate(dev_mask)]


def _region_shape(kind: str, steps, out_slots, reads, class_names,
                  donated=()):
    """The canonical plan of a region (ISSUE 29): its steps with slot
    and memory ids renumbered by first appearance, each member's parameter
    tuple cut to the positions its body names (``reads[ci]``; the others
    read 0, which no body sees). Two regions replay as ONE program exactly
    when their canonical plans are equal, so the plan, a nest of tuples,
    is its own signature and the executable cache's key; external
    operands, externally-consumed outputs and write-backs keep their
    positions, so a region hands the shared program its own lists.

    ``donated`` (ISSUE 34): per leading external operand the program is
    given for good, the slot its update chain ends in. Those lead what
    the program returns, so what is donated, and to which output, is
    part of the shape; one that donates nothing keeps the signature it
    always had. ``ret`` is what the program returns, as two
    tuples of canonical slots; ``out_pos`` and ``wb_pos`` say where in
    them (flattened) each of ``out_slots`` and each write-back lies."""
    slot_of: Dict[int, int] = {}
    mem_of: Dict[Tuple, int] = {}
    canon: List[Tuple] = []
    wb_slots: List[int] = []
    for ci, key, srcs, base, nd, wbs in steps:
        csrcs = tuple(
            (kk, slot_of[v]) if kk == "int" else
            (kk, mem_of[v]) if kk == "intm" else (kk, v)
            for kk, v in srcs)
        cbase = len(slot_of)
        for dj in range(nd):
            slot_of[base + dj] = cbase + dj
        cwbs = tuple((dj, mem_of.setdefault(mk, len(mem_of)))
                     for dj, mk in wbs)
        wb_slots.extend(cbase + dj for dj, _mk in wbs)
        ckey = tuple(v if i in reads[ci] else 0 for i, v in enumerate(key))
        canon.append((ci, ckey, csrcs, cbase, nd, cwbs))
    steps, outs = tuple(canon), tuple(slot_of[s] for s in out_slots)
    names = dict.fromkeys(class_names[step[0]] for step in steps)
    shape = {"steps": steps, "n_donated": len(donated),
             "name": "ptg_region_" + "_".join(names)}
    if not donated:
        shape["sig"] = (kind, steps, outs)
        shape["ret"] = (outs, tuple(wb_slots))
        shape["out_pos"] = list(range(len(outs)))
        shape["wb_pos"] = list(range(len(outs), len(outs) + len(wb_slots)))
        return shape
    # the chains' ends first, each serving the externally-consumed slot
    # it is, or else the (first) write-back of it; then the others. Turned
    # by one against the operands: operand i's buffer takes the end of
    # chain i + 1. Paired with its own chain's end, every member of a chain
    # would write the tile it reads, and the TPU compiler's fusions that
    # update in place are three times the code (a 128-member program's
    # executable 95 MB for 31, 1.6x the compile, +0.5 s a load and 2.3 GB
    # more of the chip held by a pool's programs; PERF.md sections 5-6),
    # for a kernel 6 % faster on a chip that waits for the host. Turned,
    # XLA orders the members so that each buffer is read before it is
    # rewritten and copies one tile a program.
    first = tuple(slot_of[s] for s in donated[1:] + donated[:1])
    free = {s: i for i, s in enumerate(first)}
    rest: List[int] = []

    def place(s):
        i = free.pop(s, None)
        if i is None:
            i = len(first) + len(rest)
            rest.append(s)
        return i
    shape["out_pos"] = [place(s) for s in outs]
    shape["wb_pos"] = [place(s) for s in wb_slots]
    shape["ret"] = (first, tuple(rest))
    shape["sig"] = (kind, steps, outs, shape["ret"])
    return shape


class PTGTaskpool(Taskpool):
    """A taskpool instantiated from a PTG program."""

    def __init__(self, program: "PTGProgram", ctx: Context,
                 globals_: Dict[str, Any],
                 collections: Dict[str, Any],
                 name: Optional[str] = None,
                 datatypes: Optional[Dict[str, NamedDatatype]] = None) -> None:
        super().__init__(name or program.spec.name)
        self.program = program
        self.ctx = ctx
        # named dep datatypes (the arenas_datatypes table of the generated
        # taskpool, ref parsec_internal.h:42-47); DEFAULT is the identity
        self.datatypes: Dict[str, NamedDatatype] = {"DEFAULT": default_datatype()}
        self.datatypes.update(datatypes or {})
        #: (id(source payload), dtt name) -> DataCopyFuture — the reshape
        #: promise table: every consumer of (copy, datatype) shares ONE
        #: conversion (ref: parsec_reshape.c repo entries;
        #: input_dep_single_copy_reshape.jdf)
        self._typed_cache: Dict[Tuple[int, str], DataCopyFuture] = {}
        self._typed_lock = threading.Lock()
        #: compiled out-dep tables per (producer class, flow) for the
        #: guard-exact producer-datatype lookup
        self._odt_cache: Dict[Tuple[str, str], List] = {}
        self.env_base: Dict[str, Any] = {"__builtins__": {}}
        self.env_base.update({
            "min": min, "max": max, "abs": abs, "range": range, "len": len,
            "int": int, "divmod": divmod,
        })
        prologue_names: Dict[str, Any] = {}
        if program.spec.prologue:
            # the '%{...%}' host-language escape (jdf2c.c:54): full Python,
            # executed once per instantiation; its definitions become
            # program globals visible to ranges, guards, and bodies
            pns: Dict[str, Any] = {"np": np}
            try:
                exec(compile(program.spec.prologue,  # noqa: S102
                             f"<ptg-prologue:{program.spec.name}>", "exec"),
                     pns)
            except Exception as e:
                output.fatal(f"PTG taskpool {self.name}: prologue failed: {e}")
            prologue_names = {k: v for k, v in pns.items()
                              if not k.startswith("__") and k != "np"}
            self.env_base.update(prologue_names)
        #: what the prologue defined and ``globals=`` did not replace: new
        #: objects in every instantiation, so never part of a cache key
        self._prologue_names = frozenset(prologue_names) - set(globals_)
        self.env_base.update(globals_)
        self.collections = collections
        missing = [g for g in program.spec.globals
                   if g not in globals_ and g not in collections
                   and g not in prologue_names]
        if missing:
            output.fatal(f"PTG taskpool {self.name}: missing globals {missing}")
        #: (tc_name, pkey, flow_index) -> payload shipped from a remote
        #: producer (consumed by prepare_input)
        self._ptg_received: Dict[Tuple, Any] = {}
        self._ptg_lock = threading.Lock()
        #: native execution lane state (set by _startup when eligible) and
        #: the decline reason ("ineligible" | "fallback" | None = engaged)
        self._ptexec_state: Optional[Dict[str, Any]] = None
        self._ptexec_refusal: Optional[str] = None
        #: what ``PTGProgram.instantiate`` took, for ``ptg.lower`` to add
        self._lower_ns = 0
        self._build()
        if ctx.comm is not None and ctx.nb_ranks > 1:
            # distributed PTG: global termination + name-keyed routing
            ctx.comm.fourcounter.monitor_taskpool(self)
            ctx.comm.register_taskpool(self)

    # ------------------------------------------------------------------ build
    def _build(self) -> None:
        spec = self.program.spec
        self._classes: Dict[str, TaskClass] = {}
        # pass 1: shells
        for tcs in spec.task_classes:
            tc = TaskClass(tcs.name, nb_locals=len(tcs.params))
            tc.count_mode = True
            for fs in tcs.flows:
                tc.add_flow(Flow(fs.name, _ACCESS_MAP[fs.access]))
            tc.make_key = (lambda params: (
                lambda tp, loc: tuple(loc[p] for p in params)
            ))(tcs.params)
            # the wire always carries the canonical parameter tuple, even
            # when make_key_fn customizes the local hash key (the receiving
            # rank re-derives locals from it)
            tc._ptg_canonical_key = (lambda params: (
                lambda task: tuple(task.locals[p] for p in params)
            ))(tcs.params)
            self.add_task_class(tc)
            self.repos[tc.task_class_id] = DataRepo(tc.nb_flows, tcs.name)
            self._classes[tcs.name] = tc
        # pass 2: deps, goals, hooks
        for tcs in spec.task_classes:
            self._build_class(tcs, self._classes[tcs.name])
        self.startup_hook = self._startup

    def _env(self, locals_: Dict[str, int]) -> Dict[str, Any]:
        env = dict(self.env_base)
        env.update(locals_)
        return env

    def _build_class(self, tcs: P.TaskClassSpec, tc: TaskClass) -> None:
        spec = self.program.spec
        # ranges, in the order they are declared (a JDF's locals): a bound
        # may read any local declared above it, and the parser has refused
        # one that reads a local declared below. Keys and parameter
        # tuples stay in parameter order
        tc._ptg_ranges = [(r.param, _Expr(r.lo_expr), _Expr(r.hi_expr),
                           _Expr(r.step_expr)) for r in tcs.ranges]
        tc._ptg_spec = tcs
        # header property block (ref: udf.jdf user-defined functions):
        # names resolve against the taskpool globals at instantiate time
        mk_fn = self._resolve_callable(tcs, "make_key_fn",
                                       tcs.header_props.get("make_key_fn"))
        if mk_fn is not None:
            # user-defined task key (ref: udf.jdf ud_make_key): fn(tp,
            # locals) -> hashable key used by the dep repo/hash tables
            tc.make_key = mk_fn
        te_fn = self._resolve_callable(tcs, "time_estimate",
                                       tcs.header_props.get("time_estimate"))
        if te_fn is not None:
            # feeds best-device selection (ref: parsec_internal.h:431-458
            # time_estimate; consumed by DeviceRegistry.select_best_device)
            tc.time_estimate = te_fn
        tc._ptg_startup_fn = self._resolve_callable(
            tcs, "startup_fn", tcs.header_props.get("startup_fn"))

        if tcs.priority_expr:
            prio = _Expr(tcs.priority_expr)
            tc.properties["priority"] = lambda loc, _p=prio: int(_p(self._env(loc)))
        if tcs.affinity is not None:
            aff_name = tcs.affinity.name
            aff_exprs = [_Expr(e) for e in tcs.affinity.index_exprs]
            def affinity_rank(loc, _n=aff_name, _e=aff_exprs):
                dc = self.collections.get(_n)
                if dc is None:
                    return 0
                env = self._env(loc)
                return dc.rank_of(*[ex(env) for ex in _e])
            tc._ptg_rank_of = affinity_rank
        else:
            tc._ptg_rank_of = lambda loc: 0

        # in-deps: per flow, ordered guarded alternatives
        in_specs: List[List[Tuple]] = []
        for fs in tcs.flows:
            alts = []
            for d in fs.deps:
                if d.direction != "in":
                    continue
                guard = _Expr(d.guard) if d.guard else None
                alts.append((guard, self._mk_ep(d.endpoint, d.dtt)))
                if d.else_endpoint is not None:
                    alts.append(("else", self._mk_ep(d.else_endpoint, d.dtt)))
            in_specs.append(alts)
        tc._ptg_in_specs = in_specs

        def active_in(alts: List[Tuple], env: Dict[str, Any]):
            taken = False
            for guard, ep in alts:
                if guard is None:
                    return ep
                if guard == "else":
                    if not taken:
                        return ep
                    continue
                taken = bool(guard(env))
                if taken:
                    return ep
            return None

        def goal_fn(loc: Dict[str, int]) -> int:
            env = self._env(loc)
            goal = 0
            for alts in in_specs:
                ep = active_in(alts, env)
                if ep is not None and ep["kind"] == "task":
                    n = 1
                    for ex in ep["exprs"]:
                        if ex.is_range:
                            n *= len(ex.values(env))
                    goal += n
            return goal

        tc.dependencies_goal_fn = goal_fn
        tc._ptg_active_in = active_in
        for fs, alts in zip(tcs.flows, in_specs):
            if fs.access == P.FLOW_CTL:
                continue
            for _guard, ep in alts:
                if ep and ep["kind"] == "task" and \
                        any(ex.is_range for ex in ep["exprs"]):
                    raise P.PTGSyntaxError(
                        f"{tcs.name}.{fs.name}: range gather is only valid "
                        f"on CTL flows (a data flow has exactly one input)")

        # out-deps -> generic-engine Dep descriptors
        for fi, fs in enumerate(tcs.flows):
            flow = tc.flows[fi]
            for d in fs.deps:
                if d.direction != "out":
                    continue
                self._add_out_dep(tc, flow, d.guard, d.endpoint, dtt=d.dtt,
                                  dtt_remote=d.dtt_remote)
                if d.else_endpoint is not None:
                    self._add_out_dep(tc, flow, d.guard, d.else_endpoint,
                                      negate=True, dtt=d.dtt,
                                      dtt_remote=d.dtt_remote)

        # hooks — flowless AND CTL-only classes (the EP/control shapes)
        # skip the data prepare hook entirely instead of paying per-task
        # env construction for flows that carry no data (the generic
        # prepare's CTL skip is a cheap loop; this one built an env first)
        has_data_flows = any(not (f.access & FLOW_ACCESS_CTL)
                             for f in tc.flows)
        tc.prepare_input = self._mk_prepare_input(tc) if has_data_flows \
            else None
        if any(getattr(f, "_ptg_mem_out", None) for f in tc.flows):
            tc.complete_execution = self._mk_complete(tc)
        nb_bodies = 0
        for body in tcs.bodies:
            fn = self._compile_body(tcs, body)
            if nb_bodies == 0:
                tc._ptg_body_fn = fn    # cross-DSL replay (pins ptg_to_dtd)
            # [evaluate = fn]: per-incarnation gate (ref: udf.jdf evaluate
            # properties selecting the chore); fn(stream, task) -> HOOK_*
            evaluate = self._resolve_callable(tcs, "evaluate", body.evaluate)
            if body.device == "TPU":
                tc.add_chore(Chore(DEV_TPU, make_tpu_hook(
                    self._mk_tpu_submit(tc, fn)), evaluate=evaluate))
                # TPU bodies also serve as host chores through the same
                # jitted function (degrades to the CPU backend off-pod)
                tc.add_chore(Chore(DEV_CPU, self._mk_cpu_hook(tc, fn),
                                   evaluate=evaluate))
            else:
                tc.add_chore(Chore(DEV_CPU, self._mk_cpu_hook(tc, fn),
                                   evaluate=evaluate))
            nb_bodies += 1

    def _resolve_callable(self, tcs: P.TaskClassSpec, prop: str,
                          name: Optional[str]):
        """Resolve a user-function property name against the taskpool
        globals; fatal when it does not name a callable."""
        if name is None:
            return None
        fn = self.env_base.get(name)
        if not callable(fn):
            output.fatal(f"{tcs.name}: property {prop}={name!r} does not "
                         f"name a callable in the taskpool globals")
        return fn

    def _mk_ep(self, ep: Optional[P.Endpoint],
               dtt: Optional[str] = None) -> Optional[Dict[str, Any]]:
        if ep is None:
            return None
        return {
            "kind": ep.kind,
            "name": ep.name,
            "flow": ep.flow,
            "exprs": [_index_expr(e) for e in ep.index_exprs],
            "dtt": dtt,
        }

    # ------------------------------------------------------------- datatypes
    def _dtt(self, name: Optional[str]) -> Optional[NamedDatatype]:
        if name is None:
            return None
        d = self.datatypes.get(name)
        if d is None:
            output.fatal(f"PTG taskpool {self.name}: dep references unknown "
                         f"datatype {name!r} (registered: "
                         f"{sorted(self.datatypes)})")
        return d

    def _typed_payload(self, value: Any, dtt: Optional[NamedDatatype]) -> Any:
        """Reshape-promise path (ref: parsec_get_copy_reshape_from_dep,
        parsec_internal.h:688-696): the conversion runs lazily, ONCE, and is
        shared by every consumer of (source copy, datatype). Identity
        datatypes return the original untouched (avoidable_reshape.jdf)."""
        if dtt is None or dtt.identity:
            return value
        payload = _payload_of(value)
        key = (id(payload), dtt.name)
        with self._typed_lock:
            fut = self._typed_cache.get(key)
            if fut is None:
                src = value if isinstance(value, DataCopy) \
                    else DataCopy(None, 0, payload)
                fut = DataCopyFuture(src, dtt, lambda c, d: d.convert(c))
                self._typed_cache[key] = fut
        return fut.request()

    def _out_dep_table(self, peer_name: str, peer_flow: str) -> List:
        """Compiled (guard, [(which, class, flow, index_exprs)], dtt, wire)
        rows for a producer flow's out-deps (compiled once per edge)."""
        key = (peer_name, peer_flow)
        tbl = self._odt_cache.get(key)
        if tbl is None:
            tbl = []
            pf = self.program.spec.task_class(peer_name).flow(peer_flow)
            for d in (pf.deps if pf is not None else []):
                if d.direction != "out":
                    continue
                g = _Expr(d.guard) if d.guard else None
                eps = {}
                for which, ep in (("then", d.endpoint),
                                  ("else", d.else_endpoint)):
                    if ep is not None and ep.kind == "task":
                        eps[which] = (ep.name, ep.flow,
                                      [_index_expr(e) for e in ep.index_exprs])
                wire = d.dtt_remote if d.dtt_remote is not None else d.dtt
                tbl.append((g, eps, d.dtt, wire))
            self._odt_cache[key] = tbl
        return tbl

    def _producer_out_dtt(self, peer_name: str, peer_flow: str,
                          my_class: str, my_flow: str,
                          plocals: Dict[str, int],
                          my_key: Tuple[int, ...]
                          ) -> Tuple[Optional[str], Optional[str]]:
        """(local [type], wire type) the producer declared on the out-dep
        that ACTUALLY feeds this task — guards evaluated under the
        producer's locals and the fan-out index set checked against my key
        (a flow may have several typed edges to the same class/flow behind
        different guards)."""
        env = self._env(plocals)
        import itertools
        for g, eps, dtt, wire in self._out_dep_table(peer_name, peer_flow):
            # guard/index exceptions propagate: the sender side evaluates
            # the same expressions (dep.cond / target_locals) and lets them
            # raise, and the two ends of a remote edge must agree
            which = "then"
            if g is not None:
                which = "then" if bool(g(env)) else "else"
            ep = eps.get(which)
            if ep is None or ep[0] != my_class or ep[1] != my_flow:
                continue
            axes = [ex.values(env) for ex in ep[2]]
            if tuple(my_key) not in set(itertools.product(*axes)):
                continue
            return dtt, wire
        return None, None

    def _add_out_dep(self, tc: TaskClass, flow: Flow, guard: Optional[str],
                     ep: P.Endpoint, negate: bool = False,
                     dtt: Optional[str] = None,
                     dtt_remote: Optional[str] = None) -> None:
        gexpr = _Expr(guard) if guard else None

        def cond(loc, _g=gexpr, _n=negate):
            if _g is None:
                return True
            v = bool(_g(self._env(loc)))
            return (not v) if _n else v

        if ep.kind == "task":
            peer_tc = self._classes[ep.name]
            peer_spec = self.program.spec.task_class(ep.name)
            peer_flow_idx = next(i for i, f in enumerate(peer_spec.flows)
                                 if f.name == ep.flow)
            exprs = [_index_expr(e) for e in ep.index_exprs]

            def target_locals(loc, _e=exprs, _params=tuple(peer_spec.params)):
                env = self._env(loc)
                import itertools
                axes = [ex.values(env) for ex in _e]
                return [dict(zip(_params, combo))
                        for combo in itertools.product(*axes)]

            dep = Dep(
                task_class=peer_tc, flow_index=peer_flow_idx,
                dep_index=len(flow.deps_out), cond=cond,
                target_locals=target_locals,
                datatype=dtt)        # named datatype (local reshape)
            # [type_remote] overrides the wire datatype only — local
            # successors keep the original copy (local_no_reshape.jdf)
            dep.wire_datatype = dtt_remote if dtt_remote is not None else dtt
            flow.deps_out.append(dep)
        elif ep.kind == "memory":
            exprs = [_Expr(e) for e in ep.index_exprs]
            flow._ptg_mem_out = getattr(flow, "_ptg_mem_out", [])
            flow._ptg_mem_out.append((cond, ep.name, exprs, dtt))
        # 'null' endpoints: data is dropped

    # ------------------------------------------------------------------ hooks
    def _mk_prepare_input(self, tc: TaskClass):
        my_class = tc._ptg_spec.name
        my_flows = [f.name for f in tc._ptg_spec.flows]

        def prepare_input(stream, task: Task) -> int:
            env = self._env(task.locals)
            # datatype resolution always compares CANONICAL parameter
            # tuples, independent of any user make_key_fn hash key
            canonical_key = tc._ptg_canonical_key(task)
            for fi, flow in enumerate(tc.flows):
                if flow.access & FLOW_ACCESS_CTL:
                    # control deps carry no data: their only job (the
                    # dependency count) was done at the producer's release
                    continue
                alts = tc._ptg_in_specs[fi]
                ep = tc._ptg_active_in(alts, env)
                if ep is None:
                    continue
                slot = task.data[fi]
                in_dtt = self._dtt(ep.get("dtt"))
                if ep["kind"] == "memory":
                    dc = self.collections.get(ep["name"])
                    if dc is None:
                        output.fatal(f"unknown collection {ep['name']!r}")
                    data = dc.data_of(*[ex(env) for ex in ep["exprs"]])
                    copy = data.newest_copy()
                    if in_dtt is not None and not in_dtt.identity:
                        # read-reshape: a NEW typed datacopy, shared by all
                        # consumers of (copy, datatype) via the promise table
                        slot.data_in = self._typed_payload(copy, in_dtt)
                    else:
                        # unattached wrapper: body outputs never mutate the
                        # collection implicitly (write-back = explicit out-deps)
                        slot.data_in = DataCopy(None, 0, _payload_of(copy))
                elif ep["kind"] == "task":
                    peer = self._classes[ep["name"]]
                    peer_spec = self.program.spec.task_class(ep["name"])
                    pkey = tuple(ex.values(env)[0] for ex in ep["exprs"])
                    pf_idx = next(i for i, f in enumerate(peer_spec.flows)
                                  if f.name == ep["flow"])
                    plocals = dict(zip(peer_spec.params, pkey))
                    out_dtt_name, wire_dtt_name = self._producer_out_dtt(
                        ep["name"], ep["flow"], my_class, my_flows[fi],
                        plocals, canonical_key)
                    if (self.ctx.nb_ranks > 1 and self.ctx.comm is not None
                            and self.task_rank_of(peer, plocals) != self.ctx.my_rank):
                        # remote producer: payload was shipped by its rank,
                        # ALREADY reshaped to the out-dep type before the
                        # wire (pre-send reshape); never re-reshape with the
                        # same type (remote_no_re_reshape.jdf). The arrival
                        # is keyed by wire datatype so one flow fanning out
                        # under several types delivers each shape intact
                        # (remote_multiple_outs_same_pred_flow.jdf)
                        with self._ptg_lock:
                            got = self._ptg_received.get(
                                (ep["name"], pkey, pf_idx, wire_dtt_name))
                        if got is None:
                            output.fatal(f"{task!r}: remote payload "
                                         f"{ep['name']}{pkey} missing")
                        payload, wire_dtt = got
                        if in_dtt is not None and not in_dtt.identity \
                                and in_dtt.name != wire_dtt:
                            slot.data_in = self._typed_payload(payload, in_dtt)
                        else:
                            slot.data_in = DataCopy(None, 0, payload)
                        continue
                    repo = self.repos[peer.task_class_id]
                    # repo entries are stored under the producer's task key,
                    # which may come from a user make_key_fn
                    entry = repo.lookup_entry(peer.make_key(self, plocals))
                    if entry is None:
                        output.fatal(f"{task!r}: missing repo entry "
                                     f"{ep['name']}{pkey}")
                    value = entry.data[pf_idx]
                    # output-reshape (producer's [type]) then input-reshape
                    # (this dep's [type]) when they differ; identical names
                    # convert exactly once (avoidable_reshape.jdf)
                    out_dtt = self._dtt(out_dtt_name)
                    value = self._typed_payload(value, out_dtt)
                    if in_dtt is not None and (out_dtt is None
                                               or in_dtt.name != out_dtt.name):
                        value = self._typed_payload(value, in_dtt)
                    slot.data_in = value
                    slot.source_repo_entry = entry
                elif ep["kind"] == "new":
                    slot.data_in = None
            return HOOK_DONE
        return prepare_input

    def _body_inputs(self, tc: TaskClass, task: Task) -> List[Any]:
        vals = [task.locals[p] for p in tc._ptg_spec.params]
        for fi, flow in enumerate(tc.flows):
            if flow.access & FLOW_ACCESS_CTL:
                continue
            vals.append(_payload_of(task.data[fi].data_in))
        return vals

    def _store_outputs(self, tc: TaskClass, task: Task, outs) -> None:
        if outs is None:
            outs = ()
        elif not isinstance(outs, (tuple, list)):
            outs = (outs,)
        oi = 0
        for fi, flow in enumerate(tc.flows):
            if flow.access & FLOW_ACCESS_CTL or not (flow.access & FLOW_ACCESS_WRITE):
                continue
            if oi < len(outs):
                task.data[fi].data_out = outs[oi]
            oi += 1

    def _mk_cpu_hook(self, tc: TaskClass, fn):
        if all(f.access & FLOW_ACCESS_CTL for f in tc.flows):
            # flowless or CTL-only class (the EP/control-task shapes): no
            # arrays flow through the body, so the jit wrapper is pure
            # dispatch overhead (~10us/call) — run the raw python body
            raw = getattr(fn, "__wrapped__", fn)
            # the agglomerated-sweep entry (flowless) and the native
            # execution lane's batched-dispatch entry (CTL-only) both
            # call the raw body with the class parameters
            tc._ptg_raw_body = raw

            def flowless_hook(stream, task: Task) -> int:
                raw(*[task.locals[p] for p in tc._ptg_spec.params])
                return HOOK_DONE
            return flowless_hook

        def hook(stream, task: Task) -> int:
            outs = fn(*self._body_inputs(tc, task))
            self._store_outputs(tc, task, outs)
            return HOOK_DONE
        return hook

    def _mk_tpu_submit(self, tc: TaskClass, fn):
        def submit(device, task: Task, inputs: List[Any]):
            vals = [task.locals[p] for p in tc._ptg_spec.params]
            for fi, flow in enumerate(tc.flows):
                if flow.access & FLOW_ACCESS_CTL:
                    continue
                vals.append(inputs[fi])
            return fn(*vals)
        return submit

    def _mk_complete(self, tc: TaskClass):
        def complete(stream, task: Task) -> int:
            env = self._env(task.locals)
            for fi, flow in enumerate(tc.flows):
                mem_outs = getattr(flow, "_ptg_mem_out", None)
                if not mem_outs:
                    continue
                slot = task.data[fi]
                value = slot.data_out if slot.data_out is not None else \
                    _payload_of(slot.data_in)
                value = _payload_of(value)
                for cond, dc_name, exprs, dtt_name in mem_outs:
                    if not cond(task.locals):
                        continue
                    dc = self.collections.get(dc_name)
                    data = dc.data_of(*[ex(env) for ex in exprs])
                    host = data.get_copy(0)
                    dtt = self._dtt(dtt_name)
                    if host is None:
                        v = value if dtt is None or dtt.identity \
                            else dtt.extract(value)
                        data.create_copy(0, v, COHERENCY_OWNED)
                    elif dtt is not None and not dtt.identity:
                        # typed write-back merges only the datatype's region
                        # into the tile; the complement is preserved
                        host.payload = dtt.insert(host.payload, value)
                    else:
                        host.payload = value
                    data.bump_version(0)
            return HOOK_DONE
        return complete

    def _compile_body(self, tcs: P.TaskClassSpec, body: P.BodySpec):
        """Body text → jitted function(params..., flows...) -> written flows."""
        data_flows = [f.name for f in tcs.flows if f.access != P.FLOW_CTL]
        written = [f.name for f in tcs.flows
                   if f.access in (P.FLOW_WRITE, P.FLOW_RW)]
        args = list(tcs.params) + data_flows
        for name in args:
            if not name.isidentifier():
                raise P.PTGSyntaxError(f"bad identifier {name!r}")
        src = textwrap.dedent(body.source)
        import re as _re
        if _re.search(r"\breturn\b", src):
            raise P.PTGSyntaxError(
                f"BODY of {tcs.name} must not use 'return'; written flows "
                f"are returned automatically", body.line_no)
        fn_src = (f"def __ptg_body__({', '.join(args)}):\n"
                  + textwrap.indent(src if src.strip() else "pass", "    ")
                  + f"\n    return ({', '.join(written)}{',' if written else ''})")
        ns: Dict[str, Any] = {}
        ns.update(self.env_base)
        try:
            import jax
            import jax.numpy as jnp
            ns.setdefault("jnp", jnp)
            ns.setdefault("jax", jax)
            ns.setdefault("lax", jax.lax)
        except Exception:
            pass
        ns.setdefault("np", np)
        try:
            exec(compile(fn_src, f"<ptg-body:{tcs.name}>", "exec"), ns)  # noqa: S102
        except SyntaxError as e:
            raise P.PTGSyntaxError(
                f"BODY of {tcs.name} does not compile: {e}", body.line_no) from e
        raw = ns["__ptg_body__"]
        import jax
        return jax.jit(raw)

    def _ptg_data_arrived(self, tc_name: str, pkey, flow_index: int,
                          payload, wire_dtt: Optional[str] = None) -> None:
        """A remote producer's output landed here: credit every local
        successor it feeds, re-deriving them from the replicated program
        (the reference's phantom-task iterate-successors,
        remote_dep_mpi.c:861). ``wire_dtt`` names the datatype the payload
        was reshaped to BEFORE the wire (pre-send reshape) so consumers
        never re-reshape with the same type."""
        pkey = tuple(pkey) if isinstance(pkey, (list, tuple)) else (pkey,)
        with self._ptg_lock:
            self._ptg_received[(tc_name, pkey, flow_index, wire_dtt)] = \
                (payload, wire_dtt)
        tc = self._classes[tc_name]
        tcs = self.program.spec.task_class(tc_name)
        plocals = dict(zip(tcs.params, pkey))
        my = self.ctx.my_rank
        ready = []
        flow = tc.flows[flow_index]
        for dep in flow.deps_out:
            if getattr(dep, "wire_datatype", dep.datatype) != wire_dtt:
                # each typed send credits exactly the successors on edges
                # of its own wire datatype (one flow may fan out under
                # several)
                continue
            if dep.cond is not None and not dep.cond(plocals):
                continue
            targets = dep.target_locals(plocals) if dep.target_locals else [plocals]
            for tl in targets:
                succ_tc = dep.task_class
                if self.task_rank_of(succ_tc, tl) != my:
                    continue
                key = succ_tc.make_key(self, tl)
                goal = (succ_tc.dependencies_goal_fn(tl)
                        if succ_tc.dependencies_goal_fn else None)
                if self.update_deps(succ_tc, key, 1, goal):
                    ready.append(self.ctx.make_task(self, succ_tc, dict(tl)))
        if ready:
            self.ctx.schedule(ready)

    def _declare_complete(self) -> None:
        super()._declare_complete()
        # retire the reshape-promise table and parked remote payloads: the
        # graph is done, no consumer can request them again (the reference
        # retires reshape promises with repo-entry refcounts)
        with self._typed_lock:
            self._typed_cache.clear()
        with self._ptg_lock:
            self._ptg_received.clear()

    # ------------------------------------------------------------------ startup
    def _enumerate(self):
        """Yield every locals assignment in the task space, class by class
        (the generated startup-task enumerator, jdf2c.c:3047)."""
        for tcs in self.program.spec.task_classes:
            tc = self._classes[tcs.name]
            yield from ((tc, loc) for loc in self._enum_class(tc))

    def _enum_class(self, tc: TaskClass):
        ranges = tc._ptg_ranges
        def rec(i: int, loc: Dict[str, int]):
            if i == len(ranges):
                yield dict(loc)
                return
            param, lo, hi, step = ranges[i]
            env = self._env(loc)
            lo_v, hi_v, st_v = int(lo(env)), int(hi(env)), int(step(env))
            end = hi_v + 1 if st_v > 0 else hi_v - 1
            for v in range(lo_v, end, st_v):        # inclusive, like JDF
                loc[param] = v
                yield from rec(i + 1, loc)
            loc.pop(param, None)
        yield from rec(0, {})

    def _agglomerable(self, tc: TaskClass) -> bool:
        """A class the runtime may execute as ONE fused sweep at startup:
        statically proven independent — no flows at all (so no deps in or
        out, no data, nothing downstream waits on any instance) and no
        custom startup seeding. The PTG analogue of capture: when the
        static structure proves there is nothing to schedule AROUND, the
        per-task scheduling cycle is pure overhead (the reference pays ~0
        for that cycle in C; we eliminate it instead)."""
        return (not tc.flows
                and getattr(tc, "_ptg_startup_fn", None) is None
                # exactly one ungated body: multi-incarnation classes pick
                # a chore per task ([evaluate] gates, device choice) — the
                # sweep must not bypass that selection
                and len(tc.incarnations) == 1
                and tc.incarnations[0].evaluate is None
                # a sweep runs on the startup thread: with worker streams
                # the per-task path spreads instances across cores instead
                and len(self.ctx.streams) == 1
                and mca.get("ptg_agglomerate", True)
                and not self.ctx.pins.enabled
                and not self.ctx.paranoid)

    def _enum_class_fast(self, tc: TaskClass):
        """Param-value tuples via itertools.product when every range bound
        is static (depends on globals only); None when bounds reference
        other params (triangular spaces fall back to the dict walk) or
        the ranges are not declared in parameter order."""
        import itertools
        env0 = self._env({})
        rs = []
        for i, (param, lo, hi, step) in enumerate(tc._ptg_ranges):
            if param != tc._ptg_spec.params[i]:
                return None
            try:
                lo_v, hi_v, st_v = int(lo(env0)), int(hi(env0)), int(step(env0))
            except Exception:  # noqa: BLE001 — bound needs an outer param
                return None
            rs.append(range(lo_v, hi_v + 1 if st_v > 0 else hi_v - 1, st_v))
        return itertools.product(*rs) if rs else iter(((),))

    def _run_agglomerated(self, stream, tc: TaskClass) -> int:
        """Execute a proven-independent flowless class as one fused sweep;
        returns the instance count (reported executed, never scheduled)."""
        raw = tc._ptg_raw_body
        my_rank = self.ctx.my_rank
        distributed = self.ctx.nb_ranks > 1 and self.ctx.comm is not None
        n = 0
        it = None if distributed else self._enum_class_fast(tc)
        if it is not None:
            for vals in it:
                raw(*vals)
                n += 1
        else:
            params = tc._ptg_spec.params
            for loc in self._enum_class(tc):
                if distributed and tc._ptg_rank_of(loc) != my_rank:
                    continue
                raw(*[loc[p] for p in params])
                n += 1
        stream.nb_executed += n
        return n

    # ------------------------------------------------------- native exec lane
    def _ptexec_class_device(self, tc: TaskClass) -> bool:
        """True for the TPU-bodied shape (``BODY [type=TPU]``): exactly
        the two ungated incarnations _build_class emits — the TPU chore
        plus its CPU twin running the same jitted function."""
        incs = tc.incarnations
        return (len(incs) == 2 and incs[0].device_type == DEV_TPU
                and incs[1].device_type == DEV_CPU
                and incs[0].evaluate is None and incs[1].evaluate is None)

    def _ptexec_class_eligible(self, tc: TaskClass) -> bool:
        """May this class's whole FSM run inside the native lane
        (native/src/ptexec.cpp)?  Eligibility = the per-task cycle carries
        no state the lane does not model. The lane models: CTL edges, DATA
        flows (the versioned slot hand-off + the datarepo usagelmt/usagecnt
        retire protocol live in the lane's per-task slot array), memory
        reads/write-backs, ``priority`` properties (a native ready heap),
        and — eligibility v3, ISSUE 10 — TPU-bodied classes: their tasks
        surface onto the native DEVICE lane (ptdev) when one is up, or run
        the same jitted function through the CPU dispatch when no
        accelerator device exists (which is exactly what the interpreted
        FSM's chore selection would have picked). It does NOT model: named
        datatypes (reshape promises), evaluate-gated or >2-incarnation
        chore selection, multi-body classes, or custom startup seeding.
        Pool-level gates (distributed ranks, PINS, paranoid, device-lane
        availability) live in :meth:`_ptexec_prepare`."""
        if getattr(tc, "_ptg_startup_fn", None) is not None:
            return False
        if tc._ptg_spec.header_props.get("make_key_fn") is not None:
            # a user task-key function feeds the dep repos / hash tables —
            # machinery the lane bypasses entirely; calling (or silently
            # not calling) a user hook is observable behavior
            return False
        if len(tc._ptg_spec.bodies) != 1:
            return False
        if not self._ptexec_class_device(tc):
            # (a device class with a user `time_estimate` hook used to
            # decline here — the PR 10 carve-out. ISSUE 18 erased it: the
            # lane now CALLS the hook at the instantiation boundary to
            # seed the cost model's cold-start prior, restoring the
            # best-device semantics natively instead of falling back to
            # the interpreted FSM. See _ptexec_seed_prior.)
            if len(tc.incarnations) != 1 or \
                    tc.incarnations[0].device_type != DEV_CPU or \
                    tc.incarnations[0].evaluate is not None:
                return False
        has_body = tc._ptg_spec.bodies[0].source.strip() not in ("", "pass")
        if not any(not (f.access & FLOW_ACCESS_CTL) for f in tc.flows):
            # CTL/flowless: non-empty bodies dispatch through the raw-body
            # callback (params only, no data marshalling)
            return not has_body or getattr(tc, "_ptg_raw_body", None) is not None
        # data flows: any NAMED datatype means reshape promises / typed
        # write-backs — state that stays with the Python FSM
        for alts in tc._ptg_in_specs:
            for _guard, ep in alts:
                if ep is not None and ep.get("dtt") is not None:
                    return False
        for f in tc.flows:
            for dep in f.deps_out:
                if dep.datatype is not None or \
                        getattr(dep, "wire_datatype", None) is not None:
                    return False
            for mo in getattr(f, "_ptg_mem_out", None) or []:
                if mo[3] is not None:     # (cond, dc_name, exprs, dtt_name)
                    return False
        # non-empty data bodies dispatch the jitted class function
        return not has_body or getattr(tc, "_ptg_body_fn", None) is not None

    #: the builtins __init__ injects into env_base — identical in every
    #: instantiation, so they never enter the cache signature. Matched by
    #: IDENTITY: a user global that shadows one of these names is real
    #: state and must poison the cache key instead.
    _PTEXEC_SAFE_ENV = {"min": min, "max": max, "abs": abs, "range": range,
                        "len": len, "int": int, "divmod": divmod}

    def _ptexec_cache_key(self, names: Tuple[str, ...], place: Tuple):
        """Cache signature for the flattened graph: the task space and the
        edge structure depend only on the program text and the globals the
        range/guard/index expressions read. A module-level function handed
        in ``globals=`` (the kernels a JDF's bodies call by name) enters
        the signature by identity, as a jitted function keys its callee:
        the key holds the function, so the identity stays its own. Any
        other non-primitive global — a nested function or a lambda made
        per call, a prologue's definitions (``exec``'d per instantiation:
        new objects every pool), an array, a module — makes the
        instantiation uncacheable; flatten still runs, per pool.

        ``place`` is the placement fingerprint (ISSUE 12 satellite):
        (nb_ranks, comm lane, device lane, device fingerprint, fusion
        config). The cached entry now carries the FUSION PLAN — which
        depends on which classes ride the device lane and on the fusion
        knobs — and the region executable cache hangs off this key, so a
        cached CSR (or compiled region program) can never be replayed
        against a different mesh/device layout."""
        sig = []
        for k, v in self.env_base.items():
            if k == "__builtins__" or self._PTEXEC_SAFE_ENV.get(k) is v:
                continue
            if v is None or isinstance(v, (int, float, str, bool)) or (
                    isinstance(v, types.FunctionType)
                    and "<locals>" not in v.__qualname__
                    and k not in self._prologue_names):
                sig.append((k, v))
            else:
                return None
        # names are unique, so the sort never compares two values
        return (tuple(sorted(sig, key=lambda kv: kv[0])), names, place)

    def _ptexec_flatten(self, classes: List[TaskClass]):
        """Emit the flattened tables the native lane consumes (the jdf2c
        moment: the whole control structure leaves Python): the CSR
        successor table + per-task dependency goals, and — for data-flow
        pools — each task's flow table: one data slot per (task, data
        flow), per-slot usage limits (the repo usagelmt, counted from the
        consumer side), input slot references resolved from the guarded
        in-deps, memory reads (symbolic: collection name + static index,
        resolved per pool), memory write-backs, and per-task priorities.
        Returns None when the declared in/out dep sides disagree — the
        Python FSM would mask one-sided declarations differently, so the
        lane refuses rather than diverge."""
        id_of: Dict[Tuple[int, Tuple[int, ...]], int] = {}
        params_by_class: List[List[Tuple[int, ...]]] = []
        bases: List[int] = []
        n = 0
        for ci, tc in enumerate(classes):
            params = tc._ptg_spec.params
            insts = [tuple(loc[p] for p in params)
                     for loc in self._enum_class(tc)]
            bases.append(n)
            params_by_class.append(insts)
            for key in insts:
                id_of[(ci, key)] = n
                n += 1
        class_index = {tc._ptg_spec.name: ci
                       for ci, tc in enumerate(classes)}
        # per-class data-flow tables: flow indices that carry data, in flow
        # order (= the body's flow-argument order, _compile_body)
        dflows_by_class = [[fi for fi, f in enumerate(tc.flows)
                            if not (f.access & FLOW_ACCESS_CTL)]
                           for tc in classes]
        has_data = any(dflows_by_class)
        has_prio = any("priority" in tc.properties for tc in classes)
        # slot assignment: contiguous per task, one per data flow
        slot_base = [0] * n
        n_slots = 0
        if has_data:
            for ci, tc in enumerate(classes):
                nd = len(dflows_by_class[ci])
                for key in params_by_class[ci]:
                    slot_base[id_of[(ci, key)]] = n_slots
                    n_slots += nd
        goals = [0] * n
        prio = [0] * n
        edges: List[List[int]] = [[] for _ in range(n)]
        indeg = [0] * n
        in_refs = [-1] * n_slots    # per slot: the owning flow's input ref
        slot_uses = [0] * n_slots   # per slot: task-kind consumer count
        in_edges: List[List[int]] = [[] for _ in range(n)] if has_data else []
        mem_idx_of: Dict[Tuple[str, Tuple[int, ...]], int] = {}
        mem_reads: List[Tuple[str, Tuple[int, ...]]] = []
        writebacks: List[Tuple[int, int, str, Tuple[int, ...]]] = []
        for ci, tc in enumerate(classes):
            params = tc._ptg_spec.params
            prio_fn = tc.properties.get("priority")
            dflows = dflows_by_class[ci]
            # replay the param tuples materialized above instead of
            # re-walking the range expressions (halves flatten latency)
            for key in params_by_class[ci]:
                loc = dict(zip(params, key))
                my_id = id_of[(ci, key)]
                goals[my_id] = tc.dependencies_goal_fn(loc)
                if prio_fn is not None:
                    p = int(prio_fn(loc))
                    if not (-(1 << 31) <= p < (1 << 31)):
                        # the native heap is int32; the Python FSM orders
                        # by full ints — decline rather than wrap/clamp
                        # into a different dispatch order
                        return None
                    prio[my_id] = p
                for flow in tc.flows:
                    for dep in flow.deps_out:
                        if dep.task_class is None:
                            continue
                        if dep.cond is not None and not dep.cond(loc):
                            continue
                        si = class_index.get(dep.task_class.name)
                        if si is None:
                            return None     # edge into a non-lane class
                        sparams = classes[si]._ptg_spec.params
                        targets = dep.target_locals(loc) \
                            if dep.target_locals else [loc]
                        if isinstance(targets, dict):
                            targets = [targets]
                        for tl in targets:
                            sid = id_of.get(
                                (si, tuple(tl[p] for p in sparams)))
                            if sid is None:
                                return None  # successor outside the space
                            edges[my_id].append(sid)
                            indeg[sid] += 1
                if not dflows:
                    continue
                # the data side of the flow table: resolve this task's
                # active in-dep per data flow (exactly what prepare_input
                # does, once, at flatten instead of per dispatch)
                env = self._env(loc)
                base = slot_base[my_id]
                for dj, fi in enumerate(dflows):
                    ep = tc._ptg_active_in(tc._ptg_in_specs[fi], env)
                    if ep is None or ep["kind"] in ("new", "null"):
                        pass                          # ref stays -1 (no input)
                    elif ep["kind"] == "task":
                        si = class_index.get(ep["name"])
                        if si is None:
                            return None   # producer outside the lane set
                        peer_spec = classes[si]._ptg_spec
                        pf_idx = next(i for i, f in enumerate(peer_spec.flows)
                                      if f.name == ep["flow"])
                        try:
                            pdj = dflows_by_class[si].index(pf_idx)
                        except ValueError:
                            return None   # data read from a CTL flow
                        pkey = tuple(ex.values(env)[0] for ex in ep["exprs"])
                        pid = id_of.get((si, pkey))
                        if pid is None:
                            return None   # producer outside the space
                        ref = slot_base[pid] + pdj
                        in_refs[base + dj] = ref
                        slot_uses[ref] += 1           # the repo usagelmt
                        in_edges[my_id].append(ref)
                    elif ep["kind"] == "memory":
                        idx = tuple(int(ex(env)) for ex in ep["exprs"])
                        mk = (ep["name"], idx)
                        mi = mem_idx_of.get(mk)
                        if mi is None:
                            mi = mem_idx_of[mk] = len(mem_reads)
                            mem_reads.append(mk)
                        in_refs[base + dj] = -2 - mi
                    else:
                        return None       # an endpoint kind the lane ignores
                    mem_outs = getattr(tc.flows[fi], "_ptg_mem_out", None)
                    if mem_outs:
                        for cond, dc_name, exprs, _dtt in mem_outs:
                            if not cond(loc):
                                continue
                            idx = tuple(int(ex(env)) for ex in exprs)
                            writebacks.append((my_id, dj, dc_name, idx))
        if indeg != goals:
            # producer-declared edges and consumer-declared goals disagree
            output.debug_verbose(1, "ptg",
                                 f"{self.name}: native lane refused "
                                 f"(in-dep goals != out-dep edges)")
            return None
        off = [0] * (n + 1)
        for i, e in enumerate(edges):
            off[i + 1] = off[i] + len(e)
        succs: List[int] = []
        for e in edges:
            succs.extend(e)
        flat = {"n": n, "goals": goals, "off": off, "succs": succs,
                "bases": bases, "params": params_by_class,
                "prio": prio if has_prio else None, "data": None}
        if has_data:
            in_off = [0] * (n + 1)
            for i, e in enumerate(in_edges):
                in_off[i + 1] = in_off[i] + len(e)
            in_slots: List[int] = []
            for e in in_edges:
                in_slots.extend(e)
            # per-id class index: the dispatch loop runs per TASK — a list
            # lookup beats a bisect over the class bases at that frequency
            cls_of: List[int] = []
            for ci in range(len(classes)):
                cls_of.extend([ci] * len(params_by_class[ci]))
            flat["data"] = {
                "slot_base": slot_base, "n_slots": n_slots,
                "in_refs": in_refs, "slot_uses": slot_uses,
                "in_off": in_off, "in_slots": in_slots,
                "ndflows": [len(d) for d in dflows_by_class],
                "dflow_idx": dflows_by_class,   # THE per-class data-flow
                # index rule (body-argument order) — derived once, shipped
                # to the dispatch callback instead of re-derived there
                "cls_of": cls_of,
                "mem_reads": mem_reads, "writebacks": writebacks,
            }
        return flat

    # ------------------------------------------ online cost model (ISSUE 18)
    def _ptexec_pool_bucket(self) -> int:
        """The pool's shape bucket: the log4 byte-size bucket of its
        largest tile (TiledMatrix mb*nb*itemsize over the bound
        collections). Pools whose tiles sit within 4x of each other —
        one cost regime — share cost-model keys; collection-less pools
        key at bucket 0."""
        from ...core.costmodel import shape_bucket
        nbytes = 0
        for dc in self.collections.values():
            mb = getattr(dc, "mb", None)
            nb = getattr(dc, "nb", None)
            if not mb or not nb:
                continue
            try:
                item = np.dtype(getattr(dc, "dtype", np.float32)).itemsize
            except TypeError:
                item = 4
            nbytes = max(nbytes, int(mb) * int(nb) * item)
        return shape_bucket(nbytes)

    def _ptexec_seed_prior(self, tc: TaskClass, name: str,
                           bucket: int) -> None:
        """Fold a user ``time_estimate`` hook into the cost model as the
        class's cold-start prior (ISSUE 18 — the PR 10 carve-out,
        inverted): call the hook once per device flavor with a
        representative task (`make_task` is side-effect free) and the
        real device modules — the observable calling convention the
        interpreted best-device path used — and install the answers (in
        seconds, like the reference's ETA vtable) as priors. Measured
        costs override the prior as soon as the key warms up."""
        est = tc.time_estimate
        if est is None:
            return
        from ...core.costmodel import model
        try:
            loc = next(iter(self._enum_class(tc)))
        except StopIteration:
            return
        task = self.ctx.make_task(self, tc, loc)
        tpus = self.ctx.devices.by_type(DEV_TPU)
        for dev_obj, key in ((self.ctx.devices.cpu, "cpu"),
                             (tpus[0] if tpus else None, "tpu")):
            if dev_obj is None:
                continue
            try:
                eta = float(est(task, dev_obj))
            except Exception:  # noqa: BLE001 — a hook error never ejects
                continue       # the pool from the lane (the old behavior
                               # it replaces was a flat decline)
            model.seed_prior(name, bucket, key, eta * 1e9)

    def _ptexec_place_classes(self, classes: List[TaskClass],
                              dev_classes: List[bool],
                              names: Tuple[str, ...],
                              bucket: int) -> List[bool]:
        """Consumer (a) of the online cost model: per-instantiation
        best-device selection for the pool's TPU-bodied classes (each
        has a CPU twin of the same jitted function — the placement is
        free to move the whole class either way).

        Decision ladder per class, most-informed first: both flavors
        MEASURED → cheaper wins, with the device side carrying its
        measured stage-in cost pro-rated by the observed stage-in/task
        ratio (the coherency table's hit rate prices itself in); one
        flavor measured → explore the cold twin ONCE (the model cannot
        compare costs it never collected); neither measured → compare
        the user-hook priors when both were seeded, else the static
        has-a-device-body heuristic. Runs at the instantiation boundary
        only — its wall time lands in ``costmodel.decision_ns`` (the
        <1% contract's numerator)."""
        from ...core import costmodel as _cm
        if not (_cm.enabled() and mca.get("costmodel_placement", True)):
            return list(dev_classes)
        m = _cm.model
        m.maybe_load()
        t0 = time.perf_counter_ns()
        stats = _cm.COSTMODEL_STATS
        out: List[bool] = []
        for ci, tc in enumerate(classes):
            if not dev_classes[ci]:
                out.append(False)
                continue
            name = names[ci]
            self._ptexec_seed_prior(tc, name, bucket)
            cpu_known = m.measured(name, bucket, "cpu")
            tpu_known = m.measured(name, bucket, "tpu")
            if cpu_known and tpu_known:
                tpu_ns = m.cost(name, bucket, "tpu")
                st = m.cost(_cm.STAGE_IN, bucket, "tpu")
                if st is not None:
                    n_st = m.count(_cm.STAGE_IN, bucket, "tpu")
                    n_tpu = max(1, m.count(name, bucket, "tpu"))
                    tpu_ns += st * min(1.0, n_st / n_tpu)
                choice = tpu_ns <= m.cost(name, bucket, "cpu")
            elif tpu_known:
                choice = not m.begin_explore(name, bucket, "cpu")
            elif cpu_known:
                choice = m.begin_explore(name, bucket, "tpu")
            else:
                pc = m.cost(name, bucket, "cpu")
                pt = m.cost(name, bucket, "tpu")
                choice = (pt <= pc) if (pc is not None and pt is not None) \
                    else True
            out.append(choice)
            stats["placements_adaptive"] += 1
            if choice != dev_classes[ci]:
                stats["placements_diverged"] += 1
        stats["decisions"] += 1
        stats["decision_ns"] += time.perf_counter_ns() - t0
        return out

    def _ptexec_cost_bind(self, lane: Dict[str, Any], graph, flat,
                          names: Tuple[str, ...], bucket: int,
                          plan=None, cold_regions=None) -> None:
        """Attach the C-side cost rows (ISSUE 18): one row per (class,
        flavor), node-mapped so run()'s batch-amortized exec bump lands
        each task's share in the right accumulator. Unfused tasks row at
        their class index ('cpu'); fused region nodes row at n_classes +
        first-member class ('cpu_fused' — a multi-class region is
        attributed to its lead class; the capturable chains the fusion
        pass emits are single-class in practice). Device-placed nodes
        never pass the C bump site (they retire through the ptdev lane,
        observed there) — their rows simply stay zero and the fold skips
        them. The row → key metadata rides the lane dict to the fold at
        detach (Context._cost_fold)."""
        from ...core import costmodel as _cm
        if not _cm.enabled():
            return
        ncls = len(names)
        meta = [(names[ci], bucket, "cpu") for ci in range(ncls)] + \
               [(names[ci], bucket, "cpu_fused") for ci in range(ncls)]
        if plan is None:
            cls_of = flat["data"]["cls_of"] if flat["data"] is not None \
                else None
            if cls_of is None:
                rows = []
                for ci, insts in enumerate(flat["params"]):
                    rows.extend([ci] * len(insts))
            else:
                rows = list(cls_of)
        else:
            cls_of = flat["data"]["cls_of"]
            rows = []
            for nd in plan["node"]:
                if nd[0] == "t":
                    rows.append(cls_of[nd[1]])
                elif cold_regions and nd[1] in cold_regions:
                    # a COLD region (executable-cache miss): its first
                    # dispatch pays the jit trace, and the C bump cannot
                    # split that one batch out — so the whole run stays
                    # unobserved (-1). Only warm instantiations feed the
                    # <cls>_fused EWMA; the trace itself is measured
                    # separately by _timed_region_program. Without this
                    # a tiny cold DAG reads fusion as "slower than
                    # unfused" forever and wrongly declines it.
                    rows.append(-1)
                else:
                    members = plan["regions"][nd[1]]["members"]
                    rows.append(ncls + cls_of[members[0]])
        try:
            graph.cost_bind(rows)
        except Exception:  # noqa: BLE001 — an old native build without
            return         # cost rows just leaves the model CPU-blind
        lane["cost_meta"] = meta

    def _ptexec_prepare(self, agg) -> Optional[Dict[str, Any]]:
        """Build (or fetch from the program cache) the native-lane state
        for this pool, or None → the Python FSM runs as before. The fall
        back is per-pool: one ineligible class keeps cross-class release
        edges in Python, so the whole pool stays there.
        ``self._ptexec_refusal`` records WHY a pool declined —
        "ineligible" (by design: class features or pool-level gates) vs
        "fallback" (every class eligible, but the lane build refused:
        flatten mismatch or missing native module) — feeding the
        PTEXEC_STATS split the ci.sh gate relies on."""
        ctx = self.ctx
        self._ptexec_refusal = "ineligible"
        # PINS no longer ejects pools from the lane (PR 5: the lane traces
        # itself — in-lane ring events land in the PBP streams, see
        # utils/native_trace.py); only --mca pins_paranoid 1 restores the
        # full per-task Python instrumentation
        if (not mca.get("ptg_native_exec", True) or ctx.pins.paranoid
                or ctx.paranoid):
            return None
        # distributed pools may now ride the lane too — when the native
        # COMMUNICATION lane (comm/native.py, ISSUE 7) is up: cross-rank
        # release edges surface as activation frames, payloads move
        # eager/rendezvous, and arrived activations ingest GIL-free. A
        # distributed context without that lane (in-process ThreadsCE
        # fabric, --mca comm_native 0, missing native modules) keeps the
        # interpreted remote_dep path, counted as ineligible-by-design.
        distributed = ctx.nb_ranks > 1 and ctx.comm is not None
        lane_comm = getattr(ctx.comm, "native", None) if distributed else None
        if (ctx.comm is not None or ctx.nb_ranks > 1) and lane_comm is None:
            return None
        classes = [self._classes[tcs.name]
                   for tcs in self.program.spec.task_classes
                   if tcs.name not in agg]
        if not classes:
            return None
        for tc in classes:
            if not self._ptexec_class_eligible(tc):
                return None
        dev_classes = [self._ptexec_class_device(tc) for tc in classes]
        use_dev = False
        if any(dev_classes):
            # eligibility v3 (ISSUE 10): TPU-bodied classes. With an
            # accelerator device registered their tasks surface onto the
            # native DEVICE lane (ptdev); without one, the CPU twin of
            # the same jitted body runs through the ordinary lane
            # dispatch — exactly the chore the interpreted FSM's device
            # selection would pick on a CPU-only host.
            if ctx.devices.by_type(DEV_TPU):
                if not dev_native.admits_pool(lane_comm is not None):
                    return None
                use_dev = True
        self._ptexec_refusal = "fallback"
        from ... import native as native_mod
        mod = native_mod.load_ptexec()
        if mod is None:
            return None
        # consumer (a) of the online cost model (ISSUE 18): per-
        # instantiation best-device selection. The static heuristic
        # ("has a device body") is the cold-start fallback; once both
        # flavors are measured the cheaper one wins, and a pool whose
        # device classes ALL measure cheaper on their CPU twins skips
        # the device lane entirely.
        bucket = self._ptexec_pool_bucket()
        # cost-model keys are qualified by the PROGRAM name: two programs
        # are free to both name a class "A" with wildly different bodies,
        # and the model must never blend their measurements (the taskpool
        # name would work too, but the program name survives a caller
        # passing per-instantiation pool names, keeping warm-cache and
        # persisted entries addressable)
        names = tuple(f"{self.program.spec.name}.{tc._ptg_spec.name}"
                      for tc in classes)
        place_dev = list(dev_classes)
        if use_dev:
            place_dev = self._ptexec_place_classes(classes, dev_classes,
                                                   names, bucket)
            if not any(place_dev):
                use_dev = False
        devlane = None
        if use_dev:
            devlane = dev_native.lane_for_pool(ctx)
            if devlane is None:
                return None
        # consumer (b): measured fusion limits (dsl/fusion.py). The
        # decline set and the break-even cap shape the fusion plan, so
        # they join the flatten cache key — a plan sized for one cost
        # regime is never replayed under another.
        fus_declined, fus_min, fus_max = adaptive_fusion_limits(
            [(names[ci], bucket,
              "tpu" if (use_dev and place_dev[ci]) else "cpu")
             for ci in range(len(classes))])
        # what the device's residency budget can hold at once bounds a
        # pack, so it joins the key as the limits do
        self._ptexec_tile_bytes = tile = None if devlane is None else \
            self._ptexec_tile()
        pin_cap = devlane.device._budget // tile if tile else None
        place = (ctx.nb_ranks, lane_comm is not None, use_dev,
                 device_fingerprint(),
                 bool(mca.get("region_fusion", True)),
                 fus_min, fus_max,
                 tuple(place_dev), tuple(sorted(fus_declined)), pin_cap)
        key = self._ptexec_cache_key(names, place)
        cache = self.program.__dict__.setdefault("_ptexec_cache", {})
        ent = cache.get(key) if key is not None else None
        if ent is None:
            flat = self._ptexec_flatten(classes)
            if flat is None:
                return None
            plan = None
            if flat["n"] and flat["data"] is not None \
                    and lane_comm is None:
                # the fusion pass (ISSUE 12): single-rank data pools only
                # — a fused region must never hide a cross-rank edge
                plan = self._ptexec_fuse_plan(
                    flat, classes, place_dev, use_dev,
                    (fus_declined, fus_min, fus_max),
                    None if pin_cap is None else (pin_cap, devlane.device))
            ent = {"flat": flat, "fusion": plan}
            if key is not None:
                cache[key] = ent
        flat = ent["flat"]
        owners = None
        if lane_comm is not None:
            # per-task owner ranks (owner-computes affinity) — computed
            # per INSTANTIATION, never cached: rank_of depends on the
            # collection dict, which is outside the flatten cache key
            owners = self._ptexec_owners(classes, flat)
            if owners is None:
                return None
        self._ptexec_refusal = None
        if flat["n"] == 0:
            return {"n": 0}
        # the CSR (the expensive flatten) is shared across instantiations;
        # the Graph (counters + ready state + ~1ms of list parsing) is
        # built fresh PER POOL — a stream holding a stale drain-queue entry
        # can then never walk another pool's tasks, and bodies/callbacks
        # (which resolve against THIS instantiation's globals) can never
        # cross pools. Empty bodies dispatch nothing at all.
        data = flat["data"]
        if data is None:
            graph = mod.Graph(flat["goals"], flat["off"], flat["succs"],
                              flat["prio"])
            bodies = [None if tc._ptg_spec.bodies[0].source.strip()
                      in ("", "pass") else tc._ptg_raw_body for tc in classes]
            callback = None
            if any(b is not None for b in bodies):
                callback = self._mk_ptexec_callback(flat["bases"], bodies,
                                                    flat["params"])
            lane = {"graph": graph, "callback": callback,
                    "n": flat["n"], "finalized": False}
            self._ptexec_cost_bind(lane, graph, flat, names, bucket)
            if owners is not None:
                self._ptexec_bind_comm(lane, lane_comm, owners)
            return lane
        # data-flow pool with a FUSION PLAN (ISSUE 12): capturable
        # subgraphs collapse into fused super-tasks — one jitted program
        # per region, dispatched through the normal callback (CPU
        # regions) or the ptdev lane (device regions) — and the graph
        # carries only regions + seams, weighted back to original tasks.
        if ent.get("fusion") is not None and owners is None:
            return self._ptexec_lane_fused(flat, ent["fusion"], classes,
                                           mod, key,
                                           devlane if use_dev else None,
                                           place_dev, names, bucket)
        # data-flow pool: the graph additionally owns slot LIFETIMES (the
        # usagelmt/usagecnt retire protocol); Python owns slot VALUES —
        # one flat list the batched callback reads inputs from and lands
        # outputs into. Memory endpoints were flattened symbolically
        # (collection name + static index) so the cached CSR stays valid
        # across instantiations with different collection dicts.
        comm_info = None
        slot_uses = data["slot_uses"]
        if owners is not None:
            # distributed data pool: slot usage limits count LOCAL
            # consumers only (a remote consumer's use is the one payload
            # send, done at production time), remote input slots pull
            # their value from the comm lane's payload store, and
            # produced slots feeding remote consumers ship once per
            # destination rank
            comm_info = self._ptexec_comm_data(flat, owners)
            slot_uses = comm_info["slot_uses"]
        graph = mod.Graph(flat["goals"], flat["off"], flat["succs"],
                          flat["prio"], data["in_off"], data["in_slots"],
                          slot_uses)
        slots: List[Any] = [None] * data["n_slots"]
        mem_datas, writebacks = self._ptexec_mem(data["mem_reads"],
                                                 data["writebacks"])
        lane = {"graph": graph, "slots": slots,
                "n": flat["n"], "finalized": False}
        self._ptexec_cost_bind(lane, graph, flat, names, bucket)
        if owners is not None:
            self._ptexec_bind_comm(lane, lane_comm, owners)
        class_fns = self._ptexec_class_fns(classes, data)
        lane["callback"] = self._mk_ptexec_data_callback(
            flat, classes, slots, mem_datas, writebacks,
            comm=None if comm_info is None else dict(
                comm_info, lane=lane_comm, pool_id=lane["pool_id"]),
            class_fns=class_fns)
        if use_dev:
            # bind LAST: dev_bind surfaces zero-dep device seeds onto the
            # lane immediately, and the manager may dispatch them before
            # this function returns — every closure it touches (slots,
            # mem_datas, writebacks) exists by now
            self._ptexec_bind_dev(lane, devlane, flat, class_fns, names,
                                  place_dev, slots, mem_datas, writebacks,
                                  bucket)
        return lane

    # ---------------------------------------------- region fusion (ISSUE 12)
    def _ptexec_tile(self) -> Optional[int]:
        """The bytes of the largest tile of this pool's collections; None
        where a collection does not say its tile."""
        tile = 0
        for dc in self.collections.values():
            try:
                tile = max(tile, int(dc.mb) * int(dc.nb)
                           * np.dtype(dc.dtype).itemsize)
            except (AttributeError, TypeError):
                return None
        return tile or None

    def _ptexec_fuse_plan(self, flat, classes: List[TaskClass],
                          dev_classes: List[bool],
                          use_dev: bool,
                          limits=None, pin=None) -> Optional[Dict[str, Any]]:
        """The fusion pass over the flattened CSR: identify capturable
        subgraphs — same-device jittable bodies (the class's single
        jitted ``_ptg_body_fn``, or an empty forwarding body), static
        shapes (automatic: jit traces per shape), no cross-rank edge
        (the caller only fuses single-rank pools) — and collapse each
        into ONE fused super-task node. Returns the fused COMPACT graph
        (regions + seams; a fused node inherits the union of its
        region's external in/out edges and in-slot lists, so the C
        release walk and the slot-retire protocol cross the seam
        correctly) plus per-region replay plans, or None when nothing
        is worth fusing. Pure structure — no per-instantiation objects
        — so the whole plan rides the flatten cache. ``pin`` is (the
        memory operands and outputs the device can hold at once, the
        device): a pack reads and writes no more of them, and a device
        region that needs more bytes than the device's budget holds is
        refused here, by name."""
        if not mca.get("region_fusion", True):
            return None
        # measured fusion limits (ISSUE 18, dsl/fusion.py): the decline
        # set un-fuses classes whose fused per-task cost measurably beats
        # nothing; the cap is the measured break-even region size. Cold
        # model → exactly the static knobs.
        if limits is None:
            limits = (set(), int(mca.get("region_fusion_min", 2)),
                      int(mca.get("region_fusion_max", 128)))
        fus_declined, fus_min, fus_max = limits
        data = flat["data"]
        n = flat["n"]
        cls_of = data["cls_of"]
        ndflows = data["ndflows"]
        # per-class capturability kind: None = seam (un-fusable)
        kind_by_class: List[Optional[str]] = []
        for ci, tc in enumerate(classes):
            if ndflows[ci] == 0 or ci in fus_declined:
                # CTL/flowless classes run raw Python bodies — seams;
                # model-declined classes stay per-task by measurement
                kind_by_class.append(None)
                continue
            empty = tc._ptg_spec.bodies[0].source.strip() in ("", "pass")
            if not empty and getattr(tc, "_ptg_body_fn", None) is None:
                kind_by_class.append(None)
                continue
            kind_by_class.append("dev" if (use_dev and dev_classes[ci])
                                 else "cpu")
        if not any(k is not None for k in kind_by_class):
            return None
        empty_body = [tc._ptg_spec.bodies[0].source.strip() in ("", "pass")
                      for tc in classes]
        slot_base0, in_refs0 = data["slot_base"], data["in_refs"]
        kind: List[Optional[str]] = []
        for t in range(n):
            ci = cls_of[t]
            k = kind_by_class[ci]
            if k is not None and empty_body[ci]:
                # an empty (forwarding) body with a NEW/NULL or memory
                # input can forward None — the per-task path's "A NULL
                # is forwarded" source guard must keep firing at the
                # producer, so such tasks stay seams (a fused region
                # would swallow the None into its trace env)
                b = slot_base0[t]
                for dj in range(data["ndflows"][ci]):
                    if in_refs0[b + dj] < 0:
                        k = None
                        break
            kind.append(k)
        regions = partition_regions(
            n, flat["off"], flat["succs"], kind, fus_min, fus_max)
        if not regions:
            return None
        off, succs = flat["off"], flat["succs"]
        in_off, in_slots = data["in_off"], data["in_slots"]
        slot_base, in_refs = data["slot_base"], data["in_refs"]
        mem_reads = data["mem_reads"]
        regions, n_packed = self._ptexec_pack(
            regions, kind, flat, fus_max, None if pin is None else pin[0])
        reg_of = [-1] * n
        for ri, members in enumerate(regions):
            for m in members:
                reg_of[m] = ri
        member_sets = [set(m) for m in regions]
        task_of_slot = [0] * data["n_slots"]
        for t in range(n):
            b = slot_base[t]
            for dj in range(ndflows[cls_of[t]]):
                task_of_slot[b + dj] = t
        # compact node list: seams/unfused keep their own node; each
        # region becomes ONE node at its topo-first member's id position
        rep_of = [m[0] for m in regions]
        node: List[Tuple] = []
        cid_of = [0] * n
        rcid = [-1] * len(regions)
        for i in range(n):
            ri = reg_of[i]
            if ri < 0:
                cid_of[i] = len(node)
                node.append(("t", i))
            elif i == rep_of[ri]:
                rcid[ri] = len(node)
                node.append(("r", ri))
        for i in range(n):
            if reg_of[i] >= 0:
                cid_of[i] = rcid[reg_of[i]]
        nc = len(node)
        # edges: internal (both ends one region) drop; the rest remap —
        # a fused node thereby inherits the union of its region's
        # external out-edges, and goals recount to external in-edges
        edges2: List[List[int]] = [[] for _ in range(nc)]
        for i in range(n):
            src = cid_of[i]
            ri = reg_of[i]
            for k in range(off[i], off[i + 1]):
                t = succs[k]
                if ri >= 0 and reg_of[t] == ri:
                    continue
                edges2[src].append(cid_of[t])
        goals2 = [0] * nc
        for es in edges2:
            for d in es:
                goals2[d] += 1
        off2 = [0] * (nc + 1)
        succs2: List[int] = []
        for i2, es in enumerate(edges2):
            off2[i2 + 1] = off2[i2] + len(es)
            succs2.extend(es)
        prio = flat["prio"]
        prio2 = None
        if prio is not None:
            prio2 = [prio[nd[1]] if nd[0] == "t"
                     else max(prio[m] for m in regions[nd[1]])
                     for nd in node]
        # in-slot lists (the retire protocol): a fused node consumes the
        # multiset of its members' EXTERNAL input slots — decrementing k
        # uses at region retire matches the k per-member decrements the
        # unfused walk would have done; internal consumption vanishes
        # (the region reads those values from its own trace env)
        in2: List[List[int]] = [[] for _ in range(nc)]
        for i2, nd in enumerate(node):
            if nd[0] == "t":
                i = nd[1]
                in2[i2] = list(in_slots[in_off[i]:in_off[i + 1]])
            else:
                mem = member_sets[nd[1]]
                in2[i2] = [ref for m in regions[nd[1]]
                           for ref in in_slots[in_off[m]:in_off[m + 1]]
                           if task_of_slot[ref] not in mem]
        in_off2 = [0] * (nc + 1)
        in_slots2: List[int] = []
        for i2, lst in enumerate(in2):
            in_off2[i2 + 1] = in_off2[i2] + len(lst)
            in_slots2.extend(lst)
        slot_uses2 = [0] * data["n_slots"]
        for ref in in_slots2:
            slot_uses2[ref] += 1
        # per-region replay plans: members in topo order (a valid
        # serialization — the same argument as DTD capture: insertion/
        # topo order respects every internal edge), each flow input
        # resolved statically to an internal value, an external slot, a
        # memory read, or an earlier member's memory WRITE (the region-
        # internal mem env — per-task dispatch would also order those
        # through the release edges)
        wb_by_task: Dict[int, List[Tuple]] = {}
        for tid, dj, dcn, idx in data["writebacks"]:
            wb_by_task.setdefault(tid, []).append((dj, dcn, idx))
        bases = flat["bases"]
        params_by_class = flat["params"]
        # the shapes of region this plan holds (ISSUE 29): regions whose
        # canonical plans are equal replay as one program, so the
        # executable cache is keyed by shape, and a region only records
        # which shape it is. A member's parameters enter its shape where
        # its body names them (PTGProgram.body_names)
        class_names = [tc._ptg_spec.name for tc in classes]
        reads = []
        for tc in classes:
            named = self.program.body_names[tc._ptg_spec.name]
            reads.append(frozenset(
                i for i, p in enumerate(tc._ptg_spec.params)
                if named is None or p in named))
        # the flows a member's body writes, and the slots a region program
        # of this pool wrote on the device: what the pool owns (ISSUE 34)
        body_writes = [frozenset(w) if fn is not None else frozenset()
                       for fn, w in zip(*self._ptexec_class_fns(classes,
                                                                data))]
        wb_slots = {slot_base[tid] + dj
                    for tid, dj, _dcn, _idx in data["writebacks"]}

        def owned(r):
            p = task_of_slot[r]
            return (reg_of[p] >= 0 and kind[p] == "dev"
                    and r - slot_base[p] in body_writes[cls_of[p]]
                    and r not in wb_slots)
        shapes: List[Dict[str, Any]] = []
        shape_ix: Dict[Tuple, int] = {}
        rplans: List[Dict[str, Any]] = []
        for ri, members in enumerate(regions):
            ext: List[Tuple] = []
            ext_ix: Dict[Tuple, int] = {}

            def eix(e):
                j = ext_ix.get(e)
                if j is None:
                    j = ext_ix[e] = len(ext)
                    ext.append(e)
                return j

            steps: List[Tuple] = []
            produced: set = set()
            memw: set = set()
            wb_keys: List[Tuple] = []
            # slot -> the member flows that read it, as (class, flow
            # position, the flow's own slot)
            readers: Dict[int, List[Tuple]] = {}
            for m in members:
                ci = cls_of[m]
                b = slot_base[m]
                nd_ = ndflows[ci]
                srcs: List[Tuple] = []
                for dj in range(nd_):
                    r = in_refs[b + dj]
                    if r == -1:
                        srcs.append(("none", 0))
                    elif r >= 0:
                        srcs.append(("int", r) if r in produced
                                    else ("ext", eix(("slot", r))))
                        readers.setdefault(r, []).append((ci, dj, b + dj))
                    else:
                        mi = -2 - r
                        mk = mem_reads[mi]
                        srcs.append(("intm", mk) if mk in memw
                                    else ("ext", eix(("mem", mi))))
                wbs = tuple((dj, (dcn, idx))
                            for dj, dcn, idx in wb_by_task.get(m, ()))
                steps.append((ci, tuple(params_by_class[ci][m - bases[ci]]),
                              tuple(srcs), b, nd_, wbs))
                for dj in range(nd_):
                    produced.add(b + dj)
                for dj, mk in wbs:
                    memw.add(mk)
                    wb_keys.append(mk)
            outs = [slot_base[m] + dj for m in members
                    for dj in range(ndflows[cls_of[m]])
                    if slot_uses2[slot_base[m] + dj] > 0]
            donated: List[Tuple[int, int]] = []
            if kind[members[0]] == "dev":
                donated = self._ptexec_donations(
                    ext, readers, slot_uses2, wb_slots, owned, body_writes,
                    empty_body)
            if donated:
                # the donated operands lead, in the order of their outputs
                given = {j for j, _end in donated}
                order = [j for j, _end in donated] + [
                    j for j in range(len(ext)) if j not in given]
                at = {j: i for i, j in enumerate(order)}
                ext = [ext[j] for j in order]
                steps = [(ci, key, tuple(
                    (kk, at[v]) if kk == "ext" else (kk, v)
                    for kk, v in srcs), b, nd_, wbs)
                    for ci, key, srcs, b, nd_, wbs in steps]
            shape = _region_shape(kind[members[0]], steps, outs, reads,
                                  class_names,
                                  [end for _j, end in donated])
            si = shape_ix.get(shape["sig"])
            if si is None:
                si = shape_ix[shape["sig"]] = len(shapes)
                shapes.append(shape)
            rplans.append({"members": list(members),
                           "kind": kind[members[0]],
                           "ext": ext,
                           "ext_mems": [v for k2, v in ext if k2 == "mem"],
                           "shape": si, "wb_keys": wb_keys,
                           "out_slots": outs})
            if pin is not None and rplans[-1]["kind"] == "dev" and \
                    len(rplans[-1]["ext_mems"]) + len(set(wb_keys)) > pin[0]:
                self._ptexec_unstageable(flat, classes, rplans[-1], ri,
                                         pin[1])
        dev_mask2 = None
        ndev_tasks = 0
        if use_dev:
            dev_mask2 = []
            for nd in node:
                if nd[0] == "t":
                    i = nd[1]
                    d = 1 if (dev_classes[cls_of[i]]
                              and ndflows[cls_of[i]] > 0) else 0
                    dev_mask2.append(d)
                    ndev_tasks += d
                else:
                    d = 1 if rplans[nd[1]]["kind"] == "dev" else 0
                    dev_mask2.append(d)
                    if d:
                        ndev_tasks += len(regions[nd[1]])
            if ndev_tasks == 0:
                dev_mask2 = None
        n_fused = sum(len(m) for m in regions)
        dev_early2 = None if dev_mask2 is None else \
            _released_at_dispatch(dev_mask2, off2, succs2)
        return {"node": node, "goals": goals2, "off": off2,
                "succs": succs2, "prio": prio2, "in_off": in_off2,
                "in_slots": in_slots2, "slot_uses": slot_uses2,
                "weights": [1 if nd[0] == "t" else len(regions[nd[1]])
                            for nd in node],
                "orig_of": [nd[1] if nd[0] == "t" else rep_of[nd[1]]
                            for nd in node],
                "rcid": rcid, "regions": rplans, "shapes": shapes,
                "writebacks": [w for w in data["writebacks"]
                               if reg_of[w[0]] < 0],
                "dev_mask": dev_mask2, "ndev_tasks": ndev_tasks,
                "dev_early": dev_early2,
                "n_seam": n - n_fused, "n_fused": n_fused,
                "n_packed": n_packed,
                "n_mixed": sum(
                    len({cls_of[m] for m in members}) > 1
                    for members in regions)}

    @staticmethod
    def _ptexec_donations(ext, readers, slot_uses, wb_slots, owned,
                          body_writes, empty_body) -> List[Tuple[int, int]]:
        """The external operands a device region is the last reader of
        (ISSUE 34), each with the slot of the output its update chain ends
        in: ``(position in ext, slot)`` pairs, in ``ext``'s order. The
        region's program is given those operands for good.

        A slot operand ``r`` qualifies when the pool owns its value (a
        member of a device region wrote it in its body and nobody wrote
        it back to memory: ``owned``), every use of it is this region's,
        and exactly one member flow reads the array, one its body also
        writes. The array goes by every slot that forwards it (a flow the
        body does not write, a class with no body): a read, an outside
        reader or a write-back under any of those names refuses it. A
        memory operand is the residency table's, never the pool's. The
        chain then follows the one writer of each value, under whatever
        name, to the first that leaves the region (an externally-consumed
        slot or a write-back): an output of the operand's shape, so every
        donated buffer has one to go to (:func:`_region_shape` says which).
        A chain that ends inside the region donates nothing: its operand
        would have no output to become. The plan is single-rank
        (``_ptexec_prepare``), so no slot here has a remote reader."""
        def leaves(s):
            return slot_uses[s] > 0 or s in wb_slots

        def array_of(s):
            # every slot the array in ``s`` goes by inside the region, and
            # per member flow whose body reads it the slot that flow
            # writes (None: it only reads)
            names, reads = [s], []
            for nm in names:
                for ci, dj, own in readers.get(nm, ()):
                    if dj in body_writes[ci]:
                        reads.append(own)
                    else:
                        names.append(own)
                        if not empty_body[ci]:
                            reads.append(None)
            return names, reads

        out: List[Tuple[int, int]] = []
        for j, (kk, r) in enumerate(ext):
            if kk != "slot" or not owned(r) \
                    or slot_uses[r] != len(readers[r]):
                continue
            names, reads = array_of(r)
            if len(reads) != 1 or any(leaves(nm) for nm in names[1:]):
                continue
            end = reads[0]
            while end is not None:
                names, reads = array_of(end)
                gone = [nm for nm in names if leaves(nm)]
                if gone:
                    out.append((j, gone[0]))
                    break
                nxt = [own for own in reads if own is not None]
                end = nxt[0] if len(nxt) == 1 else None
        return out

    def _ptexec_unstageable(self, flat, classes, rp, ri, device) -> None:
        """Refuse the pool if device region ``ri`` needs more bytes than
        ``device``'s residency budget holds, the memory it reads pinned
        beside the room of what it writes back: no admission could ever
        stage it whole."""
        mem_reads = flat["data"]["mem_reads"]
        datas = self._ptexec_datas([mem_reads[mi] for mi in rp["ext_mems"]])
        outs = self._ptexec_datas(list(dict.fromkeys(rp["wb_keys"])))
        need = sum(device.lane_bytes(d) for d in datas + outs)
        if need <= device._budget:
            return
        m = rp["members"][0]
        ci = flat["data"]["cls_of"][m]
        first = (f"{classes[ci]._ptg_spec.name}"
                 f"{tuple(flat['params'][ci][m - flat['bases'][ci]])}")
        output.fatal(
            f"PTG taskpool {self.name}: device region {ri} ({first} and "
            f"{len(rp['members']) - 1} more tasks) reads {len(datas)} "
            f"memory operands and writes {len(outs)} back, {need} bytes, "
            f"more than the residency budget of {device.name}, "
            f"{device._budget} bytes: it could never be staged")

    @staticmethod
    def _ptexec_pack(regions: List[List[int]], kind, flat, max_size: int,
                     max_held: Optional[int] = None
                     ) -> Tuple[List[List[int]], int]:
        """Pack sibling device regions that share memory operands into
        one region (ISSUE 32): gather what only this side knows of each
        region of the partition (its edges to the outside, the memory it
        reads and writes) as plain lists, let
        :func:`~parsec_tpu.dsl.fusion.pack_source_regions` choose, and
        chain the members of each pack. Returns the regions and how many
        of the partition's went into a pack of two or more. ``max_held``
        bounds the locations a pack reads and writes."""
        data = flat["data"]
        off, succs = flat["off"], flat["succs"]
        slot_base, in_refs = data["slot_base"], data["in_refs"]
        ndflows, cls_of = data["ndflows"], data["cls_of"]
        mem_reads = data["mem_reads"]
        reg_of = [-1] * flat["n"]
        for ri, members in enumerate(regions):
            for m in members:
                reg_of[m] = ri
        ext_in, ext_out = [0] * len(regions), [0] * len(regions)
        for i, ri in enumerate(reg_of):
            for k in range(off[i], off[i + 1]):
                rt = reg_of[succs[k]]
                if rt != ri or ri < 0:
                    if ri >= 0:
                        ext_out[ri] += 1
                    if rt >= 0:
                        ext_in[rt] += 1
        reads: List[List[Tuple]] = [[] for _ in regions]
        writes: List[List[Tuple]] = [[] for _ in regions]
        for ri, members in enumerate(regions):
            for m in members:
                b = slot_base[m]
                reads[ri].extend(mem_reads[-2 - in_refs[b + dj]]
                                 for dj in range(ndflows[cls_of[m]])
                                 if in_refs[b + dj] < -1)
        for tid, _dj, dcn, idx in data["writebacks"]:
            if reg_of[tid] >= 0:
                writes[reg_of[tid]].append((dcn, idx))
        packs = pack_source_regions(
            [len(m) for m in regions], [kind[m[0]] for m in regions],
            ext_in, ext_out, reads, writes, max_size, max_held)
        n_packed = sum(len(p) for p in packs if len(p) > 1)
        if n_packed:
            regions = [[m for ri in p for m in regions[ri]] for p in packs]
        return regions, n_packed

    def _ptexec_datas(self, keys) -> List[Any]:
        """The ``Data`` behind each (collection name, static index) of a
        flattened pool, resolved against THIS instantiation's collections
        (the cached CSR names memory symbolically)."""
        datas = []
        for dc_name, idx in keys:
            dc = self.collections.get(dc_name)
            if dc is None:
                output.fatal(f"PTG taskpool {self.name}: unknown "
                             f"collection {dc_name!r}")
            datas.append(dc.data_of(*idx))
        return datas

    def _ptexec_mem(self, mem_reads, wbs):
        """A lane pool's memory endpoints: the operands its tasks read,
        and per writing task its (flow position, ``Data``) pairs."""
        drefs = self._ptexec_datas((dcn, idx) for _t, _dj, dcn, idx in wbs)
        writebacks: Dict[int, List] = {}
        for (tid, dj, _dcn, _idx), dref in zip(wbs, drefs):
            writebacks.setdefault(tid, []).append((dj, dref))
        return self._ptexec_datas(mem_reads), writebacks

    def _ptexec_class_fns(self, classes: List[TaskClass], data):
        """Per-class (dispatch fn, written flow positions): the jitted
        body for data classes, the raw body for CTL classes, None for
        empty bodies. One home — the batched data callback, the device
        dispatch, and the region program builder must agree."""
        fns, written = [], []
        for ci, tc in enumerate(classes):
            empty = tc._ptg_spec.bodies[0].source.strip() in ("", "pass")
            if data["ndflows"][ci]:
                fns.append(None if empty else tc._ptg_body_fn)
                written.append(tuple(
                    dj for dj, fi in enumerate(data["dflow_idx"][ci])
                    if tc.flows[fi].access & FLOW_ACCESS_WRITE))
            else:
                fns.append(None if empty
                           else getattr(tc, "_ptg_raw_body", None))
                written.append(())
        return fns, written

    def _mk_region_runner(self, graph, cid: int, rp: Dict[str, Any],
                          jitted, slots: List[Any], mem_datas,
                          wb_datas, mod):
        """The fused super-task's dispatch wrapper (CPU regions, called
        from the batched data callback): resolve the region's external
        inputs (producer slots + memory reads at dispatch time — the
        same prepare-at-ready timing as per-task dispatch), run the ONE
        jitted region program, land externally-consumed outputs back
        into their original slot ids, and perform the members' memory
        write-backs in serialization order (one version bump per member
        write, like the per-task path). Brackets the body in EV_REGION
        ring events so merged timelines show regions vs seams."""
        ext, out_slots = rp["ext"], rp["out_slots"]
        evr, fs, fe = mod.EV_REGION, mod.FLAG_START, mod.FLAG_END

        def run_region():
            graph.trace_mark(evr, cid, fs)
            ev: List[Any] = []
            for kk, v in ext:
                if kk == "slot":
                    ev.append(slots[v])
                else:
                    copy = mem_datas[v].newest_copy()
                    ev.append(None if copy is None else copy.payload)
            # a host region donates nothing, so its program returns its
            # outputs and write-backs in the plan's own order
            outs, wbs = jitted((), tuple(ev))
            for s, v in zip(out_slots, outs):
                if v is None:
                    raise RuntimeError(
                        f"A NULL is forwarded from fused region {cid} "
                        f"(slot {s}, native lane)")
                slots[s] = v
            for dref, v in zip(wb_datas, wbs):
                dref.write_host(v)
            graph.trace_mark(evr, cid, fe)
        return run_region

    def _ptexec_lane_fused(self, flat, plan, classes: List[TaskClass],
                           mod, ckey, devlane, place_dev: List[bool],
                           names: Tuple[str, ...],
                           bucket: int) -> Dict[str, Any]:
        """Build the native-lane state for a pool with a fusion plan:
        the compact graph (regions + seams) with original-task weights,
        one jitted program per SHAPE of region out of the PERSISTENT
        executable cache (program-scoped, keyed by the class names, the
        placement, the globals a body names and the shape's canonical
        plan — regions of one shape share one program, and a second
        instantiation builds, traces and loads nothing), and the
        region-aware dispatch callbacks."""
        data = flat["data"]
        graph = mod.Graph(plan["goals"], plan["off"], plan["succs"],
                          plan["prio"], plan["in_off"], plan["in_slots"],
                          plan["slot_uses"])
        graph.region_bind(plan["weights"])
        slots: List[Any] = [None] * data["n_slots"]
        mem_datas, writebacks = self._ptexec_mem(data["mem_reads"],
                                                 plan["writebacks"])
        fns, written_by_class = self._ptexec_class_fns(classes, data)
        scopes = [tc._ptg_spec.name for tc in classes]
        cache = self.program.region_programs
        # the flatten key names every primitive global; a region program
        # depends on those a body names, so pools that differ in the
        # others (a larger grid of the same tiles) share executables
        named = self.program.globals_named
        rkey = None if ckey is None else (
            ckey[1], ckey[2], tuple(g for g in ckey[0]
                                    if named is None or g[0] in named))
        programs = []
        for shape in plan["shapes"]:
            # the cached object is the TIMED wrapper: its first call (the
            # jit trace+compile) feeds the __region_trace__ pseudo-class
            # fusion sizing reads back; a cache HIT reuses the wrapper
            # with the first call already burned, so warm replays never
            # observe a phantom trace
            jitted, hit = cache.get_or_build(
                None if rkey is None else (rkey, shape["sig"]),
                lambda shape=shape: _timed_region_program(
                    _jit_region_program(shape, fns, written_by_class,
                                        scopes),
                    len(shape["steps"])))
            if not hit:
                PTEXEC_STATS["region_programs"] += 1
            programs.append((jitted, hit))
        runners: Dict[int, Any] = {}
        dev_regions: Dict[int, Dict[str, Any]] = {}
        cold_regions: set = set()
        for ri, rp in enumerate(plan["regions"]):
            jitted, hit = programs[rp["shape"]]
            if not hit:
                cold_regions.add(ri)
            wb_datas = self._ptexec_datas(rp["wb_keys"])
            cid = plan["rcid"][ri]
            if rp["kind"] == "dev":
                shape = plan["shapes"][rp["shape"]]
                dev_regions[cid] = {
                    "ext": rp["ext"], "ext_mems": rp["ext_mems"],
                    "given": [s for _kk, s in
                              rp["ext"][:shape["n_donated"]]],
                    "outs": list(zip(rp["out_slots"], shape["out_pos"])),
                    "jitted": jitted,
                    "wb_pairs": list(zip(shape["wb_pos"], wb_datas)),
                    "ntasks": len(rp["members"]),
                    "cls": data["cls_of"][rp["members"][0]],
                    "cold": not hit}
            else:
                runners[cid] = self._mk_region_runner(
                    graph, cid, rp, jitted, slots, mem_datas, wb_datas,
                    mod)
        lane = {"graph": graph, "slots": slots, "n": flat["n"],
                "finalized": False}
        self._ptexec_cost_bind(lane, graph, flat, names, bucket, plan=plan,
                               cold_regions=cold_regions)
        lane["callback"] = self._mk_ptexec_data_callback(
            flat, classes, slots, mem_datas, writebacks,
            fusion={"orig_of": plan["orig_of"], "regions": runners},
            class_fns=(fns, written_by_class))
        PTEXEC_STATS["fused_regions"] += len(plan["regions"])
        PTEXEC_STATS["packed_regions"] += plan["n_packed"]
        PTEXEC_STATS["mixed_regions"] += plan["n_mixed"]
        PTEXEC_STATS["fused_tasks"] += plan["n_fused"]
        PTEXEC_STATS["seam_tasks"] += plan["n_seam"]
        sp = self.ctx._spans
        if sp is not None:
            for rp in plan["regions"]:
                sp.region_tasks.record(len(rp["members"]))
        if devlane is not None and plan["dev_mask"] is not None:
            self._ptexec_bind_dev(
                lane, devlane, flat, (fns, written_by_class), names,
                place_dev, slots, mem_datas, writebacks, bucket, plan,
                {"orig_of": plan["orig_of"], "dev_regions": dev_regions,
                 "marks": (mod.EV_REGION, mod.FLAG_START, mod.FLAG_END)})
        return lane

    def _ptexec_bind_dev(self, lane: Dict[str, Any], devlane, flat,
                         class_fns, names: Tuple[str, ...],
                         place_dev: List[bool], slots: List[Any],
                         mem_datas, writebacks: Dict[int, List],
                         bucket: int, plan=None, fusion=None) -> None:
        """Hand the pool's device tasks to the native device lane
        (device/lane_pool.py, which owns dispatch, residency and retire):
        the per-task device mask and its task count are what this side
        knows. ``place_dev`` is the cost model's EFFECTIVE placement
        (ISSUE 18), not the static has-a-device-body shape. With a fusion
        ``plan`` the mask covers compact nodes (the plan built it from
        the same placement) and ``fusion`` has the device REGIONS, each
        dispatched as one program. Call it LAST: ready device tasks
        surface at once."""
        data = flat["data"]
        if plan is None:
            # only data-carrying TPU classes ride the device plane; a
            # CTL-only [type=TPU] class has no arrays to place and runs
            # its raw body through the ordinary CPU dispatch
            dev_of_class = [d and nd > 0
                            for d, nd in zip(place_dev, data["ndflows"])]
            if not any(dev_of_class):
                return
            # the mask and what it allows ride the flatten cache (the
            # placement is part of its key)
            found = flat.get("dev")
            if found is None:
                dev_mask: List[int] = []
                for ci, insts in enumerate(flat["params"]):
                    dev_mask.extend(
                        [1 if dev_of_class[ci] else 0] * len(insts))
                found = flat["dev"] = (
                    dev_mask, sum(dev_mask), _released_at_dispatch(
                        dev_mask, flat["off"], flat["succs"]))
            dev_mask, ndev, early = found
        else:
            dev_mask, ndev = plan["dev_mask"], plan["ndev_tasks"]
            early = plan["dev_early"]
        from ...core import costmodel as _cm
        from ...device import lane_pool
        PTEXEC_STATS["pools_device"] += 1
        PTEXEC_STATS["tasks_device"] += ndev
        lane["dev"] = devlane
        lane["dev_pool"], lane["dev_held"] = lane_pool.bind(
            devlane, lane["graph"], bases=flat["bases"],
            params=flat["params"], slot_base=data["slot_base"],
            in_refs=data["in_refs"], ndflows=data["ndflows"],
            cls_of=data["cls_of"], fns=class_fns[0], written=class_fns[1],
            names=names, slots=slots, mem_datas=mem_datas,
            writebacks=writebacks, dev_mask=dev_mask, ndev_tasks=ndev,
            early=early, fusion=fusion, bucket=bucket,
            tile=self._ptexec_tile_bytes or 0,
            # the lane's observations, folded into the cost model at
            # detach (Context._cost_fold)
            cost_obs=lane.setdefault("cost_dev", {}) if _cm.enabled()
            else None)

    def _ptexec_owners(self, classes: List[TaskClass],
                       flat) -> Optional[List[int]]:
        """Per-task owner ranks in flattened-id order, or None when any
        rank is out of range (the lane declines rather than misroute)."""
        nb = self.ctx.nb_ranks
        owners: List[int] = []
        for ci, tc in enumerate(classes):
            params = tc._ptg_spec.params
            rank_of = tc._ptg_rank_of
            for key in flat["params"][ci]:
                try:
                    r = int(rank_of(dict(zip(params, key))))
                except Exception:  # noqa: BLE001 — decline, don't die
                    return None
                if not 0 <= r < nb:
                    return None
                owners.append(r)
        return owners

    def _ptexec_bind_comm(self, lane: Dict[str, Any], lane_comm,
                          owners: List[int]) -> None:
        """Bind a flattened graph to the native comm lane: allocate the
        rank-consistent pool id, hand the owner table + send vtable to
        the graph (remote successors then surface as activation frames
        from the GIL-free release sweep), and route this pool's inbound
        frames into the graph's ingest entry points. ``lane['n']``
        becomes the LOCAL task count — the pool accounting a rank owns."""
        pool_id = lane_comm.pool_id_for(self.name)
        graph = lane["graph"]
        # comm/compute overlap is measured, not asserted: the comm
        # lane's EV_COMM_* ring joins the same trace the engines feed.
        # Armed BEFORE the pool registration so a frame that lands the
        # instant routing opens records its ingest point — frames that
        # raced even earlier park and replay with recording, so the
        # merged timeline never reports a send without its ingest
        self.ctx._ntrace_attach("ptcomm", lane_comm.comm)
        self.ctx._hist_attach("ptcomm", lane_comm.comm)
        n_local = graph.comm_bind(lane_comm.comm.send_capsule(), pool_id,
                                  self.ctx.my_rank, owners)
        lane_comm.register_engine(pool_id, graph)
        lane["pool_id"] = pool_id
        lane["comm"] = lane_comm
        lane["n"] = n_local

    def _ptexec_comm_data(self, flat, owners: List[int]) -> Dict[str, Any]:
        """Distributed data-pool tables, derived per instantiation:

        * ``slot_uses``: LOCAL consumer count per slot (the retire
          protocol runs rank-local; a remote consumer's use is satisfied
          by the payload send at production time);
        * ``remote_in``: input slots whose producer runs elsewhere — the
          dispatch callback materializes them from the comm lane's
          payload store (landed eager or pulled rendezvous);
        * ``feeds``: produced slot -> destination ranks (payload ships
          once per rank, before the release sweep's activations — FIFO
          frame order makes eager payloads race-free)."""
        data = flat["data"]
        me = self.ctx.my_rank
        in_off, in_slots = data["in_off"], data["in_slots"]
        slot_base, cls_of = data["slot_base"], data["cls_of"]
        ndflows = data["ndflows"]
        n = flat["n"]
        task_of_slot = [0] * data["n_slots"]
        for tid in range(n):
            base = slot_base[tid]
            for dj in range(ndflows[cls_of[tid]]):
                task_of_slot[base + dj] = tid
        slot_uses = [0] * data["n_slots"]
        remote_in = set()
        feeds: Dict[int, List[int]] = {}
        for tid in range(n):
            local = owners[tid] == me
            for k in range(in_off[tid], in_off[tid + 1]):
                ref = in_slots[k]
                producer_local = owners[task_of_slot[ref]] == me
                if local:
                    slot_uses[ref] += 1
                    if not producer_local:
                        remote_in.add(ref)
                elif producer_local:
                    dsts = feeds.setdefault(ref, [])
                    if owners[tid] not in dsts:
                        dsts.append(owners[tid])
        return {"slot_uses": slot_uses, "remote_in": frozenset(remote_in),
                "feeds": feeds}

    def _mk_ptexec_callback(self, bases: List[int], bodies,
                            params_by_class):
        """Batched body dispatch: the engine hands over a list of ready
        task ids; every body must run before it returns (successor release
        happens after, preserving release-edge ordering for observers)."""
        import bisect as _bisect
        def run_batch(ids):
            for i in ids:
                k = _bisect.bisect_right(bases, i) - 1
                fn = bodies[k]
                if fn is not None:
                    fn(*params_by_class[k][i - bases[k]])
        return run_batch

    def _mk_ptexec_data_callback(self, flat, classes: List[TaskClass],
                                 slots: List[Any], mem_datas,
                                 writebacks: Dict[int, List], comm=None,
                                 fusion=None, class_fns=None):
        """Batched dispatch for data-flow pools — the lane's replacement
        for generic_prepare_input + the body hook + complete_execution +
        the repo side of generic_release_deps, amortized over one Python
        call per ~256 ready tasks:

        * inputs resolve from the slot array (producer outputs), memory
          endpoints (``newest_copy`` at dispatch time, matching the Python
          FSM's prepare-at-ready timing), or None (``NEW``);
        * non-empty bodies call the class's jitted function (the same
          object the CPU hook dispatches) — empty bodies forward inputs
          by identity with no dispatch at all;
        * every data flow's post-body value lands in the task's own slot
          (data_out for written flows, forwarded data_in otherwise), then
          memory out-deps write back and bump the data version;
        * ``retired`` slot ids (reported by the engine once a slot's last
          consumer body has run) drop their payload reference — the
          entry-retire moment of core/datarepo.py, one list op instead of
          a locked hash-table dance per use.

        With ``comm`` set (a distributed pool on the native comm lane),
        two extra moves happen inside the same batched dispatch: input
        slots produced on another rank materialize from the comm lane's
        payload store (landed eager, or rendezvous-pulled — readiness was
        gated in C until the pull completed), and produced slots feeding
        remote consumers ship BEFORE the engine's release sweep sends
        their activations, so the per-link FIFO makes eager data
        race-free by construction.
        """
        bases = flat["bases"]
        params_by_class = flat["params"]
        data = flat["data"]
        slot_base = data["slot_base"]
        in_refs = data["in_refs"]
        slot_uses = data["slot_uses"]
        ndflows = data["ndflows"]
        cls_of = data["cls_of"]
        # fused pools pass the SAME (fns, written) pair their region
        # programs were jitted against — one object, not two derivations
        fns, written_by_class = class_fns if class_fns is not None \
            else self._ptexec_class_fns(classes, data)
        if fusion is not None:
            # fused pool: region nodes dispatch through their runner,
            # everything else maps its compact id back to the original
            # (the arrays above are all original-id indexed); the C side
            # retires slots by original slot id either way
            _forig = fusion["orig_of"]
            _fregions = fusion["regions"]
        else:
            _forig = _fregions = None
        # single-data-flow classes whose flow is WRITTEN are the hot shape
        # (RW chains); the dispatch loop specializes them. A READ-only
        # single flow must take the general path: its body returns an
        # EMPTY written tuple and the flow forwards the input unchanged
        single = [nd == 1 and w == (0,)
                  for nd, w in zip(ndflows, written_by_class)]
        if comm is not None:
            lane, pool = comm["lane"], comm["pool_id"]
            remote_in, feeds = comm["remote_in"], comm["feeds"]
        else:
            lane = pool = None
            remote_in, feeds = frozenset(), {}
        has_feeds = bool(feeds)
        #: remote slots already materialized (so a producer's legitimate
        #: None payload is not re-fetched); retire clears entries
        fetched: set = set()
        _fetch_mu = threading.Lock()

        def _fetch_remote(r):
            # two workers can dispatch two consumers of the same remote
            # slot concurrently; take_payload CONSUMES the C-side entry,
            # so the check-then-fetch must be atomic (rare path: once
            # per remote slot — the lock never touches local slots)
            with _fetch_mu:
                if r in fetched:
                    return slots[r]
                v = lane.take_payload(pool, r)
                slots[r] = v
                fetched.add(r)
                return v

        def _null_guard(k, i):
            raise RuntimeError(
                f"A NULL is forwarded from {classes[k]._ptg_spec.name}"
                f"{tuple(params_by_class[k][i - bases[k]])} (native lane)")

        def run_batch(ids, retired):
            # locals: this loop runs once per TASK of every data pool
            _slots, _refs, _uses = slots, in_refs, slot_uses
            _base, _cls, _wb = slot_base, cls_of, writebacks
            for j in retired:
                _slots[j] = None          # the entry-retire moment
            if fetched:
                for j in retired:
                    fetched.discard(j)
            for i in ids:
                if _forig is not None:
                    rr = _fregions.get(i)
                    if rr is not None:
                        rr()              # ONE fused super-task dispatch
                        continue
                    i = _forig[i]
                k = _cls[i]
                fn = fns[k]
                nd = ndflows[k]
                if nd == 0:               # CTL class riding a data pool
                    if fn is not None:
                        fn(*params_by_class[k][i - bases[k]])
                    continue
                base = _base[i]
                if single[k]:
                    r = _refs[base]
                    if r >= 0:
                        v = _slots[r]
                        if v is None and r in remote_in \
                                and r not in fetched:
                            # produced on another rank: materialize from
                            # the comm lane's payload store (consumed
                            # once; later local readers hit _slots[r])
                            v = _fetch_remote(r)
                    elif r == -1:
                        v = None
                    else:
                        copy = mem_datas[-2 - r].newest_copy()
                        v = None if copy is None else copy.payload
                    if fn is not None:
                        v = fn(*params_by_class[k][i - bases[k]], v)[0]
                    if v is None and _uses[base] > 0:
                        _null_guard(k, i)    # parsec.c:1879 source guard
                    _slots[base] = v
                    if has_feeds:
                        dsts = feeds.get(base)
                        if dsts:
                            # ship BEFORE the release sweep runs: the
                            # consumer's activation then trails its data
                            # on the FIFO link
                            for dst in dsts:
                                lane.send_payload(dst, pool, base, v)
                    wbs = _wb.get(i)
                    if wbs is None:
                        continue
                    vals = (v,)
                else:
                    vals = []
                    for dj in range(nd):
                        r = _refs[base + dj]
                        if r >= 0:
                            v = _slots[r]
                            if v is None and r in remote_in \
                                    and r not in fetched:
                                v = _fetch_remote(r)
                            vals.append(v)
                        elif r == -1:
                            vals.append(None)
                        else:
                            copy = mem_datas[-2 - r].newest_copy()
                            vals.append(None if copy is None
                                        else copy.payload)
                    if fn is not None:
                        outs = fn(*params_by_class[k][i - bases[k]], *vals)
                        for oj, dj in enumerate(written_by_class[k]):
                            vals[dj] = outs[oj]
                    for dj in range(nd):
                        v = vals[dj]
                        if v is None and _uses[base + dj] > 0:
                            _null_guard(k, i)
                        _slots[base + dj] = v
                    if has_feeds:
                        for dj in range(nd):
                            dsts = feeds.get(base + dj)
                            if dsts:
                                for dst in dsts:
                                    lane.send_payload(dst, pool, base + dj,
                                                      vals[dj])
                    wbs = _wb.get(i)
                    if wbs is None:
                        continue
                for dj, dref in wbs:
                    dref.write_host(vals[dj])
        return run_batch

    def _ptexec_finalize(self, lane: Dict[str, Any]) -> None:
        """Called exactly once (by whichever stream drains the graph last)
        after every lane task executed: retire the task accounting in one
        step — the per-task complete/release cycle already ran in C — and
        drop the remaining slot payloads (terminal outputs were already
        written back by the callback; slots the last release sweep retired
        never met another dispatch to clear them)."""
        output.debug_verbose(2, "ptg",
                             f"{self.name}: native lane retired "
                             f"{lane['n']} tasks")
        if lane.get("pool_id") is not None:
            # stop routing this pool's frames; parked payloads (already
            # consumed or unreachable) drop with the registration
            lane["comm"].unregister_engine(lane["pool_id"])
        if lane.get("dev_pool") is not None:
            # every device task retired (the graph is done), so the lane
            # owes this pool nothing; drop the routing + the engine pin
            lane["dev"].unbind_pool(lane["dev_pool"])
        slots = lane.get("slots")
        if slots:
            # lane-side datarepo accounting into the counter registry
            # (the slot_stats retire counter, ptexec.slots_retired)
            from ...utils.counters import PTEXEC_SLOTS_RETIRED, counters
            counters.add(PTEXEC_SLOTS_RETIRED, lane["graph"].slot_stats()[1])
            slots.clear()
        self.addto_nb_tasks(-lane["n"])

    # ------------------------------------------------------------------ startup
    def _startup(self, stream, tp) -> List[Task]:
        """The startup hook, under the second half of the ``ptg.lower``
        span: one record an instantiation, from ``instantiate`` to the
        lanes bound (or the first ready tasks made)."""
        sp = self.ctx._spans
        if sp is None:
            return self._lower(stream)
        tok = sp.begin(PTG_LOWER)
        try:
            return self._lower(stream)
        finally:
            sp.lower.record(self._lower_ns + sp.end(tok, None))

    def _lower(self, stream) -> List[Task]:
        total = 0
        ready: List[Task] = []
        my_rank = self.ctx.my_rank
        distributed = self.ctx.nb_ranks > 1 and self.ctx.comm is not None
        agg = {tcs.name for tcs in self.program.spec.task_classes
               if self._agglomerable(self._classes[tcs.name])}
        self._agglomerated = 0
        for name in agg:
            self._agglomerated += self._run_agglomerated(
                stream, self._classes[name])
        nonagg = any(tcs.name not in agg
                     for tcs in self.program.spec.task_classes)
        lane = self._ptexec_prepare(agg)
        if lane is not None:
            PTEXEC_STATS["pools_engaged"] += 1
            PTEXEC_STATS["tasks_engaged"] += lane["n"]
            if lane.get("pool_id") is not None:
                from ...comm.native import PTCOMM_STATS
                PTCOMM_STATS["pools_engaged"] += 1
                PTCOMM_STATS["tasks_engaged"] += lane["n"]
            self._ptexec_state = lane
            self.set_nb_tasks(lane["n"])
            if lane["n"]:
                self.ctx._ptexec_enqueue(self, lane)
            elif lane.get("pool_id") is not None:
                # a rank owning zero tasks of a distributed pool still
                # keeps the registration until the pool is globally done;
                # nothing will be ingested, unregistration happens at
                # lane fini (no local finalize will run)
                pass
            output.debug_verbose(2, "ptg",
                                 f"{self.name}: {lane['n']} tasks on the "
                                 f"native execution lane")
            return []
        if nonagg and mca.get("ptg_native_exec", True):
            if self._ptexec_refusal == "fallback":
                PTEXEC_STATS["pools_fallback"] += 1
            else:
                PTEXEC_STATS["pools_ineligible"] += 1
            if distributed:
                from ...comm.native import PTCOMM_STATS
                PTCOMM_STATS["pools_fallback" if self._ptexec_refusal ==
                             "fallback" else "pools_ineligible"] += 1
        for tcs in self.program.spec.task_classes:
            if tcs.name in agg:
                continue        # executed above, never scheduled/counted
            tc = self._classes[tcs.name]
            for loc in self._enum_class(tc):
                if distributed and tc._ptg_rank_of(loc) != my_rank:
                    continue
                total += 1
                if getattr(tc, "_ptg_startup_fn", None) is not None:
                    continue    # custom startup seeds this class below
                if tc.dependencies_goal_fn(loc) == 0:
                    ready.append(self.ctx.make_task(self, tc, loc))
        # user-defined startup (ref: udf.jdf startup_fn): fn(taskpool,
        # task_class) yields the locals of this class's initial ready tasks
        for tcs in self.program.spec.task_classes:
            tc = self._classes[tcs.name]
            fn = getattr(tc, "_ptg_startup_fn", None)
            if fn is None:
                continue
            for loc in fn(self, tc):
                loc = dict(loc)
                if distributed and tc._ptg_rank_of(loc) != my_rank:
                    continue
                ready.append(self.ctx.make_task(self, tc, loc))
        self.set_nb_tasks(total)
        output.debug_verbose(2, "ptg",
                             f"{self.name}: {total} tasks, {len(ready)} at startup")
        return ready


#: names through which a body can read a variable without naming it
_DYNAMIC_LOOKUPS = frozenset(("locals", "vars", "globals", "eval", "exec"))


def _names_in(source: str) -> Optional[frozenset]:
    """Every identifier a BODY's text names, or None when the text does
    not parse alone or looks names up at run time (everything is then
    taken as named)."""
    try:
        tree = ast.parse(textwrap.dedent(source))
    except SyntaxError:
        return None
    names = frozenset(n.id for n in ast.walk(tree) if isinstance(n, ast.Name))
    return None if names & _DYNAMIC_LOOKUPS else names


class PTGProgram:
    """A compiled PTG program; instantiate per (globals, collections) run."""

    def __init__(self, spec: P.ProgramSpec) -> None:
        self.spec = spec
        #: class name -> the identifiers its bodies name. What a body does
        #: not name cannot change the program it traces to, so a task
        #: parameter or a global enters a region executable's key only
        #: where a body names it (ISSUE 29)
        self.body_names: Dict[str, Optional[frozenset]] = {}
        for tcs in spec.task_classes:
            named = [_names_in(b.source) for b in tcs.bodies]
            self.body_names[tcs.name] = None if None in named \
                else frozenset().union(*named)
        every = list(self.body_names.values())
        self.globals_named: Optional[frozenset] = None if None in every \
            else frozenset().union(*every)
        #: the region executables of this program's pools, one per shape
        #: of region (``_ptexec_lane_fused``); its hit/miss/evict counts
        #: are the cache's own attributes
        self.region_programs = ExecCache(128)

    def instantiate(self, ctx: Context, globals: Optional[Dict[str, Any]] = None,
                    collections: Optional[Dict[str, Any]] = None,
                    name: Optional[str] = None,
                    datatypes: Optional[Dict[str, NamedDatatype]] = None
                    ) -> PTGTaskpool:
        sp = ctx._spans
        if sp is not None:
            tok = sp.begin(PTG_LOWER)
        tp = PTGTaskpool(self, ctx, dict(globals or {}),
                         dict(collections or {}), name, datatypes=datatypes)
        if sp is not None:
            # recorded when the startup hook has bound the lanes
            tp._lower_ns = sp.end(tok, None)
        return tp


def compile_ptg(source: str, name: str = "ptg") -> PTGProgram:
    """Compile PTG source (the parsec-ptgpp entry point)."""
    return PTGProgram(P.parse(source, name))
