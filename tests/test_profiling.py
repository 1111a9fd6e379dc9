"""Profiling/tracing tests: trace generation + content validation.

Models tests/profiling in the reference: run a DAG with the tracer on, then
validate the trace *content* (check-async.py / check-comms.py style).
"""

import json
import os

import numpy as np
import pytest

from parsec_tpu.core.context import Context
from parsec_tpu.core.pins_modules import (ALPerf, IteratorsChecker,
                                          PrintSteals, TaskProfiler,
                                          ptg_to_dtd_replay)
from parsec_tpu.data.matrix import TiledMatrix
from parsec_tpu.dsl.dtd import DTDTaskpool, RW
from parsec_tpu.dsl.ptg.compiler import compile_ptg
from parsec_tpu.tools.trace_reader import read_pbp, to_chrome_trace, to_dataframe
from parsec_tpu.utils.grapher import DotGrapher
from parsec_tpu.utils.trace import Profiling


@pytest.fixture()
def ctx():
    c = Context(nb_cores=1)
    yield c
    c.fini()


def _run_chain(ctx, n=8):
    tp = DTDTaskpool(ctx, "profchain")
    t = tp.tile_new((4, 4), np.float32)
    for _ in range(n):
        tp.insert_task(lambda x: x + 1.0, (t, RW))
    tp.wait()
    tp.close()
    ctx.wait()
    return t


def test_trace_roundtrip(ctx, tmp_path):
    prof = Profiling()
    tprof = TaskProfiler(prof)
    tprof.enable(ctx)
    _run_chain(ctx, 8)
    path = str(tmp_path / "t.pbp")
    prof.dump(path)
    trace = read_pbp(path)
    assert trace.dictionary[0]["name"] in ("<lambda>", "dtd_task")
    df = to_dataframe(trace)
    # 8 exec intervals with matched begin/end and positive durations
    assert len(df) == 8
    assert (df["duration"] > 0).all()
    assert set(df["taskpool_id"]) == {_run_chain.__defaults__ and df["taskpool_id"].iloc[0]}
    ctf = to_chrome_trace(trace)
    assert len([e for e in ctf["traceEvents"] if e["ph"] == "X"]) == 8


def test_trace_cli(ctx, tmp_path, capsys):
    prof = Profiling()
    TaskProfiler(prof).enable(ctx)
    _run_chain(ctx, 4)
    path = str(tmp_path / "t.pbp")
    prof.dump(path)
    from parsec_tpu.tools import trace_reader
    ctf = str(tmp_path / "t.json")
    assert trace_reader.main([path, "--ctf", ctf]) == 0
    data = json.load(open(ctf))
    assert any(e.get("ph") == "X" for e in data["traceEvents"])


def test_alperf_and_steals(ctx):
    al = ALPerf()
    al.enable(ctx)
    ps = PrintSteals()
    ps.enable(ctx)
    _run_chain(ctx, 16)
    rep = al.report()
    assert al.counts["executed"] == 16
    assert al.counts["completed"] == 16
    assert rep["executed"] > 0
    assert sum(v["selects"] for v in ps.report().values()) >= 1


def test_iterators_checker_clean_ptg(ctx):
    """A well-formed PTG program produces zero violations."""
    chk = IteratorsChecker()
    chk.enable(ctx)
    src = """
%global NT
%global A
T(k)
  k = 0 .. NT-1
  : A(0, 0)
  RW X <- (k == 0) ? A(0, 0) : X T(k-1)
     -> (k < NT-1) ? X T(k+1) : A(0, 0)
BODY
  X = X + 1.0
END
"""
    A = TiledMatrix("A", 4, 4, 4, 4)
    A.fill(lambda m, n: np.zeros((4, 4), np.float32))
    tp = compile_ptg(src, "chk").instantiate(ctx, globals={"NT": 6},
                                             collections={"A": A})
    ctx.add_taskpool(tp)
    ctx.wait()
    assert chk.violations == []


def test_dot_grapher(ctx):
    g = DotGrapher()
    g.enable(ctx)
    _run_chain(ctx, 4)
    dot = g.to_dot()
    assert dot.startswith("digraph")
    assert dot.count("->") == 3  # chain of 4 has 3 edges


def test_ptg_to_dtd_replay(ctx):
    """Cross-DSL harness: the PTG chain replayed through DTD gives the same
    result (ref: pins/ptg_to_dtd)."""
    src = """
%global NT
%global A
T(k)
  k = 0 .. NT-1
  : A(0, 0)
  RW X <- (k == 0) ? A(0, 0) : X T(k-1)
     -> (k < NT-1) ? X T(k+1) : A(0, 0)
BODY
  X = X + 1.0
END
"""
    NT = 5
    A = TiledMatrix("A", 4, 4, 4, 4)
    A.fill(lambda m, n: np.zeros((4, 4), np.float32))
    prog = compile_ptg(src, "replay")
    ptp = prog.instantiate(ctx, globals={"NT": NT}, collections={"A": A})
    # replay WITHOUT running the PTG version
    dtp = ptg_to_dtd_replay(ptp, ctx)
    dtp.wait()
    dtp.close()
    ctx.wait()
    assert dtp.executed >= NT
    # the chain's memory out-dep wrote home: A(0,0) saw NT increments
    np.testing.assert_allclose(np.asarray(A.data_of(0, 0).newest_copy().payload),
                               float(NT))


# ----------------------------------------------------- comm-stream tracing

def test_comm_trace_2rank_check_comms(tmp_path):
    """Distributed run with per-rank tracers: the comm machinery writes
    typed activate/get/put events with src/dst/bytes to its own stream, and
    the cross-rank validator proves wire symmetry (the check-comms.py role,
    ref: remote_dep_mpi.c:1286-1302, tests/profiling/check-comms.py)."""
    import numpy as np

    from parsec_tpu.comm.remote_dep import RemoteDepEngine
    from parsec_tpu.comm.threads import ThreadsCE, run_distributed
    from parsec_tpu.core.context import Context
    from parsec_tpu.data.matrix import TwoDimBlockCyclic
    from parsec_tpu.ops.gemm import insert_gemm_tasks
    from parsec_tpu.tools.trace_reader import check_comms, comm_events, read_pbp
    from parsec_tpu.utils import mca

    N, TS = 64, 16
    rng = np.random.default_rng(4)
    a = rng.standard_normal((N, N)).astype(np.float32)
    b = rng.standard_normal((N, N)).astype(np.float32)
    # small eager limit so large tiles exercise the rendezvous (get/put) leg
    mca.set("comm_eager_limit", 512)
    try:
        def program(rank, fabric):
            ctx = Context(nb_cores=1, my_rank=rank, nb_ranks=2)
            ctx.profiling = Profiling()
            RemoteDepEngine(ctx, ThreadsCE(fabric, rank))
            kw = dict(nodes=2, myrank=rank, P=2, Q=1)
            A = TwoDimBlockCyclic("ctA", N, N, TS, TS, **kw)
            B = TwoDimBlockCyclic("ctB", N, N, TS, TS, **kw)
            C = TwoDimBlockCyclic("ctC", N, N, TS, TS, **kw)
            A.fill(lambda m, n: a[m*TS:(m+1)*TS, n*TS:(n+1)*TS])
            B.fill(lambda m, n: b[m*TS:(m+1)*TS, n*TS:(n+1)*TS])
            C.fill(lambda m, n: np.zeros((TS, TS), np.float32))
            tp = DTDTaskpool(ctx, "commtrace")
            insert_gemm_tasks(tp, A, B, C)
            tp.wait(timeout=60)
            tp.close()
            ctx.wait(timeout=30)
            ctx.fini()
            path = str(tmp_path / f"rank{rank}.pbp")
            ctx.profiling.dump(path)
            return path

        paths = run_distributed(2, program, timeout=120)
    finally:
        mca.params.unset("comm_eager_limit")

    evs0 = comm_events(read_pbp(paths[0]))
    assert evs0, "rank 0 recorded no comm events"
    kinds = {e["kind"] for e in evs0}
    assert "activate_snd" in kinds and "activate_rcv" in kinds
    # 16x16 f32 tiles (1KiB) exceed the 512B eager limit -> rendezvous legs
    assert "put_rcv" in kinds or "put_snd" in kinds, kinds
    summary = check_comms(paths)
    assert summary["errors"] == [], summary
    assert summary["counts"]["activate_snd"] > 0
    assert summary["counts"]["put_snd"] > 0          # rendezvous exercised
    assert summary["counts"]["activate_snd"] == summary["counts"]["activate_rcv"]

    # the CLI entry point (the reference's standalone checker script)
    from parsec_tpu.tools import trace_reader
    assert trace_reader.main(["--check-comms", *paths]) == 0


def test_dag_svg_render(ctx, tmp_path):
    """The dbp-dot2png role without graphviz: the executed DAG renders to a
    self-contained SVG with layered nodes and dependency arrows."""
    g = DotGrapher()
    g.enable(ctx)
    _run_chain(ctx, 4)
    svg = g.to_svg()
    assert svg.startswith("<svg") and svg.endswith("</svg>")
    assert svg.count("<rect") == 4          # 4 chained tasks
    assert svg.count("<line") == 3          # 3 dependency edges
    p = g.dump_svg(str(tmp_path / "dag.svg"))
    assert open(p).read() == svg


def test_animated_gantt_svg(ctx, tmp_path):
    """The trace-animation role (tools/profiling/animation.c): a
    self-drawing Gantt SVG with SMIL timing."""
    from parsec_tpu.tools import trace_reader
    from parsec_tpu.tools.trace_reader import read_pbp, to_animated_svg

    prof = Profiling()
    TaskProfiler(prof).enable(ctx)
    _run_chain(ctx, 6)
    path = prof.dump(str(tmp_path / "anim.pbp"))
    svg = to_animated_svg(read_pbp(path))
    assert svg.count("<rect") == 6
    assert svg.count("<set attributeName=") == 6       # SMIL playback
    out = str(tmp_path / "anim.svg")
    assert trace_reader.main([path, "--svg", out]) == 0
    assert open(out).read().startswith("<svg")


def test_live_counter_view(ctx, tmp_path):
    """The aggregator_visu GUI role: background counter sampling during a
    run + a rendered time-series image (headless matplotlib)."""
    from parsec_tpu.tools.live_view import LiveCounterView
    from parsec_tpu.utils.counters import install_scheduler_counters

    install_scheduler_counters(ctx)
    view = LiveCounterView(interval_s=0.01)
    view.start()
    _run_chain(ctx, 32)
    view.stop()
    assert len(view.times) >= 2
    active = view.active_series()
    assert any("sched" in n or "task" in n for n in active), active
    out = view.render(str(tmp_path / "counters.png"))
    assert os.path.getsize(out) > 1000


# ------------------------------------------------- memory-over-time (dbp2mem)

def test_device_memory_events_and_mem_view(tmp_path):
    """The dbp2mem pipeline (tools/profiling/dbp2mem.c role): a DAG under a
    tight device budget emits ::mem residency POINT events; mem_view renders
    timeline/summary/CSV/SVG, with evictions visible as negative deltas."""
    from parsec_tpu.device.tpu import TPUDevice
    from parsec_tpu.tools import mem_view
    from parsec_tpu.utils import mca

    mca.set("device_tpu_over_cpu", True)
    ctx = Context(nb_cores=1)
    try:
        ctx.profiling = Profiling()
        devs = [d for d in ctx.devices.devices if isinstance(d, TPUDevice)]
        assert devs, "device module did not register over the host device"
        dev = devs[0]
        ts = 16
        tile_b = ts * ts * 4
        dev.set_budget(3 * tile_b, unit=tile_b)      # room for ~3 tiles

        A = TiledMatrix("Amem", 8 * ts, ts, ts, ts)
        rng = np.random.default_rng(77)
        A.fill(lambda m, n: rng.standard_normal((ts, ts)).astype(np.float32))
        tp = DTDTaskpool(ctx, "memtrace")
        for m in range(8):                            # 8 tiles > 3-tile budget
            tp.insert_task(lambda x: x * 2.0, (tp.tile_of(A, m, 0), RW))
        tp.wait(timeout=60)
        tp.close()
        ctx.wait(timeout=30)
        assert dev.evictions > 0                      # pressure exercised
        path = ctx.profiling.dump(str(tmp_path / "mem.pbp"))
    finally:
        ctx.fini()
        mca.params.unset("device_tpu_over_cpu")

    trace = read_pbp(path)
    rows = mem_view.memory_timeline(trace)
    assert rows, "no ::mem events in the trace"
    assert all(r["t"] >= 0 for r in rows)
    assert any(r["delta"] > 0 for r in rows)          # stage-ins
    assert any(r["delta"] < 0 for r in rows)          # evictions
    # residency is the post-change occupancy: replaying deltas reproduces it
    run = {}
    for r in rows:
        run[r["stream"]] = run.get(r["stream"], 0) + r["delta"]
        assert run[r["stream"]] == r["resident"], r
    # residency never exceeds budget + one in-flight tile
    assert max(r["resident"] for r in rows) <= 4 * (16 * 16 * 4)

    summ = mem_view.summarize(trace)
    s = next(iter(summ.values()))
    assert s["peak"] > 0 and s["allocated"] > s["freed"] - 1

    csv = mem_view.to_csv(trace)
    assert csv.splitlines()[0] == "t_seconds,stream,resident_bytes,delta_bytes"
    assert len(csv.splitlines()) == len(rows) + 1
    svg = mem_view.to_svg(trace)
    assert svg.startswith("<svg") and "polyline" in svg

    # CLI writes both artifacts
    out_csv, out_svg = str(tmp_path / "m.csv"), str(tmp_path / "m.svg")
    assert mem_view.main([path, "--csv", out_csv, "--svg", out_svg]) == 0
    assert os.path.getsize(out_csv) > 0 and os.path.getsize(out_svg) > 0


def test_trace_perf_bench_runs():
    """The sp-perf analogue emits sane numbers (small n: smoke, not perf)."""
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    p = subprocess.run(
        [sys.executable, os.path.join(repo, "benchmarks", "trace_perf.py"),
         "2000"], capture_output=True, text=True, timeout=110)
    assert p.returncode == 0, p.stderr[-500:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert got["metric"] == "trace-events-per-sec"
    assert got["value"] > 10_000                      # trivially exceeded
    assert got["n_events"] == 2000 + 2 * (2000 // 2) + 2000 + 2000 // 10
    assert got["dump_events_per_sec"] > 0 and got["read_events_per_sec"] > 0


def test_hw_counters_pins_module(ctx):
    """The PAPI-role PINS module: samples per-class PMU deltas where
    perf_event works, enables as a NO-OP where it does not (this
    container blocks the syscall — both paths are the contract)."""
    from parsec_tpu.core.pins_modules import HWCounters
    from parsec_tpu.utils import perf_event

    hw = HWCounters()
    hw.enable(ctx)
    try:
        _run_chain(ctx, 8)
        if perf_event.available():
            rep = hw.report()
            assert hw.tasks_sampled >= 8
            cls = next(iter(rep.values()))
            assert cls.get("cycles", 0) > 0
        else:
            assert hw.tasks_sampled == 0       # clean no-op
    finally:
        hw.disable(ctx)


def test_perf_event_attr_layout():
    """The hand-packed perf_event_attr must be exactly
    PERF_ATTR_SIZE_VER7 bytes with the flags word at offset 40."""
    from parsec_tpu.utils import perf_event as pe
    raw = pe._attr_bytes(pe.EVENTS["cycles"])
    assert len(raw) == 128
    import struct
    t, size = struct.unpack_from("II", raw, 0)
    assert t == 0 and size == 128
    (flags,) = struct.unpack_from("Q", raw, 40)
    assert flags & 0x1          # disabled at open
    assert flags & (1 << 5)     # exclude_kernel


# --------------------------------------------- native in-lane tracing (PR 5)
# The observer-effect contract: profiled runs stay on the native lanes and
# the lanes trace THEMSELVES (per-worker ring buffers drained into the PBP
# streams, utils/native_trace.py) — the recorded machine is the production
# machine. --mca pins_paranoid 1 opts back into the per-task Python FSM.

_CHAIN_SRC = (
    "%global NT\n%global DEPTH\n"
    "T(i, l)\n  i = 0 .. NT-1\n  l = 0 .. DEPTH-1\n"
    "  CTL S <- (l > 0) ? S T(i, l-1)\n"
    "        -> (l < DEPTH-1) ? S T(i, l+1)\nBODY\n  pass\nEND\n")


def _run_ptg_chain(ctx, nt=16, depth=8, name="ntrace"):
    prog = compile_ptg(_CHAIN_SRC, name)
    tp = prog.instantiate(ctx, globals={"NT": nt, "DEPTH": depth},
                          collections={})
    ctx.add_taskpool(tp)
    ctx.wait(timeout=60)
    return tp


def test_native_lane_trace_chain(tmp_path):
    """A profiled chain run stays on the native lane and yields a PBP
    trace with per-worker native streams: paired START/END task
    intervals, monotonic per-stream timestamps, zero drops, and a valid
    chrome://tracing conversion."""
    from parsec_tpu.dsl.ptg.compiler import PTEXEC_STATS
    ctx = Context(nb_cores=1)
    ctx.profiling = Profiling()
    snap = PTEXEC_STATS.snapshot()
    tp = _run_ptg_chain(ctx)
    delta = PTEXEC_STATS.delta(snap)
    ctx.fini()
    assert tp._ptexec_state is not None, \
        "profiling ejected the pool from the native lane (observer effect)"
    assert delta["pools_engaged"] == 1 and delta["pools_fallback"] == 0
    path = ctx.profiling.dump(str(tmp_path / "native.pbp"))
    trace = read_pbp(path)
    assert any(s["name"].startswith("ptexec-w") for s in trace.streams)
    assert "ptexec::task" in {d["name"] for d in trace.dictionary}
    for s in trace.streams:           # ring hand-off preserves time order
        ts = [e[3] for e in s["events"]]
        assert ts == sorted(ts)
    df = to_dataframe(trace)
    tasks = df[df["name"] == "ptexec::task"]
    assert len(tasks) == 16 * 8       # every lane task: one paired interval
    assert (tasks["duration"] >= 0).all()
    ctf = to_chrome_trace(trace)
    assert len([e for e in ctf["traceEvents"] if e["ph"] == "X"]) == 16 * 8
    meta = {e["args"]["name"] for e in ctf["traceEvents"] if e["ph"] == "M"}
    assert any(n.startswith("ptexec-w") for n in meta)
    assert ctx._ntrace.dropped() == 0


def test_profiling_keeps_native_engagement():
    """Regression for the observer effect: engagement counters of a
    profiled run match an unprofiled run of the same pool shape."""
    from parsec_tpu.dsl.ptg.compiler import PTEXEC_STATS
    ctx = Context(nb_cores=1)
    base = PTEXEC_STATS.snapshot()
    _run_ptg_chain(ctx, name="plain")
    plain = PTEXEC_STATS.delta(base)
    ctx.fini()
    ctx2 = Context(nb_cores=1)
    ctx2.profiling = Profiling()
    base2 = PTEXEC_STATS.snapshot()
    _run_ptg_chain(ctx2, name="profiled")
    profiled = PTEXEC_STATS.delta(base2)
    ctx2.fini()
    assert profiled == plain, (plain, profiled)


def test_native_trace_ring_overflow():
    """Ring overflow drops events (bumping the drop counter) instead of
    blocking the lane: the run completes, the drop count is visible, and
    the drained event count stays within capacity."""
    from parsec_tpu.utils import mca
    mca.set("trace_ring_capacity", 32)
    mca.set("trace_rings", 1)
    try:
        ctx = Context(nb_cores=1)
        ctx.profiling = Profiling()
        tp = _run_ptg_chain(ctx, nt=64, depth=16, name="overflow")
        ctx.fini()
        assert tp._ptexec_state is not None
        assert tp._ptexec_state["graph"].done()      # lane unharmed
        assert ctx._ntrace.dropped() > 0
        st = ctx.profiling.stats()
        # 2 events per task would be 2048; a 32-slot ring cannot hold them
        assert st["events"] < 2 * 64 * 16
    finally:
        mca.params.unset("trace_ring_capacity")
        mca.params.unset("trace_rings")


def test_pins_paranoid_restores_python_fsm():
    """--mca pins_paranoid 1 is the full-fidelity escape hatch: an
    instrumented pool leaves the native lane (pools_ineligible, not
    fallback) and every task pays the per-task PINS cycle again."""
    from parsec_tpu.core.pins_modules import ALPerf
    from parsec_tpu.dsl.ptg.compiler import PTEXEC_STATS
    from parsec_tpu.utils import mca
    mca.set("pins_paranoid", True)
    try:
        ctx = Context(nb_cores=1)
        al = ALPerf()
        al.enable(ctx)
        assert ctx.pins.paranoid
        snap = PTEXEC_STATS.snapshot()
        tp = _run_ptg_chain(ctx, nt=4, depth=4, name="paranoid")
        delta = PTEXEC_STATS.delta(snap)
        ctx.fini()
        assert tp._ptexec_state is None
        assert delta["pools_engaged"] == 0
        assert delta["pools_ineligible"] == 1
        assert al.counts["executed"] == 4 * 4     # per-task events are back
    finally:
        mca.params.unset("pins_paranoid")


def test_dtd_batched_lane_traced(tmp_path):
    """The DTD batched lane traces its insert->link->exec cycle: link and
    per-(class, batch) exec intervals plus one completion point per
    batched task, while engagement matches an unprofiled run."""
    from parsec_tpu.dsl.dtd import PTDTD_STATS

    def inc(a):
        return a + 1.0

    ctx = Context(nb_cores=1)
    ctx.profiling = Profiling()
    snap = PTDTD_STATS.snapshot()
    tp = DTDTaskpool(ctx, "dtdtrace")
    tiles = [tp.tile_new((2, 2), np.float32) for _ in range(4)]
    for t in tiles:
        t.data.create_copy(0, np.zeros((2, 2), np.float32))
    for i in range(256):
        tp.insert_task(inc, (tiles[i % 4], RW), jit=False)
    tp.wait(timeout=60)
    tp.close()
    ctx.wait(timeout=30)
    delta = PTDTD_STATS.delta(snap)
    ctx.fini()
    assert delta["pools_batch"] == 1, delta      # profiling kept the lane
    assert delta["tasks_batched"] >= 250, delta
    for t in tiles:
        assert float(np.asarray(t.data.newest_copy().payload)[0, 0]) == 64.0
    path = ctx.profiling.dump(str(tmp_path / "dtd.pbp"))
    trace = read_pbp(path)
    kw = {d["name"] for d in trace.dictionary}
    assert {"ptdtd::link", "ptdtd::exec", "ptdtd::task"} <= kw
    by_key = {d["key"]: d["name"] for d in trace.dictionary}
    points = [e for s in trace.streams for e in s["events"]
              if by_key[e[0] >> 1] == "ptdtd::task"]
    # one completion point per batched task (per-task-lane inserts ride
    # the instrumented Python FSM instead)
    assert len(points) == delta["tasks_batched"]
    df = to_dataframe(trace)
    assert (df[df["name"] == "ptdtd::exec"]["duration"] > 0).all()
    # POINT events surface downstream too: zero-duration dataframe rows
    # and chrome instant ('i') events, not just raw stream records
    pts = df[df["name"] == "ptdtd::task"]
    assert len(pts) == delta["tasks_batched"]
    assert (pts["duration"] == 0).all()
    ctf = to_chrome_trace(trace)
    assert len([e for e in ctf["traceEvents"]
                if e["ph"] == "i" and e["name"] == "ptdtd::task"]) \
        == delta["tasks_batched"]
    assert ctx._ntrace.dropped() == 0


def test_native_drain_fires_coarse_pins_markers():
    """Each drain that lands events fires SCHEDULE_BEGIN/END batch
    markers so pins_modules consumers observe lane activity without
    per-task callbacks."""
    from parsec_tpu.core import pins as P
    from parsec_tpu.utils.native_trace import NativeDrainMarker
    ctx = Context(nb_cores=1)
    ctx.profiling = Profiling()
    seen = []
    ctx.pins.register(P.SCHEDULE_END,
                      lambda s, t, e: seen.append(t)
                      if isinstance(t, NativeDrainMarker) else None)
    _run_ptg_chain(ctx, name="markers")
    ctx.fini()
    markers = [m for m in seen if m.lane == "ptexec"]
    assert markers and sum(m.n_events for m in markers) == 2 * 16 * 8


def test_lane_stats_helpers():
    """PTEXEC_STATS/PTDTD_STATS carry snapshot()/reset()/delta() so gates
    stop hand-poking dict keys."""
    from parsec_tpu.utils.counters import LaneStats
    s = LaneStats(a=0, b=0)
    s["a"] += 3
    snap = s.snapshot()
    s["b"] += 2
    assert s.delta(snap) == {"a": 0, "b": 2}
    s.reset()
    assert s == {"a": 0, "b": 0}
    from parsec_tpu.dsl.dtd import PTDTD_STATS
    from parsec_tpu.dsl.ptg.compiler import PTEXEC_STATS
    for stats in (PTEXEC_STATS, PTDTD_STATS):
        assert stats.delta(stats.snapshot()) == {k: 0 for k in stats}


def test_native_counters_registry(tmp_path):
    """install_native_counters exposes the lanes under canonical names
    (ptexec.*, ptdtd.*, trace.*) for live_view / the SDE-style export."""
    from parsec_tpu.dsl.dtd import PTDTD_STATS
    from parsec_tpu.dsl.ptg.compiler import PTEXEC_STATS
    from parsec_tpu.utils.counters import counters, install_native_counters
    install_native_counters()
    install_native_counters()       # idempotent
    snap = counters.snapshot()
    assert snap["ptexec.pools_engaged"] == PTEXEC_STATS["pools_engaged"]
    assert snap["ptdtd.tasks_batched"] == PTDTD_STATS["tasks_batched"]
    assert snap["trace.events_dropped"] >= 0
    ctx = Context(nb_cores=1)
    ctx.profiling = Profiling()
    before = counters.read("ptexec.pools_engaged")
    _run_ptg_chain(ctx, nt=4, depth=4, name="cntreg")
    ctx.fini()
    assert counters.read("ptexec.pools_engaged") == before + 1
    assert counters.read("trace.events_native") > 0


def test_mca_profile_enabled_auto_dump(tmp_path):
    """--mca profile_enabled 1 attaches a tracer at Context creation and
    dumps to --mca profile_filename at fini (the reference's parsec_fini
    dbp write) — with the native lanes traced like an explicit attach."""
    from parsec_tpu.utils import mca
    path = str(tmp_path / "auto.pbp")
    mca.set("profile_enabled", True)
    mca.set("profile_filename", path)
    try:
        ctx = Context(nb_cores=1)
        assert ctx.profiling is not None
        tp = _run_ptg_chain(ctx, nt=4, depth=4, name="mcaauto")
        ctx.fini()
    finally:
        mca.params.unset("profile_enabled")
        mca.params.unset("profile_filename")
    assert tp._ptexec_state is not None
    trace = read_pbp(path)
    assert any(s["name"].startswith("ptexec-w") for s in trace.streams)
    assert len(to_dataframe(trace)
               .query("name == 'ptexec::task'")) == 4 * 4


def test_pins_only_keeps_lane_and_fires_markers():
    """PINS instrumentation with NO tracer attached keeps pools on the
    native lane and runs the bridge marker-only: consumers see coarse,
    balanced drain markers instead of a silently idle machine."""
    from parsec_tpu.dsl.ptg.compiler import PTEXEC_STATS
    ctx = Context(nb_cores=1)
    al = ALPerf()
    al.enable(ctx)                       # pins.enabled, ctx.profiling None
    snap = PTEXEC_STATS.snapshot()
    tp = _run_ptg_chain(ctx, nt=8, depth=4, name="pinsonly")
    delta = PTEXEC_STATS.delta(snap)
    ctx.fini()
    assert tp._ptexec_state is not None, "PINS alone ejected the pool"
    assert delta["pools_engaged"] == 1 and delta["pools_ineligible"] == 0
    assert ctx._ntrace is not None and ctx._ntrace.prof is None
    assert ctx._ntrace.events_landed == 0          # marker-only: no landing
    assert al.counts["scheduled"] >= 1, "pins consumers saw an idle machine"
    # SCHEDULE_END and COMPLETE_EXEC_END fire 1:1 per drain — balanced
    assert al.counts["scheduled"] == al.counts["completed"]


def test_drain_markers_keep_scheduler_counters_balanced():
    """NativeDrainMarker must not drift the canonical enabled/retired
    counters: every marker SCHEDULE_END has a matching COMPLETE_EXEC_END,
    so scheduler.pending_tasks returns to its pre-run value."""
    from parsec_tpu.utils.counters import (
        TASKS_ENABLED, TASKS_RETIRED, counters, install_scheduler_counters)
    ctx = Context(nb_cores=1)
    install_scheduler_counters(ctx)
    ctx.profiling = Profiling()
    before = counters.read(TASKS_ENABLED) - counters.read(TASKS_RETIRED)
    _run_ptg_chain(ctx, nt=8, depth=4, name="balance")
    ctx.fini()
    after = counters.read(TASKS_ENABLED) - counters.read(TASKS_RETIRED)
    assert counters.read(TASKS_ENABLED) > 0        # markers did land
    assert after == before, "drain markers drifted pending_tasks"


def test_trace_accounting_complete_under_ring_contention():
    """Landed + dropped covers every event the lanes tried to record,
    even when concurrent engine calls outnumber the rings (the
    all-rings-claimed case counts into the drop side, never vanishes)."""
    from parsec_tpu.utils import mca
    mca.set("trace_rings", 1)            # force worker contention
    try:
        ctx = Context(nb_cores=2)
        ctx.profiling = Profiling()
        tp = _run_ptg_chain(ctx, nt=64, depth=8, name="contend")
        ctx.fini()
        assert tp._ptexec_state is not None
        # 2 ring events (START/END) per task, no dispatch (CTL bodies):
        # whatever was not landed must be accounted as dropped
        total = ctx._ntrace.events_landed + ctx._ntrace.dropped()
        assert total == 2 * 64 * 8, total
    finally:
        mca.params.unset("trace_rings")


def test_detach_releases_lane_objects_keeps_drop_count():
    """detach() must not pin finished graphs (ring storage is freed with
    the graph) while cumulative drop accounting stays visible."""
    from parsec_tpu.utils import mca
    mca.set("trace_ring_capacity", 32)
    mca.set("trace_rings", 1)
    try:
        ctx = Context(nb_cores=1)
        ctx.profiling = Profiling()
        _run_ptg_chain(ctx, nt=64, depth=16, name="detach")
        ctx.fini()
        assert ctx._ntrace._targets == []          # nothing left attached
        assert ctx._ntrace.dropped() > 0           # snapshot survived detach
    finally:
        mca.params.unset("trace_ring_capacity")
        mca.params.unset("trace_rings")
