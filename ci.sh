#!/usr/bin/env bash
# CI entry point (the Jenkinsfile role, ref: Jenkinsfile:1): build the
# native pieces, lint the tree, run the unit suite, smoke the examples and
# the driver entry. Exits non-zero on any failure.
#
# Usage: ./ci.sh [quick]   — "quick" skips the full pytest suite and runs
# the smoke set only (native build + compile checks + one example).
set -euo pipefail
cd "$(dirname "$0")"

echo "== native build =="
make -C native "PYTHON=$(command -v python3)"

echo "== native artifacts must load (no silent pure-Python fallback) =="
python3 -c "
from parsec_tpu import native
native.require_all()
print('native artifacts OK (ptcore, ptdtd, ptexec, ptcomm, ptsched, ptdev)')"

echo "== no compiled artifacts tracked/staged =="
# .gitignore already covers __pycache__/*.pyc; this guards the regression
# where one gets force-added (or a stale one resurrected) anyway
if git ls-files | grep -E '(^|/)__pycache__/|\.pyc$'; then
    echo "ERROR: .pyc/__pycache__ artifacts are tracked or staged" >&2
    exit 1
fi

echo "== native lane engagement smoke =="
# perf gate by ENGAGEMENT, not throughput: a noisy host can't flake it,
# but a silent fall-back to the Python FSM on an eligible pool (the 48x
# regression) fails it deterministically
JAX_PLATFORMS=cpu timeout 120 python3 - <<'EOF'
import numpy as np
import parsec_tpu as pt
from parsec_tpu.data.matrix import TiledMatrix
from parsec_tpu.dsl.ptg.compiler import compile_ptg, PTEXEC_STATS

ctx = pt.Context(nb_cores=1)
snap = PTEXEC_STATS.snapshot()
# dependent-chain micro-bench shape (CTL)
chain = compile_ptg(
    "%global NT\n%global DEPTH\n"
    "T(i, l)\n  i = 0 .. NT-1\n  l = 0 .. DEPTH-1\n"
    "  CTL S <- (l > 0) ? S T(i, l-1)\n"
    "        -> (l < DEPTH-1) ? S T(i, l+1)\nBODY\n  pass\nEND\n", "ci-chain")
tp = chain.instantiate(ctx, globals={"NT": 64, "DEPTH": 16}, collections={})
ctx.add_taskpool(tp); ctx.wait(timeout=60)
assert tp._ptexec_state is not None, "CTL chain pool fell back to Python FSM"
# data-flow micro-bench shape (RW chains + memory endpoints)
X = TiledMatrix("descX", 1, 32, 1, 1)
X.fill(lambda m, i: np.zeros((1, 1), np.float32))
Y = TiledMatrix("descY", 1, 32, 1, 1)
df = compile_ptg(
    "%global NT\n%global DEPTH\n%global descX\n%global descY\n"
    "T(i, l)\n  i = 0 .. NT-1\n  l = 0 .. DEPTH-1\n"
    "  RW X <- (l == 0) ? descX(0, i) : X T(i, l-1)\n"
    "       -> (l < DEPTH-1) ? X T(i, l+1) : descY(0, i)\n"
    "BODY\n  pass\nEND\n", "ci-df")
tp2 = df.instantiate(ctx, globals={"NT": 32, "DEPTH": 8},
                     collections={"descX": X, "descY": Y})
ctx.add_taskpool(tp2); ctx.wait(timeout=60)
assert tp2._ptexec_state is not None, \
    "data-flow chain pool fell back to Python FSM"
assert tp2._ptexec_state["graph"].done()
delta = PTEXEC_STATS.delta(snap)
assert delta["pools_engaged"] >= 2 and delta["pools_fallback"] == 0, delta
ctx.fini()
print(f"native lane engagement OK: {delta}")
EOF

echo "== DTD batched lane engagement smoke =="
# same contract as the ptexec gate: assert ENGAGEMENT COUNTERS, not
# throughput — a silent per-task fallback on an eligible insert stream
# (the 10x regression) fails deterministically on any host speed
JAX_PLATFORMS=cpu timeout 120 python3 - <<'EOF'
import numpy as np
import parsec_tpu as pt
from parsec_tpu.dsl.dtd import DTDTaskpool, PTDTD_STATS, RW

def inc(a):
    return a + 1.0

ctx = pt.Context(nb_cores=1)
snap = PTDTD_STATS.snapshot()
tp = DTDTaskpool(ctx, "ci-dtd")
tiles = [tp.tile_new((2, 2), np.float32) for _ in range(8)]
for t in tiles:
    t.data.create_copy(0, np.zeros((2, 2), np.float32))
for i in range(512):
    tp.insert_task(inc, (tiles[i % 8], RW), jit=False)
tp.wait(timeout=60); tp.close(); ctx.wait(timeout=60)
delta = PTDTD_STATS.delta(snap)
assert delta["pools_batch"] >= 1, delta
# one per-task insert registers the class; the rest must ride the batch
assert delta["tasks_batched"] >= 500, delta
assert delta["tasks_per_task"] <= 8, delta
for t in tiles:
    assert float(np.asarray(t.data.newest_copy().payload)[0, 0]) == 64.0, \
        "batched RW chains lost writes"
ctx.fini()
print(f"DTD batched lane engagement OK: {delta}")
EOF

echo "== scheduler plane engagement smoke (multi-pool ptsched) =="
# ISSUE 9: N concurrent taskpools must share the lanes through the native
# scheduler plane — pools registered (zero fallbacks), per-pool served
# counters nonzero, steal machinery moving work between workers, the
# admission window stalling a runaway inserter, 2:1 weights visibly
# weighting the drain, and a LONE pool staying on its private ready
# structure (the structural form of the single-pool overhead contract)
JAX_PLATFORMS=cpu timeout 300 python3 benchmarks/serving.py --ci-gate

echo "== native device lane engagement smoke (over_cpu) =="
# ISSUE 10: a TPU-bodied pool must keep native engagement END TO END on
# CPU-only CI (device_tpu_over_cpu mode): zero pools_fallback on both the
# execution and device lanes, every device task dispatched AND retired
# through ptdev (nonzero ptdev.retired, zero dev_bad / callback errors),
# zero coherency violations in the C residency table, bit-correct GEMM
JAX_PLATFORMS=cpu timeout 300 python3 benchmarks/zone_bench.py --ci-gate

echo "== region fusion + warm-pool engagement smoke =="
# ISSUE 12: a mixed fusable/un-fusable PTG DAG must run with >= 1 fused
# region (capturable k-chains collapse into ONE jitted super-task each),
# ZERO pools_fallback, every seam task scheduled normally, and a
# bit-exact result; a SECOND instantiation of the same program must hit
# the persistent executable cache (capture.cache_hits >= 1) with a
# measurably cheaper (warm) instantiation. Engagement, not throughput.
JAX_PLATFORMS=cpu timeout 300 python3 benchmarks/fusion_bench.py --ci-gate

echo "== adaptive runtime engagement smoke (online cost models) =="
# ISSUE 18: the measurement->decision loop must demonstrably close —
# cost models nonzero for every exercised (class, device) pair, >= 1
# placement decision DIVERGING from the static has-a-device-body
# heuristic on a heterogeneous mixed DAG (the host device lane is pure
# overhead for tiny tasks, and honest measurement must say so), fusion
# sizing consulting the measured break-even, the <1% decision-overhead
# contract, and ZERO pools_fallback while adapting
JAX_PLATFORMS=cpu timeout 300 python3 benchmarks/adaptive_bench.py --ci-gate

echo "== multi-backend device lane smoke (cuda, when present) =="
# the device lane must not be TPU-shaped by accident: when this host has
# a CUDA backend, the same ptdev gate must pass under JAX_PLATFORMS=cuda
# (real accelerator, real transfers). Skipped WITH ATTRIBUTION otherwise
# — a silent skip would read as coverage
if python3 -c "import jax; assert any(d.platform == 'gpu' for d in jax.devices('cuda'))" 2>/dev/null; then
    JAX_PLATFORMS=cuda timeout 300 python3 benchmarks/zone_bench.py --ci-gate
else
    echo "SKIP: no CUDA backend on this host (jax.devices('cuda') empty/unavailable); device-lane gate ran CPU-only above"
fi

echo "== cross-rank serving fabric engagement smoke (ptfab, 2 ranks) =="
# ISSUE 11: credit grants/spends must be nonzero ON THE WIRE with zero
# frame errors (spends local — frames don't scale with spends), remote
# nowait inserts must raise under an exhausted window, the victim tenant
# must keep being served under a mesh-wide antagonist flood, and the
# rank-0 reconciliation loop must land cross-rank shares within
# tolerance of the global weights. Engagement counters, not timing.
JAX_PLATFORMS=cpu timeout 420 python3 benchmarks/serving.py --fab-gate

echo "== mesh telemetry engagement smoke (pttel, 2 ranks) =="
# ISSUE 20: nonzero TAG_PTTEL push rounds with zero frame errors, the
# pushed rollup EQUAL to the per-rank registry truth after quiesce, the
# reconciler running in push mode with ZERO per-round HTTP fetches, a
# clean watchdog on the healthy rank, and a forced stall detected within
# 2x watchdog_stall_ms producing exactly one attributed flight record;
# plus the telemetry duty cycle under the <1% overhead contract and the
# push/scrape reconciler convergence-round keys.
JAX_PLATFORMS=cpu timeout 420 python3 benchmarks/serving.py --tel-gate

echo "== native comm lane engagement smoke (2 ranks) =="
# same contract as the execution-lane gates: assert ENGAGEMENT, not
# throughput — a 2-OS-rank chain whose every edge crosses ranks must ride
# the native comm lane (activation frames counted on both ends, pools
# registered, ZERO frame errors), not silently fall back to the
# interpreted remote_dep path. Lives in a FILE (not a heredoc): the
# spawned ranks re-import the main module, which stdin cannot provide.
JAX_PLATFORMS=cpu timeout 300 python3 benchmarks/comm_lane.py --ci-gate

echo "== cross-rank observability smoke (metrics endpoint + merged trace) =="
# ISSUE 8: /metrics must answer LIVE on both ranks mid-run (cross-process
# scrape: each rank curls the peer's endpoint) with nonzero ptcomm wire
# counters + latency percentiles and zero frame errors; the two per-rank
# .pbp traces must merge into one clock-aligned timeline where EVERY
# cross-rank activation frame pairs into a send->ingest flow event
JAX_PLATFORMS=cpu timeout 300 python3 benchmarks/comm_lane.py --obs-gate

echo "== traced native-lane smoke (observer-effect gate) =="
# profiling must NOT eject pools from the native lanes (PR 5): a traced
# chain run keeps the same engagement as an untraced one, writes a .pbp
# whose native per-worker streams hold every lane task, and drops nothing
JAX_PLATFORMS=cpu timeout 120 python3 - <<'EOF'
import os, tempfile
import parsec_tpu as pt
from parsec_tpu.dsl.ptg.compiler import compile_ptg, PTEXEC_STATS
from parsec_tpu.utils.trace import Profiling
from parsec_tpu.tools.trace_reader import read_pbp, to_chrome_trace, to_dataframe

src = ("%global NT\n%global DEPTH\n"
       "T(i, l)\n  i = 0 .. NT-1\n  l = 0 .. DEPTH-1\n"
       "  CTL S <- (l > 0) ? S T(i, l-1)\n"
       "        -> (l < DEPTH-1) ? S T(i, l+1)\nBODY\n  pass\nEND\n")
prog = compile_ptg(src, "ci-traced")
NT, DEPTH = 64, 16

def run(ctx, tag):
    snap = PTEXEC_STATS.snapshot()
    tp = prog.instantiate(ctx, globals={"NT": NT, "DEPTH": DEPTH},
                          collections={}, name=f"ci-traced-{tag}")
    ctx.add_taskpool(tp); ctx.wait(timeout=60)
    return PTEXEC_STATS.delta(snap)

ctx = pt.Context(nb_cores=1)
plain = run(ctx, "plain"); ctx.fini()
ctx = pt.Context(nb_cores=1)
ctx.profiling = Profiling()
traced = run(ctx, "on"); ctx.fini()
assert traced == plain, f"profiling changed lane engagement: {plain} vs {traced}"
assert ctx._ntrace is not None and ctx._ntrace.dropped() == 0, "ring drops in smoke"
path = os.path.join(tempfile.mkdtemp(), "ci.pbp")
ctx.profiling.dump(path)
trace = read_pbp(path)
assert any(s["name"].startswith("ptexec-w") for s in trace.streams), \
    "no native worker streams in the trace"
df = to_dataframe(trace)
ntask = len(df[df["name"] == "ptexec::task"])
assert ntask == NT * DEPTH, f"native task intervals {ntask} != {NT*DEPTH}"
assert len([e for e in to_chrome_trace(trace)["traceEvents"]
            if e["ph"] == "X"]) >= ntask
print(f"traced smoke OK: engagement {traced}, {ntask} native task intervals, 0 drops")
EOF

echo "== byte-compile lint (syntax over the whole tree) =="
python3 -m compileall -q parsec_tpu tests examples benchmarks bench.py \
    chip_smoke.py __graft_entry__.py setup.py

echo "== CLI smoke =="
python3 -m parsec_tpu --version
python3 -m parsec_tpu --help-mca > /dev/null

echo "== example smoke (CPU) =="
JAX_PLATFORMS=cpu timeout 180 python3 examples/ex04_chain_data.py

if [ "${1:-}" = "quick" ]; then
    echo "== quick suite =="
    timeout 600 python3 -m pytest tests/test_core_dag.py tests/test_dtd.py \
        tests/test_native_dtd.py tests/test_ptg.py -q -x
else
    echo "== full suite =="
    timeout 1800 python3 -m pytest tests/ -q -x
fi

echo "== chip smoke: rehearsal passes, and no chip means failure =="
# the chip itself is reached only through the chip tool (python3
# chip_smoke.py); here the script is rehearsed on the CPU backend, and it
# must refuse to report success without an accelerator
timeout 600 python3 chip_smoke.py --rehearsal > /dev/null
if JAX_PLATFORMS=cpu timeout 300 python3 chip_smoke.py > /dev/null 2>&1; then
    echo "ERROR: chip_smoke.py exited 0 on the CPU backend" >&2
    exit 1
fi

echo "== driver entry dry run (8 virtual CPU devices) =="
JAX_PLATFORMS=cpu timeout 600 python3 __graft_entry__.py 8 > /dev/null

echo "CI OK"
