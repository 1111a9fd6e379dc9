"""Native multi-pool scheduler plane (native/src/ptsched.h, ISSUE 9).

Four layers:

* raw Plane semantics on the C extension (policies, weighted DRR,
  hot-queue spill, steal-half, admission windows, concurrent
  register/unregister, the queue-wait histogram);
* ptexec integration: randomized multi-pool parity (plane on/off —
  identical completion sets, release-edge order respected per pool),
  priority ordering through plane heaps, lazy one-pool fast path;
* ptdtd integration: weighted drain fairness across pools, admission
  backpressure (bounded-blocking insert + the nowait error path);
* runtime: skewed concurrent pools keep every worker busy (the
  starvation-backoff regression of ISSUE 9's satellite).
"""

import random
import threading
import time

import pytest

import parsec_tpu as pt
from parsec_tpu import native as native_mod
from parsec_tpu.utils import mca

pytestmark = pytest.mark.skipif(native_mod.load_ptsched() is None,
                                reason="native _ptsched unavailable")


def _mod():
    return native_mod.load_ptsched()


# ------------------------------------------------------------------ raw plane

def test_plane_fifo_policy_oldest_first():
    ps = _mod()
    pl = ps.Plane(nworkers=1, policy=ps.POLICY_FIFO)
    h = pl.register_pool(ext_id=1, kind=ps.KIND_EXT)
    pl.push(h, list(range(10)))           # worker -1: straight to overflow
    got = [t for _, t in pl.pop(worker=0, kind=ps.KIND_EXT, cap=10)]
    assert got == list(range(10))


def test_plane_wdrr_weights_within_tolerance():
    ps = _mod()
    pl = ps.Plane(nworkers=1, policy=ps.POLICY_WDRR, quantum=64)
    a = pl.register_pool(ext_id=1, kind=ps.KIND_EXT, weight=2)
    b = pl.register_pool(ext_id=2, kind=ps.KIND_EXT, weight=1)
    served = {a: 0, b: 0}
    nxt = {a: 0, b: 0}
    for h in (a, b):                      # sustained backlog, long run
        pl.push(h, list(range(4096)))
        nxt[h] = 4096
    for _ in range(300):
        for p, _t in pl.pop(worker=0, kind=ps.KIND_EXT, cap=64):
            served[p] += 1
        for h in (a, b):
            q = pl.queued(h)
            if q < 2048:
                pl.push(h, list(range(nxt[h], nxt[h] + 4096 - q)))
                nxt[h] += 4096 - q
    ratio = served[a] / max(1, served[b])
    assert abs(ratio - 2.0) / 2.0 < 0.25, (served, ratio)


def test_plane_prio_policy_best_pool_first():
    ps = _mod()
    pl = ps.Plane(nworkers=1, policy=ps.POLICY_PRIO)
    lo = pl.register_pool(ext_id=1, kind=ps.KIND_EXT)
    hi = pl.register_pool(ext_id=2, kind=ps.KIND_EXT)
    pl.push(lo, [0, 1], prios=[1, 2])
    pl.push(hi, [10, 11], prios=[9, 8])
    got = pl.pop(worker=0, kind=ps.KIND_EXT, cap=10)
    # the hi pool's top priority wins; within a pool, priority order
    assert [t for _, t in got[:2]] == [10, 11]
    assert [t for _, t in got[2:]] == [1, 0]


def test_plane_hotq_spill_accounting():
    ps = _mod()
    pl = ps.Plane(nworkers=2)
    h = pl.register_pool(ext_id=1, kind=ps.KIND_EXT)
    n = ps.HOTQ_CAP + 100
    pl.push(h, list(range(n)), worker=0)  # overflows the bounded hot queue
    assert pl.pool_stats(h)["spills"] == 100
    got = set()
    while True:
        batch = pl.pop(worker=0, kind=ps.KIND_EXT, cap=256)
        if not batch:
            break
        got |= {t for _, t in batch}
    assert got == set(range(n))           # nothing lost to the spill


def test_plane_steal_liveness_one_pool_n_workers():
    # 1 pool, N workers: a starved worker must steal-half from the
    # victim's cold end, counted per thief (the issue's liveness shape)
    ps = _mod()
    pl = ps.Plane(nworkers=2)
    h = pl.register_pool(ext_id=1, kind=ps.KIND_EXT)
    pl.push(h, list(range(100)), worker=0)   # all in worker 0's hot queue
    got = pl.pop(worker=1, kind=ps.KIND_EXT, cap=8)
    assert got, "starved worker found no stealable work"
    st = pl.stats()
    assert st["steals"] > 0 and st["steal_visits"] > 0
    assert pl.worker_steals(1) == st["steals"]   # counted per thief
    assert pl.worker_steals(0) == 0
    # cold-end contract: the loot comes from the OLDEST pushed items
    assert min(t for _, t in got) == 0


def test_plane_admission_window_signal():
    ps = _mod()
    pl = ps.Plane(nworkers=1)
    h = pl.register_pool(ext_id=1, kind=ps.KIND_EXT, window=8)
    assert not pl.over_window(h)
    pl.admit(h, 8)
    assert not pl.over_window(h)          # at the window, not past it
    pl.admit(h, 1)
    assert pl.over_window(h)
    assert pl.push(h, [0]) is True        # push reports the soft signal
    pl.retired(h, 5)
    assert not pl.over_window(h)
    assert pl.inflight(h) == 4


def test_plane_concurrent_register_unregister_mid_run():
    ps = _mod()
    pl = ps.Plane(nworkers=2)
    stop = threading.Event()
    errs = []

    def churn(seed):
        rng = random.Random(seed)
        try:
            while not stop.is_set():
                h = pl.register_pool(ext_id=seed, kind=ps.KIND_EXT)
                pl.push(h, list(range(rng.randrange(1, 64))),
                        worker=rng.randrange(-1, 2))
                pl.pop(worker=rng.randrange(2), kind=ps.KIND_EXT,
                       cap=rng.randrange(1, 64))
                pl.unregister_pool(h)
        except Exception as e:  # noqa: BLE001
            errs.append(e)

    threads = [threading.Thread(target=churn, args=(s,)) for s in range(4)]
    for t in threads:
        t.start()
    time.sleep(1.0)
    stop.set()
    for t in threads:
        t.join()
    assert not errs
    assert pl.stats()["pools_live"] == 0
    assert pl.stats()["pools_registered"] > 0


def test_plane_queue_wait_histogram():
    from parsec_tpu.utils.hist import decode_buckets, summarize
    ps = _mod()
    pl = ps.Plane(nworkers=1)
    pl.hist_enable()
    h = pl.register_pool(ext_id=1, kind=ps.KIND_EXT)
    # sampled 1-in-8 by task id: ids 0..63 give 8 samples
    pl.push(h, list(range(64)))
    time.sleep(0.002)
    while pl.pop(worker=0, kind=ps.KIND_EXT, cap=16):
        pass
    name, (count, sum_ns, raw) = next(iter(pl.hist_snapshot().items()))
    assert name == "queue_ns" and count == 8
    s = summarize(decode_buckets(raw), count, sum_ns)
    assert s["p50_us"] >= 1000.0          # >= the 2ms park, roughly


def test_plane_capsule_keeps_plane_alive():
    import gc
    import weakref
    ps = _mod()
    pl = ps.Plane(nworkers=1)
    cap = pl.plane_capsule()
    del pl
    gc.collect()
    assert cap is not None                # the capsule pins the plane;
    del cap                               # dropping it releases the ref
    gc.collect()


# ------------------------------------------------------- ptexec integration

def _chain_prog():
    from parsec_tpu.dsl.ptg.compiler import compile_ptg
    return compile_ptg(
        "%global NT\n%global DEPTH\n"
        "INIT(z)\n  z = 0 .. 0\n"
        "  CTL S -> (DEPTH >= 1) ? S T(1 .. NT, 1)\nBODY\n  pass\nEND\n\n"
        "T(i, l)\n  i = 1 .. NT\n  l = 1 .. DEPTH\n"
        "  CTL S <- (l == 1) ? S INIT(0) : S T(i, l-1)\n"
        "        -> (l < DEPTH) ? S T(i, l+1)\nBODY\n  pass\nEND\n",
        "ptsched_chain")


@pytest.mark.skipif(native_mod.load_ptexec() is None,
                    reason="native _ptexec unavailable")
def test_graph_multi_pool_parity_and_ordering():
    """Randomized DAGs through sched-bound graphs: identical completion
    sets to the unbound run, and every release edge respected in the
    observed order — per pool, with three pools interleaving."""
    pe, ps = native_mod.load_ptexec(), _mod()

    def rand_dag(rng, n):
        goals, off, succs = [0] * n, [0], []
        edges = []
        for i in range(n):
            for j in range(i + 1, min(n, i + 1 + rng.randrange(3))):
                if rng.random() < 0.5:
                    succs.append(j)
                    goals[j] += 1
                    edges.append((i, j))
            off.append(len(succs))
        return goals, off, succs, edges

    rng = random.Random(7)
    pl = ps.Plane(nworkers=2)
    for trial in range(5):
        n = 40 + rng.randrange(60)
        goals, off, succs, edges = rand_dag(rng, n)
        orders = []
        for bind in (False, True):
            g = pe.Graph(goals, off, succs)
            if bind:
                h = pl.register_pool(ext_id=trial, kind=ps.KIND_PTEXEC)
                g.sched_bind(pl.plane_capsule(), h)
            order = []
            cb = lambda ids: order.extend(ids)  # noqa: E731
            while not g.done():
                assert g.run(cb, 16, 0, trial % 2) >= 0
            orders.append(order)
            if bind:
                g.sched_unbind()
        unbound, bound = orders
        assert sorted(unbound) == sorted(bound) == list(range(n))
        pos = {t: k for k, t in enumerate(bound)}
        for a, b in edges:                # release edges respected
            assert pos[a] < pos[b], (a, b, trial)
    assert pl.stats()["pools_live"] == 0


def test_context_multi_pool_concurrent_chains():
    """Three concurrent PTG pools on two workers: all complete through
    the plane (every pool's tasks served), and a LONE pool afterwards
    does not bind at all — the one-pool fast path."""
    ctx = pt.Context(nb_cores=2)
    plane = ctx.sched_plane
    if plane is None:
        ctx.fini()
        pytest.skip("scheduler plane unavailable on this context")
    prog = _chain_prog()
    before = plane.stats()
    tps = [prog.instantiate(ctx, globals={"NT": 64, "DEPTH": 8},
                            collections={}, name=f"mp-{i}")
           for i in range(3)]
    for tp in tps:
        ctx.add_taskpool(tp)
    ctx.wait(timeout=120)
    mid = plane.stats()
    assert mid["pools_registered"] - before["pools_registered"] == 3
    assert mid["served"] - before["served"] == 3 * (64 * 8 + 1)
    assert mid["pools_live"] == 0         # all retired at finalize
    solo = prog.instantiate(ctx, globals={"NT": 64, "DEPTH": 8},
                            collections={}, name="solo")
    ctx.add_taskpool(solo)
    ctx.wait(timeout=120)
    assert plane.stats()["pools_registered"] == mid["pools_registered"]
    ctx.fini()


def test_skewed_pools_keep_workers_busy():
    """Satellite regression (deflaked, ISSUE 11): with two pools of
    skewed sizes, the tiny pool draining must not park workers while
    the big pool still holds queued work.

    The INVARIANTS assert on PLANE COUNTERS each attempt — both pools
    registered and retired, the big pool's tasks all served THROUGH the
    plane, every task completed — which no host load can flake. The
    per-stream busy-balance observation (each worker executed > 0) is
    wall-clock-sensitive: on a loaded 2-core host the OS can deschedule
    one worker for the entire ~1s run, which is starvation by the OS,
    not by the plane. That observation therefore gets a bounded
    retry/soak: a plane-level starvation bug reproduces on every
    attempt; an OS scheduling flap does not survive three."""
    prog = _chain_prog()
    busy_attempts = []
    for _attempt in range(3):
        ctx = pt.Context(nb_cores=2)
        plane = ctx.sched_plane
        if plane is None:
            ctx.fini()
            pytest.skip("scheduler plane unavailable on this context")
        before = plane.stats()
        small = prog.instantiate(ctx, globals={"NT": 4, "DEPTH": 4},
                                 collections={}, name="small")
        big = prog.instantiate(ctx, globals={"NT": 512, "DEPTH": 64},
                               collections={}, name="big")
        ctx.add_taskpool(small)
        ctx.add_taskpool(big)
        ctx.wait(timeout=120)
        after = plane.stats()
        # -- counter invariants: hold on EVERY attempt
        assert after["pools_registered"] - before["pools_registered"] == 2
        assert after["pools_live"] == 0       # both retired at finalize
        # the big pool queues while the small one drains, so its tasks
        # ride the plane (small slack: items the pre-bind window ran)
        assert after["served"] - before["served"] >= 512 * 64
        # a worker adds a batch to its count after the batch ran, which
        # can be after the master saw the pools complete: join it first
        ctx.fini()
        busy = [s.nb_executed for s in ctx.streams]
        assert sum(busy) >= 512 * 64 + 4 * 4 + 2
        if all(b > 0 for b in busy):
            return
        busy_attempts.append(busy)
    assert False, ("a worker executed nothing on every attempt "
                   f"(plane starvation, not an OS flap): {busy_attempts}")


# -------------------------------------------------------- ptdtd integration

@pytest.mark.skipif(native_mod.load_ptdtd() is None,
                    reason="native _ptdtd unavailable")
def test_engine_weighted_drain_fairness():
    """2:1 pool weights -> served ratio within 25% over a long drain
    (the engine-level weighted-fairness contract; both pools held
    backlogged so the weights actually bind)."""
    pd, ps = native_mod.load_ptdtd(), _mod()
    eng = pd.Engine()
    pl = ps.Plane(nworkers=2, policy=ps.POLICY_WDRR)
    eng.sched_bind(pl.plane_capsule())
    assert eng.sched_bound()
    a = pl.register_pool(ext_id=1, kind=ps.KIND_PTDTD, weight=2)
    b = pl.register_pool(ext_id=2, kind=ps.KIND_PTDTD, weight=1)
    done = {a: 0, b: 0}
    ca = eng.register_class(
        lambda args: done.__setitem__(a, done[a] + len(args)),
        [0], [1], None, a)
    cb = eng.register_class(
        lambda args: done.__setitem__(b, done[b] + len(args)),
        [0], [1], None, b)
    ta, tb = eng.tile(), eng.tile()
    for r in range(120):
        for cls, h, t in ((ca, a, ta), (cb, b, tb)):
            q = pl.queued(h)
            if q < 1024:
                eng.insert_many([(cls, None, t, 1)] * (1024 - q))
        eng.drain_ready(256, 256, r % 2)
    ratio = done[a] / max(1, done[b])
    assert abs(ratio - 2.0) / 2.0 < 0.25, (done, ratio)
    # admission accounting drained back to the live backlog
    assert pl.inflight(a) == pl.queued(a)
    assert pl.inflight(b) == pl.queued(b)


def test_dtd_multi_pool_parity_plane_on_off():
    """Randomized inserts into 3 concurrent pools, plane on vs off:
    identical completion counts and final tile payloads."""
    import numpy as np
    from parsec_tpu.dsl.dtd import RW, DTDTaskpool

    def run(native_plane: bool):
        if not native_plane:
            mca.set("sched_native", False)
        try:
            ctx = pt.Context(nb_cores=2)
            rng = random.Random(42)
            pools = []
            for i in range(3):
                tp = DTDTaskpool(ctx, f"par{i}")
                tp.qos_weight = i + 1
                tiles = [tp.tile_new(np.zeros((2, 2), np.float32))
                         for _ in range(4)]
                pools.append((tp, tiles))

            def bump(x):
                return x + 1.0

            for _ in range(400):
                tp, tiles = pools[rng.randrange(3)]
                tp.insert_task(bump, (tiles[rng.randrange(4)], RW),
                               jit=False, name="B")
            outs = []
            for tp, tiles in pools:
                tp.wait(timeout=120)
                outs.append([float(np.asarray(
                    t.data.newest_copy().payload)[0, 0]) for t in tiles])
                tp.close()
            ctx.wait(timeout=120)
            ctx.fini()
            return outs
        finally:
            if not native_plane:
                mca.params.unset("sched_native")

    assert run(True) == run(False)


def test_dtd_admission_window_blocks_and_counts():
    from parsec_tpu.core.sched_plane import SCHED_STATS
    from parsec_tpu.dsl.dtd import READ, DTDTaskpool
    # nb_cores=1: nothing drains between flush boundaries, so the window
    # (128 < the 256-spec flush) MUST trip and the inserter MUST drain
    # its way back under it — deterministic block/unblock
    ctx = pt.Context(nb_cores=1)
    if ctx.sched_plane is None:
        ctx.fini()
        pytest.skip("scheduler plane unavailable on this context")
    before = SCHED_STATS.snapshot()
    tp = DTDTaskpool(ctx, "adm")
    tp.admission_window = 128
    tiles = [tp.tile_new((2, 2)) for _ in range(4)]

    def body(x):               # ONE fn object: inserts ride the batch
        return None            # lane's fast cache (and thus the plane)

    for i in range(4000):
        tp.insert_task(body, (tiles[i % 4], READ), jit=False, name="A")
    tp.wait(timeout=120)
    tp.close()
    ctx.wait(timeout=120)
    delta = SCHED_STATS.delta(before)
    assert delta["admission_stalls"] > 0     # the window bit, blocking
    assert delta["pools_engaged"] >= 1       # ... on an engaged pool
    ctx.fini()


def test_dtd_admission_nowait_raises():
    from parsec_tpu.dsl.dtd import READ, AdmissionBackpressure, DTDTaskpool
    ctx = pt.Context(nb_cores=1)
    if ctx.sched_plane is None:
        ctx.fini()
        pytest.skip("scheduler plane unavailable on this context")
    tp = DTDTaskpool(ctx, "nowait")
    tp.admission_window = 64
    tile = tp.tile_new((2, 2))

    def body(x):
        return None

    tp.insert_task(body, (tile, READ), jit=False, name="N")
    assert tp._sched_pool is not None
    # force the pool past its window (the deterministic form: a real
    # overrun needs a drain stalled at exactly the wrong moment)
    ctx.sched_plane.plane.admit(tp._sched_pool, 100)
    try:
        with pytest.raises(AdmissionBackpressure):
            tp.insert_task(body, (tile, READ), jit=False,
                           name="N", nowait=True)
        # blocking inserts would drain their way under the window; a
        # nowait caller that backs off and retries after the overrun
        # clears must succeed
        ctx.sched_plane.plane.retired(tp._sched_pool, 100)
        tp.insert_task(body, (tile, READ), jit=False,
                       name="N", nowait=True)
    finally:
        tp.wait(timeout=60)
        tp.close()
        ctx.wait(timeout=60)
        ctx.fini()


# ------------------------------------------------------------ policy routing

def test_native_policy_mapping_and_fallback():
    from parsec_tpu.core.sched_plane import SCHED_STATS
    # ap maps to the native prio flavor
    ctx = pt.Context(nb_cores=1, scheduler="ap")
    assert ctx.sched_plane is not None and ctx.sched_plane.policy == "prio"
    ctx.fini()
    # ip has no native analogue: honest fallback, counted
    before = SCHED_STATS.snapshot()
    ctx = pt.Context(nb_cores=1, scheduler="ip")
    assert ctx.sched_plane is None
    assert SCHED_STATS.delta(before)["policy_fallback"] == 1
    ctx.fini()


def test_sched_py_counters_exported():
    from parsec_tpu.utils.counters import counters, install_native_counters
    install_native_counters()
    ctx = pt.Context(nb_cores=1)
    snap = counters.snapshot()
    assert "sched.py.queued" in snap         # interpreted side
    assert "sched.served" in snap            # native plane side
    assert "sched.pools_engaged" in snap     # engagement split
    ctx.fini()
