"""Device-module pipeline tests over a host jax device (test mode).

Exercises the full async device path — kernel_scheduler enqueue, manager
drive, version-checked stage-in, LRU residency, is_ready event polling,
epilog write-back, and batched dispatch — without TPU hardware (the
reference's analogue: device tests runnable on any CUDA-capable node).
"""

import numpy as np
import pytest

from parsec_tpu.core.context import Context
from parsec_tpu.data.matrix import TiledMatrix
from parsec_tpu.dsl.dtd import DTDTaskpool, READ, RW
from parsec_tpu.utils import mca


@pytest.fixture()
def dctx():
    mca.set("device_tpu_over_cpu", True)
    c = Context(nb_cores=1)
    yield c
    c.fini()
    mca.params.unset("device_tpu_over_cpu")


def _tpu_dev(ctx):
    from parsec_tpu.device.tpu import TPUDevice
    devs = [d for d in ctx.devices.devices if isinstance(d, TPUDevice)]
    assert devs, "device module did not register over the host device"
    return devs[0]


def test_async_device_pipeline(dctx):
    dev = _tpu_dev(dctx)
    A = TiledMatrix("AD", 32, 32, 16, 16)
    rng = np.random.default_rng(40)
    dense = rng.standard_normal((32, 32)).astype(np.float32)
    A.fill(lambda m, n: dense[m*16:(m+1)*16, n*16:(n+1)*16])
    tp = DTDTaskpool(dctx, "dev")
    for m in range(2):
        for n in range(2):
            tp.insert_task(lambda x: x * 2.0, (tp.tile_of(A, m, n), RW))
    tp.wait(); tp.close(); dctx.wait()
    np.testing.assert_allclose(A.to_dense(), dense * 2.0, rtol=1e-5)
    assert dev.executed_tasks == 4
    assert dev.transfer_in_bytes > 0          # staged tiles in
    assert len(dev._lru) > 0                  # resident copies tracked


def test_device_chain_reuses_resident_tiles(dctx):
    """Second pass over the same tiles must not re-stage (version match)."""
    dev = _tpu_dev(dctx)
    A = TiledMatrix("AR", 16, 16, 16, 16)
    A.fill(lambda m, n: np.ones((16, 16), np.float32))
    tp = DTDTaskpool(dctx, "resident")
    t = tp.tile_of(A, 0, 0)
    for _ in range(4):
        tp.insert_task(lambda x: x + 1.0, (t, RW))
    tp.wait(); tp.close(); dctx.wait()
    staged_once = dev.transfer_in_bytes
    assert staged_once == 16 * 16 * 4          # exactly one initial stage-in
    assert np.allclose(np.asarray(t.data.newest_copy().payload), 5.0)


def test_batched_dispatch(dctx):
    """Independent tasks of a ``batch=True`` class leave as flat multi-task
    programs (ref: parsec_gpu_task_collect_batch): one XLA program takes the
    operands of the whole group and gives one output per task. The enqueues
    here happen under the held manager lock, so all eight are pending when
    the manager next runs (tests/test_device_groups.py has the groups that
    form by observation, without the flag and without the lock)."""
    dev = _tpu_dev(dctx)
    A = TiledMatrix("AB", 16 * 8, 16, 16, 16)
    A.fill(lambda m, n: np.full((16, 16), float(m), np.float32))
    tp = DTDTaskpool(dctx, "batch")

    def scale(x):
        return x * 3.0

    for m in range(8):
        tp.insert_task(scale, (tp.tile_of(A, m, 0), RW), batch=True)
    # run the hooks (enqueue on the device) while the manager is "busy":
    # progress is a no-op for everyone else, so the batch accumulates
    with dev._manager_lock:
        dctx._progress_loop(dctx.streams[0],
                            until=lambda: len(dev._pending) == 8,
                            timeout=10)
    tp.wait(); tp.close(); dctx.wait()
    for m in range(8):
        assert np.allclose(np.asarray(A.data_of(m, 0).newest_copy().payload),
                           3.0 * m)
    assert dev.batched_dispatches >= 1


def test_eviction_under_pressure(dctx):
    """A tiny HBM budget forces LRU eviction with write-back; the pt_zone
    ledger (offsets + occupancy stats) tracks every resident tile."""
    dev = _tpu_dev(dctx)
    tile_b = 16 * 16 * 4
    dev.set_budget(3 * tile_b, unit=tile_b)    # room for ~3 tiles
    A = TiledMatrix("AE", 16 * 8, 16, 16, 16)
    A.fill(lambda m, n: np.full((16, 16), float(m), np.float32))
    tp = DTDTaskpool(dctx, "evict")
    for m in range(8):
        tp.insert_task(lambda x: x + 0.5, (tp.tile_of(A, m, 0), RW))
    tp.wait(); tp.close(); dctx.wait()
    for m in range(8):
        assert np.allclose(np.asarray(A.data_of(m, 0).newest_copy().payload),
                           m + 0.5)
    assert dev._resident_bytes <= dev._budget + tile_b
    # the zone ledger: one live segment per resident tile, occupancy within
    # budget, eviction churn visible in the high-water mark
    zs = dev.zone_stats()
    assert len(dev._lru_segs) == len(dev._lru)
    assert zs["in_use_bytes"] == len(dev._lru_segs) * tile_b
    assert zs["in_use_bytes"] <= zs["total_bytes"]
    assert zs["hwm_bytes"] >= zs["in_use_bytes"] > 0


def test_ptg_body_through_device_module(dctx):
    """PTG [type=TPU] bodies route through the async device module; PTG
    intermediates ride as raw arrays without a backing Data (regression:
    _gather_inputs/_epilog assumed DataCopy everywhere and crashed on
    ArrayImpl inputs)."""
    from parsec_tpu.dsl.ptg.compiler import compile_ptg

    src = """
%global KT
%global descC

STEP(k)
  k = 0 .. KT-1
  : descC(0, 0)
  RW C <- (k == 0) ? descC(0, 0) : C STEP(k-1)
       -> (k < KT-1) ? C STEP(k+1) : descC(0, 0)
BODY [type=TPU]
  C = C + 1.0
END
"""
    dev = _tpu_dev(dctx)
    C = TiledMatrix("PDEV", 8, 8, 8, 8)
    C.fill(lambda m, n: np.zeros((8, 8), np.float32))
    prog = compile_ptg(src, "pdev")
    tp = prog.instantiate(dctx, globals={"KT": 5},
                          collections={"descC": C}, name="pdev")
    dctx.add_taskpool(tp)
    dctx.wait(timeout=30)
    np.testing.assert_allclose(C.to_dense(), np.full((8, 8), 5.0), rtol=1e-6)
    assert dev.executed_tasks >= 5


def test_pinned_copies_survive_eviction(dctx):
    """An inflight task's reader pin protects its device copies from the
    eviction walks (ref: the readers guard of device_gpu.c:1210) — the
    guard that was previously dead code because nothing ever incremented
    DataCopy.readers."""
    dev = _tpu_dev(dctx)
    A = TiledMatrix("PIN", 32, 16, 16, 16)
    A.fill(lambda m, n: np.full((16, 16), float(m + 1), np.float32))
    tp = DTDTaskpool(dctx, "pin")
    t0, t1 = tp.tile_of(A, 0, 0), tp.tile_of(A, 1, 0)
    tp.insert_task(lambda x: x * 2.0, (t0, RW))
    tp.insert_task(lambda x: x * 3.0, (t1, RW))
    tp.wait(); tp.close(); dctx.wait()
    # both tiles resident; pin one through the device's pin protocol
    # (exactly what _gather_inputs does for an inflight task — pin_copy
    # mirrors the reader count into the native coherency table so C's
    # victim selection honors it too)
    c0 = t0.data.get_copy(dev.device_index)
    c1 = t1.data.get_copy(dev.device_index)
    assert c0 is not None and c1 is not None
    dev.pin_copy(c0)
    try:
        freed = dev.evict_bytes(dev._resident_bytes)   # demand everything
        assert dev.pinned_skips > 0, "eviction walk never saw the pin"
        assert c0.payload is not None, "pinned copy was evicted"
        assert c0.coherency_state != 0                  # not INVALID
        assert c1.payload is None, "unpinned copy should have been evicted"
        assert freed > 0
    finally:
        dev.unpin_copy(c0)
    # unpinned now: the same demand evicts it
    dev.evict_bytes(dev._resident_bytes)
    assert c0.payload is None


def _acc(a, x):
    return a + x


def test_inflight_pins_balance_and_pressure_correctness(dctx):
    """Seeded eviction pressure (budget = ~2 tiles) while a DAG with many
    live tiles runs through the device module: every task's reader pins
    are dropped at epilog (readers balances back to 0), evictions DO
    happen, and the results are still correct."""
    dev = _tpu_dev(dctx)
    tile_bytes = 16 * 16 * 4
    dev.set_budget(2 * tile_bytes + 64, unit=1024)
    n_rows = 8
    A = TiledMatrix("PRS", 16 * n_rows, 16, 16, 16)
    dense = np.stack([np.full((16, 16), float(m), np.float32)
                      for m in range(n_rows)])
    A.fill(lambda m, n: dense[m])
    tp = DTDTaskpool(dctx, "pressure")
    acc = tp.tile_new(np.zeros((16, 16), np.float32))
    for m in range(n_rows):
        tp.insert_task(_acc, (acc, RW), (tp.tile_of(A, m, 0), READ))
    tp.wait(); tp.close(); dctx.wait()
    out = np.asarray(acc.data.newest_copy().payload)
    np.testing.assert_allclose(out, dense.sum(axis=0), rtol=1e-5)
    assert dev.evictions > 0, "budget pressure produced no evictions"
    # pins all released: no copy left with a nonzero reader count
    for m in range(n_rows):
        for c in A.data_of(m, 0).copies.values():
            assert c.readers == 0
    for c in acc.data.copies.values():
        assert c.readers == 0


# ---------------------------------------------------------------------------
# ISSUE 10: the native device lane (ptdev) + C-side coherency table
# ---------------------------------------------------------------------------

_MIXED_SRC = """
%global NT
%global DEPTH
%global descA
%global descB

DEVSTEP(i, l)
  i = 0 .. NT-1
  l = 0 .. DEPTH-1
  : descA(0, i)
  RW X <- (l == 0) ? descA(0, i) : Y HOSTSTEP(i, l-1)
       -> Y HOSTSTEP(i, l)
BODY [type=TPU]
  X = X * 2.0 + l
END

HOSTSTEP(i, l)
  i = 0 .. NT-1
  l = 0 .. DEPTH-1
  : descA(0, i)
  RW Y <- X DEVSTEP(i, l)
       -> (l < DEPTH-1) ? X DEVSTEP(i, l+1) : descB(0, i)
BODY
  Y = Y - 0.5 * i
END
"""


def _mixed_replay(a_cols, nt, depth):
    """Exact numpy replay of the mixed CPU+TPU DAG."""
    out = []
    for i in range(nt):
        x = a_cols[i].astype(np.float64)
        for l in range(depth):
            x = x * 2.0 + l          # DEVSTEP
            x = x - 0.5 * i          # HOSTSTEP
        out.append(x)
    return out


def _run_mixed(ctx, nt, depth, a_cols, tag):
    from parsec_tpu.data.matrix import TiledMatrix
    from parsec_tpu.dsl.ptg.compiler import compile_ptg
    A = TiledMatrix(f"mxA{tag}", 4, 4 * nt, 4, 4)
    A.fill(lambda m, n: a_cols[n])
    B = TiledMatrix(f"mxB{tag}", 4, 4 * nt, 4, 4)
    B.fill(lambda m, n: np.zeros((4, 4), np.float32))
    prog = compile_ptg(_MIXED_SRC, f"mixed-{tag}")
    tp = prog.instantiate(ctx, globals={"NT": nt, "DEPTH": depth},
                          collections={"descA": A, "descB": B},
                          name=f"mixed-{tag}")
    ctx.add_taskpool(tp)
    ctx.wait(timeout=90)
    return tp, A, B


def test_mixed_dag_parity_lane_on_off(dctx):
    """Randomized mixed CPU+TPU-body DAG parity harness (the PR 1-7
    template): the native execution+device lanes on vs the full
    interpreted FSM + interpreted device module — identical completion,
    final payloads (vs an exact numpy replay), data versions, and
    coherency invariants."""
    from parsec_tpu.device.native import PTDEV_STATS
    from parsec_tpu.dsl.ptg.compiler import PTEXEC_STATS
    rng = np.random.default_rng(1234)
    for round_ in range(3):
        nt = int(rng.integers(2, 5))
        depth = int(rng.integers(2, 6))
        a_cols = [rng.standard_normal((4, 4)).astype(np.float32)
                  for _ in range(nt)]
        expect = _mixed_replay(a_cols, nt, depth)

        snap = PTEXEC_STATS.snapshot()
        dsnap = PTDEV_STATS.snapshot()
        tp_on, _A_on, B_on = _run_mixed(dctx, nt, depth, a_cols,
                                        f"on{round_}")
        delta = PTEXEC_STATS.delta(snap)
        ddelta = PTDEV_STATS.delta(dsnap)
        assert tp_on._ptexec_state is not None, "lane leg fell back"
        assert delta["pools_fallback"] == 0 and \
            ddelta["pools_fallback"] == 0, (delta, ddelta)
        assert delta["pools_device"] == 1, delta
        assert ddelta["tasks_engaged"] == nt * depth, ddelta

        mca.set("ptg_native_exec", False)
        try:
            tp_off, _A_off, B_off = _run_mixed(dctx, nt, depth, a_cols,
                                               f"off{round_}")
        finally:
            mca.params.unset("ptg_native_exec")
        assert tp_off._ptexec_state is None

        for i in range(nt):
            on = np.asarray(B_on.data_of(0, i).newest_copy().payload,
                            np.float64)
            off = np.asarray(B_off.data_of(0, i).newest_copy().payload,
                             np.float64)
            np.testing.assert_allclose(on, expect[i], rtol=1e-4)
            np.testing.assert_allclose(on, off, rtol=1e-5)
            # data versions: both legs land exactly one write-back per
            # descB tile on top of fill()'s version 1; coherency
            # invariant: the newest version is carried by a valid copy
            # with a live payload
            d_on = B_on.data_of(0, i)
            d_off = B_off.data_of(0, i)
            assert d_on.version == d_off.version == 2
            for d in (d_on, d_off):
                best = d.newest_copy()
                assert best is not None and best.payload is not None
                assert best.version == d.version


def test_device_lane_engagement_counters(dctx):
    """The ci.sh gate contract: a TPU-bodied pool engages the native
    device lane end-to-end — pools_fallback == 0, every device task
    dispatched AND retired through ptdev (graph dev counters match the
    lane's), zero coherency violations in the table."""
    from parsec_tpu.dsl.ptg.compiler import PTEXEC_STATS
    dev = _tpu_dev(dctx)
    rng = np.random.default_rng(7)
    a_cols = [rng.standard_normal((4, 4)).astype(np.float32)
              for _ in range(3)]
    snap = PTEXEC_STATS.snapshot()
    tp, A, _B = _run_mixed(dctx, 3, 4, a_cols, "gate")
    delta = PTEXEC_STATS.delta(snap)
    assert delta["pools_fallback"] == 0 and delta["pools_device"] == 1
    lane = dctx._ptdev
    assert lane is not None and lane is not False
    gstats = tp._ptexec_state["graph"].dev_stats()
    assert gstats["dev_tx"] == gstats["dev_done"] == 3 * 4
    assert gstats["dev_bad"] == 0
    ls = lane.clane.stats()
    assert ls["retired"] >= 3 * 4 and ls["cb_errors"] == 0
    assert lane.failed() is None
    # coherency: every staged descA tile's table entry matches the live
    # Data version (zero violations)
    cs = lane.coh_stats_cached(ttl=0)
    if cs is not None:
        for i in range(3):
            d = A.data_of(0, i)
            st = dev._ncoh.state(dev.res_key(d))
            if st is not None and st[0] != 0:      # still resident+valid
                assert st[1] == (d.version & 0xFFFFFFFF), \
                    f"coherency violation on descA(0,{i}): {st} vs {d.version}"


def test_device_lane_dispatch_error_surfaces(dctx):
    """A body raising on the lane's manager thread must poison the pool
    and surface to the waiter — not hang the drain loops."""
    from parsec_tpu.data.matrix import TiledMatrix
    from parsec_tpu.dsl.ptg.compiler import compile_ptg
    src = ("%global NT\n%global descA\n"
           "T(k)\n  k = 0 .. NT-1\n"
           "  RW X <- (k == 0) ? descA(0, k) : X T(k-1)\n"
           "       -> (k < NT-1) ? X T(k+1) : descA(0, k)\n"
           "BODY [type=TPU]\n  X = jnp.linalg.cholesky(X) * bad_name\nEND\n")
    A = TiledMatrix("errA", 1, 4, 1, 1)
    A.fill(lambda m, k: np.zeros((1, 1), np.float32))
    prog = compile_ptg(src, "dev-err")
    tp = prog.instantiate(dctx, globals={"NT": 4}, collections={"descA": A})
    dctx.add_taskpool(tp)
    with pytest.raises(BaseException):
        dctx.wait(timeout=30)
    # the context stays poisoned: the fixture's fini skips the drain and
    # tears down cleanly (the documented error contract)


def test_coh_table_units():
    """CohTable policy units: version-checked stage-in, LRU victim order,
    pin veto, budget shrink, ownership bumps."""
    from parsec_tpu import native as native_mod
    mod = native_mod.load_ptdev()
    if mod is None:
        pytest.skip("_ptdev unavailable")
    t = mod.CohTable(1000)
    need, v = t.stage_in(1, 400, 0)
    assert need == 1 and v == []
    need, v = t.stage_in(1, 400, 0)          # same version: resident hit
    assert need == 0 and v == []
    need, v = t.stage_in(1, 400, 1)          # version bumped: re-stage
    assert need == 1 and v == []
    need, v = t.stage_in(2, 400, 0)
    assert need == 1 and v == []
    # third tile exceeds the budget: key 1 is LRU victim
    need, v = t.stage_in(3, 400, 0)
    assert need == 1 and v == [(1, 0)]
    st = t.stats()
    assert st["evictions"] == 1 and st["resident_bytes"] == 800
    # a pinned entry is skipped; the next unpinned one evicts instead
    t.pin(2)
    need, v = t.stage_in(4, 400, 0)
    assert need == 1 and v == [(3, 0)]
    assert t.stats()["pinned_skips"] >= 1
    t.unpin(2)
    # ownership: mark_owned flags the victim as dirty (owned) on eviction
    vs = t.mark_owned(4, 5, 400)
    assert vs == []
    assert t.state(4)[:2] == (mod.COH_OWNED, 5)
    vict = t.set_budget(100)                 # evicts everything resident
    assert (2, 0) in vict                    # clean victim
    assert (4, 1) in vict                    # owned victim reported dirty
    assert t.stats()["resident_bytes"] == 0


def test_eviction_races_reader_atomically(dctx):
    """Regression (the zone-heap eviction/coherency gap): an OWNED copy
    evicted under pressure writes back AND downgrades atomically with the
    version check. A reader racing eviction must always find the data's
    newest version on a valid copy with a live payload, and a concurrent
    host write must never be clobbered by a stale write-back."""
    import threading
    from parsec_tpu.data.data import COHERENCY_INVALID, data_from_array
    dev = _tpu_dev(dctx)
    data = data_from_array(np.zeros((16, 16), np.float32), key="race-tile")
    stop = threading.Event()
    errors = []

    def reader():
        last = -1
        while not stop.is_set():
            with data._lock:
                best = None
                for c in data.copies.values():
                    if c.coherency_state != COHERENCY_INVALID:
                        if best is None or c.version > best.version:
                            best = c
                if best is None or best.payload is None:
                    errors.append("newest version lost its payload")
                    break
                if best.version < last or best.version < data.version:
                    errors.append(
                        f"version went backwards: {best.version} < "
                        f"{max(last, data.version)}")
                    break
                last = best.version
        stop.set()

    def host_writer():
        n = 0
        while not stop.is_set() and n < 400:
            host = data.get_copy(0)
            if host is not None and host.payload is not None:
                data.bump_version(0)
            n += 1
            time.sleep(0)
        stop.set()

    import time
    rt = threading.Thread(target=reader)
    wt = threading.Thread(target=host_writer)
    rt.start(); wt.start()
    try:
        for _ in range(400):
            if stop.is_set():
                break
            copy = dev.lane_stage_in(data)
            data.bump_version(dev.device_index)      # device owns newest
            dev._lru_touch(dev.res_key(data), copy)
            dev._coh_mark_owned(data, copy)
            dev.evict_bytes(1 << 30)                 # force the write-back
    finally:
        stop.set()
        rt.join(timeout=10); wt.join(timeout=10)
    assert not errors, errors
    best = data.newest_copy()
    assert best is not None and best.payload is not None
    assert best.version == data.version


def test_ptdtd_dev_wiring_engine_level():
    """The ptdtd half of the lane contract (wired + tested at the engine
    level; DTD pools stay on the interpreted device path this PR): ready
    tasks of a device-marked class surface onto a ptdev Lane, the
    manager dispatches them through the pool callbacks, and the GIL-free
    dev_retire release walk completes them — including surfacing their
    per-task-lane successors through drain_ready."""
    import time as _t
    from parsec_tpu import native as native_mod
    dmod = native_mod.load_ptdev()
    emod = native_mod.load_ptdtd()
    if dmod is None or emod is None:
        pytest.skip("native modules unavailable")
    eng = emod.Engine()
    tile = eng.tile()
    eng.slot_set(tile, 1.0)
    lane = dmod.Lane()

    def cb(args_list):                 # CPU batch callback (unused here)
        return [(a[0],) for a in args_list]

    cls = eng.register_class(cb, [0], [3], None, -1, 1)   # device=1
    dispatched = []

    def dispatch(pool, ids):
        for tid in ids:
            v = eng.slot_get(tile)
            eng.slot_set(tile, v * 2.0)
            dispatched.append(tid)
        return len(ids)

    done_box = []

    def poll():
        out = [(1, tid) for tid in dispatched]
        done_box.extend(out)
        del dispatched[:]
        return out

    lane.bind_pool(1, eng.dev_retire_capsule(), eng)
    lane.start(dispatch, poll, 100)
    try:
        eng.dev_bind(lane.submit_capsule(), 1)
        # a device-class chain: t0 -> t1 (RAW on the tile), plus a
        # per-task-lane reader that must surface at the end
        n = eng.insert_many([(cls, None, tile, 3), (cls, None, tile, 3)])
        assert n == 2
        tid, held = eng.insert([tile], [1])   # per-task-lane reader
        eng.activate(tid)
        deadline = _t.monotonic() + 10
        surfaced = []
        while _t.monotonic() < deadline:
            _nexec, sur = eng.drain_ready(64, 1024)
            surfaced.extend(sur)
            if eng.dev_stats()["dev_done"] == 2 and surfaced:
                break
            _t.sleep(0.005)
        ds = eng.dev_stats()
        assert ds["dev_tx"] == 2 and ds["dev_done"] == 2 and \
            ds["dev_bad"] == 0, ds
        assert surfaced == [tid], (surfaced, tid)
        assert eng.slot_get(tile) == 4.0      # both device bodies ran
        ls = lane.stats()
        assert ls["retired"] == 2 and ls["cb_errors"] == 0
    finally:
        lane.stop()
        lane.unbind_pool(1)


def test_device_lane_off_by_mca(dctx):
    """--mca device_native 0 keeps TPU-bodied pools on the interpreted
    device module (counted ineligible, never fallback)."""
    from parsec_tpu.device.native import PTDEV_STATS
    mca.set("device_native", False)
    try:
        rng = np.random.default_rng(3)
        a_cols = [rng.standard_normal((4, 4)).astype(np.float32)
                  for _ in range(2)]
        snap = PTDEV_STATS.snapshot()
        tp, _A, B = _run_mixed(dctx, 2, 2, a_cols, "mcaoff")
        delta = PTDEV_STATS.delta(snap)
        assert tp._ptexec_state is None
        assert delta["pools_ineligible"] >= 1 and delta["pools_fallback"] == 0
        expect = _mixed_replay(a_cols, 2, 2)
        for i in range(2):
            np.testing.assert_allclose(
                np.asarray(B.data_of(0, i).newest_copy().payload,
                           np.float64), expect[i], rtol=1e-4)
    finally:
        mca.params.unset("device_native")


#: the tiled GEMM as a JDF, one k-chain per C tile, bodies on the device
_GEMM_SRC = ("%global MT\n%global KT\n%global descA\n%global descB\n"
             "%global descC\n"
             "GEMM(m, n, k)\n  m = 0 .. MT-1\n  n = 0 .. MT-1\n"
             "  k = 0 .. KT-1\n  : descC(m, n)\n"
             "  READ A <- descA(m, k)\n  READ B <- descB(k, n)\n"
             "  RW   C <- (k == 0) ? descC(m, n) : C GEMM(m, n, k-1)\n"
             "       -> (k < KT-1) ? C GEMM(m, n, k+1) : descC(m, n)\n"
             "BODY [type=TPU]\n"
             "  C = C + jnp.dot(A, B, preferred_element_type=jnp.float32)\n"
             "END\n")


def test_device_lane_under_budget_pressure(dctx):
    """Regression (found by the verify drive): under a tight HBM budget,
    staging tile k+1 of one dispatch batch must not evict tile k staged
    moments earlier — staged copies pin the moment they stage. The run
    stays correct, C-decided evictions DO happen, and every pin balances
    back to zero."""
    from parsec_tpu.dsl.ptg.compiler import compile_ptg
    dev = _tpu_dev(dctx)
    n, ts = 64, 16
    dev.set_budget(4 * ts * ts * 4, unit=1024)   # room for ~4 tiles
    rng = np.random.default_rng(21)
    a = rng.standard_normal((n, n)).astype(np.float32)
    b = rng.standard_normal((n, n)).astype(np.float32)
    A = TiledMatrix("pbA", n, n, ts, ts)
    A.fill(lambda m, k: a[m*ts:(m+1)*ts, k*ts:(k+1)*ts])
    B = TiledMatrix("pbB", n, n, ts, ts)
    B.fill(lambda m, k: b[m*ts:(m+1)*ts, k*ts:(k+1)*ts])
    C = TiledMatrix("pbC", n, n, ts, ts)
    C.fill(lambda m, k: np.zeros((ts, ts), np.float32))
    prog = compile_ptg(_GEMM_SRC, "pb-gemm")
    # per-task staging pressure under test: region fusion stages each
    # fused chain's tiles once per REGION (different pressure shape,
    # covered by tests/test_fusion.py); the in-batch pin regression
    # needs the per-task dispatch path
    mca.set("region_fusion", False)
    try:
        tp = prog.instantiate(dctx,
                              globals={"MT": n // ts, "KT": n // ts},
                              collections={"descA": A, "descB": B,
                                           "descC": C})
        dctx.add_taskpool(tp)
        dctx.wait(timeout=90)
    finally:
        mca.params.unset("region_fusion")
    assert tp._ptexec_state is not None and \
        tp._ptexec_state.get("dev_pool") is not None
    err = float(np.abs(C.to_dense() - a @ b).max())
    assert err < 1e-2, f"tight-budget device-lane GEMM wrong: {err}"
    cs = dev.coh_stats()
    if cs is not None:
        assert cs["evictions"] > 0, cs
        # pins balance: with the pool done, nothing stays pinned
        for M in (A, B, C):
            for m in range(M.mt):
                for nn in range(M.nt):
                    st = dev._ncoh.state(dev.res_key(M.data_of(m, nn)))
                    assert st is None or st[3] == 0, (m, nn, st)
    assert dctx._ptdev.failed() is None


# ---------------------------------------------------------------------------
# ISSUE 30: residency once per operand of a batch; adoption of resident bytes
# ---------------------------------------------------------------------------

_N, _TS = 64, 16
_NT = _N // _TS


def _need_lane(ctx):
    lane = ctx._ptdev_lane()
    if lane is None or _tpu_dev(ctx)._ncoh is None:
        pytest.skip("native _ptdev unavailable")
    return lane


def _gemm_operands(tag, seed):
    """A, B, C of a 4x4-tile GEMM with small whole numbers in them: every
    product and sum is exact in f32, so the right C is numpy's, bit for bit."""
    rng = np.random.default_rng(seed)
    a = rng.integers(-4, 5, (_N, _N)).astype(np.float32)
    b = rng.integers(-4, 5, (_N, _N)).astype(np.float32)
    mats = []
    for name, dense in (("A", a), ("B", b), ("C", np.zeros_like(a))):
        M = TiledMatrix(f"{tag}{name}", _N, _N, _TS, _TS)
        M.fill(lambda m, k, d=dense: d[m*_TS:(m+1)*_TS, k*_TS:(k+1)*_TS])
        mats.append(M)
    return a, b, mats


def _gemm_pool(ctx, prog, mats):
    return prog.instantiate(
        ctx, globals={"MT": _NT, "KT": _NT},
        collections=dict(zip(("descA", "descB", "descC"), mats)))


class _TableSpy:
    """The C coherency table, with its pin traffic written to ``log``."""

    def __init__(self, table, log):
        self._table, self.log = table, log

    def stage_in(self, key, nbytes, version, flags=0, pin=0):
        if pin:
            self.log.append(("stage", key))
        return self._table.stage_in(key, nbytes, version, flags, pin)

    def pin(self, key):
        self.log.append(("pin", key))
        return self._table.pin(key)

    def unpin(self, key):
        self.log.append(("unpin", key))
        return self._table.unpin(key)

    def __getattr__(self, name):
        return getattr(self._table, name)


def _spy_closures(tp, monkeypatch, on_dispatch=None, on_poll=None):
    """Wrap the pool's dispatch/poll closures as the lane receives them;
    returns the box that will hold the closure's ``held`` dict."""
    from parsec_tpu.device import lane_pool
    make = lane_pool._closures
    box = {}

    def spied(*args, **kw):
        dispatch, poll, drop, held = make(*args, **kw)
        box["held"] = held

        def spy_dispatch(ids):
            n = dispatch(ids)
            if on_dispatch is not None:
                on_dispatch(ids, held)
            return n

        def spy_poll():
            done = poll()
            if on_poll is not None:
                on_poll(done, held)
            return done
        return spy_dispatch, spy_poll, drop, held
    monkeypatch.setattr(lane_pool, "_closures", spied)
    return box


def _assert_unpinned(dev, mats):
    for M in mats:
        for m in range(M.mt):
            for n in range(M.nt):
                data = M.data_of(m, n)
                st = dev._ncoh.state(dev.res_key(data))
                assert st is None or st[3] == 0, (M.name, m, n, st)
                for c in data.copies.values():
                    assert c.readers == 0, (M.name, m, n)


@pytest.mark.parametrize("bound", [128, 16, 0],
                         ids=["one-pack", "packs-of-4", "per-task"])
def test_lane_pins_once_per_operand_of_a_batch(dctx, monkeypatch, bound):
    """The lane touches residency once per distinct memory operand of a
    batch: the stage-in's pin is the operand's only pin (no table pin per
    program and operand), every pin is given back, and nothing stays
    counted or pinned after the pool. A region program is a pack of
    k-chains (ISSUE 32: all 16, or the 4 of a row under a bound of 16
    tasks), and an operand that several chains of one program read counts
    as one reader of that program."""
    from parsec_tpu.dsl.ptg.compiler import compile_ptg
    _need_lane(dctx)
    dev = _tpu_dev(dctx)
    a, b, mats = _gemm_operands(f"po{bound}", 30)
    log, readers = [], {}
    monkeypatch.setattr(dev, "_ncoh", _TableSpy(dev._ncoh, log))
    puts = _count_puts(dev, monkeypatch)

    def on_dispatch(ids, held):
        log.append(("batch", len(ids)))
        for mi, h in held.items():
            readers[mi] = max(readers.get(mi, 0), h[1])
    mca.set("region_fusion", bool(bound))
    mca.set("region_fusion_max", bound or 128)
    try:
        tp = _gemm_pool(dctx, compile_ptg(_GEMM_SRC, f"po-gemm-{bound}"), mats)
        box = _spy_closures(tp, monkeypatch, on_dispatch=on_dispatch)
        dctx.add_taskpool(tp)
        dctx.wait(timeout=90)
    finally:
        mca.params.unset("region_fusion")
        mca.params.unset("region_fusion_max")
    assert dctx._ptdev.failed() is None
    assert np.array_equal(mats[2].to_dense(), a @ b)
    assert box["held"] == {}
    _assert_unpinned(dev, mats)
    # a batch's pins: the distinct operands it staged, each once
    batches, staged = [], []
    for what, v in log:
        if what == "stage":
            staged.append(v)
        elif what == "batch":
            batches.append((v, staged))
            staged = []
    programs = {128: 1, 16: _NT, 0: _NT ** 3}[bound]
    assert not staged and sum(n for n, _ in batches) == programs
    # chains a program, and the tiles a program reads: a row of A for the
    # chains of a row, a column of B and a C tile for each
    chains = _NT * _NT // programs if bound else 0
    tiles = {128: 3 * _NT * _NT, 16: _NT + chains * (_NT + 1), 0: 3}[bound]
    for n, keys in batches:
        assert len(keys) == len(set(keys))
        assert len(keys) <= min(n * tiles, 3 * _NT * _NT)
    pins = sum(len(keys) for _, keys in batches)
    assert pins >= 3 * _NT * _NT
    # the 48 host tiles move once each, a batch's misses in one put (ISSUE 38)
    assert sum(puts.calls) == 3 * _NT * _NT
    assert len(puts.calls) <= sum(1 for _, keys in batches if keys)
    assert not [e for e in log if e[0] == "pin"], "a pin per program operand"
    assert sum(1 for e in log if e[0] == "unpin") == pins
    # readers in flight count PROGRAMS: the A tiles of a row are read by its
    # four chains and by one program; a B tile by a chain of every row
    assert len(readers) == 3 * _NT * _NT
    if bound:
        assert max(readers.values()) <= programs
        assert sum(1 for r in readers.values() if r == 1) >= \
            (3 if bound == 128 else 2) * _NT * _NT


def _staggered_is_ready():
    """An ``is_ready`` for every array: true from the ``n``-th ask on, ``n``
    going 0, 1, 2, 0, ... over the arrays in the order they are first asked
    about, so the programs of one batch retire at different polls."""
    left = {}

    def is_ready(array):
        ent = left.setdefault(id(array), [array, len(left) % 3])
        ent[1] -= 1
        return ent[1] < 0
    return is_ready


def test_operand_in_flight_is_no_victim_after_a_reader_retired(dctx,
                                                               monkeypatch):
    """Under a budget of four tiles for a pool that reads 48, with the
    programs of a batch retiring at different polls: a copy that a program
    in flight still reads is never evicted, also once another reader of it
    has retired, and every count and pin comes back to zero."""
    import jax
    from parsec_tpu.dsl.ptg.compiler import compile_ptg
    _need_lane(dctx)
    dev = _tpu_dev(dctx)
    dev.set_budget(4 * _TS * _TS * 4, unit=1024)
    a, b, mats = _gemm_operands("vi", 31)
    monkeypatch.setattr(type(jax.device_put(np.zeros(1), dev.jax_device)),
                        "is_ready", _staggered_is_ready())
    peak, seen, bad = {}, {"partial": 0, "evictions": 0}, []

    def on_dispatch(ids, held):
        for mi, h in held.items():
            peak[mi] = max(peak.get(mi, 0), h[1])

    evict = dev._evict_key_locked

    def spy_evict(key, copy, drop_table):
        held = box.get("held", {})
        seen["evictions"] += 1
        seen["partial"] += any(0 < h[1] < peak.get(mi, 0)
                               for mi, h in held.items())
        if copy.readers or any(h[0] is copy for h in held.values()):
            bad.append(key)
        return evict(key, copy, drop_table)
    monkeypatch.setattr(dev, "_evict_key_locked", spy_evict)
    mca.set("region_fusion", False)
    try:
        tp = _gemm_pool(dctx, compile_ptg(_GEMM_SRC, "vi-gemm"), mats)
        box = _spy_closures(tp, monkeypatch, on_dispatch=on_dispatch)
        dctx.add_taskpool(tp)
        dctx.wait(timeout=90)
    finally:
        mca.params.unset("region_fusion")
    assert dctx._ptdev.failed() is None
    assert np.array_equal(mats[2].to_dense(), a @ b)
    assert seen["evictions"] > 0 and not bad, (seen, bad)
    assert seen["partial"] > 0, "no eviction while a reader had retired"
    assert box["held"] == {}
    _assert_unpinned(dev, mats)


class _Puts(list):
    """The arrays handed to ``device_put``, a list's members one by one;
    ``calls`` holds how many each call carried."""

    def __init__(self):
        super().__init__()
        self.calls = []


def _count_puts(dev, monkeypatch):
    puts, real = _Puts(), dev._jax.device_put

    def device_put(x, *args, **kw):
        members = x if isinstance(x, list) else [x]
        puts.extend(members)
        puts.calls.append(len(members))
        return real(x, *args, **kw)
    monkeypatch.setattr(dev._jax, "device_put", device_put)
    return puts


def test_stage_in_adopts_an_array_already_on_the_device(dctx, monkeypatch):
    """A datum whose newest copy holds a committed array on this device
    stages in without a ``device_put``: no byte counted, ``adopted`` + 1,
    and the table and the pin as a miss leaves them."""
    import jax
    dev = _tpu_dev(dctx)
    M = TiledMatrix("AD", 2 * _TS, _TS, _TS, _TS)
    M.fill(lambda m, n: np.full((_TS, _TS), 1.0 + m, np.float32))
    here, twin = M.data_of(0, 0), M.data_of(1, 0)
    arr = jax.device_put(np.full((_TS, _TS), 7.0, np.float32), dev.jax_device)
    here.get_copy(0).payload = arr
    here.bump_version(0)
    twin.bump_version(0)            # the same version, a numpy payload
    puts = _count_puts(dev, monkeypatch)
    moved, adopted, resident = \
        dev.transfer_in_bytes, dev.adopted, dev._resident_bytes
    copy = dev.lane_stage_in(here, pin=True)
    assert not puts and dev.transfer_in_bytes == moved
    assert dev.adopted == adopted + 1
    assert copy.payload is arr and copy.version == here.version
    assert copy.readers == 1 and dev._resident_bytes == resident + arr.nbytes
    other = dev.lane_stage_in(twin, pin=True)       # a miss, for comparison
    assert len(puts) == 1 and dev.transfer_in_bytes == moved + arr.nbytes
    assert dev.adopted == adopted + 1
    if dev._ncoh is not None:
        assert dev._ncoh.state(dev.res_key(here)) == \
            dev._ncoh.state(dev.res_key(twin))
        assert dev._ncoh.state(dev.res_key(here))[3] == 1
    dev.unpin_copy(copy)
    dev.unpin_copy(other)
    assert copy.readers == 0
    # a newer version written on the device: adopted again, in place
    newer = arr + 1.0
    here.get_copy(0).payload = newer
    here.bump_version(0)
    assert dev.lane_stage_in(here) is copy and copy.payload is newer
    assert copy.version == here.version and copy.readers == 0
    assert dev.adopted == adopted + 2 and len(puts) == 1
    assert dev._resident_bytes == resident + 2 * arr.nbytes
    assert dctx.devices.statistics()[dev.name]["adopted"] == dev.adopted


@pytest.mark.parametrize("where", ["numpy", "another device", "uncommitted"])
def test_stage_in_still_transfers_what_is_elsewhere(dctx, monkeypatch, where):
    """Adoption reads where the newest bytes live: a host tile, an array on
    another device and an array no device was chosen for go through
    ``device_put`` and count their bytes, as before."""
    import jax
    import jax.numpy as jnp
    dev = _tpu_dev(dctx)
    tile = np.full((_TS, _TS), 3.0, np.float32)
    if where == "another device":
        others = [d for d in jax.devices() if d != dev.jax_device]
        if not others:
            pytest.skip("one device only")
        tile = jax.device_put(tile, others[0])
    elif where == "uncommitted":
        tile = jnp.asarray(tile)
        assert not tile.committed
    M = TiledMatrix("EL" + where[:2], _TS, _TS, _TS, _TS)
    M.fill(lambda m, n: np.asarray(tile))
    data = M.data_of(0, 0)
    data.get_copy(0).payload = tile
    puts = _count_puts(dev, monkeypatch)
    moved, adopted = dev.transfer_in_bytes, dev.adopted
    copy = dev.lane_stage_in(data)
    assert len(puts) == 1 and puts[0] is tile
    assert dev.transfer_in_bytes == moved + tile.nbytes
    assert dev.adopted == adopted
    assert copy.payload.devices() == {dev.jax_device}
    assert np.array_equal(np.asarray(copy.payload), np.asarray(tile))


def test_chained_pools_stage_c_in_by_adoption(dctx, monkeypatch):
    """A second instantiation over the first pool's written C: the C tiles'
    newest copies are the device arrays the write-back left, so they stage
    in by adoption, and C = 2 A B, bit for bit."""
    from parsec_tpu.dsl.ptg.compiler import compile_ptg
    _need_lane(dctx)
    dev = _tpu_dev(dctx)
    a, b, mats = _gemm_operands("ch", 32)
    prog = compile_ptg(_GEMM_SRC, "ch-gemm")
    puts = _count_puts(dev, monkeypatch)
    for solve in (1, 2):
        moved, adopted, put = dev.transfer_in_bytes, dev.adopted, len(puts)
        tp = _gemm_pool(dctx, prog, mats)
        dctx.add_taskpool(tp)
        dctx.wait(timeout=90)
        assert dctx._ptdev.failed() is None
        assert np.array_equal(mats[2].to_dense(), solve * (a @ b))
        tiles, tile_bytes = _NT * _NT, _TS * _TS * 4
        if solve == 1:              # 48 host tiles, every one a transfer
            assert dev.adopted == adopted and len(puts) == put + 3 * tiles
            assert dev.transfer_in_bytes == moved + 3 * tiles * tile_bytes
        else:                       # A and B resident, C adopted
            assert dev.adopted == adopted + tiles and len(puts) == put
            assert dev.transfer_in_bytes == moved
    _assert_unpinned(dev, mats)


@pytest.mark.parametrize("spans", [True, False], ids=["on", "off"])
def test_ptdev_pins_records_once_a_dispatch_callback(monkeypatch, spans):
    """``ptdev.pins``: with spans on, one record per dispatch callback
    holding the table pins it took (3 a program where a pool surfaces as
    one batch); with spans off, nothing."""
    from parsec_tpu.dsl.ptg.compiler import compile_ptg
    from parsec_tpu.utils.hist import HIST_NAMES, histograms
    assert "pins" in HIST_NAMES["ptdev"]
    mca.set("device_tpu_over_cpu", True)
    if spans:
        mca.set("hist_enabled", True)
    ctx = Context(nb_cores=1)
    try:
        _need_lane(ctx)
        assert (ctx._spans is not None) == spans
        a, b, mats = _gemm_operands("ph" + "ny"[spans], 33)
        before = histograms.snapshot().get("ptdev.pins",
                                           {"count": 0, "sum_ns": 0})
        log = []
        tp = _gemm_pool(ctx, compile_ptg(_GEMM_SRC, f"ph-gemm-{spans}"), mats)
        _spy_closures(tp, monkeypatch,
                      on_dispatch=lambda ids, held: log.append(len(ids)))
        dev = _tpu_dev(ctx)
        pins = []
        monkeypatch.setattr(dev, "_ncoh", _TableSpy(dev._ncoh, pins))
        ctx.add_taskpool(tp)
        ctx.wait(timeout=90)
        assert np.array_equal(mats[2].to_dense(), a @ b)
        after = histograms.snapshot().get("ptdev.pins",
                                          {"count": 0, "sum_ns": 0})
        if spans:
            assert after["count"] - before["count"] == len(log)
            assert after["sum_ns"] - before["sum_ns"] == \
                sum(1 for e in pins if e[0] == "stage")
        else:
            assert after == before
    finally:
        ctx.fini()
        mca.params.unset("hist_enabled")
        mca.params.unset("device_tpu_over_cpu")


@pytest.mark.parametrize("snapshot, want", [
    ({}, None),                                     # no histograms at all
    ({"ptdev.dispatch_ns": {"count": 1024, "sum_ns": 1}}, None),   # no counter
    ({"ptdev.pins": {"count": 0, "sum_ns": 0},
      "ptdev.dispatch_ns": {"count": 0, "sum_ns": 0}}, None),      # no program
    ({"ptdev.pins": {"count": 2, "sum_ns": 6144},
      "ptdev.dispatch_ns": {"count": 2048, "sum_ns": 1}}, 3.0),
])
def test_pins_per_program_reader(monkeypatch, snapshot, want):
    """``chipbench/layers/pins_per_program.py``: ``ptdev.pins`` sum over
    ``ptdev.dispatch_ns`` count, nothing where the program lacks either; its
    entry lists the PTG cells (ISSUE 33 appended the factorization's)."""
    import json
    import os
    from parsec_tpu.utils.hist import histograms
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.syspath_prepend(root)
    from chipbench.layers import pins_per_program
    monkeypatch.setattr(histograms, "snapshot", lambda: snapshot)
    assert pins_per_program.read(None) == want
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        entry, = [m for m in json.load(f)["per_layer"]
                  if m["name"] == "pins_per_program"]
    assert entry == {"name": "pins_per_program", "unit": "pins/program",
                     "better": "lower", "source": "program_counter",
                     "layer": "device issue", "moves": "tasks_per_s",
                     "workloads": ["ptg_gemm.ts512", "ptg_potrf.ts512"]}


# ---------------------------------------------------------------------------
# ISSUE 38: the push phase asks for a batch's operands at once, and the
# misses among them move in one device_put
# ---------------------------------------------------------------------------

_TILE = _TS * _TS * 4


def _tiles(n, base=0.0):
    """``n`` data, each with one host copy: a numpy tile of its own value."""
    from parsec_tpu.data.data import data_from_array
    return [data_from_array(np.full((_TS, _TS), base + i, np.float32))
            for i in range(n)]


def _hand_pool(devlane, reads, mem_datas):
    """The lane's closures over independent tasks, for the test to drive by
    hand on its own thread: task ``i`` adds the memory operands ``reads[i]
    = (mi, mj)`` into its one written flow. A batch is what ``dispatch`` is
    given. Returns ``(dispatch, drain, held, slots)``; ``drain()`` polls
    until every dispatched task has come back."""
    import time as _t
    import jax
    from parsec_tpu.device import lane_pool
    n = len(reads)
    in_refs = [r for a, b in reads for r in (-1, -2 - a, -2 - b)]
    slots = [None] * (3 * n)
    dispatch, poll, _drop, held = lane_pool._closures(
        devlane, None, [0], [[()] * n], list(range(0, 3 * n, 3)), in_refs,
        [3], [0] * n, [jax.jit(lambda o, a, b: (a + b,))], [(0,)],
        ["hand.add"], slots, mem_datas, {}, None, 0, None, None, n)
    sent = []

    def send(ids):
        n = dispatch(ids)
        sent.extend(ids)
        return n

    def drain():
        deadline = _t.monotonic() + 30
        while sent and _t.monotonic() < deadline:
            for i in poll():
                sent.remove(i)
        assert not sent
    return send, drain, held, slots


def _pins(dev, data):
    """(table pins, ``readers`` of the device copy) of ``data``."""
    st = dev._ncoh.state(dev.res_key(data))
    copy = data.get_copy(dev.device_index)
    return (0 if st is None else st[3], 0 if copy is None else copy.readers)


def _batch_of_misses(dctx, monkeypatch):
    """k misses: one ``device_put`` of the k tiles, k copies at the newest
    versions, each pinned once in the table and in ``readers``, the bytes
    and the table's misses counted, the lane's two counters up."""
    from parsec_tpu.device.native import PTDEV_STATS
    devlane, dev = _need_lane(dctx), _tpu_dev(dctx)
    datas = _tiles(5)
    datas[3].bump_version(0)
    send, drain, held, slots = _hand_pool(
        devlane, [(0, 1), (2, 3), (4, 0)], datas)
    puts = _count_puts(dev, monkeypatch)
    moved, misses, stats = dev.transfer_in_bytes, \
        dev.coh_stats()["coh_misses"], PTDEV_STATS.snapshot()
    assert send([0, 1, 2]) == 3
    assert puts.calls == [5]
    assert all(a is d.get_copy(0).payload for a, d in zip(puts, datas))
    assert dev.transfer_in_bytes == moved + 5 * _TILE
    assert dev.coh_stats()["coh_misses"] == misses + 5
    for mi, d in enumerate(datas):
        copy = d.get_copy(dev.device_index)
        assert copy.version == d.version and held[mi][0] is copy
        assert held[mi][2] == 1 and _pins(dev, d) == (1, 1)
    assert [h[1] for h in held.values()] == [2, 1, 1, 1, 1]
    delta = PTDEV_STATS.delta(stats)
    assert (delta["staged_tiles"], delta["stage_in_puts"]) == (5, 1)
    drain()
    assert held == {} and all(_pins(dev, d) == (0, 0) for d in datas)
    assert [float(np.asarray(slots[s])[0, 0]) for s in (0, 3, 6)] == \
        [1.0, 5.0, 4.0]
    # nothing left to move: a second batch over the same operands puts none
    send([0, 1, 2])
    drain()
    assert puts.calls == [5] and PTDEV_STATS.delta(stats)["stage_in_puts"] == 1


def _mixed_batch(dctx, monkeypatch):
    """Hits, an adoption, misses, and an operand that two programs (and one
    of them twice) name: only the misses are in the put, the adoption is
    counted, a hit's copy is the object it was, each operand pinned once."""
    import jax
    devlane, dev = _need_lane(dctx), _tpu_dev(dctx)
    hit0, hit1, here, miss0, miss1, miss2 = datas = _tiles(6)
    was = [dev.lane_stage_in(d) for d in (hit0, hit1)]
    arr = jax.device_put(np.full((_TS, _TS), 7.0, np.float32), dev.jax_device)
    here.get_copy(0).payload = arr
    here.bump_version(0)
    send, drain, held, slots = _hand_pool(
        devlane, [(3, 3), (3, 0), (2, 4), (1, 5)], datas)
    puts = _count_puts(dev, monkeypatch)
    moved, adopted = dev.transfer_in_bytes, dev.adopted
    send([0, 1, 2, 3])
    assert puts.calls == [3] and len(puts) == 3
    assert all(any(a is d.get_copy(0).payload for a in puts)
               for d in (miss0, miss1, miss2))
    assert dev.adopted == adopted + 1
    assert dev.transfer_in_bytes == moved + 3 * _TILE
    assert held[0][0] is was[0] and held[1][0] is was[1]
    assert held[2][0].payload is arr
    assert sorted(held) == list(range(6))
    assert all(h[2] == 1 for h in held.values())
    assert all(_pins(dev, d) == (1, 1) for d in datas)
    assert held[3][1] == 3              # two programs read it, one twice
    drain()
    assert held == {} and all(_pins(dev, d) == (0, 0) for d in datas)
    assert [float(np.asarray(slots[s])[0, 0]) for s in (0, 3, 6, 9)] == \
        [6.0, 3.0, 11.0, 6.0]


def _batch_under_a_budget(dctx, monkeypatch):
    """Four tiles of room. A batch of four new tiles evicts the four LRU
    unpinned ones and none of its own, and the dirty one among them is on
    the host, at its version, before the put is called. A batch of three
    programs that need six tiles is cut to the two whose four fit; the
    third waits, counted once, until a poll that gave pins back admits
    it: the resident bytes never pass the budget."""
    from parsec_tpu.device.native import PTDEV_STATS
    devlane, dev = _need_lane(dctx), _tpu_dev(dctx)
    dev.set_budget(4 * _TILE, unit=1024)
    old, new, more = _tiles(4), _tiles(4, 10.0), _tiles(6, 20.0)
    datas = old + new + more
    send, drain, held, slots = _hand_pool(
        devlane, [(0, 1), (2, 3), (4, 5), (6, 7), (8, 9), (10, 11),
                  (12, 13)], datas)
    send([0, 1])
    drain()
    dirty = old[0].get_copy(dev.device_index)
    dirty.payload = dirty.payload + 100.0       # a write on the device
    old[0].bump_version(dev.device_index)
    dev._coh_mark_owned(old[0], dirty)
    assert dev._resident_bytes == 4 * _TILE and dev.evictions == 0
    seen, real = [], dev._jax.device_put

    def device_put(x, *args, **kw):
        host = old[0].get_copy(0)
        seen.append((len(x), dev.evictions, dev.owned_evictions,
                     type(host.payload), float(host.payload[0, 0]),
                     host.version == old[0].version))
        return real(x, *args, **kw)
    monkeypatch.setattr(dev._jax, "device_put", device_put)
    out = dev.transfer_out_bytes
    send([2, 3])
    assert seen == [(4, 4, 1, np.ndarray, 100.0, True)]
    assert dev.transfer_out_bytes == out + _TILE
    assert all(d.get_copy(dev.device_index).payload is None for d in old)
    assert all(_pins(dev, d) == (1, 1) and
               d.get_copy(dev.device_index).payload is not None for d in new)
    assert dev._resident_bytes == 4 * _TILE
    drain()
    stats = PTDEV_STATS.snapshot()
    assert send([4, 5, 6]) == 3         # all three counted in flight
    assert seen[-1][:2] == (4, 8)       # the four before it, none of its own
    assert PTDEV_STATS.delta(stats)["held_back"] == 1
    assert dev._resident_bytes == 4 * _TILE == dev.lane_room() + 4 * _TILE
    assert all(_pins(dev, d) == (1, 1) for d in more[:4])
    assert all(d.get_copy(dev.device_index) is None for d in more[4:])
    drain()
    assert seen[-1][:2] == (2, 10) and len(seen) == 3
    assert PTDEV_STATS.delta(stats)["held_back"] == 1
    assert dev.coh_stats()["hwm_bytes"] <= 4 * _TILE
    assert dev._resident_bytes == 4 * _TILE and dev.lane_room() == 4 * _TILE
    assert held == {} and all(_pins(dev, d) == (0, 0) for d in datas)
    assert [float(np.asarray(slots[s])[0, 0]) for s in range(6, 21, 3)] == \
        [21.0, 25.0, 41.0, 45.0, 49.0]


def _a_put_that_raises(dctx, monkeypatch):
    """The put fails: every pin the batch took, a hit's, an adoption's and
    the misses' alike, is given back before the error surfaces, nothing is
    held, and the same batch then stages in and runs."""
    import jax
    devlane, dev = _need_lane(dctx), _tpu_dev(dctx)
    hit, here, miss0, miss1 = datas = _tiles(4)
    dev.lane_stage_in(hit)
    here.get_copy(0).payload = jax.device_put(
        np.full((_TS, _TS), 7.0, np.float32), dev.jax_device)
    here.bump_version(0)
    send, drain, held, slots = _hand_pool(devlane, [(0, 2), (1, 3)], datas)

    def device_put(x, *args, **kw):
        raise RuntimeError("RESOURCE_EXHAUSTED: no room for this transfer")
    with monkeypatch.context() as patch:
        patch.setattr(dev._jax, "device_put", device_put)
        with pytest.raises(RuntimeError, match="no room for this transfer"):
            send([0, 1])
    assert held == {} and all(_pins(dev, d) == (0, 0) for d in datas)
    assert miss0.get_copy(dev.device_index) is None
    puts = _count_puts(dev, monkeypatch)
    send([0, 1])
    assert puts.calls == [2] and all(_pins(dev, d) == (1, 1) for d in datas)
    drain()
    assert held == {} and all(_pins(dev, d) == (0, 0) for d in datas)
    assert [float(np.asarray(slots[s])[0, 0]) for s in (0, 3)] == [2.0, 10.0]


def _batch_with_the_spans_on(dctx, monkeypatch):
    """``ptdev.stage_in_ns`` and ``tpudev.stage_in_ns`` count the tiles that
    moved, not the puts, ``ptdev.pins`` one a distinct operand, and the
    pool's six parts add up to its life."""
    from parsec_tpu.utils import xla_trace as X
    from parsec_tpu.utils.hist import histograms
    devlane, dev = _need_lane(dctx), _tpu_dev(dctx)
    assert dctx._spans is not None
    datas = _tiles(6)
    dev.lane_stage_in(datas[5])
    kept = list(X.POOL_ACCOUNTS)
    try:
        send, drain, held, _slots = _hand_pool(
            devlane, [(0, 1), (2, 3), (4, 5), (5, 0)], datas)
        puts = _count_puts(dev, monkeypatch)
        s0 = histograms.snapshot()
        send([0, 1, 2, 3])
        drain()
        s1 = histograms.snapshot()
        acct = X.POOL_ACCOUNTS[-1]
    finally:
        X.POOL_ACCOUNTS.clear()
        X.POOL_ACCOUNTS.extend(kept)

    def grew(name, field):
        return s1[name][field] - s0.get(name, {field: 0})[field]
    assert puts.calls == [5]
    assert grew("ptdev.stage_in_ns", "count") == 5
    assert grew("tpudev.stage_in_ns", "count") == 5
    # n equal shares of one put: the two histograms hold the same time
    assert abs(grew("ptdev.stage_in_ns", "sum_ns")
               - grew("tpudev.stage_in_ns", "sum_ns")) < 5
    assert (grew("ptdev.pins", "count"), grew("ptdev.pins", "sum_ns")) == \
        (1, 6)
    assert grew("ptdev.push_ns", "sum_ns") >= \
        grew("ptdev.stage_in_ns", "sum_ns") > 0
    assert (acct["programs"], acct["callbacks"], acct["tasks"]) == (4, 1, 4)
    assert acct["push_ns"] == grew("ptdev.push_ns", "sum_ns")
    assert acct["life_ns"] == sum(acct[k] for k in (
        "push_ns", "call_ns", "own_ns", "poll_ns", "retire_ns", "away_ns"))
    assert held == {}


@pytest.mark.parametrize("case, spans", [
    (_batch_of_misses, False), (_mixed_batch, False),
    (_batch_under_a_budget, False), (_a_put_that_raises, False),
    (_batch_with_the_spans_on, True)],
    ids=["misses", "mixed", "budget", "put-raises", "spans"])
def test_the_push_phase_stages_a_batch_at_once(monkeypatch, case, spans):
    mca.set("device_tpu_over_cpu", True)
    if spans:
        mca.set("hist_enabled", True)
    ctx = Context(nb_cores=1)
    try:
        case(ctx, monkeypatch)
    finally:
        monkeypatch.undo()
        ctx.fini()
        mca.params.unset("hist_enabled")
        mca.params.unset("device_tpu_over_cpu")


# ---------------------------------------------------------------------------
# ISSUE 31: the lane binds a pool from plain data (device/lane_pool.py)
# ---------------------------------------------------------------------------

def test_lane_pool_binds_plain_data(dctx):
    """A pool made of lists and one jitted callable, on a bare ``ptexec``
    ``Graph``: no ``compile_ptg``, no ``TaskClass``. A chain of three tasks
    ``x <- x + k * m``, the first ``x`` and every ``m`` read from memory,
    the last ``x`` written back."""
    import time as _t
    import jax
    from parsec_tpu import native as native_mod
    from parsec_tpu.data.data import data_from_array
    from parsec_tpu.device import lane_pool
    from parsec_tpu.device.native import PTDEV_STATS
    devlane = _need_lane(dctx)
    dev = _tpu_dev(dctx)
    m = data_from_array(np.full((8, 8), 2.0, np.float32))
    x0 = data_from_array(np.ones((8, 8), np.float32))
    out = data_from_array(np.zeros((8, 8), np.float32))
    # task i owns slots 2i (x, written) and 2i + 1 (m, read); memory
    # operand mi is the in_ref -2 - mi
    graph = native_mod.load_ptexec().Graph(
        [0, 1, 1], [0, 1, 2, 2], [1, 2], None,       # goals, off, succs, prio
        [0, 0, 1, 2], [0, 2], [1, 0, 1, 0, 0, 0])    # in_off, in_slots, uses
    slots = [None] * 6

    def cpu_batch(ids, retired):        # every task is the device's
        assert not list(ids)
        for j in retired:
            slots[j] = None
    before = PTDEV_STATS.snapshot()
    pid, held = lane_pool.bind(
        devlane, graph, bases=[0], params=[[(1,), (2,), (3,)]],
        slot_base=[0, 2, 4], in_refs=[-3, -2, 0, -2, 2, -2], ndflows=[2],
        cls_of=[0, 0, 0], fns=[jax.jit(lambda k, x, m: (x + k * m,))],
        written=[(0,)], names=["hand.acc"], slots=slots, mem_datas=[m, x0],
        writebacks={2: [(0, out)]}, dev_mask=[1, 1, 1], ndev_tasks=3)
    try:
        deadline = _t.monotonic() + 30
        while not graph.done() and _t.monotonic() < deadline:
            assert devlane.failed() is None
            graph.run(cpu_batch, 256, 4096, 0)
            _t.sleep(1e-3)
        assert graph.done() and devlane.failed() is None
    finally:
        devlane.unbind_pool(pid)
    np.testing.assert_array_equal(np.asarray(out.get_copy(0).payload),
                                  np.full((8, 8), 13.0, np.float32))
    assert out.version == 1 and held == {}
    for d in (m, x0):
        st = dev._ncoh.state(dev.res_key(d))
        assert st is not None and st[3] == 0, st
        assert all(c.readers == 0 for c in d.copies.values())
    delta = PTDEV_STATS.delta(before)
    assert (delta["pools_engaged"], delta["tasks_engaged"]) == (1, 3)


# ---------------------------------------------------------------------------
# ISSUE 36: release at dispatch
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bound", [128, 0], ids=["packs", "per-task"])
def test_a_k_chain_gemm_pool_releases_what_its_structure_allows(dctx, bound):
    """A pool of packed k-chains is regions with no successor: none is
    released at dispatch and every one retires when seen complete. Run a
    task a program, each GEMM(m, n, k) but a chain's last has the next as
    its one successor, a device task: three of every four are released."""
    from parsec_tpu.device.native import PTDEV_STATS
    from parsec_tpu.dsl.ptg.compiler import compile_ptg
    _need_lane(dctx)
    dev = _tpu_dev(dctx)
    a, b, mats = _gemm_operands(f"er{bound}", 36)
    mca.set("region_fusion", bool(bound))
    try:
        prog = compile_ptg(_GEMM_SRC, f"er-gemm-{bound}")
        tp = _gemm_pool(dctx, prog, mats)
        before = PTDEV_STATS.snapshot()
        dctx.add_taskpool(tp)
        dctx.wait(timeout=90)
        delta = PTDEV_STATS.delta(before)
    finally:
        mca.params.unset("region_fusion")
    assert dctx._ptdev.failed() is None
    assert np.array_equal(mats[2].to_dense(), a @ b)
    (ent,) = prog._ptexec_cache.values()
    if bound:
        assert ent["fusion"]["dev_early"] == [0] * len(
            ent["fusion"]["dev_mask"])
        assert delta["programs"] >= 1 and delta["released_early"] == 0
    else:
        assert delta["programs"] == _NT ** 3
        assert delta["released_early"] == _NT * _NT * (_NT - 1) \
            == sum(ent["flat"]["dev"][2])
    assert tp._ptexec_state["dev_held"] == {}
    _assert_unpinned(dev, mats)


def test_a_program_that_fails_on_the_device_still_poisons_the_lane(
        dctx, monkeypatch):
    """A chain of regions released at dispatch, the first of which fails
    on the device (its outputs raise when asked whether they are ready):
    the failure surfaces when the lane looks for a completion, poisons the
    lane and ends the wait as the pool's error, and tearing the context
    down leaves no pin behind an unbound pool."""
    import jax
    from parsec_tpu.dsl.ptg.compiler import compile_ptg
    _need_lane(dctx)
    dev = _tpu_dev(dctx)
    kind = type(jax.device_put(np.zeros(1, np.float32), dev.jax_device))

    def is_ready(array):
        raise RuntimeError("INTERNAL: the device gave up on this program")
    src = ("%global NT\n%global descA\n"
           "T(k)\n  k = 0 .. NT-1\n"
           "  RW X <- (k == 0) ? descA(0, 0) : X T(k-1)\n"
           "       -> (k < NT-1) ? X T(k+1) : descA(0, 0)\n"
           "  READ M <- descA(0, k+1)\n"
           "BODY [type=TPU]\n  X = X + M\nEND\n")
    A = TiledMatrix("failA", 4, 4 * 7, 4, 4)
    A.fill(lambda m, k: np.ones((4, 4), np.float32))
    mca.set("region_fusion_max", 2)
    try:
        prog = compile_ptg(src, "dev-fail-early")
        tp = prog.instantiate(dctx, globals={"NT": 6},
                              collections={"descA": A})
        monkeypatch.setattr(kind, "is_ready", is_ready)
        dctx.add_taskpool(tp)
        with pytest.raises(BaseException):
            dctx.wait(timeout=30)
    finally:
        mca.params.unset("region_fusion_max")
        monkeypatch.undo()
    assert "gave up on this program" in (dctx._ptdev.failed() or "")
    (ent,) = prog._ptexec_cache.values()
    assert ent["fusion"]["dev_early"] == [1, 1, 0]
    dctx._ptdev.fini()
    _assert_unpinned(dev, [A])
