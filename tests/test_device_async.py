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
    src = ("%global MT\n%global KT\n%global descA\n%global descB\n"
           "%global descC\n"
           "GEMM(m, n, k)\n  m = 0 .. MT-1\n  n = 0 .. MT-1\n"
           "  k = 0 .. KT-1\n  : descC(m, n)\n"
           "  READ A <- descA(m, k)\n  READ B <- descB(k, n)\n"
           "  RW   C <- (k == 0) ? descC(m, n) : C GEMM(m, n, k-1)\n"
           "       -> (k < KT-1) ? C GEMM(m, n, k+1) : descC(m, n)\n"
           "BODY [type=TPU]\n"
           "  C = C + jnp.dot(A, B, preferred_element_type=jnp.float32)\n"
           "END\n")
    A = TiledMatrix("pbA", n, n, ts, ts)
    A.fill(lambda m, k: a[m*ts:(m+1)*ts, k*ts:(k+1)*ts])
    B = TiledMatrix("pbB", n, n, ts, ts)
    B.fill(lambda m, k: b[m*ts:(m+1)*ts, k*ts:(k+1)*ts])
    C = TiledMatrix("pbC", n, n, ts, ts)
    C.fill(lambda m, k: np.zeros((ts, ts), np.float32))
    prog = compile_ptg(src, "pb-gemm")
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
