"""The PTG tiled GEMM over a budget that cannot hold its three matrices (the
CPU twin of the benchmark's ``ptg_gemm_ooc.ts2048``): the JDF of
``examples/ex06_gemm_ptg.py`` through ``compile_ptg`` + ``instantiate``,
fusion and the ``ptdev`` lane, with C accumulating over three solves.

The lane admits a batch by the bytes the budget can pin: the packs that do
not fit wait in the pool's backlog, the written C tiles are OWNED residents
of the table, and those the budget cannot keep are written back and staged
in again at the version they were written. A pool that fits takes the path
it took before, and a region that no budget could stage is refused when the
pool is bound.
"""

import os
import sys

import numpy as np
import pytest

from parsec_tpu.core.context import Context
from parsec_tpu.data.matrix import TwoDimBlockCyclic
from parsec_tpu.device.native import PTDEV_STATS
from parsec_tpu.device.tpu import TPUDevice
from parsec_tpu.dsl.ptg.compiler import compile_ptg
from parsec_tpu.utils import mca
from parsec_tpu.utils import xla_trace as X

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "examples"))
import ex06_gemm_ptg  # noqa: E402

NT, TS, SOLVES = 6, 8, 3
TILE = TS * TS * 4


def _context(budget=None, spans=False):
    mca.set("device_tpu_over_cpu", True)
    if budget is not None:
        mca.set("device_tpu_max_bytes", budget)
    if spans:
        mca.set("hist_enabled", True)
    try:
        ctx = Context(nb_cores=1)
    finally:
        for name in ("device_tpu_over_cpu", "device_tpu_max_bytes",
                     "hist_enabled"):
            mca.params.unset(name)
    if ctx._ptdev_lane() is None:
        ctx.fini()
        pytest.skip("native _ptdev unavailable")
    dev = next(d for d in ctx.devices.devices if isinstance(d, TPUDevice))
    if dev._ncoh is None:
        ctx.fini()
        pytest.skip("native coherency table unavailable")
    return ctx, dev


def _operands(seed):
    rng = np.random.default_rng(seed)
    n = NT * TS
    a, b = (rng.standard_normal((n, n)).astype(np.float32) for _ in range(2))
    A, B, C = (TwoDimBlockCyclic(f"oo{name}{seed}", n, n, TS, TS)
               for name in "ABC")
    A.fill(lambda m, k: a[m*TS:(m+1)*TS, k*TS:(k+1)*TS])
    B.fill(lambda k, j: b[k*TS:(k+1)*TS, j*TS:(j+1)*TS])
    C.fill(lambda m, j: np.zeros((TS, TS), np.float32))
    return a, b, (A, B, C)


def _solve(ctx, prog, mats):
    tp = prog.instantiate(
        ctx, globals={"MT": NT, "NT": NT, "KT": NT},
        collections=dict(zip(("descA", "descB", "descC"), mats)))
    ctx.add_taskpool(tp)
    ctx.wait(timeout=120)
    assert tp.completed and ctx._ptdev.failed() is None
    assert tp._ptexec_state["dev_held"] == {}
    return tp


def _unpinned(dev, mats):
    for M in mats:
        for m in range(NT):
            for j in range(NT):
                d = M.data_of(m, j)
                assert all(c.readers == 0 for c in d.copies.values())
                st = dev._ncoh.state(dev.res_key(d))
                assert st is None or st[3] == 0, st
    assert dev._pinned_bytes == 0


@pytest.mark.parametrize("spans", [False, True], ids=["spans-off", "spans-on"])
def test_a_pool_over_the_budget_waits_evicts_and_writes_back(spans):
    # a chain reads 13 tiles and writes one, 108 tiles in all
    budget = 24 * TILE
    ctx, dev = _context(budget, spans)
    kept = list(X.POOL_ACCOUNTS)
    try:
        a, b, mats = _operands(40)
        C = mats[2]
        c_data = [C.data_of(m, j) for m in range(NT) for j in range(NT)]
        version0 = c_data[0].version
        prog = compile_ptg(ex06_gemm_ptg.SRC, f"ooc-gemm-{spans}")
        stats, cb_errors = PTDEV_STATS.snapshot(), \
            ctx._ptdev.clane.stats()["cb_errors"]
        accounts = len(X.POOL_ACCOUNTS)
        for solve in range(1, SOLVES + 1):
            _solve(ctx, prog, mats)
            # one write-back a k-chain and solve, wherever the tile is now
            assert {d.version for d in c_data} == {version0 + solve}
        delta = PTDEV_STATS.delta(stats)
        np.testing.assert_allclose(C.to_dense(), SOLVES * (a @ b),
                                   rtol=1e-4, atol=1e-3)
        assert ctx._ptdev.clane.stats()["cb_errors"] == cb_errors
        # packs of two chains (20 reads: one A row, two B columns, two C
        # tiles; and the two C tiles written), one of which the budget
        # holds at a time
        (ent,) = prog._ptexec_cache.values()
        assert len(ent["fusion"]["regions"]) == NT * NT // 2
        assert all(len(r["ext_mems"]) == 20 for r in ent["fusion"]["regions"])
        assert delta["held_back"] >= SOLVES * (NT * NT // 2 - 2)
        assert delta["programs"] == SOLVES * NT * NT // 2
        assert delta["tasks_engaged"] == SOLVES * NT ** 3
        coh = dev.coh_stats()
        assert coh["hwm_bytes"] <= budget == coh["budget"]
        assert dev.evictions > 0 and dev.owned_evictions > 0
        assert dev.transfer_out_bytes >= TILE * dev.owned_evictions
        _unpinned(dev, mats)
        # every C tile has one newest valid copy: on the device, an OWNED
        # resident of the table, or written back to the host
        on_host = 0
        for d in c_data:
            newest = d.newest_copy()
            assert newest.version == d.version and newest.payload is not None
            if newest.device_index == 0:
                assert isinstance(newest.payload, np.ndarray)
                on_host += 1
            else:
                st = dev._ncoh.state(dev.res_key(d))
                assert st[:2] == (1, d.version & 0xFFFFFFFF)     # OWNED
        assert on_host > 0
        if spans:
            mine = list(X.POOL_ACCOUNTS)[accounts:]
            assert len(mine) == SOLVES
            for acct in mine:
                assert sum(acct[k] for k in (
                    "push_ns", "call_ns", "own_ns", "poll_ns", "retire_ns",
                    "away_ns")) == acct["life_ns"]
                assert 0 < acct["room_ns"] <= acct["push_ns"]
                assert acct["programs"] == NT * NT // 2
                assert acct["callbacks"] < acct["programs"]
    finally:
        ctx.fini()
        X.POOL_ACCOUNTS.clear()
        X.POOL_ACCOUNTS.extend(kept)


def test_a_pool_that_fits_takes_the_path_it_took_before(monkeypatch):
    """The program's own budget holds the 108 tiles: no program waits, the
    packs are the fusion pass's (21 chains, its 128-task bound), and a
    dispatch callback pins each operand of its batch once and moves the
    misses in one put (the two packs surface in one callback or two, as
    the manager thread wakes)."""
    ctx, dev = _context()
    try:
        calls = []
        stage = dev.lane_stage_in_batch

        def spy(datas):
            calls.append(len(datas))
            return stage(datas)
        monkeypatch.setattr(dev, "lane_stage_in_batch", spy)
        a, b, mats = _operands(41)
        prog = compile_ptg(ex06_gemm_ptg.SRC, "fits-gemm")
        stats = PTDEV_STATS.snapshot()
        _solve(ctx, prog, mats)
        delta = PTDEV_STATS.delta(stats)
        np.testing.assert_allclose(mats[2].to_dense(), a @ b,
                                   rtol=1e-4, atol=1e-3)
        (ent,) = prog._ptexec_cache.values()
        assert [len(r["members"]) for r in ent["fusion"]["regions"]] == \
            [126, 90]
        assert delta["held_back"] == 0 and delta["programs"] == 2
        first, second = (set(r["ext_mems"]) for r in ent["fusion"]["regions"])
        assert sorted(calls) in ([len(first | second)],
                                 sorted([len(first), len(second)]))
        assert (delta["staged_tiles"], delta["stage_in_puts"]) == \
            (3 * NT * NT, len(calls))
        assert dev.evictions == 0 and dev.coh_stats()["hwm_bytes"] == \
            3 * NT * NT * TILE
        # C written back as before: the host copy's payload, a device array
        c = mats[2].data_of(0, 0)
        assert c.newest_copy() is c.get_copy(0)
        _unpinned(dev, mats)
    finally:
        ctx.fini()


def test_a_region_no_budget_can_hold_is_refused_at_bind():
    """A k-chain reads 13 tiles and writes one; a budget of 13 can never
    stage one. The pool is refused when it is bound, by name, before a tile
    is pinned, and the context owes it nothing: a wait returns at once."""
    ctx, dev = _context(13 * TILE)
    try:
        _a, _b, mats = _operands(42)
        tp = compile_ptg(ex06_gemm_ptg.SRC, "huge-gemm").instantiate(
            ctx, globals={"MT": NT, "NT": NT, "KT": NT},
            collections=dict(zip(("descA", "descB", "descC"), mats)))
        with pytest.raises(RuntimeError,
                           match=r"device region 0 \(GEMM\(0, 0, 0\) and 5 "
                                 r"more tasks\) reads 13 memory operands and "
                                 r"writes 1 back, 3584 bytes"):
            ctx.add_taskpool(tp)
        ctx.wait(timeout=5)
        assert dev._pinned_bytes == 0 and dev._resident_bytes == 0
    finally:
        ctx.fini()
