"""The per-task device path's spans (utils/xla_trace.py Spans) and the
Python histograms they record into (utils/hist.py PyHistograms): bucket
mirror, registry round trip, exact counts on a TPU-over-CPU context, the
ready-wait interval, and nothing at all when off; then the ``ptdev``
lane's sub-spans and a pool's account (ISSUE 37). Counts, and sums held
against each other: no test here holds a duration against a number."""

import os
import sys
import types

import numpy as np
import pytest

from parsec_tpu import native as native_mod
from parsec_tpu.core.context import Context
from parsec_tpu.data.matrix import TiledMatrix
from parsec_tpu.device.tpu import TPUDevice
from parsec_tpu.dsl.dtd import DTDTaskpool, RW
from parsec_tpu.utils import hist as H
from parsec_tpu.utils import mca
from parsec_tpu.utils import xla_trace as X

SPAN_KEYS = ("dtd.link_ns", "dtd.stall_ns", "tpudev.submit_ns",
             "tpudev.stage_in_ns", "tpudev.poll_ns", "tpudev.retire_ns",
             "tpudev.gather_ns", "tpudev.call_ns")
READY = "ptdtd.ready_wait_ns"
NTILES, NTASKS = 4, 40


# ------------------------------------------------------------ PyHistograms

@pytest.mark.parametrize("ns", [0, 1, 7, 8, 9, 1000, 10**6, 2**40])
def test_pyhist_buckets_mirror_bucket_index(ns):
    h = H.PyHistograms(("a_ns", "b_ns"))
    h.cell("a_ns").record(ns)
    count, total, raw = h.hist_snapshot()["a_ns"]
    buckets = H.decode_buckets(raw)
    assert (count, total) == (1, ns)
    assert buckets[H.bucket_index(ns)] == 1 and sum(buckets) == 1
    assert h.hist_snapshot()["b_ns"][0] == 0


def test_pyhist_blob_has_the_native_layout():
    mod = native_mod.load_ptexec()
    if mod is None:
        pytest.skip("native _ptexec unavailable")
    raw = H.PyHistograms(("x_ns",)).hist_snapshot()["x_ns"][2]
    assert len(raw) == mod.HIST_BUCKETS * 8 == H.NBUCKETS * 8


def test_pyhist_records_n_equal_observations():
    h = H.PyHistograms(("x_ns",))
    h.cell("x_ns").record(500, 4)
    count, total, raw = h.hist_snapshot()["x_ns"]
    assert (count, total) == (4, 2000)
    assert H.decode_buckets(raw)[H.bucket_index(500)] == 4


def test_pyhist_round_trips_through_the_registry():
    reg = H.NativeHistograms()
    h = H.PyHistograms(("submit_ns", "retire_ns"))
    assert reg.attach("tpudev", h) and reg.attach("tpudev", h)  # idempotent
    for ns in (100, 2000, 2000, 10**6):
        h.cell("submit_ns").record(ns)
    live = reg.snapshot()
    assert live["tpudev.submit_ns"]["count"] == 4
    assert live["tpudev.submit_ns"]["sum_ns"] == 100 + 4000 + 10**6
    assert live["tpudev.retire_ns"]["count"] == 0
    reg.detach(h)
    h.cell("submit_ns").record(5)           # after detach: not the registry's
    after = reg.snapshot()["tpudev.submit_ns"]
    assert after["count"] == 4              # folded, not lost
    s = reg.summaries(ttl=0)["tpudev.submit_ns"]
    assert s["count"] == 4
    lo = H.bucket_lo(H.bucket_index(2000))
    assert lo / 1e3 <= s["p50_us"] < (lo + H.bucket_width(
        H.bucket_index(2000))) / 1e3


def test_pyhist_concurrent_records_sum_exactly():
    """Two devices' managers, or two inserting threads, share a cell: no
    record may be lost (``+=`` is not atomic under the GIL)."""
    import os
    import sys
    import threading

    h = H.PyHistograms(("x_ns",))
    cell = h.cell("x_ns")
    nthreads, per = 2 * (os.cpu_count() or 4), 4000
    go = threading.Event()

    def work():
        go.wait(10)
        for i in range(per):
            cell.record(i)

    threads = [threading.Thread(target=work) for _ in range(nthreads)]
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for t in threads:
            t.start()
        go.set()
        for t in threads:
            t.join(60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    count, total, raw = h.hist_snapshot()["x_ns"]
    assert count == nthreads * per == sum(H.decode_buckets(raw))
    assert total == nthreads * per * (per - 1) // 2


def test_new_kinds_are_served_as_counters():
    from parsec_tpu.utils.counters import counters, install_native_counters
    install_native_counters()
    names = set(counters.snapshot())
    for kind, hists in (("tpudev", H.HIST_NAMES["tpudev"]),
                        ("dtd", H.HIST_NAMES["dtd"])):
        for name in hists:
            for stat in ("count", "p50_us", "p99_us"):
                assert f"{kind}.hist.{name}.{stat}" in names


# ------------------------------------------------- a pool with the spans on

def _counts():
    return {k: v["count"] for k, v in H.histograms.snapshot().items()}


def _delta(before):
    after = _counts()
    return {k: after[k] - before.get(k, 0) for k in after}


def _sums():
    return {k: v["sum_ns"] for k, v in H.histograms.snapshot().items()}


def _tpu_dev(ctx):
    devs = [d for d in ctx.devices.devices if isinstance(d, TPUDevice)]
    assert devs, "device module did not register over the host device"
    return devs[0]


def _chain_pool(ctx, name, ntasks=NTASKS, **kw):
    A = TiledMatrix(name, 16 * NTILES, 16, 16, 16)
    A.fill(lambda m, n: np.ones((16, 16), np.float32))
    tp = DTDTaskpool(ctx, name)

    def body(x):
        return x + 1.0

    for i in range(ntasks):
        tp.insert_task(body, (tp.tile_of(A, i % NTILES, 0), RW), **kw)
    return tp, A


@pytest.fixture()
def mca_params():
    """Set MCA parameters for one test; unset them all afterwards."""
    names = []

    def set_(name, value):
        names.append(name)
        mca.set(name, value)
    yield set_
    for name in names:
        mca.params.unset(name)


@pytest.fixture(scope="module")
def spanned_pool():
    """One 40-task pool over 4 tiles, window of 8, on a TPU-over-CPU
    context with ``hist_enabled``: what each histogram counted, live and
    after ``ctx.fini()``."""
    params = {"device_tpu_over_cpu": True, "hist_enabled": True,
              "dtd_window_size": 8, "dtd_threshold_size": 4}
    for k, v in params.items():
        mca.set(k, v)
    try:
        before, sums0 = _counts(), _sums()
        ctx = Context(nb_cores=1)
        dev = _tpu_dev(ctx)
        held = (ctx._spans, dev._spans)
        tp, A = _chain_pool(ctx, "spans")
        tp_spans = tp._spans
        tp.wait(); tp.close(); ctx.wait()
        sums1 = _sums()
        out = {"live": _delta(before), "stalls": tp.window_stalls,
               "sums": {k: sums1[k] - sums0.get(k, 0) for k in sums1},
               "native": tp._neng is not None, "executed": dev.executed_tasks,
               "held": held + (tp_spans,),
               "result": np.asarray(A.data_of(0, 0).newest_copy().payload)}
        ctx.fini()
        out["after_fini"] = _delta(before)
        return out
    finally:
        for k in params:
            mca.params.unset(k)


def test_spanned_pool_ran_on_the_device_path(spanned_pool):
    assert spanned_pool["executed"] == NTASKS and spanned_pool["native"]
    assert np.allclose(spanned_pool["result"], 1.0 + NTASKS // NTILES)
    ctx_sp, dev_sp, tp_sp = spanned_pool["held"]
    assert isinstance(ctx_sp, X.Spans) and dev_sp is ctx_sp is tp_sp
    assert spanned_pool["stalls"] >= 1


@pytest.mark.parametrize("key", SPAN_KEYS + (READY,))
def test_span_histograms_count_exactly_and_survive_fini(spanned_pool, key):
    want = {
        "dtd.link_ns": NTASKS,                  # one per insert
        "dtd.stall_ns": spanned_pool["stalls"],
        "tpudev.submit_ns": NTASKS,             # one per executed task
        "tpudev.stage_in_ns": NTILES,           # misses only: first touch
        "tpudev.retire_ns": NTASKS,
        "tpudev.gather_ns": NTASKS,             # the two halves of a submit,
        "tpudev.call_ns": NTASKS,               # a program's at its share
        READY: NTASKS,                          # one per executed task
    }
    live = spanned_pool["live"][key]
    if key == "tpudev.poll_ns":
        assert live >= 1        # one record per manager pass that polled
    else:
        assert live == want[key]
    assert spanned_pool["after_fini"][key] == live


def test_gather_and_call_are_inside_submit(spanned_pool):
    """``dev.gather`` then ``dev.call``, both inside ``dev.submit``: what
    the two recorded never passes what the whole did."""
    sums = spanned_pool["sums"]
    assert 0 < sums["tpudev.gather_ns"] and 0 < sums["tpudev.call_ns"]
    assert sums["tpudev.gather_ns"] + sums["tpudev.call_ns"] \
        <= sums["tpudev.submit_ns"]
    # a miss's dev.stage_in is inside the gather that asked for it
    assert sums["tpudev.stage_in_ns"] <= sums["tpudev.gather_ns"]


def test_batched_dispatch_counts_once_per_member(mca_params):
    """One multi-task program records ``submit_ns`` and the ready-wait once
    per member (the manager lock is held during enqueue so the eight are
    pending together, as in test_device_async)."""
    mca_params("device_tpu_over_cpu", True)
    mca_params("hist_enabled", True)
    ctx = Context(nb_cores=1)
    try:
        dev = _tpu_dev(ctx)
        before = _counts()
        A = TiledMatrix("SB", 16 * 8, 16, 16, 16)
        A.fill(lambda m, n: np.full((16, 16), float(m), np.float32))
        tp = DTDTaskpool(ctx, "spans-batch")

        def scale(x):
            return x * 3.0

        for m in range(8):
            tp.insert_task(scale, (tp.tile_of(A, m, 0), RW), batch=True)
        with dev._manager_lock:
            ctx._progress_loop(ctx.streams[0],
                               until=lambda: len(dev._pending) == 8,
                               timeout=10)
        tp.wait(); tp.close(); ctx.wait()
        assert dev.batched_dispatches >= 1
        d = _delta(before)
        assert d["tpudev.submit_ns"] == d["tpudev.retire_ns"] == d[READY] == 8
        assert d["tpudev.gather_ns"] == d["tpudev.call_ns"] == 8
    finally:
        ctx.fini()


@pytest.mark.parametrize("fails_in", ["gather", "call"])
def test_a_group_that_falls_back_records_only_its_singles(
        mca_params, monkeypatch, fails_in):
    """A group whose gather or whose program fails is issued again a task
    at a time: the group's own attempt records under none of the three
    names (as ``dev.submit`` never did), each single under all three."""
    mca_params("device_tpu_over_cpu", True)
    mca_params("hist_enabled", True)
    ctx = Context(nb_cores=1)
    try:
        dev = _tpu_dev(ctx)
        before = _counts()
        A = TiledMatrix("SF", 16 * 4, 16, 16, 16)
        A.fill(lambda m, n: np.full((16, 16), float(m), np.float32))
        tp = DTDTaskpool(ctx, f"spans-fallback-{fails_in}")
        if fails_in == "call":
            def no_room(device, tasks, inputs_list):
                raise RuntimeError("RESOURCE_EXHAUSTED: injected")
            monkeypatch.setattr(tp, "_tpu_batch_submit", no_room)
        else:
            gather, calls = dev._gather_inputs, [0]

            def flaky(gt):
                calls[0] += 1
                if calls[0] == 3:   # the group's third member
                    raise RuntimeError("RESOURCE_EXHAUSTED: injected")
                return gather(gt)
            monkeypatch.setattr(dev, "_gather_inputs", flaky)

        def scale(x):
            return x * 3.0

        for m in range(4):
            tp.insert_task(scale, (tp.tile_of(A, m, 0), RW), batch=True)
        with dev._manager_lock:
            ctx._progress_loop(ctx.streams[0],
                               until=lambda: len(dev._pending) == 4,
                               timeout=10)
        tp.wait(); tp.close(); ctx.wait()
        assert dev.batched_dispatches == 0 and dev.executed_tasks == 4
        d = _delta(before)
        assert d["tpudev.submit_ns"] == d["tpudev.gather_ns"] \
            == d["tpudev.call_ns"] == d["tpudev.retire_ns"] == 4
        assert d["tpudev.group_tasks"] == 0
    finally:
        ctx.fini()


def test_oom_bounce_records_ready_wait_once(mca_params, monkeypatch):
    """Both attempts of one task's first submit fail with an OOM, the task
    goes back through ``Context.schedule`` and is submitted again: one
    ready-wait record per executed task, from the first stamp."""
    mca_params("device_tpu_over_cpu", True)
    mca_params("hist_enabled", True)
    ctx = Context(nb_cores=1)
    try:
        dev = _tpu_dev(ctx)
        before = _counts()
        gather, failures, stamps = dev._gather_inputs, [2], []

        def flaky(gt):
            stamps.append((gt.task, gt.task.prof_info))
            if failures[0]:
                failures[0] -= 1
                raise RuntimeError("RESOURCE_EXHAUSTED: injected")
            return gather(gt)

        monkeypatch.setattr(dev, "_gather_inputs", flaky)
        monkeypatch.setattr(dev, "evict_bytes", lambda nbytes: 1)
        tp, _A = _chain_pool(ctx, "spans-oom", ntasks=6)
        tp.wait(); tp.close(); ctx.wait()
        assert failures == [0] and dev.executed_tasks == 6
        d = _delta(before)
        assert d[READY] == 6 and d["tpudev.retire_ns"] == 6
        assert d["tpudev.submit_ns"] == 6 + 2   # a failed attempt's cost too
        # both failed in their gather: no call was entered for them
        assert d["tpudev.gather_ns"] == 6 + 2 and d["tpudev.call_ns"] == 6
        # the bounced task kept its first stamp through the re-schedule
        bounced = [stamp for task, stamp in stamps if task is stamps[0][0]]
        assert len(bounced) == 3 and bounced[0] is not None
        assert bounced[0] == bounced[1] == bounced[2]
    finally:
        ctx.fini()


# ------------------------------------------------------------------- off

def test_everything_off_holds_no_span_object(mca_params):
    mca_params("device_tpu_over_cpu", True)
    ctx = Context(nb_cores=1)
    try:
        assert ctx.metrics is None and not ctx._hist_on
        before = set(H.histograms.snapshot())
        counts = _counts()
        tp, _A = _chain_pool(ctx, "spans-off", ntasks=8)
        assert ctx._spans is None and tp._spans is None
        assert _tpu_dev(ctx)._spans is None
        tp.wait(); tp.close(); ctx.wait()
        assert set(H.histograms.snapshot()) == before
        assert all(v == 0 for v in _delta(counts).values())
    finally:
        ctx.fini()


def test_profile_xla_dir_alone_arms_the_spans_not_the_registry(
        mca_params, tmp_path):
    """The spans are for the timeline too: with ``profile_xla_dir`` set and
    the histograms off they annotate, and the registry gains nothing."""
    mca_params("device_tpu_over_cpu", True)
    mca_params("profile_xla_dir", str(tmp_path))
    ctx = Context(nb_cores=1)
    try:
        assert not ctx._hist_on and isinstance(ctx._spans, X.Spans)
        counts = _counts()
        tp, _A = _chain_pool(ctx, "spans-xla", ntasks=8)
        tp.wait(); tp.close(); ctx.wait()
        tpudev = dict(ctx._spans.hists)["tpudev"]
        assert tpudev.hist_snapshot()["submit_ns"][0] == 8
        assert all(v == 0 for v in _delta(counts).values())
    finally:
        ctx.fini()


# ------------------------------------- the ptdev lane's spans and accounts

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "examples"))

PATHS = {"regions": ("region_fusion_max", 16, 4),   # packs of four k-chains
         "per-task": ("region_fusion", False, 2)}   # a program a task
SIX = ("push_ns", "call_ns", "own_ns", "poll_ns", "retire_ns", "away_ns")


@pytest.fixture(scope="module")
def lane_pools():
    """``ex06``'s GEMM through ``ptexec`` + ``ptdev`` on a TPU-over-CPU
    context with the spans on, once as fused regions and once a program a
    task: per pool the accounts it filed, what the lane's counters say of
    it, and what each ``dispatch`` callback added to the ``ptdev``
    histograms (count, sum); then how many accounts ``ctx.fini()`` left."""
    if native_mod.load_ptexec() is None or native_mod.load_ptdev() is None:
        pytest.skip("native _ptexec/_ptdev unavailable")
    import ex06_gemm_ptg
    from parsec_tpu.device import lane_pool
    from parsec_tpu.device.native import PTDEV_STATS
    from parsec_tpu.dsl.ptg.compiler import compile_ptg

    kept = list(X.POOL_ACCOUNTS)
    X.POOL_ACCOUNTS.clear()
    make, patch = lane_pool._closures, pytest.MonkeyPatch()
    callbacks = []

    def spied(devlane, *args):
        dispatch, poll, drop, held = make(devlane, *args)
        ptdev = dict(devlane.ctx._spans.hists)["ptdev"]

        def spy_dispatch(ids):
            h0 = ptdev.hist_snapshot()
            try:
                return dispatch(ids)
            finally:
                h1 = ptdev.hist_snapshot()
                callbacks.append((len(ids), {
                    k: (h1[k][0] - h0[k][0], h1[k][1] - h0[k][1])
                    for k in h1}))
        return spy_dispatch, poll, drop, held
    patch.setattr(lane_pool, "_closures", spied)
    params = {"device_tpu_over_cpu": True, "hist_enabled": True}
    for k, v in params.items():
        mca.set(k, v)
    out = {}
    try:
        ctx = Context(nb_cores=1)
        for path, (knob, value, nt) in PATHS.items():
            rng = np.random.default_rng(37)
            mats = []
            for name in "ABC":
                M = TiledMatrix(f"la{name}{nt}", 16 * nt, 16 * nt, 16, 16)
                M.fill(lambda m, n: rng.integers(-2, 3, (16, 16)).astype(
                    np.float32))
                mats.append(M)
            mca.set(knob, value)
            try:
                del callbacks[:]
                n0, d0, s0 = len(X.POOL_ACCOUNTS), PTDEV_STATS.snapshot(), \
                    H.histograms.snapshot()
                tp = compile_ptg(ex06_gemm_ptg.SRC, f"la-{path}").instantiate(
                    ctx, globals={"MT": nt, "NT": nt, "KT": nt},
                    collections=dict(zip(("descA", "descB", "descC"), mats)))
                ctx.add_taskpool(tp)
                ctx.wait(timeout=120)
                assert tp.completed and ctx._ptdev.failed() is None
                s1 = H.histograms.snapshot()
                out[path] = {
                    "accounts": list(X.POOL_ACCOUNTS)[n0:],
                    "stats": PTDEV_STATS.delta(d0),
                    "callbacks": list(callbacks),
                    "sums": {k: s1[k]["sum_ns"] - s0.get(k, {"sum_ns": 0})[
                        "sum_ns"] for k in s1 if k.startswith("ptdev.")}}
            finally:
                mca.params.unset(knob)
        patch.undo()
        live = len(X.POOL_ACCOUNTS)
        ctx.fini()
        out["fini"] = (live, len(X.POOL_ACCOUNTS))
        yield out
    finally:
        patch.undo()
        for k in params:
            mca.params.unset(k)
        X.POOL_ACCOUNTS.clear()
        X.POOL_ACCOUNTS.extend(kept)


@pytest.mark.parametrize("path", list(PATHS))
def test_ptdev_sub_spans_record_once_per_their_unit(lane_pools, path):
    """``ptdev.push``: one record a program that pushes, the first of a
    ``dispatch`` callback always (it holds the round's admission too), so
    one to one a program, holding at least what the callback's stage-in
    misses recorded; ``ptdev.call``: one a device program, like
    ``ptdev.dispatch``."""
    got = lane_pools[path]
    assert got["callbacks"] and got["stats"]["programs"] >= 4
    for n_ids, d in got["callbacks"]:
        assert 1 <= d["push_ns"][0] <= n_ids
        assert d["call_ns"][0] == d["dispatch_ns"][0] == n_ids
        assert d["push_ns"][1] >= d["stage_in_ns"][1]
    assert sum(n for n, _d in got["callbacks"]) == got["stats"]["programs"]
    # every tile was a miss once, inside some callback's push phase
    assert sum(d["stage_in_ns"][0] for _n, d in got["callbacks"]) > 0


@pytest.mark.parametrize("path", list(PATHS))
def test_a_pool_files_one_account_at_its_end(lane_pools, path):
    """Filed once (at the pool's end: ``drop()`` at the unbind that follows
    files no second one), its six parts add up to its life, ``own`` is
    what ``ptdev.dispatch`` held beside ``push`` and ``call``, and its
    counts are the pool's."""
    got = lane_pools[path]
    (acct,) = got["accounts"]
    assert tuple(acct) == X.POOL_ACCOUNT_FIELDS
    assert all(isinstance(v, int) and v >= 0 for v in acct.values()), acct
    assert sum(acct[k] for k in SIX) == acct["life_ns"]
    assert 0 < acct["head_ns"] <= acct["life_ns"]
    sums, programs = got["sums"], got["stats"]["programs"]
    assert acct["push_ns"] == sums["ptdev.push_ns"]
    assert acct["call_ns"] == sums["ptdev.call_ns"]
    assert acct["retire_ns"] == sums["ptdev.retire_ns"]
    # ptdev.dispatch_ns holds a callback's span as one floor share a program
    dispatch = acct["push_ns"] + acct["call_ns"] + acct["own_ns"]
    assert 0 <= dispatch - sums["ptdev.dispatch_ns"] < programs
    assert acct["programs"] == programs
    assert acct["tasks"] == got["stats"]["tasks_engaged"]
    assert acct["callbacks"] == len(got["callbacks"])
    assert acct["passes"] >= 1


def _bare_closures(spans):
    """The closures of a pool nobody will run, over a lane that is only
    where ``_closures`` looks for the device and the spans."""
    from parsec_tpu.device import lane_pool
    devlane = types.SimpleNamespace(
        device=None, ctx=types.SimpleNamespace(_spans=spans))
    return lane_pool._closures(
        devlane, None, [0], [[()]], [0], [-1], [1], [0], [None], [()],
        ["bare"], [None], [], {}, None, 0, None, None, 1)


@pytest.mark.parametrize("spans", [True, False], ids=["on", "off"])
def test_a_dropped_pool_files_its_account_only_with_the_spans_on(spans):
    """``drop()`` ends a pool that did not reach its end: one account, its
    whole life away from the pool's callbacks and before any call. With
    the spans off ``_closures`` hands back its plain functions and nothing
    is ever filed."""
    kept = list(X.POOL_ACCOUNTS)
    try:
        dispatch, poll, drop, _held = _bare_closures(
            X.Spans() if spans else None)
        names = [f.__name__ for f in (dispatch, poll, drop)]
        if not spans:
            assert names == ["dispatch", "poll", "drop"]
            drop()
            assert list(X.POOL_ACCOUNTS) == kept
            return
        assert names == ["traced_dispatch", "traced_poll", "traced_drop"]
        assert poll() == [] and list(X.POOL_ACCOUNTS) == kept
        drop()
        drop()                              # filed once
        acct = X.POOL_ACCOUNTS[-1]
        assert len(X.POOL_ACCOUNTS) == min(len(kept) + 1, 64)
        assert acct["programs"] == acct["tasks"] == acct["callbacks"] == 0
        assert acct["passes"] == 1 and acct["head_ns"] == acct["life_ns"]
        assert acct["push_ns"] == acct["call_ns"] == acct["own_ns"] == 0
        assert acct["away_ns"] + acct["poll_ns"] == acct["life_ns"]
    finally:
        X.POOL_ACCOUNTS.clear()
        X.POOL_ACCOUNTS.extend(kept)


def test_the_accounts_are_bounded_at_64_and_outlive_the_context(lane_pools):
    live, after_fini = lane_pools["fini"]
    assert live == after_fini == 2
    kept = list(X.POOL_ACCOUNTS)
    try:
        for i in range(70):
            X.file_pool_account(
                10, 20, 100 + i, dispatch_ns=30, push_ns=10, call_ns=15,
                poll_ns=5, retire_ns=2, programs=1, callbacks=1, passes=3,
                tasks=4)
        assert X.POOL_ACCOUNTS.maxlen == len(X.POOL_ACCOUNTS) == 64
        assert X.POOL_ACCOUNTS[-1] == {
            "life_ns": 159, "head_ns": 10, "push_ns": 10, "room_ns": 0,
            "call_ns": 15, "own_ns": 5, "poll_ns": 5, "retire_ns": 2,
            "away_ns": 122, "programs": 1, "callbacks": 1, "passes": 3,
            "tasks": 4}
        assert X.POOL_ACCOUNTS[0]["life_ns"] == 96     # the oldest six left
    finally:
        X.POOL_ACCOUNTS.clear()
        X.POOL_ACCOUNTS.extend(kept)


def test_the_newest_account_is_served_by_the_registry():
    """``/metrics`` serves the registry: ``ptdev.pool.<field>`` is the
    newest account's field, 0 while there is none."""
    from parsec_tpu.utils.counters import counters, install_native_counters
    install_native_counters()
    kept = list(X.POOL_ACCOUNTS)
    try:
        X.POOL_ACCOUNTS.clear()
        assert all(counters.read(f"ptdev.pool.{k}") == 0
                   for k in X.POOL_ACCOUNT_FIELDS)
        for end in (1000, 2000):
            acct = X.file_pool_account(
                100, 300, end, dispatch_ns=400, push_ns=150, call_ns=200,
                poll_ns=60, retire_ns=40, programs=3, callbacks=2, passes=9,
                tasks=96)
        snap = counters.snapshot()
        assert {k: snap[f"ptdev.pool.{k}"]
                for k in X.POOL_ACCOUNT_FIELDS} == acct
        assert acct["life_ns"] == 1900 and acct["head_ns"] == 200
    finally:
        X.POOL_ACCOUNTS.clear()
        X.POOL_ACCOUNTS.extend(kept)
