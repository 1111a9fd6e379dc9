"""The per-task device path's spans (utils/xla_trace.py Spans) and the
Python histograms they record into (utils/hist.py PyHistograms): bucket
mirror, registry round trip, exact counts on a TPU-over-CPU context, the
ready-wait interval, and nothing at all when off. Counts only: no test
here reads a clock."""

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
             "tpudev.stage_in_ns", "tpudev.poll_ns", "tpudev.retire_ns")
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
        before = _counts()
        ctx = Context(nb_cores=1)
        dev = _tpu_dev(ctx)
        held = (ctx._spans, dev._spans)
        tp, A = _chain_pool(ctx, "spans")
        tp_spans = tp._spans
        tp.wait(); tp.close(); ctx.wait()
        out = {"live": _delta(before), "stalls": tp.window_stalls,
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
        READY: NTASKS,                          # one per executed task
    }
    live = spanned_pool["live"][key]
    if key == "tpudev.poll_ns":
        assert live >= 1        # one record per manager pass that polled
    else:
        assert live == want[key]
    assert spanned_pool["after_fini"][key] == live


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
