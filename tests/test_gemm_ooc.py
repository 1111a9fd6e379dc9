"""The tiled GEMM over a budget that cannot hold its three matrices (the
CPU twin of the benchmark's ``gemm_ooc.ts2048``): the residency layer has to
evict, write dirty C tiles back, and stage them in again at the version that
was written, while C accumulates over three solves.

Walked with two DTD windows: one that holds the whole pool (every chain head
is ready at once) and one of three k-chains (the inserter streams), so both
orders in which tiles can leave are covered.
"""

import numpy as np
import pytest

from parsec_tpu.core.context import Context
from parsec_tpu.data.matrix import TwoDimBlockCyclic
from parsec_tpu.device.tpu import TPUDevice
from parsec_tpu.dsl.dtd import DTDTaskpool
from parsec_tpu.ops.gemm import gemm_reference, insert_gemm_tasks
from parsec_tpu.utils import mca

NT, SOLVES = 6, 3


@pytest.fixture(params=[(8, 2048), (8, 18), (16, 18)],
                ids=["ts8-whole-pool", "ts8-three-chains", "ts16-three-chains"])
def ooc(request):
    """(context, device, tile size) under a budget of two thirds of the
    three matrices and the parametrised window."""
    ts, window = request.param
    budget = 2 * (3 * NT * NT * ts * ts * 4) // 3
    mca.set("device_tpu_over_cpu", True)
    mca.set("device_tpu_max_bytes", budget)
    mca.set("dtd_window_size", window)
    ctx = Context(nb_cores=1)
    dev = next(d for d in ctx.devices.devices if isinstance(d, TPUDevice))
    assert dev._budget == budget
    yield ctx, dev, ts
    ctx.fini()
    for name in ("device_tpu_over_cpu", "device_tpu_max_bytes",
                 "dtd_window_size"):
        mca.params.unset(name)


def _spy(dev, monkeypatch, c_keys):
    """Watch every eviction and every install: what left dirty and at which
    version, what came back and at which, and the budget at each step."""
    seen = {"dirty": 0, "ahead": 0, "restaged": 0, "over_budget": [],
            "wrong": []}
    written = {}                        # key -> version its write-back carried
    evict, install = dev._evict_key_locked, dev._install

    def spy_evict(key, copy, drop_table):
        data = copy.original
        host = data.get_copy(0)
        dirty = data.newest_copy() is copy and \
            (host is None or host.version < copy.version)
        version = copy.version
        # its D2H was begun while it was still in line (_fetch_ahead_locked)
        ahead = dev._fetching.get(key) == id(copy.payload)
        evict(key, copy, drop_table)
        if dirty:
            seen["dirty"] += 1
            seen["ahead"] += ahead
            written[key] = version
            host = data.get_copy(0)
            if not isinstance(host.payload, np.ndarray) \
                    or host.version != version or copy.payload is not None:
                seen["wrong"].append(("write-back", key, version, host))
        if dev._resident_bytes > dev._budget:
            seen["over_budget"].append(dev._resident_bytes)

    def spy_install(data, copy, arr, version, pin, moved):
        key = dev.res_key(data)
        if key in written:
            seen["restaged"] += 1
            if not moved or version != written.pop(key) \
                    or version != data.version:
                seen["wrong"].append(("re-stage", key, version, data))
        elif key in c_keys and moved and version != c_keys[key]:
            # a C tile staged in from the host past its first version can
            # only be a written-back one
            seen["wrong"].append(("unwritten", key, version, data))
        return install(data, copy, arr, version, pin, moved)

    monkeypatch.setattr(dev, "_evict_key_locked", spy_evict)
    monkeypatch.setattr(dev, "_install", spy_install)
    return seen, written


def test_gemm_over_budget_evicts_writes_back_and_restages(ooc, monkeypatch):
    ctx, dev, ts = ooc
    n, tile = NT * ts, ts * ts * 4
    rng = np.random.default_rng(35)
    a, b = (rng.standard_normal((n, n)).astype(np.float32) for _ in range(2))
    A, B, C = (TwoDimBlockCyclic(name, n, n, ts, ts) for name in "ABC")
    A.fill(lambda m, k: a[m*ts:(m+1)*ts, k*ts:(k+1)*ts])
    B.fill(lambda k, j: b[k*ts:(k+1)*ts, j*ts:(j+1)*ts])
    C.fill(lambda m, j: np.zeros((ts, ts), np.float32))
    c_data = [C.data_of(m, j) for m in range(NT) for j in range(NT)]
    version0 = c_data[0].version
    seen, written = _spy(dev, monkeypatch,
                         {dev.res_key(d): d.version for d in c_data})

    for solve in range(1, SOLVES + 1):
        tp = DTDTaskpool(ctx, f"ooc-{solve}")
        assert insert_gemm_tasks(tp, A, B, C) == NT ** 3
        assert tp.wait(timeout=60)
        tp.close()
        ctx.wait(timeout=60)
        # a C tile's version counts its chain's writes, wherever it is
        assert {d.version for d in c_data} == {version0 + NT * solve}

    np.testing.assert_allclose(C.to_dense(),
                               np.asarray(gemm_reference(a, b, 0, SOLVES)),
                               rtol=1e-4, atol=1e-3)
    assert not seen["wrong"], seen["wrong"][:3]
    assert not seen["over_budget"], seen["over_budget"][:3]
    assert dev.evictions > 0 and seen["dirty"] > 0
    assert dev.transfer_out_bytes == tile * seen["dirty"] > 0
    assert dev.owned_evictions == seen["dirty"]
    assert seen["restaged"] > 0
    # most write-backs were under way before their eviction, and the marks
    # of those that left are gone
    assert seen["ahead"] > seen["dirty"] // 2
    assert set(dev._fetching) <= set(dev._lru)
    # what was written back and not staged in again is on the host, newest
    for d in c_data:
        key = dev.res_key(d)
        if key in written:
            host = d.newest_copy()
            assert host.device_index == 0 and host.version == written[key]
    coh = dev.coh_stats()
    if coh is not None:
        assert coh["hwm_bytes"] <= dev._budget
        assert coh["stage_out_bytes"] == dev.transfer_out_bytes
    for M in (A, B, C):
        for m in range(NT):
            for j in range(NT):
                d = M.data_of(m, j)
                assert all(c.readers == 0 for c in d.copies.values())
                st = dev._ncoh.state(dev.res_key(d)) if dev._ncoh else None
                assert st is None or st[3] == 0, st
    stats = ctx.devices.statistics()
    assert dev.executed_tasks == SOLVES * NT ** 3
    assert sum(s["executed_tasks"] for name, s in stats.items()
               if name != dev.name) == 0
