"""Cross-rank serving fabric (ptfab, ISSUE 11) tests.

Four layers, mirroring how the fabric is built:

* **wire protocol units** — two ``_ptcomm.Comm`` objects joined by a
  socketpair, pumped synchronously: the K_CRED frame codec (grants,
  returns, reclaim idempotence, wire counters, EV_FAB trace points) and
  the ptsched remote-window/set_weight entries;
* **fabric harness** — in-process ServingFabric pairs driven by
  :meth:`step`: replenishment from retire-driven headroom,
  ``AdmissionBackpressure`` nowait -> retry semantics, credit reclaim on
  peer death WITHOUT a hang or a leaked window (the satellite), and
  headroom-aware gateway routing across a 3-rank mesh;
* **2-OS-rank legs** — the acceptance program
  (:mod:`parsec_tpu.serving.harness`): antagonist flood vs victim p99,
  cross-rank share reconciliation, real-process peer death;
* **observability** — ptfab.* counters through the unified registry.

Program functions live in ``parsec_tpu.serving.harness`` so
multiprocessing spawn can import them (the test_tcp_distributed.py
pattern, shared with the ci gate and bench keys).
"""

import functools
import socket
import struct
import time

import numpy as np
import pytest

from parsec_tpu import native as native_mod
from parsec_tpu.comm.tcp import run_distributed_procs

_ptcomm = native_mod.load_ptcomm()
_ptsched = native_mod.load_ptsched()

pytestmark = pytest.mark.skipif(
    _ptcomm is None or _ptsched is None,
    reason="native extensions unavailable")

POOL, TEN = 4242, 7


def _pair():
    a, b = socket.socketpair()
    c0 = _ptcomm.Comm(0, 2)
    c1 = _ptcomm.Comm(1, 2)
    c0.add_peer_fd(1, a.fileno())
    c1.add_peer_fd(0, b.fileno())
    return c0, c1, a, b


def _pump(*comms, iters=3):
    for _ in range(iters):
        for c in comms:
            c.pump(2)


# ----------------------------------------------------------- wire protocol

def test_cred_grant_take_return_roundtrip():
    c0, c1, a, b = _pair()
    c0.cred_grant(1, POOL, TEN, 16)
    assert c0.cred_outstanding(1, POOL, TEN) == 16
    _pump(c0, c1)
    assert c1.cred_avail(0, POOL, TEN) == 16
    # spends are LOCAL: no new frames cross the wire
    frames_before = c0.stats()["cred_frames_tx"]
    assert c1.cred_take(0, POOL, TEN, 10)
    assert not c1.cred_take(0, POOL, TEN, 10)     # balance 6 < 10
    assert c1.cred_take(0, POOL, TEN)             # default n=1
    _pump(c0, c1)
    assert c0.stats()["cred_frames_tx"] == frames_before
    # return the remainder; the granting side's ledger shrinks
    assert c1.cred_return(0, POOL, TEN, 100) == 5
    _pump(c1, c0)
    assert c0.cred_outstanding(1, POOL, TEN) == 11   # 16 - 5 returned
    s0, s1 = c0.stats(), c1.stats()
    assert s0["creds_granted_tx"] == 16 and s1["creds_granted_rx"] == 16
    assert s1["creds_spent"] == 11
    assert s1["creds_returned_tx"] == 5 and s0["creds_returned_rx"] == 5
    assert s0["frame_errors"] == s1["frame_errors"] == 0
    c0.stop(); c1.stop(); a.close(); b.close()


def test_cred_reclaim_idempotent_and_consume_floor():
    c0, c1, a, b = _pair()
    c0.cred_grant(1, POOL, TEN, 8)
    c0.cred_grant(1, POOL + 1, TEN, 4)
    _pump(c0, c1)
    # an arrival consumes from the outstanding ledger, flooring at 0
    assert c0.cred_consume(1, POOL, TEN, 3) == 3
    assert c0.cred_consume(1, POOL, TEN, 100) == 5
    assert c0.cred_consume(1, POOL, TEN, 1) == 0
    rec, dropped = c0.cred_reclaim(1)
    assert sorted(rec) == [(POOL + 1, TEN, 4)]
    assert dropped == 0
    assert c0.cred_reclaim(1) == ([], 0)          # idempotent
    # the inserter side drops its unspendable balance on ITS reclaim
    assert c1.cred_take(0, POOL, TEN, 2)
    rec1, dropped1 = c1.cred_reclaim(0)
    assert rec1 == [] and dropped1 == 6 + 4       # 8-2 spent + 4
    assert c1.cred_avail(0, POOL, TEN) == 0
    c0.stop(); c1.stop(); a.close(); b.close()


def test_cred_frame_traced_and_malformed_contained():
    """EV_FAB points record on both ends; a malformed K_CRED (nonzero
    body / zero count) is counted and contained."""
    c0, c1, a, b = _pair()
    c0.trace_enable(4096)
    c1.trace_enable(4096)
    c0.cred_grant(1, POOL, TEN, 3)
    _pump(c0, c1)
    c1.cred_return(0, POOL, TEN, 1)
    _pump(c1, c0)

    def _keys(comm):
        evs = []
        for _ring, blob in comm.trace_drain():
            for off in range(0, len(blob), 24):
                t, i, k, f = struct.unpack_from("<qqII", blob, off)
                evs.append((k, i))
        return evs

    ev0, ev1 = _keys(c0), _keys(c1)
    assert (_ptcomm.EV_FAB_CRED_TX, 3) in ev0      # grant out
    assert (_ptcomm.EV_FAB_CRED_RX, 3) in ev1      # grant in
    assert (_ptcomm.EV_FAB_CRED_TX, -1) in ev1     # return out (negative)
    assert (_ptcomm.EV_FAB_CRED_RX, -1) in ev0
    # malformed: a K_CRED with a body / a zero count
    hdr = struct.Struct("<IBBHIIQ")
    a.sendall(hdr.pack(0, 1, 0, 0, 0, 0, 0x7074636F6D6D0001))  # hello
    a.sendall(hdr.pack(4, 8, 0, 0, POOL, TEN, 5) + b"oops")
    a.sendall(hdr.pack(0, 8, 0, 0, POOL, TEN, 0))
    time.sleep(0.05)
    c1.pump(4)
    s1 = c1.stats()
    assert s1["frame_errors"] == 2
    assert c1.cred_avail(0, POOL, TEN) == 2        # 3 - 1 returned, no junk
    c0.stop(); c1.stop(); a.close(); b.close()


# --------------------------------------------------- plane remote windows

def test_plane_remote_window_shares_budget():
    ps = _ptsched
    pl = ps.Plane(nworkers=1)
    h = pl.register_pool(ext_id=1, kind=ps.KIND_EXT, window=10)
    assert pl.headroom(h) == 10
    pl.admit(h, 4)
    pl.remote_grant(h, 3)
    assert pl.headroom(h) == 3
    assert not pl.over_window(h)
    pl.remote_grant(h, 4)                 # 4 + 7 > 10
    assert pl.over_window(h) and pl.headroom(h) == 0
    pl.remote_release(h, 100)             # floors at 0, never negative
    assert pl.remote_granted(h) == 0 and pl.headroom(h) == 6
    assert pl.pool_stats(h)["remote_granted"] == 0
    hu = pl.register_pool(ext_id=2, kind=ps.KIND_EXT)
    assert pl.headroom(hu) == -1          # unlimited sentinel


def test_plane_set_weight_binds_on_next_round():
    ps = _ptsched
    pl = ps.Plane(nworkers=1, policy=ps.POLICY_WDRR, quantum=64)
    a = pl.register_pool(ext_id=1, kind=ps.KIND_EXT, weight=1)
    b = pl.register_pool(ext_id=2, kind=ps.KIND_EXT, weight=1)
    pl.set_weight(a, 3)
    assert pl.pool_stats(a)["weight"] == 3
    assert pl.stats()["weight_adjusts"] == 1
    served = {a: 0, b: 0}
    nxt = {a: 0, b: 0}
    for h in (a, b):
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
    assert abs(ratio - 3.0) / 3.0 < 0.25, (served, ratio)


# ------------------------------------------------------- fabric harness

def _mk_fabrics(nranks=2, windows=None, weight=1):
    """nranks in-process fabrics joined by socketpair meshes, each with
    its own SchedPlane; fabric i serves tenant 'T' iff windows[i] is
    not None. Returns (fabrics, comms, socks)."""
    from parsec_tpu.core.sched_plane import SchedPlane
    from parsec_tpu.serving import ServingFabric
    comms = [_ptcomm.Comm(r, nranks) for r in range(nranks)]
    socks = []
    for i in range(nranks):
        for j in range(i + 1, nranks):
            a, b = socket.socketpair()
            comms[i].add_peer_fd(j, a.fileno())
            comms[j].add_peer_fd(i, b.fileno())
            socks += [a, b]
    fabs = []
    for r in range(nranks):
        sp = SchedPlane(_ptsched, 1, "wdrr")
        fab = ServingFabric(comms[r], sp, r, nranks, replenish=False)
        fabs.append(fab)
    for r, fab in enumerate(fabs):
        fab.insert_transport = functools.partial(
            lambda dst, hdr, payload, _src: fabs[dst].on_fab(
                _src, hdr, payload), _src=r)
        w = windows[r] if windows else None
        if w is not None:
            fab.serve("T", handler=lambda p, src: None, window=w,
                      weight=weight)
    return fabs, comms, socks


def _step_all(fabs, comms, rounds=3):
    for _ in range(rounds):
        for fab in fabs:
            fab.step()
        _pump(*comms)


def test_fabric_nowait_reject_then_retry_succeeds():
    """The satellite's nowait -> retry contract end to end: exhaust the
    remote balance, see AdmissionBackpressure + the reject counter, let
    the target retire work (headroom reopens, replenishment grants),
    then the SAME nowait acquire succeeds."""
    from parsec_tpu.dsl.dtd import AdmissionBackpressure
    from parsec_tpu.serving.fabric import FAB_STATS
    fabs, comms, socks = _mk_fabrics(2, windows=[8, None])
    f0, f1 = fabs
    try:
        _step_all(fabs, comms)
        t = f0.tenant("T")
        line = f1.avail(0, "T")
        assert line > 0
        for _ in range(line):             # drain the whole balance
            f1.acquire(0, "T", nowait=True)
        before = FAB_STATS.snapshot()
        with pytest.raises(AdmissionBackpressure):
            f1.acquire(0, "T", nowait=True)
        assert FAB_STATS.delta(before)["remote_rejects"] == 1
        # simulate the spends arriving + completing at the target: the
        # window reopens, the replenisher re-grants, the retry succeeds
        for _ in range(line):
            f0.on_fab(1, {"k": "insert", "t": "T"}, None)
        f0.done("T", line)
        _step_all(fabs, comms)
        assert f1.avail(0, "T") > 0
        f1.acquire(0, "T", nowait=True)   # the retry
        # zero hot-path round trips: spends outnumber credit frames
        s1 = comms[1].stats()
        assert s1["creds_spent"] > s1["cred_frames_rx"] > 0
    finally:
        for f in fabs:
            f.fini()
        for c in comms:
            c.stop()
        for s in socks:
            s.close()


def test_fabric_peer_death_reclaims_without_hang_or_leak():
    """The satellite: the target dies mid-window. Inserter side — the
    balance is dropped and a BLOCKING acquire raises promptly (no hang).
    Target side (symmetric death) — outstanding grants release their
    window reservation (no leaked window: headroom returns to full)."""
    fabs, comms, socks = _mk_fabrics(2, windows=[16, None])
    f0, f1 = fabs
    try:
        _step_all(fabs, comms)
        t = f0.tenant("T")
        assert f1.avail(0, "T") > 0
        granted = f0.plane.plane.remote_granted(t.handle)
        assert granted > 0
        # kill the link from under both ends (the mid-window death):
        # shutdown, not close — the Comm holds a dup of the fd, and only
        # shutdown() tears the CONNECTION down across every dup
        for s in socks:
            s.shutdown(socket.SHUT_RDWR)
            s.close()
        _pump(*comms)                      # EOF -> broken peer
        assert 1 in comms[0].stats()["broken_peers"]
        # inserter: blocking acquire must RAISE once death is seen
        t0 = time.monotonic()
        with pytest.raises(RuntimeError):
            f1.acquire(0, "T", n=10**6, timeout=30)
        assert time.monotonic() - t0 < 5, "acquire hung on a dead peer"
        assert f1.avail(0, "T") == 0
        # target: reclaim releases the reservation — no leaked window
        f0.step()
        assert f0.plane.plane.remote_granted(t.handle) == 0
        assert f0.plane.headroom(t.handle) == 16
        assert f0.comm_stats()["creds_reclaimed"] == granted
        # idempotent: another round reclaims nothing more
        f0.step()
        assert f0.comm_stats()["creds_reclaimed"] == granted
    finally:
        for f in fabs:
            f.fini()
        for c in comms:
            c.stop()
        for s in socks:
            try:
                s.close()
            except OSError:
                pass


def test_gateway_routes_by_advertised_headroom():
    """3-rank mesh: ranks 0+1 serve tenant T (small vs large window),
    rank 2 is a pure gateway. Routing follows the credit balances —
    most inserts land on the roomy rank — and when EVERY balance is
    exhausted the gateway raises under nowait."""
    from parsec_tpu.dsl.dtd import AdmissionBackpressure
    from parsec_tpu.serving import IngestGateway
    fabs, comms, socks = _mk_fabrics(3, windows=[4, 64, None])
    f0, f1, f2 = fabs
    try:
        _step_all(fabs, comms)
        gw = IngestGateway(f2, ranks=[0, 1])
        assert gw.headroom_of(1, "T") > gw.headroom_of(0, "T") > 0
        landed = []
        f0.tenant("T").handler = lambda p, src: landed.append(0)
        f1.tenant("T").handler = lambda p, src: landed.append(1)
        total = gw.headroom_of(0, "T") + gw.headroom_of(1, "T")
        for i in range(total):
            gw.submit("T", {"i": i}, nowait=True)
        # every advertised credit spent, nothing retired or replenished
        # yet: the NEXT nowait submit is hard backpressure
        with pytest.raises(AdmissionBackpressure):
            gw.submit("T", {"i": -1}, nowait=True)
        _step_all(fabs, comms)             # deliver the insert AMs
        assert len(landed) == total
        assert landed.count(1) > landed.count(0) > 0, landed
        assert sum(gw.routed.values()) == total
    finally:
        for f in fabs:
            f.fini()
        for c in comms:
            c.stop()
        for s in socks:
            s.close()


# ----------------------------------------------------------- 2-OS-rank legs

def _fabric_2rank(**sizes):
    """One run of the acceptance program on two OS ranks."""
    from parsec_tpu.serving.harness import fabric_2rank_program
    res = run_distributed_procs(
        2, functools.partial(fabric_2rank_program, **sizes), timeout=300)
    for r in res:
        if not r.get("fabric"):
            pytest.skip(f"serving fabric unavailable: {r.get('reason')}")
    return res


def _assert_fabric_engaged(res):
    """What holds on EVERY run (engagement, not timing); returns the
    tenants' served counts over the shares window."""
    # the antagonist actually flooded and actually hit the wall
    assert sum(r["antagonist_rejects"] for r in res) > 0
    assert sum(r["antagonist_served"] for r in res) > 0
    # zero hot-path round trips: spends dwarf credit frames
    for r in res:
        w = r["wire"]
        assert w["creds_spent"] > 0
        assert w["cred_frames_rx"] < \
            w["creds_spent"] + w["creds_granted_rx"]
        assert w["frame_errors"] == 0
    assert sum(r["wire"]["creds_granted_tx"] for r in res) > 0
    sv = sum(r["shares_window"]["sv"] for r in res)
    sa = sum(r["shares_window"]["sa"] for r in res)
    assert sv > 0 and sa > 0
    assert res[0]["reconcile_rounds"] > 0
    for r in res:
        assert r["weight_adjusts"] > 0   # nudges landed on BOTH ranks
    return sv, sa


def test_two_rank_antagonist_isolation_and_shares():
    """The acceptance scenario with real processes, its counter
    invariants: the antagonist floods both ranks through the gateway and
    is both served and rejected; remote backpressure engaged with zero
    hot-path round trips (spends local, verified by wire counters); the
    reconciler ran and nudged the weights on both ranks.

    Bodies are cut to ONE BLAS pass and the phases shortened: nothing
    here reads a clock, and at the program's own sizes a run takes
    minutes on a shared host (threaded OpenBLAS, 5 ms a pass where the
    tuning contract counts 20 us). What is wall-clock is the ``slow``
    test below."""
    _assert_fabric_engaged(_fabric_2rank(
        isolation_s=0.6, loaded_s=1.0, shares_s=2.0,
        work_victim=500_000, work_shares=500_000))


@pytest.mark.slow
def test_two_rank_antagonist_p99_and_share_ratio():
    """The wall-clock half, at the program's own sizes: the victim's
    p99 stays within 2x of its unloaded p99, and the reconciled
    cross-rank shares converge within 25% of the global 2:1 weights.

    Both need a host whose cores the ranks own (a p99 over ~200 samples
    is near max-of-samples, and a starved reconciler makes no round).
    The p99 leg follows the bounded-retry discipline of the deflake
    satellites: a systematic isolation failure violates the bound on
    EVERY attempt; a host-load flap does not survive three."""
    attempts = []
    for attempt in range(3):
        res = _fabric_2rank()
        sv, sa = _assert_fabric_engaged(res)
        # cross-rank share convergence (measured over the second half)
        ratio = sv / sa
        assert abs(ratio - 2.0) / 2.0 < 0.25, \
            f"cross-rank shares {sv}:{sa} (ratio {ratio:.2f}) vs " \
            f"weights 2:1"
        base = [x for r in res for x in r["victim_lats_base_ns"]]
        load = [x for r in res for x in r["victim_lats_load_ns"]]
        assert len(base) > 40 and len(load) > 40, (len(base), len(load))
        p99b = float(np.percentile(np.asarray(base), 99))
        p99l = float(np.percentile(np.asarray(load), 99))
        attempts.append((p99b, p99l))
        if p99l <= 2.0 * p99b:
            return
    assert False, \
        "victim p99 moved past 2x of unloaded on every attempt: " + \
        ", ".join(f"{b / 1e3:.0f}us -> {l / 1e3:.0f}us"
                  for b, l in attempts)


def test_two_rank_target_death_reclaims():
    """Real-process peer death: the serving rank hard-exits mid-window;
    the inserter's blocking acquire raises promptly (no hang) and its
    balance is reclaimed."""
    from parsec_tpu.serving.harness import reclaim_2rank_program
    res = run_distributed_procs(
        2, functools.partial(reclaim_2rank_program), timeout=240)
    target, inserter = res
    if not target.get("fabric") or not inserter.get("fabric"):
        pytest.skip("serving fabric unavailable in spawned ranks")
    assert target["granted"] > 0
    assert inserter["avail_before"] > 0
    assert inserter["outcome"] == "raised", inserter
    assert inserter["waited_s"] < 30, inserter
    assert inserter["avail_after"] == 0
    assert 0 in inserter["dead"]


# ------------------------------------------------------------ observability

def test_ptfab_counters_exported():
    from parsec_tpu.utils.counters import counters, install_native_counters
    install_native_counters()
    snap = counters.snapshot()
    for key in ("ptfab.credits_granted", "ptfab.credits_spent",
                "ptfab.credits_reclaimed", "ptfab.remote_stalls",
                "ptfab.remote_rejects", "ptfab.reconcile_rounds",
                "ptfab.share_err_pct", "ptfab.fabrics_up"):
        assert key in snap, key


def test_served_counter_registers_per_tenant():
    from parsec_tpu.core.sched_plane import SchedPlane
    from parsec_tpu.serving import ServingFabric
    from parsec_tpu.utils.counters import counters
    c = _ptcomm.Comm(0, 2)
    sp = SchedPlane(_ptsched, 1, "wdrr")
    fab = ServingFabric(c, sp, 0, 2, replenish=False)
    try:
        fab.serve("acct-42", handler=lambda p, s: None, window=4)
        assert counters.read("ptfab.served.acct-42") == 0
        h = fab.tenant("acct-42").handle
        sp.plane.push(h, [1, 2, 3])
        while sp.plane.pop(worker=0, kind=_ptsched.KIND_EXT, cap=8):
            pass
        assert counters.read("ptfab.served.acct-42") == 3
    finally:
        fab.fini()
        c.stop()
