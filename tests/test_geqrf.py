"""The DTD tile QR in compact-WY form (``ops/geqrf.py``) on the device lane
over a host jax device, against a plain reference: R up to row signs, the
factors V and T rebuilding A = QR with Q orthogonal, T upper triangular. And
the lane's rule for a flow written without being read (T's): it takes room on
the device, moves no byte, hands the body no stale bytes, and its output is
read, evicted and written back as any other."""

import jax
import numpy as np
import pytest

from parsec_tpu.core.context import Context
from parsec_tpu.data.matrix import TiledMatrix
from parsec_tpu.device.tpu import TPUDevice
from parsec_tpu.dsl import dtd as dtd_mod
from parsec_tpu.dsl.dtd import DTDTaskpool, READ, RW, WRITE
from parsec_tpu.ops import geqrf as G
from parsec_tpu.utils import mca


@pytest.fixture()
def dctx():
    mca.set("device_tpu_over_cpu", True)
    dtd_mod._ladders.clear()    # every test's pools are the first of a body
    c = Context(nb_cores=1)
    yield c
    c.fini()
    mca.params.unset("device_tpu_over_cpu")


def _dev(ctx):
    return next(d for d in ctx.devices.devices if isinstance(d, TPUDevice))


def _tile(M, m, k):
    return np.asarray(M.data_of(m, k).newest_copy().payload)


def _stale(M, value=np.nan):
    """Host bytes no result may see."""
    M.fill(lambda m, k: np.full((M.mb, M.nb), value, np.float32))


def _factor(ctx, a, ts):
    n = a.shape[0]
    A = TiledMatrix("A", n, n, ts, ts)
    A.fill(lambda m, k: a[m * ts:(m + 1) * ts, k * ts:(k + 1) * ts])
    T = TiledMatrix("T", n, n, ts, ts)
    _stale(T)
    tp = DTDTaskpool(ctx, "geqrf")
    inserted = G.insert_geqrf_tasks(tp, A, T)
    assert tp.wait(timeout=120)
    tp.close()
    ctx.wait(timeout=60)
    return A, T, inserted


def apply_q(v_tile, t_tile, x_rows, nt):
    """``Q X`` for the Q the factorization left in its V and T tiles, on the
    host in float64: ``v_tile(k, m)`` is A's tile (m, k) after the
    factorization (m = k: GEQRT's unit lower V, m > k: TSQRT's V2),
    ``t_tile(m, k)`` T's; ``x_rows`` is a list of NT row blocks."""
    x = [np.asarray(r, np.float64) for r in x_rows]
    for k in reversed(range(nt)):
        for m in reversed(range(k + 1, nt)):
            v2 = np.asarray(v_tile(k, m), np.float64)
            t = np.asarray(t_tile(m, k), np.float64)
            w = t @ (x[k] + v2.T @ x[m])
            x[k] -= w
            x[m] -= v2 @ w
        a = np.asarray(v_tile(k, k), np.float64)
        v = np.tril(a, -1) + np.eye(a.shape[0])
        x[k] -= v @ (np.asarray(t_tile(k, k), np.float64) @ (v.T @ x[k]))
    return x


def _q_times(A, T, x, nt, ts):
    rows = apply_q(lambda k, m: _tile(A, m, k), lambda m, k: _tile(T, m, k),
                     [x[i * ts:(i + 1) * ts] for i in range(nt)], nt)
    return np.vstack(rows)


@pytest.mark.parametrize("ts", [8, 16])
@pytest.mark.parametrize("nt", [1, 2, 4, 6])
def test_qr_matches_the_reference(dctx, nt, ts):
    _check_qr(dctx, nt, ts)


@pytest.fixture()
def blocked(monkeypatch):
    """The update bodies applied in blocks of TS / 4 reflectors (at TS = 8
    or 16 the program's rule gives one block); the rule notes each tile it
    was asked for, so a test sees that the bodies were traced under it. The
    jit caches are cleared on both sides: a trace made under the program's
    rule is not reused here, nor this one after."""
    asked = []

    def quarter(ts):
        asked.append(ts)
        return ts // 4

    jax.clear_caches()
    monkeypatch.setattr(G, "_inner_block", quarter)
    yield asked
    jax.clear_caches()


@pytest.mark.parametrize("nt", [2, 4])
def test_blocked_qr_matches_the_reference(dctx, blocked, nt):
    _check_qr(dctx, nt, 16)
    assert 16 in blocked


def _check_qr(dctx, nt, ts):
    n = nt * ts
    a = np.random.default_rng((nt, ts)).standard_normal((n, n)).astype(
        np.float32)
    dev = _dev(dctx)
    A, T, inserted = _factor(dctx, a, ts)
    assert inserted == nt + nt * (nt - 1) + sum(j * j for j in range(nt))
    assert dev.executed_tasks == inserted

    R = np.triu(np.vstack([np.hstack([_tile(A, m, k) for k in range(nt)])
                           for m in range(nt)]).astype(np.float64))
    ref = np.linalg.qr(a.astype(np.float64), mode="r")
    sign = lambda r: np.sign(np.diag(r))[:, None]
    assert np.linalg.norm(sign(R) * R - sign(ref) * ref) \
        / np.linalg.norm(ref) < 1e-5
    # A = QR and Q^T Q = I, Q from the stored V and T
    assert np.linalg.norm(_q_times(A, T, R, nt, ts) - a) \
        / np.linalg.norm(a) < 1e-5
    q = _q_times(A, T, np.eye(n), nt, ts)
    assert np.linalg.norm(q.T @ q - np.eye(n)) / np.sqrt(n) < 1e-5
    for m in range(nt):
        for k in range(m + 1):
            t = _tile(T, m, k)
            assert np.isfinite(t).all() and not np.tril(t, -1).any()
    # T was staged by no byte: A moved, T's tiles were room alone
    tiles = nt * (nt + 1) // 2
    assert dev.transfer_in_bytes == n * n * 4
    assert (dev.write_allocs, dev.write_alloc_bytes) == \
        (tiles, tiles * ts * ts * 4)
    stats = dctx.devices.statistics()[dev.name]
    assert stats["write_allocs"] == tiles
    assert stats["write_alloc_bytes"] == dev.write_alloc_bytes


def _reflectors(ts, zero_column):
    """V (unit lower) and T of GEQRT and TSQRT on seeded Gaussian tiles:
    ``(v, t)`` for UNMQR's and ``(v2, t)`` for TSMQR's shape. With
    ``zero_column`` one column of each panel is 0, so one reflector of each
    has tau = 0 and a zero row and column in T."""
    rng = np.random.default_rng((ts, zero_column))
    akk, amk = (rng.standard_normal((ts, ts)).astype(np.float32)
                for _ in range(2))
    if zero_column:
        akk[:, ts // 3] = 0.0
        amk[:, 0] = 0.0     # the stack's column 0 is R's, zero below row 0
    r, t_kk = jax.jit(G.tile_geqrt)(akk, None)
    _, v2, t_mk = jax.jit(G.tile_tsqrt)(r, amk, None)
    r, t_kk, v2, t_mk = map(np.asarray, (r, t_kk, v2, t_mk))
    if zero_column:
        assert not t_kk[:, ts // 3].any() and not t_kk[ts // 3].any()
        assert not t_mk[:, 0].any() and not t_mk[0].any()
    return (r, t_kk), (v2, t_mk)


@pytest.mark.parametrize("zero_column", [False, True],
                         ids=["full", "tau0"])
@pytest.mark.parametrize("ib", [8, 16, 32, 64])
@pytest.mark.parametrize("body", ["unmqr", "tsmqr"])
def test_blocked_update_equals_the_dense_one(monkeypatch, body, ib,
                                             zero_column):
    """Applied in ib-wide blocks, each with its own diagonal block of T, the
    update is Q^T X = X - V T^T V^T X of the whole T: float64 on the host
    from the same V and T."""
    ts = 64
    (r, t_kk), (v2, t_mk) = _reflectors(ts, zero_column)
    rng = np.random.default_rng(ib)
    x, y = (rng.standard_normal((ts, ts)).astype(np.float32)
            for _ in range(2))
    monkeypatch.setattr(G, "_inner_block", lambda n: ib)
    f64 = lambda a: np.asarray(a, np.float64)
    # a function of its own, so jax traces it under this rule
    if body == "unmqr":
        v = np.tril(f64(r), -1) + np.eye(ts)
        got = [jax.jit(lambda *a: G.tile_unmqr(*a))(r, t_kk, x)]
        want = [f64(x) - v @ (f64(t_kk).T @ (v.T @ f64(x)))]
    else:
        w = f64(t_mk).T @ (f64(x) + f64(v2).T @ f64(y))
        got = jax.jit(lambda *a: G.tile_tsmqr(*a))(x, y, v2, t_mk)
        want = [f64(x) - w, f64(y) - f64(v2) @ w]
    for g, want in zip(got, want):
        assert np.linalg.norm(f64(g) - want) / np.linalg.norm(want) < 1e-5


@pytest.mark.parametrize("ts, ib", [(2048, 256), (4096, 256), (1024, 256),
                                    (512, 512), (16, 16), (8, 8)])
def test_inner_block_rule(ts, ib):
    """256-wide blocks from a tile of 1024 up, the dense form (one block)
    below: the v5e's readings at f32 HIGHEST."""
    assert G._inner_block(ts) == ib


def test_tsqrt_outputs_land_in_flow_order(dctx):
    """TSQRT writes three tiles: A[k,k], A[m,k] and T[m,k], in that order."""
    ts = 16
    rng = np.random.default_rng(3)
    akk = np.triu(rng.standard_normal((ts, ts))).astype(np.float32) \
        + np.tril(np.full((ts, ts), 7.0, np.float32), -1)
    amk = rng.standard_normal((ts, ts)).astype(np.float32)
    A = TiledMatrix("A", 2 * ts, ts, ts, ts)
    A.fill(lambda m, k: (akk, amk)[m])
    T = TiledMatrix("T", ts, ts, ts, ts)
    _stale(T)
    tp = DTDTaskpool(dctx, "tsqrt")
    tp.insert_task(G.tile_tsqrt, (tp.tile_of(A, 0, 0), RW),
                   (tp.tile_of(A, 1, 0), RW), (tp.tile_of(T, 0, 0), WRITE))
    assert tp.wait(timeout=60)
    tp.close()
    dctx.wait(timeout=60)
    want = jax.jit(G.tile_tsqrt)(akk, amk, None)
    for got, w in zip((_tile(A, 0, 0), _tile(A, 1, 0), _tile(T, 0, 0)), want):
        np.testing.assert_array_equal(got, np.asarray(w))
    # GEQRT's V in A[k,k]'s lower part is kept
    np.testing.assert_array_equal(np.tril(_tile(A, 0, 0), -1),
                                  np.tril(akk, -1))


@pytest.mark.parametrize("k", [2, 4])
@pytest.mark.parametrize("body", [G.tile_unmqr, G.tile_tsmqr],
                         ids=["unmqr", "tsmqr"])
def test_a_group_equals_single_programs(body, k):
    ts = 8
    rng = np.random.default_rng(k)
    ops = [[rng.standard_normal((ts, ts)).astype(np.float32)
            for _ in range(body.__code__.co_argcount)] for _ in range(k)]
    grouped = dtd_mod._grouped(body, k)(*[x for o in ops for x in o])
    single = jax.jit(body)
    for o, g in zip(ops, grouped):
        want = single(*o)
        want = want if isinstance(want, tuple) else (want,)
        g = g if isinstance(g, tuple) else (g,)
        assert len(g) == len(want)
        for x, y in zip(g, want):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       rtol=1e-6, atol=1e-6)


def test_updates_are_issued_in_groups(dctx, monkeypatch):
    """Over a host device the manager takes a class as paced by the host:
    UNMQR and TSMQR (two outputs a task) go as multi-task programs, and the
    factorization is still right. The first step's UNMQRs are released
    together by its GEQRT, and each is judged at the next one's issue,
    before a program on the host device is done: such a burst ends the
    class's streak. On a loaded host the ten UNMQRs of the later steps at
    NT = 6 did not always build it again (UNMQR then never went grouped);
    NT = 16 gives the streak enough steps to form."""
    seen = []
    submit = DTDTaskpool._tpu_batch_submit

    def spy(self, device, tasks, inputs_list):
        seen.append((tasks[0].task_class.name, len(tasks)))
        return submit(self, device, tasks, inputs_list)

    monkeypatch.setattr(DTDTaskpool, "_tpu_batch_submit", spy)
    nt, ts = 16, 8
    a = np.random.default_rng(11).standard_normal((nt * ts, nt * ts)).astype(
        np.float32)
    A, T, _ = _factor(dctx, a, ts)
    grouped = {name for name, size in seen if size > 1}
    assert {"UNMQR", "TSMQR"} <= grouped
    assert _dev(dctx).batched_tasks > 0
    R = np.triu(A.to_dense().astype(np.float64))
    assert np.linalg.norm(_q_times(A, T, R, nt, ts) - a) \
        / np.linalg.norm(a) < 1e-5


# ------------------------------------------------ write-only flows, alone

SEEN = []


def _write_probe(x, t):
    """Writes ``t`` without reading it; what it was handed is noted at
    trace time."""
    SEEN.append(t)
    return x + 1.0, 2.0 * x


def _read_after(t, y):
    return y + t


def _pool(ctx, X, W, Y):
    tp = DTDTaskpool(ctx, "write-only")
    for m in range(X.mt):
        tp.insert_task(_write_probe, (tp.tile_of(X, m, 0), RW),
                       (tp.tile_of(W, m, 0), WRITE))
        tp.insert_task(_read_after, (tp.tile_of(W, m, 0), READ),
                       (tp.tile_of(Y, m, 0), RW))
    assert tp.wait(timeout=60)
    tp.close()
    ctx.wait(timeout=60)


def _column(name, ntiles, ts, fill):
    M = TiledMatrix(name, ntiles * ts, ts, ts, ts)
    M.fill(lambda m, n: np.full((ts, ts), fill(m), np.float32))
    return M


def test_a_write_only_flow_takes_room_and_moves_no_byte(dctx):
    ts, ntiles = 16, 4
    tile_bytes = ts * ts * 4
    X = _column("X", ntiles, ts, float)
    W = _column("W", ntiles, ts, lambda m: np.nan)
    Y = _column("Y", ntiles, ts, lambda m: 10.0)
    dev = _dev(dctx)
    SEEN.clear()
    _pool(dctx, X, W, Y)
    # the body was handed no bytes for W, stale NaNs never reach a result
    assert SEEN and all(t is None for t in SEEN)
    assert dev.transfer_in_bytes == 2 * ntiles * tile_bytes     # X and Y
    assert (dev.write_allocs, dev.write_alloc_bytes) == \
        (ntiles, ntiles * tile_bytes)
    for m in range(ntiles):
        # the later READ saw the written tile
        np.testing.assert_array_equal(_tile(Y, m, 0), 10.0 + 2.0 * m)
        data = W.data_of(m, 0)
        assert data.newest_copy() is data.get_copy(dev.device_index)
        np.testing.assert_array_equal(_tile(W, m, 0), 2.0 * m)

    # a copy of the newest version resident here is used as it stands
    _pool(dctx, X, W, Y)
    assert dev.write_allocs == ntiles
    np.testing.assert_array_equal(_tile(W, 3, 0), 2.0 * 4)
    # newer host bytes make it stale: room again, still no byte
    moved = dev.transfer_in_bytes
    _stale(W)
    _pool(dctx, X, W, Y)
    assert dev.write_allocs == 2 * ntiles
    assert dev.transfer_in_bytes == moved
    np.testing.assert_array_equal(_tile(W, 3, 0), 2.0 * 5)


def test_a_written_tile_is_evicted_and_written_back(dctx):
    """Under a budget of a few tiles the allocations evict: a written W
    tile leaves dirty, its bytes reach the host at its version, and a later
    READ stages it back from there."""
    ts, ntiles = 16, 8
    tile_bytes = ts * ts * 4
    dev = _dev(dctx)
    dev.set_budget(4 * tile_bytes)
    X = _column("X", ntiles, ts, float)
    W = _column("W", ntiles, ts, lambda m: np.nan)
    Y = _column("Y", ntiles, ts, lambda m: 10.0)
    _pool(dctx, X, W, Y)
    assert dev.write_allocs == ntiles
    assert dev.owned_evictions > 0 and dev.transfer_out_bytes > 0
    written_back = 0
    for m in range(ntiles):
        data = W.data_of(m, 0)
        host = data.get_copy(0)
        if host.version == data.version:
            written_back += 1
            np.testing.assert_array_equal(np.asarray(host.payload), 2.0 * m)
        np.testing.assert_array_equal(_tile(Y, m, 0), 10.0 + 2.0 * m)
    assert written_back > 0

    # a READ of every W tile: the evicted ones come back from the host
    Z = _column("Z", ntiles, ts, lambda m: 0.0)
    tp = DTDTaskpool(dctx, "read-back")
    for m in range(ntiles):
        tp.insert_task(_read_after, (tp.tile_of(W, m, 0), READ),
                       (tp.tile_of(Z, m, 0), RW))
    assert tp.wait(timeout=60)
    tp.close()
    dctx.wait(timeout=60)
    for m in range(ntiles):
        np.testing.assert_array_equal(_tile(Z, m, 0), 2.0 * m)
