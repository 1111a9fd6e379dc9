"""Graph-capture tests: a DTD taskpool compiled into one XLA executable.

The capture mode (dsl/capture.py) must produce bit-for-bit the same tile
results as the task-by-task scheduler on the same DAGs, cache compiled
programs across identical DAG shapes, and reject what it cannot capture.
"""

import numpy as np
import pytest

import parsec_tpu as pt
from parsec_tpu.data.matrix import TwoDimBlockCyclic
from parsec_tpu.dsl.dtd import DTDTaskpool, READ, RW
from parsec_tpu.ops.gemm import insert_gemm_tasks
from parsec_tpu.ops.potrf import insert_potrf_tasks, make_spd


@pytest.fixture()
def ctx():
    c = pt.Context(nb_cores=1)
    yield c
    c.fini()


def _gemm_collections(prefix, n, ts, a, b):
    A = TwoDimBlockCyclic(prefix + "A", n, n, ts, ts, P=1, Q=1)
    B = TwoDimBlockCyclic(prefix + "B", n, n, ts, ts, P=1, Q=1)
    C = TwoDimBlockCyclic(prefix + "C", n, n, ts, ts, P=1, Q=1)
    A.fill(lambda m, k: a[m*ts:(m+1)*ts, k*ts:(k+1)*ts])
    B.fill(lambda m, k: b[m*ts:(m+1)*ts, k*ts:(k+1)*ts])
    C.fill(lambda m, k: np.zeros((ts, ts), np.float32))
    return A, B, C


@pytest.mark.parametrize("batch_k", [False, True])
def test_capture_gemm_matches_scheduler(ctx, batch_k):
    n, ts = 64, 16
    rng = np.random.default_rng(3)
    a = rng.standard_normal((n, n)).astype(np.float32)
    b = rng.standard_normal((n, n)).astype(np.float32)

    _, _, Cs = _gemm_collections("s", n, ts, a, b)
    As, Bs, _ = _gemm_collections("s2", n, ts, a, b)
    tp = DTDTaskpool(ctx, "sched-gemm")
    insert_gemm_tasks(tp, As, Bs, Cs, batch_k=batch_k)
    tp.wait(timeout=60)
    tp.close()
    ctx.wait(timeout=30)

    Ac, Bc, Cc = _gemm_collections("c", n, ts, a, b)
    cap = DTDTaskpool(ctx, "cap-gemm", capture=True)
    insert_gemm_tasks(cap, Ac, Bc, Cc, batch_k=batch_k)
    assert cap.inserted == tp.inserted
    cap.wait()
    cap.close()
    ctx.wait(timeout=30)

    np.testing.assert_allclose(np.asarray(Cc.to_dense()),
                               np.asarray(Cs.to_dense()), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(Cc.to_dense()), a @ b,
                               rtol=1e-3, atol=1e-3)


def test_capture_potrf_matches_scheduler(ctx):
    """The serial-critical-path DAG where capture matters most: POTRF's
    panel chain becomes one executable."""
    n, ts = 64, 16
    spd = make_spd(n, seed=9)

    P1 = TwoDimBlockCyclic("pS", n, n, ts, ts, P=1, Q=1)
    P1.fill(lambda m, k: spd[m*ts:(m+1)*ts, k*ts:(k+1)*ts])
    tp = DTDTaskpool(ctx, "sched-potrf")
    insert_potrf_tasks(tp, P1)
    tp.wait(timeout=60)
    tp.close()
    ctx.wait(timeout=30)

    P2 = TwoDimBlockCyclic("pC", n, n, ts, ts, P=1, Q=1)
    P2.fill(lambda m, k: spd[m*ts:(m+1)*ts, k*ts:(k+1)*ts])
    cap = DTDTaskpool(ctx, "cap-potrf", capture=True)
    insert_potrf_tasks(cap, P2)
    cap.wait()
    cap.close()
    ctx.wait(timeout=30)

    got = np.tril(np.asarray(P2.to_dense(), dtype=np.float64))
    ref = np.tril(np.asarray(P1.to_dense(), dtype=np.float64))
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(got, np.linalg.cholesky(spd.astype(np.float64)),
                               rtol=0, atol=2e-2)


def test_capture_program_cache(ctx):
    """Identical DAG shapes reuse the compiled executable; a changed shape
    recompiles."""
    n, ts = 32, 16
    rng = np.random.default_rng(5)
    a = rng.standard_normal((n, n)).astype(np.float32)
    b = rng.standard_normal((n, n)).astype(np.float32)

    A, B, C = _gemm_collections("h", n, ts, a, b)
    cap = DTDTaskpool(ctx, "cache-gemm", capture=True)
    insert_gemm_tasks(cap, A, B, C, batch_k=True)
    cap.wait()
    assert not cap._capture.cache_hit        # first shape: compile
    insert_gemm_tasks(cap, A, B, C, batch_k=True)
    cap.wait()
    assert cap._capture.cache_hit            # same shape: cached
    assert cap._capture.executions == 2
    cap.close()
    ctx.wait(timeout=30)
    # C accumulated the product twice
    np.testing.assert_allclose(np.asarray(C.to_dense()), 2 * (a @ b),
                               rtol=1e-3, atol=1e-3)


def test_capture_rejects_nonjit_and_multirank(ctx):
    from parsec_tpu.utils import mca as _mca
    _mca.set("capture_auto_defer", False)   # restore the hard reject
    try:
        cap = DTDTaskpool(ctx, "cap-neg", capture=True)
        t = cap.tile_new((4, 4), np.float32)
        with pytest.raises(RuntimeError, match="jit-traceable"):
            cap.insert_task(lambda x: x, (t, RW), jit=False)
        cap.close()
    finally:
        _mca.params.unset("capture_auto_defer")

    from parsec_tpu.comm.remote_dep import RemoteDepEngine
    from parsec_tpu.comm.threads import ThreadsCE, run_distributed

    def program(rank, fabric):
        c = pt.Context(nb_cores=1, my_rank=rank, nb_ranks=2)
        RemoteDepEngine(c, ThreadsCE(fabric, rank))
        try:
            DTDTaskpool(c, "cap2", capture=True)
            return "accepted"
        except RuntimeError as e:
            return str(e)
        finally:
            c.fini(timeout=5)

    results = run_distributed(2, program, timeout=30)
    assert all("single-rank" in r for r in results)


def test_capture_close_executes_pending(ctx):
    """close() without wait() must execute the recorded DAG, matching
    scheduler semantics where inserted tasks run without an explicit
    taskpool wait."""
    cap = DTDTaskpool(ctx, "cap-close", capture=True)
    t = cap.tile_new((4, 4), np.float32)
    t.data.create_copy(0, np.ones((4, 4), np.float32))
    cap.insert_task(lambda x: x + 1.0, (t, RW))
    cap.close()                     # no wait()
    ctx.wait(timeout=30)
    np.testing.assert_allclose(np.asarray(t.data.newest_copy().payload), 2.0)
    assert cap._capture.executions == 1


def test_capture_mixed_value_args(ctx):
    """Scalar params bake into the trace; ndarray params ride as inputs."""
    cap = DTDTaskpool(ctx, "cap-mixed", capture=True)
    t = cap.tile_new((4, 4), np.float32)
    host = cap.tile_new((4, 4), np.float32)
    t.data.create_copy(0, np.ones((4, 4), np.float32))
    host.data.create_copy(0, np.zeros((4, 4), np.float32))
    bias = np.full((4, 4), 0.5, np.float32)

    def scale_add(x, alpha, b):
        return x * alpha + b

    cap.insert_task(scale_add, (t, RW), 3.0, bias)
    cap.insert_task(lambda dst, s: dst + s, (host, RW), (t, READ))
    cap.wait()
    cap.close()
    ctx.wait(timeout=30)
    np.testing.assert_allclose(np.asarray(host.data.newest_copy().payload),
                               3.0 + 0.5)
    np.testing.assert_allclose(np.asarray(t.data.newest_copy().payload),
                               3.0 + 0.5)


def test_capture_ptg_via_replay(ctx):
    """A PTG program — static task space — compiled into ONE XLA executable
    through the cross-DSL replay (ptg_to_dtd + capture): tile GEMM results
    match the PTG scheduler execution."""
    from parsec_tpu.core.pins_modules import ptg_to_dtd_replay
    from parsec_tpu.data.matrix import TiledMatrix
    from parsec_tpu.dsl.ptg.compiler import compile_ptg

    src = """
%global MT
%global KT
%global descA
%global descB
%global descC

GEMM(m, n, k)
  m = 0 .. MT-1
  n = 0 .. MT-1
  k = 0 .. KT-1
  : descC(m, n)
  READ A <- descA(m, k)
  READ B <- descB(k, n)
  RW   C <- (k == 0) ? descC(m, n) : C GEMM(m, n, k-1)
       -> (k < KT-1) ? C GEMM(m, n, k+1) : descC(m, n)
BODY
  C = C + jnp.dot(A, B, preferred_element_type=jnp.float32)
END
"""
    MT = KT = 2
    TS = 8
    rng = np.random.default_rng(13)
    a = rng.standard_normal((MT*TS, KT*TS)).astype(np.float32)
    b = rng.standard_normal((KT*TS, MT*TS)).astype(np.float32)

    def mats(prefix):
        A = TiledMatrix(prefix + "A", MT*TS, KT*TS, TS, TS)
        B = TiledMatrix(prefix + "B", KT*TS, MT*TS, TS, TS)
        Cm = TiledMatrix(prefix + "C", MT*TS, MT*TS, TS, TS)
        A.fill(lambda m, k: a[m*TS:(m+1)*TS, k*TS:(k+1)*TS])
        B.fill(lambda k, n: b[k*TS:(k+1)*TS, n*TS:(n+1)*TS])
        Cm.fill(lambda m, n: np.zeros((TS, TS), np.float32))
        return A, B, Cm

    # scheduler PTG execution
    A1, B1, C1 = mats("rs")
    prog = compile_ptg(src, "capgemm")
    ptp = prog.instantiate(ctx, globals={"MT": MT, "KT": KT},
                           collections={"descA": A1, "descB": B1, "descC": C1})
    ctx.add_taskpool(ptp)
    ctx.wait(timeout=60)

    # captured replay of the same program
    A2, B2, C2 = mats("rc")
    ptp2 = prog.instantiate(ctx, globals={"MT": MT, "KT": KT},
                            collections={"descA": A2, "descB": B2,
                                         "descC": C2}, name="capgemm2")
    dtp = ptg_to_dtd_replay(ptp2, ctx, capture=True)
    assert dtp._capture is not None
    dtp.wait()
    dtp.close()
    ctx.wait(timeout=60)
    assert dtp._capture.executions == 1

    # replay writes through the same C tiles the PTG version wrote
    np.testing.assert_allclose(np.asarray(C2.to_dense()),
                               np.asarray(C1.to_dense()), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(C2.to_dense()), a @ b,
                               rtol=1e-4, atol=1e-4)


# ------------------------------------------------------- mesh capture

def _mesh2d():
    import jax
    from jax.sharding import Mesh
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs 8 virtual devices")
    return Mesh(np.array(devs[:8]).reshape(2, 4), ("x", "y"))


def test_mesh_capture_gemm(ctx):
    """The whole tiled-GEMM DAG as ONE GSPMD program over a 2x4 mesh:
    collection tiles become slices of sharded globals, XLA partitions the
    ops and inserts the transfers; results match numpy."""
    mesh = _mesh2d()
    n, ts = 64, 16
    rng = np.random.default_rng(21)
    a = rng.standard_normal((n, n)).astype(np.float32)
    b = rng.standard_normal((n, n)).astype(np.float32)
    A, B, C = _gemm_collections("m", n, ts, a, b)
    cap = DTDTaskpool(ctx, "mesh-gemm", capture=True)
    insert_gemm_tasks(cap, A, B, C, batch_k=True)
    cap.wait_mesh(mesh)
    cap.close()
    ctx.wait(timeout=30)
    np.testing.assert_allclose(np.asarray(C.to_dense()), a @ b,
                               rtol=1e-3, atol=1e-3)


def test_mesh_capture_potrf_matches_single(ctx):
    """Mesh capture on the factorization DAG (slices + update-slices with
    serial dependencies) matches the single-device captured result."""
    mesh = _mesh2d()
    n, ts = 64, 16
    spd = make_spd(n, seed=17)

    P1 = TwoDimBlockCyclic("mp1", n, n, ts, ts, P=1, Q=1)
    P1.fill(lambda m, k: spd[m*ts:(m+1)*ts, k*ts:(k+1)*ts])
    cap1 = DTDTaskpool(ctx, "mp-single", capture=True)
    insert_potrf_tasks(cap1, P1)
    cap1.wait()
    cap1.close()

    P2 = TwoDimBlockCyclic("mp2", n, n, ts, ts, P=1, Q=1)
    P2.fill(lambda m, k: spd[m*ts:(m+1)*ts, k*ts:(k+1)*ts])
    cap2 = DTDTaskpool(ctx, "mp-mesh", capture=True)
    insert_potrf_tasks(cap2, P2)
    cap2.wait_mesh(mesh)
    cap2.close()
    ctx.wait(timeout=30)

    got = np.tril(np.asarray(P2.to_dense(), np.float64))
    ref = np.tril(np.asarray(P1.to_dense(), np.float64))
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)


def test_mesh_capture_scratch_and_guards(ctx):
    """Scratch tiles ride replicated; indivisible globals are rejected."""
    mesh = _mesh2d()
    cap = DTDTaskpool(ctx, "mesh-scratch", capture=True)
    t = cap.tile_new((8, 8), np.float32)
    t.data.create_copy(0, np.ones((8, 8), np.float32))
    cap.insert_task(lambda x: x * 3.0, (t, RW))
    cap.wait_mesh(mesh)
    cap.close()
    ctx.wait(timeout=30)
    np.testing.assert_allclose(np.asarray(t.data.newest_copy().payload), 3.0)

    bad = TwoDimBlockCyclic("meshbad", 10, 10, 5, 5, P=1, Q=1)  # 10 % 4 != 0
    bad.fill(lambda m, n: np.zeros((5, 5), np.float32))
    cap2 = DTDTaskpool(ctx, "mesh-bad", capture=True)
    try:
        cap2.insert_task(lambda x: x + 1.0, (cap2.tile_of(bad, 0, 0), RW))
        with pytest.raises(RuntimeError, match="divisible"):
            cap2.wait_mesh(mesh)
        # the rejected batch is DISCARDED: close() must not silently run it
        # single-device
        assert cap2._capture.ops == []
    finally:
        cap2.close()
    assert cap2._capture.executions == 0
    np.testing.assert_allclose(
        np.asarray(bad.data_of(0, 0).newest_copy().payload), 0.0)


def test_mesh_capture_program_cache(ctx):
    """Identical distributed DAG shapes over the same mesh reuse the
    compiled GSPMD executable."""
    mesh = _mesh2d()
    n, ts = 32, 8
    rng = np.random.default_rng(23)
    a = rng.standard_normal((n, n)).astype(np.float32)
    b = rng.standard_normal((n, n)).astype(np.float32)
    A, B, C = _gemm_collections("mc", n, ts, a, b)
    cap = DTDTaskpool(ctx, "mesh-cache", capture=True)
    insert_gemm_tasks(cap, A, B, C, batch_k=True)
    cap.wait_mesh(mesh)
    assert not cap._capture.cache_hit
    insert_gemm_tasks(cap, A, B, C, batch_k=True)
    cap.wait_mesh(mesh)
    assert cap._capture.cache_hit
    cap.close()
    ctx.wait(timeout=30)
    np.testing.assert_allclose(np.asarray(C.to_dense()), 2 * (a @ b),
                               rtol=1e-3, atol=1e-3)


def test_wait_mesh_requires_capture(ctx):
    tp = DTDTaskpool(ctx, "nomesh")
    with pytest.raises(RuntimeError, match="capture"):
        tp.wait_mesh(None)
    tp.close()


@pytest.mark.parametrize("which", ["getrf", "geqrf"])
def test_capture_lu_qr_match_scheduler(ctx, which):
    """Capture generality: the LU and QR tile DAGs (solves, householder
    panels) compile whole and match the scheduler path."""
    n, ts = 48, 16
    if which == "getrf":
        from parsec_tpu.ops.getrf import insert_getrf_tasks as ins, make_dd
        src = make_dd(n, seed=3)
    else:
        from parsec_tpu.ops.geqrf import insert_geqrf_tasks as ins
        rng = np.random.default_rng(3)
        src = rng.standard_normal((n, n)).astype(np.float32)

    def run(capture):
        M = TwoDimBlockCyclic(f"{which}{capture}", n, n, ts, ts, P=1, Q=1)
        M.fill(lambda m, k: src[m*ts:(m+1)*ts, k*ts:(k+1)*ts])
        tp = DTDTaskpool(ctx, f"{which}-{capture}", capture=capture)
        if which == "geqrf":
            ins(tp, M, TwoDimBlockCyclic(f"T{capture}", n, n, ts, ts,
                                         P=1, Q=1))
        else:
            ins(tp, M)
        tp.wait(timeout=60)
        tp.close()
        ctx.wait(timeout=30)
        return np.asarray(M.to_dense(), np.float64)

    sched = run(False)
    cap = run(True)
    np.testing.assert_allclose(cap, sched, rtol=1e-4, atol=1e-4)


def test_capture_stencil_matches_scheduler(ctx):
    """The iterative halo-exchange DAG (BASELINE config 4's 1D shape)
    compiles whole: ping-pong buffers and neighbor reads trace through."""
    from parsec_tpu.data.matrix import TiledMatrix
    from parsec_tpu.ops.stencil import insert_stencil1d_tasks

    cols, ts, iters = 64, 16, 4
    rng = np.random.default_rng(2)
    init = rng.standard_normal((8, cols)).astype(np.float32)

    def run(capture):
        A = TiledMatrix(f"stA{capture}", 8, cols, 8, ts)
        B = TiledMatrix(f"stB{capture}", 8, cols, 8, ts)
        A.fill(lambda m, n: init[:, n*ts:(n+1)*ts])
        B.fill(lambda m, n: np.zeros((8, ts), np.float32))
        tp = DTDTaskpool(ctx, f"st{capture}", capture=capture)
        insert_stencil1d_tasks(tp, A, B, iters)
        tp.wait(timeout=60)
        tp.close()
        ctx.wait(timeout=30)
        return np.asarray(A.to_dense())     # iters even -> result in A

    np.testing.assert_allclose(run(True), run(False), rtol=1e-6, atol=1e-6)


# ------------------------------------------------- scan-interpreter capture

def test_scan_capture_gemm_matches_scheduler(ctx):
    """The scanned task interpreter (capture="scan") produces the same tile
    results as the scheduler on the tiled-GEMM DAG."""
    n, ts = 64, 16
    rng = np.random.default_rng(31)
    a = rng.standard_normal((n, n)).astype(np.float32)
    b = rng.standard_normal((n, n)).astype(np.float32)

    A1, B1, C1 = _gemm_collections("zs", n, ts, a, b)
    tp = DTDTaskpool(ctx, "zsched")
    insert_gemm_tasks(tp, A1, B1, C1, batch_k=False)
    tp.wait(timeout=60)
    tp.close()
    ctx.wait(timeout=30)

    A2, B2, C2 = _gemm_collections("zc", n, ts, a, b)
    cap = DTDTaskpool(ctx, "zscan", capture="scan")
    insert_gemm_tasks(cap, A2, B2, C2, batch_k=False)
    cap.wait()
    cap.close()
    ctx.wait(timeout=30)
    assert cap._capture.last_mode == "scan"

    np.testing.assert_allclose(np.asarray(C2.to_dense()),
                               np.asarray(C1.to_dense()), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(C2.to_dense()), a @ b,
                               rtol=1e-3, atol=1e-3)


def test_scan_capture_potrf_matches_scheduler(ctx):
    """The DAG the scan mode exists for: POTRF's decompose-heavy bodies
    (cholesky, triangular solves) appear ONCE per class in the program
    instead of once per task."""
    n, ts = 64, 16
    spd = make_spd(n, seed=29)

    P1 = TwoDimBlockCyclic("zp1", n, n, ts, ts, P=1, Q=1)
    P1.fill(lambda m, k: spd[m*ts:(m+1)*ts, k*ts:(k+1)*ts])
    tp = DTDTaskpool(ctx, "zp-sched")
    insert_potrf_tasks(tp, P1)
    tp.wait(timeout=60)
    tp.close()
    ctx.wait(timeout=30)

    P2 = TwoDimBlockCyclic("zp2", n, n, ts, ts, P=1, Q=1)
    P2.fill(lambda m, k: spd[m*ts:(m+1)*ts, k*ts:(k+1)*ts])
    cap = DTDTaskpool(ctx, "zp-scan", capture="scan")
    insert_potrf_tasks(cap, P2)
    cap.wait()
    cap.close()
    ctx.wait(timeout=30)
    assert cap._capture.last_mode == "scan"

    got = np.tril(np.asarray(P2.to_dense(), np.float64))
    ref = np.tril(np.asarray(P1.to_dense(), np.float64))
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)


def test_scan_capture_program_reuse_across_different_dags(ctx):
    """Descriptor rows are runtime DATA: two DIFFERENT DAGs with the same
    task classes, op count and store geometry share one compiled executable
    (the PTG task-class insight applied to XLA program size)."""
    ts = 8

    def axpy(y, x):
        return y + 2.0 * x

    def run(perm, name):
        cap = DTDTaskpool(ctx, name, capture="scan")
        tiles = [cap.tile_new((ts, ts), np.float32) for _ in range(4)]
        for i, t in enumerate(tiles):
            t.data.create_copy(0, np.full((ts, ts), float(i), np.float32))
        for dst, src in perm:                     # same class, different rows
            cap.insert_task(axpy, (tiles[dst], RW), (tiles[src], READ))
        cap.wait()
        hit = cap._capture.cache_hit
        cap.close()
        ctx.wait(timeout=30)
        vals = [np.asarray(t.data.newest_copy().payload)[0, 0] for t in tiles]
        return hit, vals

    hit1, v1 = run([(0, 1), (2, 3), (0, 2), (1, 3)], "zr1")
    hit2, v2 = run([(3, 0), (1, 2), (3, 1), (2, 0)], "zr2")
    assert not hit1 and hit2       # second DAG reuses the first's executable
    # independent references (task graph semantics on the host side)
    assert v1 == [0 + 2*1 + 2*(2 + 2*3), 1 + 2*3, 2 + 2*3, 3.0]
    assert v2 == [0.0, 1 + 2*2, 2 + 2*0, 3 + 2*0 + 2*(1 + 2*2)]


def test_scan_capture_scalar_args_split_classes(ctx):
    """Scalar args are baked per class: ops differing only in a scalar are
    distinct classes and produce distinct results."""
    cap = DTDTaskpool(ctx, "zsc", capture="scan")
    t1 = cap.tile_new((4, 4), np.float32)
    t2 = cap.tile_new((4, 4), np.float32)
    t1.data.create_copy(0, np.ones((4, 4), np.float32))
    t2.data.create_copy(0, np.ones((4, 4), np.float32))

    def scale(x, alpha):
        return x * alpha

    cap.insert_task(scale, (t1, RW), 3.0)
    cap.insert_task(scale, (t2, RW), 5.0)
    cap.wait()
    cap.close()
    ctx.wait(timeout=30)
    np.testing.assert_allclose(np.asarray(t1.data.newest_copy().payload), 3.0)
    np.testing.assert_allclose(np.asarray(t2.data.newest_copy().payload), 5.0)


def test_scan_capture_rejects_raw_array_args(ctx):
    """Raw ndarray args are not scannable (they would bloat the descriptor
    rows); explicit scan mode must fail loudly, auto must fall back."""
    cap = DTDTaskpool(ctx, "zneg", capture="scan")
    t = cap.tile_new((4, 4), np.float32)
    t.data.create_copy(0, np.ones((4, 4), np.float32))
    cap.insert_task(lambda x, b: x + b, (t, RW),
                    np.zeros((4, 4), np.float32))
    with pytest.raises(Exception, match="scan"):
        cap.wait()
    cap._capture.ops.clear()        # drop the unexecutable recording
    cap.close()


def test_auto_capture_picks_scan_above_threshold(ctx):
    """capture=True (auto) stays inline below the MCA threshold and switches
    to the scan interpreter above it."""
    from parsec_tpu.utils import mca
    old = mca.get("capture_scan_threshold", 64)
    mca.set("capture_scan_threshold", 8)
    try:
        def bump(x):
            return x + 1.0

        def run(nops, name):
            cap = DTDTaskpool(ctx, name, capture=True)
            t = cap.tile_new((4, 4), np.float32)
            t.data.create_copy(0, np.zeros((4, 4), np.float32))
            for _ in range(nops):
                cap.insert_task(bump, (t, RW))
            cap.wait()
            mode = cap._capture.last_mode
            cap.close()
            ctx.wait(timeout=30)
            return mode, np.asarray(t.data.newest_copy().payload)[0, 0]

        mode_small, v_small = run(4, "zat-s")
        mode_big, v_big = run(16, "zat-b")
        assert mode_small == "inline" and v_small == 4.0
        assert mode_big == "scan" and v_big == 16.0
    finally:
        mca.set("capture_scan_threshold", old)


# ------------------------------------------- mesh-capture sharding quality

def _collective_ops(hlo: str):
    """(op kind, result bytes) for every collective in compiled HLO text."""
    import re
    bytes_of = {"f32": 4, "f64": 8, "bf16": 2, "f16": 2, "s32": 4,
                "u32": 4, "s8": 1, "u8": 1, "pred": 1}
    out = []
    for line in hlo.splitlines():
        m = re.search(
            r"=\s+(\w+)\[([\d,]*)\][^ ]*\s+"
            r"(all-gather|all-reduce|collective-permute|all-to-all|"
            r"reduce-scatter)", line)
        if m:
            el = 1
            for d in m.group(2).split(","):
                if d:
                    el *= int(d)
            out.append((m.group(3), el * bytes_of.get(m.group(1), 4)))
    return out


@pytest.mark.parametrize("n", [64, 128])
def test_mesh_capture_collectives_scale_with_halo(ctx, n):
    """Sharding quality of the GSPMD program wait_mesh compiles: every
    collective moves tile-halo-sized data — no collective materializes a
    whole matrix, and the largest transfer stays at tile granularity as
    the matrix grows (communication scales with the halo, not O(N^2)
    replication)."""
    mesh = _mesh2d()
    ts = 16
    rng = np.random.default_rng(25)
    a = rng.standard_normal((n, n)).astype(np.float32)
    b = rng.standard_normal((n, n)).astype(np.float32)
    A, B, C = _gemm_collections(f"hq{n}", n, ts, a, b)
    cap = DTDTaskpool(ctx, f"hlo-gemm{n}", capture=True)
    insert_gemm_tasks(cap, A, B, C, batch_k=True)
    cap.wait_mesh(mesh)
    hlo = cap._capture.mesh_hlo()
    cap.close()
    ctx.wait(timeout=30)
    np.testing.assert_allclose(np.asarray(C.to_dense()), a @ b,
                               rtol=1e-3, atol=1e-3)

    colls = _collective_ops(hlo)
    assert colls, "compiled mesh program has no collectives (unexpected " \
                  "for a 2x4-sharded GEMM)"
    tile_bytes = ts * ts * 4
    matrix_bytes = n * n * 4
    worst = max(by for _, by in colls)
    # halo granularity: the largest single collective moves at most one
    # tile (2x slack for fused pairs) — and NEVER a whole matrix
    assert worst <= 2 * tile_bytes, \
        f"largest collective moves {worst} B (> tile {tile_bytes} B)"
    assert worst < matrix_bytes / 4, \
        f"collective {worst} B is matrix-scale ({matrix_bytes} B)"


def test_scan_capture_scales_to_hundreds_of_tasks(ctx):
    """The round-3 pathology regression gate: an 816-task POTRF DAG under
    the scan strategy compiles + runs in seconds (the inlined strategy
    compiled superlinearly and ran 25-60x its op-sum on chip), and a
    second DAG of the same geometry reuses the executable."""
    import time

    NT, ts = 16, 32
    n = NT * ts
    spd = make_spd(n, seed=3)
    P = TwoDimBlockCyclic("scS", n, n, ts, ts, P=1, Q=1)
    P.fill(lambda m, k: spd[m*ts:(m+1)*ts, k*ts:(k+1)*ts])
    tp = DTDTaskpool(ctx, "scan-scale", capture="scan")
    insert_potrf_tasks(tp, P)
    t0 = time.perf_counter()
    tp.wait()
    first_s = time.perf_counter() - t0
    assert not tp._capture.cache_hit
    assert first_s < 60, f"compile+run took {first_s:.1f}s (blowup regressed)"

    P.fill(lambda m, k: spd[m*ts:(m+1)*ts, k*ts:(k+1)*ts])
    insert_potrf_tasks(tp, P)
    tp.wait()
    assert tp._capture.cache_hit        # same classes/geometry: cached
    tp.close()
    ctx.wait(timeout=30)
    L = np.tril(np.asarray(P.to_dense(), np.float64))
    np.testing.assert_allclose(
        L, np.linalg.cholesky(spd.astype(np.float64)), rtol=0, atol=1e-4)


def test_scan_capture_multi_write_flows(ctx):
    """A body with TWO write flows under the scan interpreter: both
    outputs land in their stores in argument order (the inline path's
    semantics)."""
    def swapscale(a, b):
        return b * 2.0, a * 3.0             # writes (a_new, b_new)

    cap = DTDTaskpool(ctx, "zmw", capture="scan")
    ta = cap.tile_new((4, 4), np.float32)
    tb = cap.tile_new((4, 4), np.float32)
    ta.data.create_copy(0, np.full((4, 4), 1.0, np.float32))
    tb.data.create_copy(0, np.full((4, 4), 10.0, np.float32))
    cap.insert_task(swapscale, (ta, RW), (tb, RW))
    cap.insert_task(swapscale, (ta, RW), (tb, RW))
    cap.wait()
    cap.close()
    ctx.wait(timeout=30)
    # step1: a=20, b=3; step2: a=6, b=60
    np.testing.assert_allclose(np.asarray(ta.data.newest_copy().payload), 6.0)
    np.testing.assert_allclose(np.asarray(tb.data.newest_copy().payload), 60.0)


def test_scan_rejects_dtype_mismatch_auto_falls_back_to_inline(ctx):
    """ADVICE r4 (medium): a body upcasting its f16 tile to f32 must land
    f32 under EVERY strategy — scan would silently round-trip through f16,
    so the planner rejects it and auto takes inline."""
    from parsec_tpu.utils import mca

    def upcast(a):
        return a.astype(np.float32) * 1.5

    mca.set("capture_scan_threshold", 2)   # force auto into scan territory
    try:
        cap = DTDTaskpool(ctx, "zdt", capture="auto")
        t = cap.tile_new((4, 4), np.float16)
        t.data.create_copy(0, np.full((4, 4), 2.0, np.float16))
        for _ in range(4):
            cap.insert_task(upcast, (t, RW))
        cap.wait()
        assert cap._capture.last_mode == "inline"
        cap.close()
        ctx.wait(timeout=30)
        out = np.asarray(t.data.newest_copy().payload)
        assert out.dtype == np.float32          # inline semantics preserved
        np.testing.assert_allclose(out, 2.0 * 1.5 ** 4)
    finally:
        mca.params.unset("capture_scan_threshold")


def test_scan_explicit_mode_rejects_dtype_mismatch(ctx):
    """Explicit capture='scan' with a dtype-changing body is an error, not
    a silent cast (f16 -> f32: a real change without x64 enabled)."""
    def upcast(a):
        return a.astype(np.float32)

    cap = DTDTaskpool(ctx, "zdx", capture="scan")
    t = cap.tile_new((4, 4), np.float16)
    t.data.create_copy(0, np.ones((4, 4), np.float16))
    cap.insert_task(upcast, (t, RW))
    with pytest.raises(Exception, match="scan capture rejected.*float32"):
        cap.wait()
    cap.close()


def test_scan_matching_dtypes_still_scans(ctx):
    """The dtype gate must not regress the scannable case."""
    def scale(a):
        return a * 2.0

    cap = DTDTaskpool(ctx, "zok", capture="scan")
    t = cap.tile_new((4, 4), np.float32)
    t.data.create_copy(0, np.ones((4, 4), np.float32))
    for _ in range(3):
        cap.insert_task(scale, (t, RW))
    cap.wait()
    assert cap._capture.last_mode == "scan"
    cap.close()
    ctx.wait(timeout=30)
    np.testing.assert_allclose(np.asarray(t.data.newest_copy().payload), 8.0)


def test_capture_auto_defers_noncapturable_window(ctx):
    """Per-region auto-defer (ISSUE 10): a window poisoned by a jit=False
    insert replays through the scheduler — the recorded prefix keeps its
    program order, results match a captured run — and the NEXT window
    captures again."""
    from parsec_tpu.dsl.dtd import PTDTD_STATS
    cap = DTDTaskpool(ctx, "cap-defer", capture=True)
    t = cap.tile_new((4, 4), np.float32)
    t.data.create_copy(0, np.ones((4, 4), np.float32))
    snap = PTDTD_STATS.snapshot()
    # window 1: two capturable inserts, then one that defeats capture
    cap.insert_task(lambda x: x * 2.0, (t, RW))
    cap.insert_task(lambda x: x + 1.0, (t, RW))

    def host_body(x):
        return np.asarray(x) + 0.5          # numpy: not jit-traceable

    cap.insert_task(host_body, (t, RW), jit=False)
    assert cap._capture_deferred
    assert PTDTD_STATS.delta(snap)["capture_windows_deferred"] == 1
    assert cap._capture.ops == []           # prefix handed to the scheduler
    cap.wait(timeout=30)
    np.testing.assert_allclose(np.asarray(t.data.newest_copy().payload),
                               1.0 * 2.0 + 1.0 + 0.5)
    # window 2: capture re-armed — a capturable window compiles whole
    assert not cap._capture_deferred
    cap.insert_task(lambda x: x * 3.0, (t, RW))
    assert len(cap._capture.ops) == 1
    cap.wait(timeout=30)
    cap.close()
    ctx.wait(timeout=30)
    np.testing.assert_allclose(np.asarray(t.data.newest_copy().payload),
                               3.5 * 3.0)
