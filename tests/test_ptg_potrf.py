"""DPLASMA's dpotrf written as a JDF (ISSUE 33): ``ops/potrf.py:POTRF_JDF``,
four classes over a triangular task space with ranges in DPLASMA's
declaration order, bodies calling the program's tile functions by name,
through ``ptexec`` + region fusion + ``ptdev`` (the device module over a
host device). Against the plain reference (``np.linalg.cholesky`` in
float64 on the host) and the DTD twin ``insert_potrf_tasks``. Counts and
results only: no test here reads a clock."""

import numpy as np
import pytest

from parsec_tpu import native as native_mod
from parsec_tpu.core.context import Context
from parsec_tpu.data.matrix import TiledMatrix
from parsec_tpu.device.native import PTDEV_STATS
from parsec_tpu.dsl.dtd import DTDTaskpool
from parsec_tpu.dsl.fusion import CAPTURE_CACHE_STATS
from parsec_tpu.dsl.ptg.compiler import PTEXEC_STATS, compile_ptg
from parsec_tpu.ops import potrf as ops
from parsec_tpu.utils import hist as H
from parsec_tpu.utils import mca
from parsec_tpu.utils.counters import counters

pytestmark = pytest.mark.skipif(
    native_mod.load_ptexec() is None or native_mod.load_ptdev() is None,
    reason="native _ptexec/_ptdev unavailable")

TS = 8
TILE_FNS = {"tile_potrf": ops.tile_potrf, "tile_trsm": ops.tile_trsm,
            "tile_syrk": ops.tile_syrk,
            "tile_gemm_update": ops.tile_gemm_update}


def ntasks(nt):
    return nt * (nt + 1) * (nt + 2) // 6


@pytest.fixture()
def dctx():
    mca.set("device_tpu_over_cpu", True)
    c = Context(nb_cores=1)
    yield c
    c.fini()
    mca.params.unset("device_tpu_over_cpu")


@pytest.fixture()
def ctx():
    c = Context(nb_cores=1)
    yield c
    c.fini()


def _matrix(nt, seed=0):
    n = nt * TS
    a = ops.make_spd(n, seed=seed)
    A = TiledMatrix(f"A{nt}", n, n, TS, TS)
    A.fill(lambda m, k: a[m * TS:(m + 1) * TS, k * TS:(k + 1) * TS].copy())
    return a, A


def _factor(ctx, A, prog=None):
    """One PTG solve of ``A``; returns its lower factor, dense."""
    nt = A.mt
    tp = ops.potrf_taskpool(ctx, A) if prog is None else prog.instantiate(
        ctx, globals={"NT": nt, **TILE_FNS}, collections={"descA": A})
    ctx.add_taskpool(tp)
    ctx.wait(timeout=300)
    assert tp.completed
    return np.tril(np.asarray(A.to_dense()))


def _reference(a):
    """The plain reference: float64 Cholesky on the host."""
    return np.linalg.cholesky(a.astype(np.float64))


def _assert_factor(got, a):
    np.testing.assert_allclose(got, _reference(a), rtol=0, atol=2e-5)
    assert np.abs(got @ got.T - a).max() <= 1e-5 * np.abs(a).max()


@pytest.mark.parametrize("nt", [1, 2, 3, 5, 8, 12])
def test_the_jdf_against_the_reference_and_the_dtd_twin(dctx, nt):
    """Every task on both lanes, none declined; NT = 12 is 364 tasks in
    three regions with edges between them."""
    a, A = _matrix(nt, seed=nt)
    x0, d0 = PTEXEC_STATS.snapshot(), PTDEV_STATS.snapshot()
    got = _factor(dctx, A)
    dx, dd = PTEXEC_STATS.delta(x0), PTDEV_STATS.delta(d0)
    assert dx["pools_engaged"] == dd["pools_engaged"] == 1
    assert dx["tasks_engaged"] == dd["tasks_engaged"] == ntasks(nt)
    assert dx["tasks_device"] == ntasks(nt)
    assert dx["pools_fallback"] == dx["pools_ineligible"] == 0
    assert dd["pools_fallback"] == dd["pools_ineligible"] == 0
    # one task is nothing to fuse: no plan, the task alone on the lanes
    assert dx["fused_tasks"] == (ntasks(nt) if nt > 1 else 0)
    assert dx["seam_tasks"] == 0
    if nt == 12:
        assert dx["fused_regions"] == dx["mixed_regions"] == 3
    assert counters.read("ptdev.cb_errors") == 0
    _assert_factor(got, a)
    _a, T = _matrix(nt, seed=nt)
    tp = DTDTaskpool(dctx, f"twin{nt}")
    assert ops.insert_potrf_tasks(tp, T) == ntasks(nt)
    assert tp.wait(timeout=300)
    tp.close()
    dctx.wait(timeout=300)
    np.testing.assert_allclose(got, np.tril(np.asarray(T.to_dense())),
                               rtol=0, atol=2e-5)


@pytest.mark.parametrize("knob, fixture, engaged, regions", [
    ("region_fusion", "dctx", ntasks(5), 0),    # per-task dispatch on the lanes
    ("ptg_native_exec", "dctx", 0, 0),          # the interpreted FSM
    (None, "ctx", ntasks(5), 1),    # no accelerator: the bodies' CPU twins
])
def test_the_other_paths_agree(request, knob, fixture, engaged, regions):
    if knob:
        mca.set(knob, False)
    try:
        a, A = _matrix(5, seed=3)
        x0 = PTEXEC_STATS.snapshot()
        got = _factor(request.getfixturevalue(fixture), A)
        dx = PTEXEC_STATS.delta(x0)
        assert dx["tasks_engaged"] == engaged
        assert dx["fused_regions"] == regions
        _assert_factor(got, a)
    finally:
        if knob:
            mca.params.unset(knob)


def test_a_second_instantiation_builds_nothing_with_callable_globals(dctx):
    """The bodies call ``tile_*`` by name, handed in as globals: module-
    level functions enter the flatten signature and the region-program key
    by identity, so later pools of the program build no plan and no
    executable. A function made per call keeps the pool uncacheable."""
    prog = compile_ptg(ops.POTRF_JDF, "potrf")
    assert prog.globals_named >= set(TILE_FNS)
    for solve in range(3):
        a, A = _matrix(12, seed=solve)
        x0, c0 = PTEXEC_STATS.snapshot(), CAPTURE_CACHE_STATS.snapshot()
        _assert_factor(_factor(dctx, A, prog), a)
        dx, dc = PTEXEC_STATS.delta(x0), CAPTURE_CACHE_STATS.delta(c0)
        assert dx["fused_regions"] == 3
        assert dx["region_programs"] == (0 if solve else 3)
        assert dc["cache_hits"] == (3 if solve else 0)
        assert dc["cache_evictions"] == 0
    assert len(prog._ptexec_cache) == 1
    cache = prog.region_programs
    assert (len(cache), cache.misses, cache.hits) == (3, 3, 6)

    def made_per_call(t):
        return ops.tile_potrf(t)
    a, A = _matrix(3, seed=9)
    tp = prog.instantiate(dctx, globals={"NT": 3, **TILE_FNS,
                                         "tile_potrf": made_per_call},
                          collections={"descA": A})
    dctx.add_taskpool(tp)
    dctx.wait(timeout=300)
    _assert_factor(np.tril(np.asarray(A.to_dense())), a)
    assert len(prog._ptexec_cache) == 1


def test_pools_of_two_sizes_share_one_program_object(dctx):
    """NT = 6, then NT = 7, then NT = 6 again: the shape keys of mixed
    regions keep the pools apart (a region's canonical plan holds its
    members' classes and wiring), and the third pool builds nothing."""
    prog = compile_ptg(ops.POTRF_JDF, "potrf")
    built = []
    for nt in (6, 7, 6):
        a, A = _matrix(nt, seed=nt)
        x0 = PTEXEC_STATS.snapshot()
        _assert_factor(_factor(dctx, A, prog), a)
        dx = PTEXEC_STATS.delta(x0)
        assert dx["tasks_engaged"] == ntasks(nt) and dx["mixed_regions"] == 1
        built.append(dx["region_programs"])
    assert built == [1, 1, 0]
    assert len(prog.region_programs) == 2


def test_a_region_program_names_each_members_class(dctx, monkeypatch):
    """``jax.named_scope(<class>)`` around each member's body: the
    operations of a mixed region's program say whose they are."""
    import jax
    import jax.numpy as jnp
    from parsec_tpu.dsl.ptg import compiler as C

    built = []
    make = C._mk_region_program
    monkeypatch.setattr(C, "_mk_region_program",
                        lambda *a: built.append(make(*a)) or built[-1])
    prog = compile_ptg(ops.POTRF_JDF, "potrf")
    a, A = _matrix(4, seed=4)
    _assert_factor(_factor(dctx, A, prog), a)
    plan, = [e["fusion"] for e in prog._ptexec_cache.values()]
    tile = jax.ShapeDtypeStruct((TS, TS), jnp.float32)
    text = jax.jit(built[0]).lower(
        (tile,) * len(plan["regions"][0]["ext"])).as_text(debug_info=True)
    assert "module @jit_ptg_region_POTRF_TRSM_SYRK_GEMM" in text
    for name in ("POTRF", "TRSM", "SYRK", "GEMM"):
        assert f"ptg_region_POTRF_TRSM_SYRK_GEMM)/{name}/" in text


def _hist(field):
    return {k: v[field] for k, v in H.histograms.snapshot().items()}


def test_inflight_and_region_tasks_record_where_hist_enabled_says_so():
    """``ptexec.region_tasks``: one record a fused region a pool binds, its
    members. ``ptdev.inflight``: one record a ``dispatch`` callback, the
    programs in flight before it. ``ptdev.stage_in_ns``: the lower tiles
    handed in as numpy, each staged in once."""
    mca.set("hist_enabled", True)
    mca.set("device_tpu_over_cpu", True)
    try:
        c = Context(nb_cores=1)
        assert c._spans is not None
        n0, s0 = _hist("count"), _hist("sum_ns")
        a, A = _matrix(12, seed=1)
        _assert_factor(_factor(c, A), a)
        n1, s1 = _hist("count"), _hist("sum_ns")

        def delta(snap1, snap0, key):
            return snap1.get(key, 0) - snap0.get(key, 0)
        assert delta(n1, n0, "ptexec.region_tasks") == 3
        assert delta(s1, s0, "ptexec.region_tasks") == ntasks(12)
        assert 1 <= delta(n1, n0, "ptdev.inflight") <= 3
        assert delta(n1, n0, "ptdev.inflight") == delta(n1, n0, "ptdev.pins")
        # over a host device a program is complete at once: never more in
        # flight than the pool has programs
        assert 0 <= delta(s1, s0, "ptdev.inflight") <= 3
        assert delta(n1, n0, "ptdev.stage_in_ns") == 12 * 13 // 2
        c.fini()
    finally:
        mca.params.unset("hist_enabled")
        mca.params.unset("device_tpu_over_cpu")
    assert "inflight" in H.HIST_NAMES["ptdev"]
    assert "region_tasks" in H.HIST_NAMES["ptexec"]


def test_the_new_histograms_record_nothing_with_the_spans_off(dctx):
    assert dctx._spans is None
    n0 = _hist("count")
    a, A = _matrix(5, seed=2)
    _assert_factor(_factor(dctx, A), a)
    n1 = _hist("count")
    for key in ("ptdev.inflight", "ptexec.region_tasks",
                "ptdev.stage_in_ns"):
        assert n1.get(key, 0) == n0.get(key, 0)
