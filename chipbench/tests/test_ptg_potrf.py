"""The PTG Cholesky cell's counts against closed forms, and its three
readers on hand-made runs: what they divide by what, and that they give
nothing (never raise) where the program or the trace has nothing to read."""

import types

import pytest

from chipbench.graphs import potrf, ptg_potrf
from chipbench.layers import (factor_region_roofline, inflight_at_dispatch,
                              ptdev_stage_in_per_tile)

T512 = {"n": 16384, "ts": 512}


def test_counts_are_the_twins():
    assert ptg_potrf.tasks(T512) == potrf.tasks(T512) == 5984
    assert ptg_potrf.flops(T512) == potrf.flops(T512)
    assert ptg_potrf.dot_flops_total(T512) == (4960 + 496) * 2.0 * 512 ** 3
    assert ptg_potrf.dot_bytes(T512) == (4 * 4960 + 3 * 496) * 512 * 512 * 4
    assert ptg_potrf.dot_flops(T512) == {}


def fake_run(modules, solves=2, graph=ptg_potrf):
    return types.SimpleNamespace(
        graph=graph, traffic=T512, peaks={"bf16_flops_per_s": 197e12},
        trace={"solves": solves, "modules": modules})


def test_factor_region_roofline_sums_every_region_module():
    modules = {"jit_ptg_region_GEMM_SYRK": {"count": 60, "seconds": 0.12},
               "jit_ptg_region_POTRF_TRSM_SYRK_GEMM":
               {"count": 2, "seconds": 0.08},
               "jit_tril": {"count": 9, "seconds": 5.0}}
    want = 100.0 * ptg_potrf.dot_flops_total(T512) * 2 / 197e12 / 0.20
    assert factor_region_roofline.read(fake_run(modules)) == \
        pytest.approx(want)
    assert 0 < want < 20


@pytest.mark.parametrize("run", [
    fake_run({"jit_tile_gemm": {"count": 1, "seconds": 1.0}}),
    fake_run({}, solves=0),
    fake_run({"jit_ptg_region_GEMM": {"count": 1, "seconds": 1.0}},
             graph=potrf),
])
def test_factor_region_roofline_reads_nothing_without_its_sources(run):
    assert factor_region_roofline.read(run) is None


def test_the_histogram_readers_divide_sum_by_count(monkeypatch):
    from parsec_tpu.utils import hist

    snap = {"ptdev.stage_in_ns": {"count": 528, "sum_ns": 528 * 330_000},
            "ptdev.inflight": {"count": 40, "sum_ns": 10}}
    monkeypatch.setattr(hist.histograms, "snapshot", lambda: snap)
    assert ptdev_stage_in_per_tile.read(None) == pytest.approx(330.0)
    assert inflight_at_dispatch.read(None) == pytest.approx(0.25)
    monkeypatch.setattr(hist.histograms, "snapshot", lambda: {})
    assert ptdev_stage_in_per_tile.read(None) is None
    assert inflight_at_dispatch.read(None) is None
