"""The out-of-core GEMM cell: its counts against closed forms, its
configuration against the contract, its blocked reference against the
in-core one, and its two readers on hand-made runs (what they divide by
what, and that they give nothing, never raise, where there is nothing to
read)."""

import json
import os
import types

import numpy as np
import pytest

from chipbench import run as harness
from chipbench.graphs import gemm, gemm_ooc
from chipbench.layers import d2h_per_solve, writeback_per_tile
from chipbench.reference import gemm as ref_incore
from chipbench.reference import gemm_ooc as ref

CELL = "gemm_ooc.ts2048"
T = {"n": 36864, "ts": 2048}
GIB = 2 ** 30


def test_counts_are_the_in_core_drivers():
    assert gemm_ooc.tasks(T) == 18 ** 3 == 5832
    assert gemm_ooc.flops(T) == 2.0 * 36864 ** 3
    assert gemm_ooc.dot_flops(T) == {"jit_tile_gemm": 5832 * 2.0 * 2048 ** 3}
    assert gemm_ooc.KERNEL_MODULES == gemm.KERNEL_MODULES


def test_the_size_passes_the_default_budget_by_more_than_a_window():
    tile = T["ts"] ** 2 * 4
    three = 3 * 18 ** 2 * tile
    assert three / GIB == pytest.approx(15.1875)
    budget = int(0.75 * 15.75 * GIB)            # what the v5e reports

    def over(nt):
        return 3 * nt * nt - budget // tile
    assert (over(16), over(17), over(18)) == (12, 111, 216)
    # a DTD window of 2,048 tasks is 113 k-chains: their C tiles
    assert over(18) > 2048 // 18 > over(17)


def test_the_configuration_states_its_guarantees_and_cuts():
    cell = harness.Cell(CELL, rehearsal=False)
    cfg, bench = cell.config, json.load(open(
        os.path.join(harness.ROOT, "BENCHMARK.json")))
    entry = {c["name"]: c for c in bench["configs"]}["dtd_gemm_f32_ooc"]
    assert entry["source"] == cfg["source"] and len(cfg["source"]) <= 200
    assert entry["reduced"] == cfg["reduced"] == ["chips", "dtype", "n"]
    assert set(cfg["reduced_how"]) == set(cfg["reduced"])
    assert cfg["precision"] == "highest" and cfg["dtype"] == "float32"
    for word in ("write-back", "newest valid copy", "budget", "accelerator"):
        assert word in cfg["guarantees"], word
    assert 1e-5 < cfg["tolerance"]["value"] < 6.6e-5
    assert "readings" in cfg["tolerance"]
    # only the rehearsal carries a budget: on the chip it is the program's
    assert "budget_bytes" not in cell.traffic
    small = harness.Cell(CELL, rehearsal=True).traffic
    three = 3 * (small["n"] // small["ts"]) ** 2 * small["ts"] ** 2 * 4
    assert small["budget_bytes"] == 2 * three // 3
    assert [m["name"] for m in cell.end_to_end] == ["tflops", "hbm_peak",
                                                    "setup_s"]
    assert {m["name"] for m in cell.per_layer} >= {
        "d2h_per_solve", "writeback_per_tile", "h2d_per_solve",
        "evictions_per_solve", "stage_in_per_tile", "kernel_s_per_solve",
        "kernel_roofline", "idle_s_per_solve"}


def test_build_refuses_a_program_that_does_not_count_dirty_evictions(
        monkeypatch):
    """Before a tile is made, with exit code 1 and the reason on stderr: the
    check could not hold such a program to the write-back guarantee."""
    from parsec_tpu.utils import mca

    made = []
    mca.set("device_tpu_over_cpu", True)
    monkeypatch.setattr(gemm_ooc, "_tpu", lambda st: object())
    run = types.SimpleNamespace(traffic={"n": 32, "ts": 8}, seed=35,
                                make_tiles=lambda *a: made.append(a))
    try:
        with pytest.raises(SystemExit) as gone:
            gemm_ooc.build(run)
    finally:
        mca.params.unset("device_tpu_over_cpu")
    assert gone.value.code == gemm_ooc.UNSUPPORTED and not made
    assert "owned_evictions" in gemm_ooc.UNSUPPORTED


def test_blocked_reference_agrees_with_the_in_core_one():
    nt, ts, seed, k = 4, 8, 35, 3.0
    grid = [(m, j) for m in range(nt) for j in range(nt)]
    a = {g: ref.operand_tile(0, ts, *g, seed) for g in grid}
    b = {g: ref.operand_tile(1, ts, *g, seed) for g in grid}

    def dense(t):
        return np.block([[t[m, j] for j in range(nt)] for m in range(nt)])
    c = k * dense(a).astype(np.float64) @ dense(b).astype(np.float64)
    c = c.astype(np.float32)
    c[ts:2 * ts, :ts] += 0.5            # one tile off by a visible amount
    rows = list(range(nt))

    def c_tile(m, j):
        tile = c[m*ts:(m+1)*ts, j*ts:(j+1)*ts]
        import jax.numpy as jnp
        return jnp.asarray(tile) if (m + j) % 2 else tile   # either side
    err, err_high = ref.max_abs_err(c_tile, a, b, nt, rows, k)
    assert err == pytest.approx(
        ref_incore.max_abs_err(c_tile, a, b, nt, rows, k), abs=1e-5)
    assert err == pytest.approx(0.5, abs=1e-3) and err_high >= 0.49
    assert ref.tolerance(36864, 5, 2.4e-5) == pytest.approx(5 * 2.4e-5 * 192)


def fake_run(counters, oks=(True, True, False)):
    return types.SimpleNamespace(counters=counters,
                                 solves=[{"ok": ok} for ok in oks])


def test_d2h_per_solve_is_bytes_written_back_over_good_solves():
    run = fake_run({"transfer_out_bytes": 3 * GIB})
    assert d2h_per_solve.read(run) == pytest.approx(1.5)
    assert d2h_per_solve.read(fake_run({"transfer_out_bytes": 0})) == 0.0
    assert d2h_per_solve.read(fake_run({})) is None
    assert d2h_per_solve.read(fake_run({"transfer_out_bytes": 1},
                                       oks=(False,))) is None


def test_writeback_per_tile_divides_sum_by_count(monkeypatch):
    from parsec_tpu.utils import hist

    snap = {"tpudev.writeback_ns": {"count": 324, "sum_ns": 324 * 2_500_000}}
    monkeypatch.setattr(hist.histograms, "snapshot", lambda: snap)
    assert writeback_per_tile.read(None) == pytest.approx(2500.0)
    snap["tpudev.writeback_ns"] = {"count": 0, "sum_ns": 0}
    assert writeback_per_tile.read(None) is None
    monkeypatch.setattr(hist.histograms, "snapshot", lambda: {})
    assert writeback_per_tile.read(None) is None
