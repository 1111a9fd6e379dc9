"""The out-of-core PTG GEMM cell: its counts against closed forms, its
configuration against the contract, its two readers on hand-made runs (what
they divide by what, and that they give nothing, never raise, where there is
nothing to read), and a rehearsal on the CPU whose budget makes the lane hold
programs back and evict."""

import json
import os
import subprocess
import sys
import types

import pytest

from chipbench import run as harness
from chipbench.graphs import ptg_gemm, ptg_gemm_ooc
from chipbench.layers import held_back_per_solve, pool_account, room_ms
from chipbench.reference import gemm_ooc as twin_ref
from chipbench.reference import ptg_gemm_ooc as ref

CELL = "ptg_gemm_ooc.ts2048"
T = {"n": 36864, "ts": 2048}


def test_counts_in_closed_form():
    assert ptg_gemm_ooc.tasks(T) == 18 ** 3 == 5832
    assert ptg_gemm_ooc.flops(T) == 2.0 * 36864 ** 3
    assert ptg_gemm_ooc.dot_flops(T) == {
        "jit_ptg_region_GEMM": 5832 * 2.0 * 2048 ** 3}
    assert ptg_gemm_ooc.KERNEL_MODULES == ptg_gemm.KERNEL_MODULES == (
        "jit_ptg_region_GEMM",)


def test_the_reference_is_the_dtd_twins():
    for name in ("operand_tile", "sample_rows", "tolerance", "max_abs_err"):
        assert getattr(ref, name) is getattr(twin_ref, name)


def test_the_configuration_states_its_guarantees_and_cuts():
    cell = harness.Cell(CELL, rehearsal=False)
    cfg, bench = cell.config, json.load(open(
        os.path.join(harness.ROOT, "BENCHMARK.json")))
    entry = {c["name"]: c for c in bench["configs"]}["ptg_gemm_f32_ooc"]
    assert entry["source"] == cfg["source"] and len(cfg["source"]) <= 200
    assert entry["reduced"] == cfg["reduced"] == ["chips", "dtype", "n"]
    assert set(cfg["reduced_how"]) == set(cfg["reduced"])
    assert cfg["precision"] == "highest" and cfg["dtype"] == "float32"
    assert cfg["compiles_in_window_allowed"] is False
    for word in ("write-back", "newest valid copy", "budget", "cb_errors",
                 "tasks_engaged"):
        assert word in cfg["guarantees"], word
    assert "readings" in cfg["tolerance"]
    assert cell.traffic == harness.Cell("gemm_ooc.ts2048", False).traffic
    assert [m["name"] for m in cell.end_to_end] == ["tflops", "hbm_peak",
                                                    "setup_s"]
    assert {m["name"] for m in cell.per_layer} == {
        "first_solve_s", "held_back_per_solve", "room_ms", "d2h_per_solve",
        "writeback_per_tile", "h2d_per_solve", "evictions_per_solve",
        "stage_in_per_tile", "kernel_s_per_solve", "kernel_roofline",
        "idle_s_per_solve"}


def fake_run(counters, oks=(True, True, False)):
    return types.SimpleNamespace(counters=counters,
                                 solves=[{"ok": ok} for ok in oks])


def test_held_back_per_solve_is_the_windows_count_over_good_solves():
    run = fake_run({"ptdev.held_back": 84})
    assert held_back_per_solve.read(run) == pytest.approx(42.0)
    assert held_back_per_solve.read(fake_run({"ptdev.held_back": 0})) == 0.0
    assert held_back_per_solve.read(fake_run({})) is None
    assert held_back_per_solve.read(fake_run({"ptdev.held_back": 3},
                                             oks=(False,))) is None


def test_room_ms_is_the_median_of_the_pools_room(monkeypatch):
    from parsec_tpu.utils import xla_trace

    accounts = [{"room_ns": 2_000_000}, {"room_ns": 9_000_000},
                {"room_ns": 4_000_000}]
    monkeypatch.setattr(xla_trace, "POOL_ACCOUNTS", accounts)
    assert room_ms.read(None) == pytest.approx(4.0)
    # accounts filed by a program without the field, or none at all
    monkeypatch.setattr(xla_trace, "POOL_ACCOUNTS", [{"push_ns": 5}])
    assert room_ms.read(None) is None
    monkeypatch.setattr(xla_trace, "POOL_ACCOUNTS", [])
    assert room_ms.read(None) is None
    assert room_ms.median_ms is pool_account.median_ms


def test_a_rehearsal_holds_programs_back_evicts_and_is_correct():
    """The traffic's ``rehearsal`` budget (72 tiles for 108) on the CPU: the
    lane holds programs back, evicts and writes C back, every solve is
    good and the run is ``correct``."""
    out = subprocess.run(
        [sys.executable, os.path.join(harness.HERE, "run.py"),
         "--workload", CELL, "--seed", "4000000005", "--rehearsal",
        "--seconds", "0.5", "--trace", "0"],
        cwd=harness.ROOT, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = out.stdout.splitlines()
    last, note = json.loads(lines[-1]), json.loads(
        next(x for x in lines if x.startswith("RUN "))[4:])
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] >= 1 and last["rehearsal"] is True
    counters, check = note["counters"], note["check"]
    assert counters["ptdev.held_back"] > 0 and counters["evictions"] > 0
    assert counters["owned_evictions"] > 0
    assert counters["transfer_out_bytes"] > 0
    assert counters["ptdev.cb_errors"] == 0
    assert check["guarantees_broken"] == []
    assert check["resident_hwm_bytes"] <= check["budget_bytes"]
