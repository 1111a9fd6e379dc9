"""The tile QR cell: its counts against closed forms, its configuration
against the contract, its reference's two comparisons on a factor made
here (the program's own, and a wrong one), and its three new readers on
hand-made runs (what they divide by what, and that they give nothing,
never raise, where there is nothing to read)."""

import json
import os
import types

import numpy as np
import pytest

from chipbench import run as harness
from chipbench.graphs import geqrf
from chipbench.layers import (panel_roofline, panel_s_per_solve,
                              write_alloc_per_solve)
from chipbench.reference import geqrf as ref

CELL = "geqrf.ts2048"
T = {"n": 32768, "ts": 2048}
GIB = 2 ** 30


def test_counts_are_the_closed_forms():
    assert geqrf.tasks(T) == 16 + 240 + 1240 == 1496
    nt = 16
    by_class = nt + nt * (nt - 1) // 2 * 2 + sum(j * j for j in range(nt))
    assert geqrf.tasks(T) == by_class
    assert geqrf.tasks({"n": 64, "ts": 8}) == 8 + 56 + 140
    assert geqrf.flops(T) == pytest.approx(4.6914e13, rel=1e-4)
    assert geqrf.flops({"n": 6, "ts": 2}) == 4 * 216 / 3 + 2 * 36 + 14 * 6 / 3
    ts3 = 2048 ** 3
    assert geqrf.dot_flops(T) == {"jit_tile_unmqr": 120 * 6.0 * ts3,
                                  "jit_tile_tsmqr": 1240 * 6.0 * ts3}
    panel = geqrf.panel_flops(T)
    assert panel["jit_tile_geqrt"] == pytest.approx(16 * (4 / 3 + 3) * ts3)
    assert panel["jit_tile_tsqrt"] == pytest.approx(120 * (10 / 3 + 3) * ts3)
    assert set(geqrf.dot_flops(T)) | set(panel) == set(geqrf.KERNEL_MODULES)
    assert set(geqrf.PANEL_MODULES) == set(panel)
    # the updates do 6 TS^3 where the algorithm needs 4: 1.5 x its products
    assert sum(geqrf.dot_flops(T).values()) / geqrf.flops(T) \
        == pytest.approx(1.5, rel=0.1)


def test_the_configuration_states_its_guarantees_and_cuts():
    cell = harness.Cell(CELL, rehearsal=False)
    cfg, bench = cell.config, json.load(open(
        os.path.join(harness.ROOT, "BENCHMARK.json")))
    entry = {c["name"]: c for c in bench["configs"]}["dtd_geqrf_f32"]
    assert entry["source"] == cfg["source"] and len(cfg["source"]) <= 200
    assert entry["reduced"] == cfg["reduced"] == ["chips", "dtype", "n"]
    assert set(cfg["reduced_how"]) == set(cfg["reduced"])
    assert cfg["precision"] == "highest" and cfg["dtype"] == "float32"
    assert cfg["compiles_in_window_allowed"] is False and cfg["ranks"] == 1
    for word in ("ib", "operands", "priorities"):
        assert word in cfg["assumed"], word
    tol = cfg["tolerance"]
    assert "sample_blocks" not in tol and tol["r_tiles"] == 4
    assert 0 < tol["backward"] < 1e-3 and 0 < tol["leading_r"] < 1e-3
    assert cell.traffic["n"] // cell.traffic["ts"] == 16
    assert [m["name"] for m in cell.end_to_end] == ["tflops", "hbm_peak",
                                                    "setup_s"]
    assert {"panel_s_per_solve", "panel_roofline", "write_alloc_per_solve",
            "h2d_per_solve", "kernel_roofline"} <= \
        {m["name"] for m in cell.per_layer}
    small = harness.Cell(CELL, rehearsal=True).traffic
    assert small["n"] // small["ts"] >= 4      # the check's c tiles exist


def _factor(n, ts, seed, skip=()):
    """A's tiles factored as the program leaves them, by the program's own
    kernels called in DAG order on the host's default device; the TSMQRs
    (k, m, j) in ``skip`` are left out."""
    from parsec_tpu.ops import geqrf as G
    nt = n // ts
    a = {(m, k): ref.operand_tile(n, ts, m, k, seed)
         for m in range(nt) for k in range(nt)}
    t = {}
    for k in range(nt):
        a[k, k], t[k, k] = G.tile_geqrt(a[k, k], None)
        for j in range(k + 1, nt):
            a[k, j] = G.tile_unmqr(a[k, k], t[k, k], a[k, j])
        for m in range(k + 1, nt):
            a[k, k], a[m, k], t[m, k] = G.tile_tsqrt(a[k, k], a[m, k], None)
            for j in range(k + 1, nt):
                if (k, m, j) not in skip:
                    a[k, j], a[m, j] = G.tile_tsmqr(a[k, j], a[m, j],
                                                    a[m, k], t[m, k])
    return a, t


@pytest.mark.parametrize("nt", [1, 4, 6])
def test_the_reference_passes_the_program_and_fails_a_wrong_factor(nt):
    ts, seed = 8, 3000000019
    n = nt * ts
    a, t = _factor(n, ts, seed)
    orig = lambda m, k: ref.operand_tile(n, ts, m, k, seed)
    c = min(4, nt)
    backward = ref.backward_errors(lambda m, k: a[m, k],
                                   lambda m, k: t[m, k], orig, n, ts)
    assert len(backward) == nt
    assert max(backward) < 1e-5
    assert ref.leading_r_error(lambda m, k: a[m, k], orig, n, ts, c) < 1e-5
    # a T tile lost: the backward error sees it; R's first tile is A's
    # first column block's R whatever T holds
    wrong = dict(t)
    wrong[nt - 1, nt - 1] = np.zeros((ts, ts), np.float32)
    assert max(ref.backward_errors(lambda m, k: a[m, k],
                                   lambda m, k: wrong[m, k],
                                   orig, n, ts)) > 1e-2
    bad = dict(a)
    bad[0, c - 1] = np.asarray(bad[0, c - 1]) + 1.0
    assert ref.leading_r_error(lambda m, k: bad[m, k], orig, n, ts, c) > 1e-2


def test_an_update_lost_in_one_column_block_is_seen():
    """A TSMQR left out in a column block that is neither among R's leading
    tiles nor the last: the block's own backward error sees it, the others
    and the leading R do not."""
    nt, ts, seed = 6, 8, 3000000023
    n = nt * ts
    a, t = _factor(n, ts, seed, skip={(0, 2, 4)})
    orig = lambda m, k: ref.operand_tile(n, ts, m, k, seed)
    backward = ref.backward_errors(lambda m, k: a[m, k],
                                   lambda m, k: t[m, k], orig, n, ts)
    assert backward[4] > 1e-2
    assert max(backward[:4] + backward[5:]) < 1e-5
    assert ref.leading_r_error(lambda m, k: a[m, k], orig, n, ts, 4) < 1e-5


def _run(trace=None, counters=None, solves=(True, True)):
    return types.SimpleNamespace(
        graph=geqrf, traffic=T, trace=trace,
        peaks={"bf16_flops_per_s": 197e12}, counters=counters or {},
        solves=[{"ok": ok} for ok in solves])


def test_the_panel_readers():
    modules = {"jit_tile_geqrt": {"seconds": 0.2},
               "jit_tile_tsqrt": {"seconds": 1.8},
               "jit_tile_tsmqr": {"seconds": 5.0}}
    run = _run(trace={"solves": 2, "modules": modules})
    assert panel_s_per_solve.read(run) == pytest.approx(1.0)
    flop = 2 * sum(geqrf.panel_flops(T).values())
    assert panel_roofline.read(run) == pytest.approx(
        100.0 * flop / 197e12 / 2.0)
    assert panel_roofline.read(run) < 100
    # nothing to read: no trace, no panel module, a graph with no panel
    for r in (_run(), _run(trace={"solves": 0, "modules": modules}),
              _run(trace={"solves": 2, "modules": {}})):
        assert panel_s_per_solve.read(r) is None
        assert panel_roofline.read(r) is None
    other = _run(trace={"solves": 2, "modules": modules})
    other.graph = types.SimpleNamespace()
    assert panel_s_per_solve.read(other) is None
    assert panel_roofline.read(other) is None


def test_write_alloc_per_solve():
    run = _run(counters={"write_alloc_bytes": int(2 * 2.125 * GIB)})
    assert write_alloc_per_solve.read(run) == pytest.approx(2.125)
    assert write_alloc_per_solve.read(_run()) is None
    assert write_alloc_per_solve.read(
        _run(counters={"write_alloc_bytes": 5}, solves=(False,))) is None


def test_t_takes_room_in_the_first_solve_and_is_current_after():
    """The traffic on the host's device: T zeroed once and never restored,
    so the first solve gives its tiles room and moves no byte of them, and
    every later one finds them current on the device; A moves every solve."""
    from parsec_tpu.utils import mca

    mca.set("device_tpu_over_cpu", True)
    try:
        cell = harness.Cell(CELL, rehearsal=True)
        run = harness.Run(cell, types.SimpleNamespace(
            seed=4400000000123, trace=0, rehearsal=True))
        n, ts = cell.traffic["n"], cell.traffic["ts"]
        nt = n // ts
        st = geqrf.build(run)
        seen = [geqrf.counters(st, run)]
        for _ in range(2):
            geqrf.solve(st, run)
            seen.append(geqrf.counters(st, run))
            geqrf.restore(st, run)
        step = [{k: b[k] - a[k] for k in b} for a, b in zip(seen, seen[1:])]
        t_bytes = nt * (nt + 1) // 2 * ts * ts * 4
        assert (step[0]["write_alloc_bytes"], step[1]["write_alloc_bytes"]) \
            == (t_bytes, 0)
        assert step[0]["transfer_in_bytes"] == step[1]["transfer_in_bytes"] \
            == n * n * 4
        geqrf.solve(st, run)
        ok, detail = geqrf.check(st, run)
        assert ok and len(detail["backward_by_block"]) == nt
        geqrf.close(st, run)
    finally:
        mca.params.unset("device_tpu_over_cpu")
