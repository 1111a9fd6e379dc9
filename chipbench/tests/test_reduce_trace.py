"""The reduction from a profiler trace to busy time, idle share, gaps and
per-module sums: on hand-made events, and on a small trace recorded on the
chip (``record_trace.py`` says how)."""

import os

import pytest

from chipbench import reduce_trace as rt

SMALL = os.path.join(os.path.dirname(__file__), "data", "small.xplane.pb")


def test_union_and_clip():
    assert rt.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]
    assert rt.clip([(0, 10), (20, 30), (40, 50)], 5, 25) == [(5, 10), (20, 25)]


def test_module_name_drops_the_fingerprint():
    assert rt.module_name("jit_tile_gemm(123456789)") == "jit_tile_gemm"
    assert rt.module_name("jit_tile_gemm_update") == "jit_tile_gemm_update"


def test_reduce_events_by_hand():
    # window 0..1000 ns from the spans; programs at 100-200, 300-400 (gemm),
    # 400-450 (syrk), and one outside the window that must not count
    programs = [("jit_tile_gemm", 100, 100), ("jit_tile_gemm", 300, 100),
                ("jit_tile_syrk", 400, 50), ("jit_tile_gemm", 2000, 100)]
    ops = [(100, 40), (150, 50), (300, 100), (400, 50), (2000, 100)]
    spans = [("insert", 0, 500), ("wait", 500, 1000)]
    r = rt.reduce_events(programs, ops, spans)
    assert r["window_s"] == pytest.approx(1000e-9)
    # busy: 100-140, 150-200, 300-450 = 240 ns
    assert r["busy_s"] == pytest.approx(240e-9)
    assert r["modules"]["jit_tile_gemm"] == {"count": 2,
                                             "seconds": pytest.approx(200e-9)}
    assert r["modules"]["jit_tile_syrk"]["count"] == 1
    assert r["programs"] == 3
    # programs merge to 100-200 and 300-450: one gap of 100 ns
    assert r["launch_gap_p50_us"] == pytest.approx(0.1)
    # idle: 0-100, 140-150, 200-300 inside "insert"; 450-1000 has its
    # middle (725) inside "wait"
    idle = dict(r["idle_gaps"])
    assert idle["insert"] == pytest.approx(210e-9)
    assert idle["wait"] == pytest.approx(550e-9)
    assert r["busy_s"] + sum(idle.values()) == pytest.approx(r["window_s"])
    assert r["device_ops"][0][0] == "jit_tile_gemm"


def test_reduce_events_without_ops_or_spans():
    r = rt.reduce_events([("jit_a", 10, 10), ("jit_a", 30, 10)], [], [])
    assert r["window_s"] == pytest.approx(30e-9)
    assert r["busy_s"] == pytest.approx(20e-9)
    assert dict(r["idle_gaps"]) == {"outside": pytest.approx(10e-9)}
    empty = rt.reduce_events([], [], [])
    assert empty["busy_s"] == 0 and empty["launch_gap_p50_us"] is None


@pytest.mark.skipif(not os.path.exists(SMALL), reason="no recorded trace")
def test_recorded_chip_trace():
    r = rt.reduce_file(SMALL)
    # record_trace.py issued 12 + 3 programs. In this file the device's
    # plane runs ~1.3 ms ahead of the host's (the syrk programs end before
    # the span that issued them starts), so the first four programs start
    # before the first host span and fall outside the window: 8 + 3. A
    # window of seconds does not feel 1.3 ms; this one of 10 ms does.
    programs, _ops, spans = rt.load(SMALL)
    assert len(programs) == 15 and [n for n, _s, _e in spans] == \
        ["insert", "wait", "refill"]
    assert r["modules"]["jit_tile_gemm"]["count"] == 8
    assert r["modules"]["jit_tile_syrk"]["count"] == 3
    assert r["programs"] == 11
    assert 0 < r["busy_s"] < r["window_s"]
    kernel_s = sum(m["seconds"] for m in r["modules"].values())
    # operations run inside their programs, one at a time
    assert r["busy_s"] <= kernel_s * 1.001
    idle = dict(r["idle_gaps"])
    assert r["busy_s"] + sum(idle.values()) == pytest.approx(r["window_s"])
    # three sleeps of 2 ms inside the insert span left the device idle
    assert idle["insert"] > 5e-3
    assert r["launch_gap_p50_us"] > 0
    assert {k: r[k] for k in GOLDEN} == pytest.approx(GOLDEN, rel=1e-9)


#: read once from the recorded file with this code; a change to the
#: reduction that moves them is a change to the yardstick
GOLDEN = {"window_s": 0.01056023, "busy_s": 3.0684e-05,
          "launch_gap_p50_us": 199.288}
