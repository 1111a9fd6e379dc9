"""BENCHMARK.json against the contract's limits, every cell's files found by
name, and the last line's shape."""

import importlib
import json
import os
import re
import types

import pytest

from chipbench import check_line
from chipbench import run as harness

ROOT = harness.ROOT
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["chipbench"]
    assert isinstance(BENCH["run_seconds"], int) and \
        1 <= BENCH["run_seconds"] <= 51
    assert 2 <= len(BENCH["workloads"]) <= 24
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 2)
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


def test_names_units_and_keys():
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(set(names)) == len(names)
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                                "program_counter", "host_clock")
        assert m["moves"] in e2e
        assert "\n" not in m["layer"] and len(m["layer"]) <= 200
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= set(CELLS)
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("chipbench/") and len(c["source"]) <= 200
        assert any(w["config"] == c["name"] for w in BENCH["workloads"])


@pytest.mark.parametrize("name", CELLS)
def test_a_cell_is_nothing_but_files_found_by_name(name):
    cell = harness.Cell(name, rehearsal=False)
    for fn in ("build", "restore", "solve", "check", "counters", "close",
               "tasks", "flops", "dot_flops"):
        assert callable(getattr(cell.graph, fn)), fn
    importlib.import_module("chipbench.reference." + cell.config["reference"])
    reported = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2
    assert cell.per_layer and all(m["moves"] in reported
                                  for m in cell.per_layer)
    for traced in (False, True):
        for _entry, reader in cell.readers(traced):
            assert callable(reader.read)
    assert cell.config["ranks"] == cell.chips
    assert cell.config["compiles_in_window_allowed"] is False


def fake_run(traced):
    run = types.SimpleNamespace(
        attempted=12, failed=0, rehearsal=False, traced=traced,
        trace={"busy_s": 1.5, "window_s": 3.0,
               "device_ops": [["jit_tile_gemm", 1.0]] * 14,
               "idle_gaps": [["wait", 1.5]]})
    rec = {"correct": True,
           "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                      "memory_peak_bytes": 123}}
    return run, rec


def test_last_line_has_the_contract_keys_and_no_others():
    run, rec = fake_run(traced=False)
    line = harness.last_line(run, rec,
                             {"tflops": {"value": 1.0, "unit": "TFLOP/s"}})
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    run, rec = fake_run(traced=True)
    line = harness.last_line(run, rec, {})
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device", "breakdown"}
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes", "busy_s", "window_s"}
    assert len(line["breakdown"]["device_ops"]) == 10
    json.dumps(line)


def test_a_metric_with_nothing_to_read_is_left_out_and_named(capsys):
    found = types.SimpleNamespace(read=lambda run: 2.5)
    nothing = types.SimpleNamespace(read=lambda run: None)
    cell = types.SimpleNamespace(name="a.cell", readers=lambda traced: [
        ({"name": "found", "unit": "us"}, found),
        ({"name": "nothing", "unit": "us"}, nothing)])
    out = harness.metrics_of(cell, types.SimpleNamespace(traced=True))
    assert out == {"found": {"value": 2.5, "unit": "us"}}
    assert "nothing found nothing to read in a.cell" in capsys.readouterr().err


@pytest.mark.parametrize("traced", [False, True])
@pytest.mark.parametrize("name", CELLS)
def test_check_line_wants_every_listed_metric_and_no_other(name, traced):
    cell = harness.Cell(name, rehearsal=False)
    want = check_line.expected(BENCH, name, traced)
    # the harness and the check agree on what a cell reports
    assert sorted(want) == sorted(
        m["name"] for m in (cell.per_layer if traced else cell.end_to_end))
    run, rec = fake_run(traced)
    rec["device"]["count"] = cell.chips
    metrics = {n: {"value": 1.0, "unit": u} for n, u in want.items()}
    text = "RUN {}\n" + json.dumps(harness.last_line(run, rec, metrics))
    assert check_line.faults(BENCH, name, traced, text) == []
    dropped = sorted(metrics)[0]
    del metrics[dropped]
    metrics["stray"] = {"value": 1.0, "unit": "s"}
    text = json.dumps(harness.last_line(run, rec, metrics))
    assert check_line.faults(BENCH, name, traced, text) == [
        f"metric {dropped!r} is missing",
        "metric 'stray' is not one of the cell's"]


def test_a_rehearsal_prints_no_metric():
    run, rec = fake_run(traced=False)
    run.rehearsal = True
    line = harness.last_line(run, rec,
                             {"tflops": {"value": 1.0, "unit": "TFLOP/s"}})
    assert line["metrics"] == {} and line["rehearsal"] is True


def test_unknown_device_kind_is_an_error():
    peaks = json.load(open(os.path.join(ROOT, "chipbench", "peaks.json")))
    assert "TPU v5 lite" in peaks["by_device_kind"]
    assert peaks["by_device_kind"]["TPU v5 lite"]["bf16_flops_per_s"] == 197e12
    assert "source" in peaks
