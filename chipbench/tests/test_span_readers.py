"""The readers of the program's own spans: each on a synthetic snapshot of
``parsec_tpu.utils.hist.histograms``, a reader file for every ``per_layer``
entry, and the new names on a rehearsed traced line."""

import importlib
import json
import os
import subprocess
import sys

import pytest

from chipbench import run as harness

ROOT = harness.ROOT
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NEW = {"link_per_task": ("potrf.ts512", "potrf2x2.ts512"),
       "submit_per_task": ("potrf.ts512", "potrf2x2.ts512"),
       "poll_per_task": ("potrf.ts512", "potrf2x2.ts512"),
       "retire_per_task": ("potrf.ts512", "potrf2x2.ts512"),
       "ready_wait_p99": ("potrf.ts512", "potrf2x2.ts512"),
       "stage_in_per_tile": ("potrf.ts2048",)}


def hist(count, sum_ns):
    return {"count": count, "sum_ns": sum_ns, "buckets": []}


SNAPSHOT = {"dtd.link_ns": hist(100, 5_000_000),          # 50 us an insert
            "tpudev.submit_ns": hist(102, 8_000_000),      # two failed attempts
            "tpudev.poll_ns": hist(40, 1_000_000),         # 40 passes
            "tpudev.retire_ns": hist(100, 6_000_000),
            "tpudev.stage_in_ns": hist(10, 25_000_000)}
WANT = {"link_per_task": 50.0, "submit_per_task": 80.0, "poll_per_task": 10.0,
        "retire_per_task": 60.0, "stage_in_per_tile": 2500.0}


@pytest.fixture()
def snapshot(monkeypatch):
    from parsec_tpu.utils.hist import histograms
    held = {}
    monkeypatch.setattr(histograms, "snapshot", lambda: held)
    return held


@pytest.mark.parametrize("name", sorted(WANT))
def test_a_span_reader_on_a_synthetic_snapshot(name, snapshot):
    reader = importlib.import_module("chipbench.layers." + name)
    assert reader.read(None) is None                # histogram absent
    snapshot.update({k: hist(0, 0) for k in SNAPSHOT})
    assert reader.read(None) is None                # histogram empty
    snapshot.update(SNAPSHOT)
    assert reader.read(None) == pytest.approx(WANT[name])


def test_every_per_layer_entry_has_a_reader_file_and_the_new_ones_their_cells():
    for m in BENCH["per_layer"]:
        assert os.path.isfile(os.path.join(
            ROOT, "chipbench", "layers", m["name"] + ".py")), m["name"]
    listed = {m["name"]: tuple(m["workloads"]) for m in BENCH["per_layer"]
              if "workloads" in m}
    for name, cells in NEW.items():
        assert listed[name] == cells
    # appended, not inserted: the accepted entries keep their places
    assert [m["name"] for m in BENCH["per_layer"]][-6:] == [
        "ready_wait_p99", "link_per_task", "submit_per_task", "poll_per_task",
        "retire_per_task", "stage_in_per_tile"]


@pytest.mark.parametrize("cell", ["potrf.ts512", "potrf.ts2048"])
def test_a_rehearsed_traced_line_would_report_the_new_names(cell):
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", cell, "--seed", "2600000001", "--seconds", "1",
         "--trace", "1", "--rehearsal"],
        cwd=ROOT, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["failed"] == 0
    want = {name for name, cells in NEW.items() if cell in cells}
    assert want <= set(line["would_report"])
    for name in want:
        assert f"chipbench: {name} found nothing" not in out.stderr
