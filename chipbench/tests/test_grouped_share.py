"""``grouped_share``: its reader on synthetic snapshots of
``parsec_tpu.utils.hist.histograms``, its entry in BENCHMARK.json, and its
name on a rehearsed traced line."""

import json
import os
import subprocess
import sys

import pytest

from chipbench import run as harness
from chipbench.layers import grouped_share

ROOT = harness.ROOT
CELLS = ["potrf.ts512", "potrf2x2.ts512"]


def hist(count, total):
    return {"count": count, "sum_ns": total, "buckets": []}


@pytest.mark.parametrize("snapshot, want", [
    ({}, None),                                         # no histograms at all
    ({"tpudev.retire_ns": hist(100, 1)}, None),         # a program before groups
    ({"tpudev.group_tasks": hist(0, 0),
      "tpudev.retire_ns": hist(0, 0)}, None),           # nothing retired
    ({"tpudev.group_tasks": hist(0, 0),
      "tpudev.retire_ns": hist(100, 1)}, 0.0),          # no group formed
    ({"tpudev.group_tasks": hist(10, 80),
      "tpudev.retire_ns": hist(100, 1)}, 80.0),         # ten programs of 8
])
def test_reader_on_a_synthetic_snapshot(monkeypatch, snapshot, want):
    from parsec_tpu.utils.hist import histograms
    monkeypatch.setattr(histograms, "snapshot", lambda: snapshot)
    got = grouped_share.read(None)
    assert got == want if want is None else got == pytest.approx(want)


def test_entry_lists_the_two_host_bound_cells():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(m for m in bench["per_layer"] if m["name"] == "grouped_share")
    assert entry == {"name": "grouped_share", "unit": "%", "better": "higher",
                     "source": "program_counter", "layer": "device issue",
                     "moves": "tasks_per_s", "workloads": CELLS}


def test_a_rehearsed_traced_line_would_report_it():
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", "potrf.ts512", "--seed", "2700000001", "--seconds", "1",
         "--trace", "1", "--rehearsal"],
        cwd=ROOT, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["failed"] == 0
    assert "grouped_share" in line["would_report"]
    assert "chipbench: grouped_share found nothing" not in out.stderr
