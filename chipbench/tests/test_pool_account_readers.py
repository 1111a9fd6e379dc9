"""The seven readers of ISSUE 37: the five that take the median of a field
over the program's pool accounts and the two halves of ``submit_per_task``,
each on made-up data, ``None`` without any, and listed for exactly the
cells it always has something to read in."""

import importlib
import json
import os

import pytest

from chipbench import run as harness

BENCH = json.load(open(os.path.join(harness.ROOT, "BENCHMARK.json")))
PTG = ["ptg_gemm.ts512", "ptg_potrf.ts512"]
DTD = ["potrf.ts512", "potrf2x2.ts512", "gemm.ts512"]
#: metric -> (layer, the account field it reads)
POOL = {"pool_head_ms": ("device issue", "head_ns"),
        "pool_push_ms": ("residency", "push_ns"),
        "pool_call_ms": ("device issue", "call_ns"),
        "pool_own_ms": ("device issue", "own_ns"),
        "pool_away_ms": ("ready/sched", "away_ns")}
HALVES = {"gather_per_task": "tpudev.gather_ns",
          "call_per_task": "tpudev.call_ns"}


@pytest.fixture()
def accounts():
    """The program's deque, emptied for the test and put back after it."""
    from parsec_tpu.utils import xla_trace
    kept = list(xla_trace.POOL_ACCOUNTS)
    xla_trace.POOL_ACCOUNTS.clear()
    yield xla_trace.POOL_ACCOUNTS
    xla_trace.POOL_ACCOUNTS.clear()
    xla_trace.POOL_ACCOUNTS.extend(kept)


@pytest.mark.parametrize("name", sorted(POOL))
def test_a_pool_reader_takes_the_median_over_the_accounts(name, accounts):
    reader = importlib.import_module("chipbench.layers." + name)
    field = POOL[name][1]
    assert reader.read(None) is None                # no pool has ended
    # the warm-up pool far out, then the window's: the median drops it
    for ns in (900_000_000, 4_000_000, 6_000_000, 5_000_000, 7_000_000):
        accounts.append({field: ns, "life_ns": 10 * ns})
    assert reader.read(None) == pytest.approx(6.0)
    accounts.append({field: 1_000_000})
    assert reader.read(None) == pytest.approx(5.5)


def test_a_program_without_the_account_gives_nothing_to_read(monkeypatch):
    from parsec_tpu.utils import xla_trace
    monkeypatch.delattr(xla_trace, "POOL_ACCOUNTS")
    for name in POOL:
        reader = importlib.import_module("chipbench.layers." + name)
        assert reader.read(None) is None


@pytest.mark.parametrize("name", sorted(HALVES))
def test_a_half_of_submit_on_a_synthetic_snapshot(name, monkeypatch):
    from parsec_tpu.utils.hist import histograms
    held = {}
    monkeypatch.setattr(histograms, "snapshot", lambda: held)
    reader = importlib.import_module("chipbench.layers." + name)
    assert reader.read(None) is None                # histogram absent
    held.update({HALVES[name]: {"count": 0, "sum_ns": 0},
                 "tpudev.retire_ns": {"count": 0, "sum_ns": 0}})
    assert reader.read(None) is None                # histogram empty
    # 102 records (two failed attempts) over 100 executed tasks
    held.update({HALVES[name]: {"count": 102, "sum_ns": 7_000_000},
                 "tpudev.retire_ns": {"count": 100, "sum_ns": 1}})
    assert reader.read(None) == pytest.approx(70.0)


def test_the_seven_are_listed_for_their_cells():
    entries = [m for m in BENCH["per_layer"]
               if m["name"] in POOL or m["name"] in HALVES]
    assert [m["name"] for m in entries] == list(POOL) + list(HALVES)
    for m in entries:
        layer, cells, unit = (POOL[m["name"]][0], PTG, "ms") \
            if m["name"] in POOL else ("device issue", DTD, "us/task")
        assert m == {"name": m["name"], "unit": unit, "better": "lower",
                     "source": "program_span", "layer": layer,
                     "moves": "tasks_per_s", "workloads": cells}
        assert os.path.isfile(os.path.join(
            harness.ROOT, "chipbench", "layers", m["name"] + ".py"))
