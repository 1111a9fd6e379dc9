#!/usr/bin/env python3
"""chipbench/check_line.py — a run's last line against BENCHMARK.json, as the
driver holds it: the five keys, every metric the cell lists for that kind of
run with its unit and no other, and the device's keys.

    python3 chipbench/run.py --workload <cell> ... --trace <t> > out.txt
    python3 chipbench/check_line.py --workload <cell> --trace <t> < out.txt

Prints the faults and exits 1 if there are any.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def expected(bench, cell, traced):
    """{metric name: unit} the cell's line must carry."""
    def here(m):
        return cell in m.get("workloads", [cell])
    end_to_end = [m for m in bench["end_to_end"] if here(m)]
    if not traced:
        return {m["name"]: m["unit"] for m in end_to_end}
    reported = {m["name"] for m in end_to_end}
    return {m["name"]: m["unit"] for m in bench["per_layer"]
            if here(m) and m["moves"] in reported}


def faults(bench, cell, traced, text):
    """Why the last line of ``text`` is not the line the driver wants."""
    lines = text.strip().splitlines()
    try:
        line = json.loads(lines[-1])
    except (IndexError, ValueError):
        return ["the last line is not a JSON object"]
    if not isinstance(line, dict):
        return ["the last line is not a JSON object"]
    out = [f"key {k!r} is missing" for k in
           ("correct", "attempted", "failed", "metrics", "device")
           if k not in line]
    if out:
        return out
    want, got = expected(bench, cell, traced), line["metrics"]
    out += [f"metric {n!r} is missing" for n in want if n not in got]
    out += [f"metric {n!r} is not one of the cell's" for n in got
            if n not in want]
    for name in set(want) & set(got):
        m = got[name]
        if not isinstance(m.get("value"), (int, float)) \
                or m.get("unit") != want[name]:
            out.append(f"metric {name!r} is {m}, wanted a number in "
                       f"{want[name]!r}")
    chips = {w["name"]: w["chips"] for w in bench["workloads"]}[cell]
    dev = line["device"]
    keys = ["platform", "kind", "count", "memory_peak_bytes"] \
        + ["busy_s", "window_s"] * traced
    out += [f"device lacks {k!r}" for k in keys if k not in dev]
    if not out:
        if dev["count"] != chips:
            out.append(f"device.count is {dev['count']}, the cell asks for "
                       f"{chips}")
        if traced and not 0 < dev["busy_s"] <= dev["window_s"]:
            out.append(f"busy_s {dev['busy_s']} is not above 0 and at most "
                       f"window_s {dev['window_s']}")
    for key in ("device_ops", "idle_gaps"):
        if len(line.get("breakdown", {}).get(key, [])) > 10:
            out.append(f"breakdown.{key} has more than 10 entries")
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    found = faults(bench, args.workload, bool(args.trace), sys.stdin.read())
    for fault in found:
        print(f"check_line: {fault}")
    return 1 if found else 0


if __name__ == "__main__":
    sys.exit(main())
