#!/usr/bin/env python3
"""Readings of a cell's control and planted faults, on the chip at the
cell's own size; they set the upper ends of the check's limits.

    python3 bench/control.py --workload <cell> --seeds 11,12,13

One JSON line per seed: for each reading (the control in a lower
precision, each planted fault), the numbers the cell compares. The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402
import run  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    args = p.parse_args(argv)
    cell = harness.load_json(harness.BENCH_DIR, "workloads",
                             args.workload + ".json")
    conf = harness.load_json(harness.BENCH_DIR, "configs",
                             cell["config"] + ".json")
    sys.path.insert(0, os.path.join(harness.ROOT, "src"))
    try:
        run.enable_cache()
        run.take_chips(int(cell["chips"]))
    except harness.RunError as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    driver = harness.load_module(
        os.path.join(harness.BENCH_DIR, "drivers", cell["kind"] + ".py"),
        "bench_driver_" + cell["kind"])
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps({"seed": seed, **driver.control(
            cell, conf, seed)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
