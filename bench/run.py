#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of the machine it starts on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout. The cell is ``bench/workloads/<cell>.json``.
With ``--trace 0`` the result reports the cell's end-to-end metrics,
with ``--trace 1`` its per-layer metrics, read from a profiler trace of
the window. The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` also ``breakdown``, and last ``compared``: each number
checked against the plain reference beside its limit. The same numbers
are the last lines of standard error.

It exits non-zero, printing no result, where JAX finds no TPU or fewer
chips than the cell asks for, or where a device is missing from
``bench/peaks.json``. JAX's persistent compilation cache is kept where
``JAX_COMPILATION_CACHE_DIR`` says, else in ``.jax_cache`` at the root
of the checkout.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def process_start_time() -> float:
    try:
        import psutil
        return psutil.Process().create_time()
    except ImportError:
        return time.time()


PROCESS_START = process_start_time()
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402


def parse(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def enable_cache() -> str:
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        harness.ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    # every program this run compiles goes into the cache, however
    # quick its compile: the next run of the cell then compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def take_chips(n: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise harness.RunError(
            f"needs a TPU; JAX found {devs[0].platform}")
    if len(devs) < n:
        raise harness.RunError(
            f"the cell asks for {n} chips; JAX found {len(devs)}")
    return devs[:n]


def main(argv=None) -> int:
    args = parse(argv)
    try:
        bench = harness.Bench(args.workload, args.seed, args.seconds,
                              bool(args.trace), PROCESS_START)
        sys.path.insert(0, os.path.join(harness.ROOT, "src"))
        import jax  # noqa: F401
        cache = enable_cache()
        bench.devices = take_chips(int(bench.cell["chips"]))
        kind = bench.devices[0].device_kind
        peaks = harness.load_json(harness.BENCH_DIR, "peaks.json")
        if kind not in peaks:
            raise harness.RunError(f"no peaks for device {kind!r} in "
                                   f"bench/peaks.json")
        bench.log(f"bench: {args.workload} seed {args.seed} on "
                  f"{len(bench.devices)} x {kind}, compile cache {cache}")
        bench.listen_to_compiles()
        driver = harness.load_module(
            os.path.join(harness.BENCH_DIR, "drivers",
                         bench.cell["kind"] + ".py"),
            "bench_driver_" + bench.cell["kind"])
        bench.phase("init")
        outcome = driver.run(bench)
    except harness.RunError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    return report(bench, outcome, peaks[kind])


def report(bench, outcome, peaks) -> int:
    setup = {k: round(v, 3) for k, v in bench.phases.items()
             if k != "window"}
    bench.log(f"bench: setup_s {bench.setup_s:.3f} split {setup}")
    bench.log(f"bench: compiles {bench.compiles}")
    if bench.compiles_in_window:
        bench.log(f"bench: {bench.compiles_in_window} compile(s) inside "
                  f"the window")
    dev = bench.devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(bench.devices),
              "memory_peak_bytes": max(bench.memory.values() or [0])}
    result = {"correct": all(v <= lim for v, lim in
                             outcome.compared.values()),
              "attempted": outcome.attempted, "failed": outcome.failed}
    if bench.trace:
        import devtrace
        summary = devtrace.summarize(devtrace.find_xspace(
            harness.TRACE_DIR), [d.id for d in bench.devices])
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        metrics = harness.per_layer_values(bench, outcome, summary, peaks)
        result["metrics"] = metrics
        result["device"] = device
        result["breakdown"] = summary.breakdown
    else:
        e2e = dict(outcome.end_to_end, setup_s=bench.setup_s)
        metrics = {}
        for m in harness.metrics_of_cell(bench.benchmark, bench.workload,
                                         "end_to_end"):
            if m["name"] in e2e:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
        result["metrics"] = metrics
        result["device"] = device
    result["compared"] = harness.compared_block(outcome.compared)
    for name, (v, lim) in outcome.compared.items():
        bench.log(f"compared {name} {v!r} limit {lim!r} "
                  f"{'ok' if v <= lim else 'FAILED'}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
