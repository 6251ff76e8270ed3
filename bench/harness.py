"""One run of one benchmark cell: everything that is not particular to
a kind of traffic.

A cell is ``bench/workloads/<cell>.json``. It names its configuration
(``bench/configs/<config>.json``) and its ``kind``, the driver that
runs it (``bench/drivers/<kind>.py``). The end-to-end and per-layer
metrics a cell reports are the entries of ``BENCHMARK.json`` that list
it (or list no cells at all); a per-layer metric is computed by its
own reader, ``bench/metrics/<metric>.py``. Nothing here changes when a
cell, a configuration or a metric is added.

A driver's ``run(bench)`` builds the system under test, warms up every
shape inside set-up, measures for ``bench.seconds`` between
``bench.start_window()`` and ``bench.end_window()``, reads the memory
peak with ``bench.read_memory()``, frees the program's state, and
checks what the timed path produced against the plain reference. It
returns a :class:`Outcome`.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import time
from typing import Any, Dict, List, Optional, Tuple

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


class RunError(Exception):
    """The run cannot give a result (no chip, a bad cell file, ...)."""


def load_json(*parts: str) -> Any:
    path = os.path.join(*parts)
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import a file of the benchmark by its path: metric readers are
    named after their metric, which has dots in it."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise RunError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def seed_words(seed: int) -> Tuple[int, int]:
    """A seed of any size (more than 32 bits) as two
    uint32 words, so that jitted programs take it as data and one
    compiled program serves every seed."""
    if seed < 0:
        raise RunError(f"--seed must be >= 0, got {seed}")
    return seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF


@dataclasses.dataclass
class Outcome:
    """What a driver hands back to the harness."""
    attempted: int
    failed: int
    end_to_end: Dict[str, float]
    # numbers compared with the reference: name -> (value, limit);
    # the run is correct when every value is at most its limit
    compared: Dict[str, Tuple[float, float]]
    # counts and host-clock times that per-layer readers read
    counters: Dict[str, Any] = dataclasses.field(default_factory=dict)


class Bench:
    """The state of one run, handed to the driver."""

    def __init__(self, workload: str, seed: int, seconds: float,
                 trace: bool, process_start: float,
                 benchmark: Optional[dict] = None,
                 cell: Optional[dict] = None,
                 config: Optional[dict] = None,
                 chips: Optional[list] = None):
        self.workload = workload
        self.seed = seed
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.process_start = process_start
        self.benchmark = (benchmark if benchmark is not None
                          else load_json(ROOT, "BENCHMARK.json"))
        self.cell = (cell if cell is not None else
                     load_json(BENCH_DIR, "workloads", workload + ".json"))
        self.config = (config if config is not None else
                       load_json(BENCH_DIR, "configs",
                                 self.cell["config"] + ".json"))
        self.devices = chips or []
        # set-up split: phase -> seconds, compile time kept apart
        self.phases: Dict[str, float] = {}
        self.compiles: List[dict] = []
        self._phase = ("process_import", process_start)
        self._compile_s_in_phase = 0.0
        self._cache_events = {"hits": 0, "misses": 0}
        self.window_t0: Optional[float] = None
        self.window_t1: Optional[float] = None
        self.setup_s: Optional[float] = None
        self.compiles_in_window = 0
        self.memory: Dict[str, int] = {}

    # -- logging -----------------------------------------------------
    @staticmethod
    def log(msg: str) -> None:
        print(msg, file=sys.stderr, flush=True)

    # -- set-up ------------------------------------------------------
    def listen_to_compiles(self) -> None:
        """Count persistent-cache hits and misses, and compiles inside
        the window, through jax's monitoring events."""
        import jax

        def on_event(event: str, **_kw) -> None:
            if event == "/jax/compilation_cache/cache_hits":
                self._cache_events["hits"] += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self._cache_events["misses"] += 1

        def on_duration(event: str, _secs: float, **_kw) -> None:
            if (event == "/jax/core/compile/backend_compile_duration"
                    and self.window_t0 is not None
                    and self.window_t1 is None):
                self.compiles_in_window += 1

        jax.monitoring.register_event_listener(on_event)
        jax.monitoring.register_event_duration_secs_listener(on_duration)

    def phase(self, name: str) -> None:
        """End the current set-up phase and start ``name``."""
        now = time.time()
        prev, t0 = self._phase
        self.phases[prev] = (self.phases.get(prev, 0.0) + now - t0
                             - self._compile_s_in_phase)
        self._compile_s_in_phase = 0.0
        self._phase = (name, now)

    def compile(self, name: str, jitted, *args, **kwargs):
        """Lower and compile ``jitted`` for ``args`` and record the time
        and whether the persistent cache held it."""
        before = dict(self._cache_events)
        t0 = time.time()
        compiled = jitted.lower(*args, **kwargs).compile()
        dt = time.time() - t0
        hit = self._cache_events["hits"] > before["hits"]
        wrote = self._cache_events["misses"] > before["misses"]
        self.compiles.append({"name": name, "s": dt, "cache_hit": hit,
                              "cache_written": wrote})
        self._compile_s_in_phase += dt
        self.phases["compile"] = self.phases.get("compile", 0.0) + dt
        self.log(f"bench: compiled {name} in {dt:.3f}s "
                 f"({'cache hit' if hit else 'cache miss'}"
                 f"{', written to cache' if wrote else ''})")
        return compiled

    # -- the measured window -----------------------------------------
    def span(self, name: str):
        """A host span in the profiler's trace (traced runs only)."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def start_window(self) -> float:
        self.phase("window")
        if self.trace:
            import jax
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            jax.profiler.start_trace(TRACE_DIR)
        self.window_t0 = time.perf_counter()
        self.setup_s = time.time() - self.process_start
        self._window_span = self.span("bench.window")
        self._window_span.__enter__()
        return self.window_t0

    def in_window(self) -> bool:
        return time.perf_counter() - self.window_t0 < self.seconds

    def end_window(self) -> float:
        """Close the window; the caller has waited for its last work."""
        self.window_t1 = time.perf_counter()
        self._window_span.__exit__(None, None, None)
        if self.trace:
            import jax
            jax.profiler.stop_trace()
        return self.window_t1 - self.window_t0

    @property
    def window_s(self) -> float:
        return self.window_t1 - self.window_t0

    def read_memory(self, compiled: Tuple[Any, ...] = ()) -> int:
        """Peak device memory of the fullest chip. Two sources: the
        allocator's ``peak_bytes_in_use`` and, for each compiled
        program the window ran, its arguments, outputs and temporaries
        less what it donates (``memory_analysis``), which the allocator
        statistic may not have seen. The larger is reported."""
        stats_peak = 0
        for d in self.devices:
            st = d.memory_stats() or {}
            stats_peak = max(stats_peak, int(st.get("peak_bytes_in_use", 0)))
        program_peak = 0
        for c in compiled:
            ma = c.memory_analysis()
            if ma is None:
                continue
            program_peak = max(program_peak, int(
                ma.argument_size_in_bytes + ma.output_size_in_bytes
                + ma.temp_size_in_bytes - ma.alias_size_in_bytes))
        self.memory = {"peak_bytes_in_use": stats_peak,
                       "compiled_program_bytes": program_peak}
        self.log(f"bench: memory peak_bytes_in_use {stats_peak} B, "
                 f"largest compiled program {program_peak} B")
        return max(stats_peak, program_peak)


def metrics_of_cell(benchmark: dict, cell: str, key: str) -> List[dict]:
    """The entries of ``benchmark[key]`` that this cell reports."""
    return [m for m in benchmark.get(key, [])
            if cell in m.get("workloads", [cell])]


def per_layer_values(bench: Bench, outcome: Outcome, trace_summary,
                     peaks: dict) -> Dict[str, dict]:
    """Run each per-layer metric's reader; a reader that finds nothing
    returns None and its metric is left out."""
    out = {}
    ctx = {"cell": bench.cell, "config": bench.config,
           "counters": outcome.counters, "trace": trace_summary,
           "peaks": peaks, "chips": len(bench.devices),
           "end_to_end": outcome.end_to_end, "window_s": bench.window_s}
    for m in metrics_of_cell(bench.benchmark, bench.workload, "per_layer"):
        path = os.path.join(BENCH_DIR, "metrics", m["name"] + ".py")
        reader = load_module(path, "bench_metric_" + m["name"].replace(
            ".", "_").replace("-", "_"))
        value = reader.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def compared_block(compared: Dict[str, Tuple[float, float]]) -> dict:
    return {k: {"value": v, "limit": lim} for k, (v, lim) in compared.items()}
