"""Reduce a profiler trace of the measured window to the numbers the
per-layer metrics read.

``load_events`` turns the ``.xplane.pb`` that ``jax.profiler`` writes
into plain records: per chip, the operations of its "XLA Ops" line
(name, start, end, and the HLO text of each distinct name), and the
harness's host spans (``bench.*``). Everything after that works on
those records alone, so it is tested on a small recorded trace
(``bench/testdata/trace_small.json``):

- busy time: the union of the chip's operation intervals inside the
  window, averaged over chips; the idle share is 1 - busy / window;
- time of a kernel, by the stable name in its HLO text;
- collective time that no other operation on the chip overlaps;
- ``breakdown``: the operations that took most time, and the longest
  idle gaps, each named by the innermost harness span around it.
"""
from __future__ import annotations

import dataclasses
import glob
import json
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[int, int]
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all")
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"


def find_xspace(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def load_events(path: str, device_ids: Sequence[int]) -> dict:
    """Plain records of the trace at ``path`` for the chips
    ``device_ids``: ``{"ops": {chip: [[name, start_ns, end_ns], ...]},
    "hlo": {name: hlo text}, "spans": [[name, start_ns, end_ns], ...]}``.
    """
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    wanted = {f"/device:TPU:{i}" for i in device_ids}
    ops: Dict[str, list] = {}
    hlo: Dict[str, str] = {}
    spans = []
    for plane in pd.planes:
        if plane.name in wanted:
            evs = ops.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    # an op's event is named by its HLO instruction:
                    # keep the instruction's name, and its text once
                    name = e.name.split(" = ", 1)[0].lstrip("%")
                    evs.append([name, int(e.start_ns),
                                int(e.start_ns + e.duration_ns)])
                    if name not in hlo:
                        hlo[name] = e.name[:400]
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        spans.append([e.name, int(e.start_ns),
                                      int(e.start_ns + e.duration_ns)])
    return {"ops": ops, "hlo": hlo, "spans": spans}


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Iterable[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def total(intervals: Iterable[Interval]) -> int:
    return sum(e - s for s, e in intervals)


def gaps(busy: List[Interval], lo: int, hi: int) -> List[Interval]:
    """The complement of merged ``busy`` within [lo, hi]."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def overlap(a: List[Interval], b: List[Interval]) -> int:
    """Length of the intersection of two merged interval lists."""
    i = j = n = 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            n += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return n


def self_times(recs: Sequence[Sequence]) -> List[int]:
    """Time of each op less the ops nested inside it (a conditional or
    a loop is an op whose body's ops lie within it), in the order of
    ``recs``."""
    own = [e - s for _, s, e in recs]
    stack: List[Tuple[int, int]] = []          # (index, end)
    for i in sorted(range(len(recs)),
                    key=lambda i: (recs[i][1], -recs[i][2])):
        _, s, e = recs[i]
        while stack and stack[-1][1] <= s:
            stack.pop()
        if stack:
            own[stack[-1][0]] -= e - s
        stack.append((i, e))
    return own


def innermost_span(spans: Sequence[Sequence], t: int) -> str:
    best: Optional[Sequence] = None
    for name, s, e in spans:
        if s <= t < e and name != WINDOW_SPAN and (
                best is None or s >= best[1]):
            best = (name, s, e)
    return best[0] if best else "no harness span"


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float                     # averaged over chips
    op_s: Dict[str, float]            # self time per op name, averaged
                                      # over chips
    hlo: Dict[str, str]
    collective_s: float               # averaged over chips
    collective_exposed_s: float       # part with no other op beside it
    breakdown: dict

    def kernel_s(self, pattern: str) -> Optional[float]:
        """Time of the ops whose name or HLO text matches ``pattern``,
        averaged over chips; None where no op matches."""
        rx = re.compile(pattern)
        hits = [s for name, s in self.op_s.items()
                if rx.search(name) or rx.search(self.hlo.get(name, ""))]
        return sum(hits) if hits else None


def summarize_events(ev: dict, top: int = 10) -> TraceSummary:
    windows = [(s, e) for name, s, e in ev["spans"] if name == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"the trace holds no {WINDOW_SPAN} span")
    lo, hi = windows[0]
    chips = sorted(ev["ops"])
    if not chips:
        raise ValueError("the trace holds no device operations")
    busy_ns = coll_ns = exposed_ns = 0
    op_ns: Dict[str, int] = {}
    all_gaps: List[Interval] = []
    for chip in chips:
        recs = [(n, max(s, lo), min(e, hi)) for n, s, e in ev["ops"][chip]
                if e > lo and s < hi]
        busy = merge((s, e) for _, s, e in recs)
        busy_ns += total(busy)
        own = self_times(recs)
        for (n, s, e), t in zip(recs, own):
            op_ns[n] = op_ns.get(n, 0) + t
        coll = merge((s, e) for n, s, e in recs if COLLECTIVE.search(n))
        # compute beside a collective: ops with nothing nested in them
        other = merge((s, e) for (n, s, e), t in zip(recs, own)
                      if not COLLECTIVE.search(n) and t == e - s)
        coll_ns += total(coll)
        exposed_ns += total(coll) - overlap(coll, other)
        all_gaps += gaps(busy, lo, hi)
    k = len(chips)
    ops_sorted = sorted(op_ns.items(), key=lambda kv: -kv[1])
    gaps_sorted = sorted(all_gaps, key=lambda g: g[0] - g[1])[:top]
    breakdown = {
        "device_ops": [[n, t / k / 1e9] for n, t in ops_sorted[:top]],
        "idle_gaps": [[innermost_span(ev["spans"], (s + e) // 2),
                       (e - s) / 1e9] for s, e in gaps_sorted],
    }
    return TraceSummary(
        window_s=(hi - lo) / 1e9, busy_s=busy_ns / k / 1e9,
        op_s={n: t / k / 1e9 for n, t in op_ns.items()},
        hlo=dict(ev["hlo"]), collective_s=coll_ns / k / 1e9,
        collective_exposed_s=exposed_ns / k / 1e9, breakdown=breakdown)


def summarize(path: str, device_ids: Sequence[int]) -> TraceSummary:
    return summarize_events(load_events(path, device_ids))


def export_events(path: str, device_ids: Sequence[int], out: str) -> None:
    """Write a small recorded trace for the tests: the window's first
    whole step, from the window's start to the second ``bench.batch``
    span, with every operation of each chip in it and the HLO text of
    the custom calls and collectives."""
    ev = load_events(path, device_ids)
    lo = min(s for n, s, e in ev["spans"] if n == WINDOW_SPAN)
    hi = sorted(s for n, s, e in ev["spans"] if n == "bench.batch")[1]
    ops = {chip: sorted((r for r in recs if r[2] > lo and r[1] < hi),
                        key=lambda r: r[1])
           for chip, recs in ev["ops"].items()}
    spans = [[n, max(s, lo), min(e, hi)] for n, s, e in ev["spans"]
             if e > lo and s < hi]
    names = {r[0] for recs in ops.values() for r in recs}
    hlo = {n: t[:200] for n, t in ev["hlo"].items() if n in names and (
        "custom-call" in t or COLLECTIVE.search(n))}
    with open(out, "w") as f:
        json.dump({"ops": ops, "hlo": hlo, "spans": spans}, f)
