"""Device time of the train step's named scopes.

``repro.core.sharded_ddal``'s train step wraps each of its pieces in
``jax.named_scope`` (``ddal.grad``, ``ddal.window``, ``ddal.sketch``,
``ddal.exchange``, ``ddal.combine``, ``ddal.optimizer``). The compiler
keeps the name stack as each instruction's ``op_name`` metadata. The
device ops of the profiler's "XLA Ops" line carry only the
instruction's name, but the trace also holds each program's compiled
HLO (an ``Hlo Proto`` stat in its ``/host:metadata`` plane), which maps
the name to its ``op_name``. An op's scope is the innermost ``ddal.*``
component of that path, so a conditional op (``ddal.exchange/cond``)
keeps its own scope while its body's ops (``ddal.exchange/cond/
branch_1_fun/ddal.combine/...``) take theirs. Ops that the compiler
adds carry no name; they take the scope of the conditional or loop
around them.

This extends ``devtrace``, whose records and ``TraceSummary`` it
reads unchanged: the scopes are a map from op name to scope (kept
beside ``hlo`` under ``"scope"`` in a recorded trace), and a scope's
time is the sum of ``TraceSummary.op_s`` (self time, clipped to the
window, averaged over chips) over its ops, with the ops of no scope
under ``"unscoped"``. The scope times therefore add up to the sum of
``op_s`` exactly.
"""
from __future__ import annotations

import json
import sys
from typing import Dict, Iterator, Optional, Sequence, Tuple

import devtrace
import harness

PREFIX = "ddal."
UNSCOPED = "unscoped"
METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"


def scope_of(op_name: Optional[str]) -> Optional[str]:
    """The innermost ``ddal.*`` component of an op's name stack."""
    hits = [c for c in (op_name or "").split("/") if c.startswith(PREFIX)]
    return hits[-1] if hits else None


# The trace is a serialized ``XSpace`` protobuf. The few fields read
# here are decoded from the wire format by hand; their numbers are
# those of tsl/profiler/protobuf/xplane.proto and xla/service/hlo.proto.

def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, object]]:
    """(field number, value) of each field of one message: an int for
    a varint, a memoryview for any other field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {kind} in the trace")
        yield key >> 3, value


def _text(buf) -> str:
    return bytes(buf).decode("utf-8", "replace")


def _add_op_names(hlo_proto, out: Dict[str, Optional[str]]) -> None:
    """Add ``{instruction name: op_name}`` of one ``HloProto``
    (hlo_module 1 > computations 3 > instructions 2 > name 1,
    metadata 7 > op_name 2). Programs may share an instruction name;
    an ``op_name`` with a scope is kept over one without."""
    for f, module in _fields(hlo_proto):
        if f != 1:
            continue
        for g, comp in _fields(module):
            if g != 3:
                continue
            for h, ins in _fields(comp):
                if h != 2:
                    continue
                name = op_name = None
                for k, v in _fields(ins):
                    if k == 1:
                        name = _text(v)
                    elif k == 7:
                        op_name = next((_text(w) for m, w in _fields(v)
                                        if m == 2), None)
                if name is not None and scope_of(out.get(name)) is None:
                    out[name] = op_name


def hlo_op_names(path: str) -> Dict[str, Optional[str]]:
    """``{instruction name: op_name}`` over every program whose HLO the
    trace at ``path`` holds (XSpace planes 1 > name 2, event_metadata
    4 > value 2 > stats 5 > metadata_id 1, bytes_value 6;
    stat_metadata 5 > value 2 > id 1, name 2)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: Dict[str, Optional[str]] = {}
    for f, plane in _fields(space):
        if f != 1:
            continue
        parts = list(_fields(plane))
        if not any(k == 2 and _text(v) == METADATA_PLANE
                   for k, v in parts):
            continue
        stat_names = {}
        for k, entry in parts:
            if k == 5:
                meta = dict(_fields(dict(_fields(entry))[2]))
                stat_names[meta.get(1, 0)] = _text(meta.get(2, b""))
        for k, entry in parts:
            if k != 4:
                continue
            for m, stat in _fields(dict(_fields(entry))[2]):
                if m != 5:
                    continue
                st = dict(_fields(stat))
                if (stat_names.get(st.get(1, 0)) == HLO_PROTO_STAT
                        and 6 in st):
                    _add_op_names(st[6], out)
    return out


def resolve(recs: Sequence[Sequence]) -> Dict[str, Optional[str]]:
    """``{op name: scope or None}`` for one chip's ops, given as
    ``(name, start, end, op_name)``. An op without a scope of its own
    (a copy or a slice that the compiler added, whose ``op_name`` is
    empty) takes the scope of the innermost op around it: the
    conditional or loop whose body it is. Ops of two programs may
    share a name; where one of them has a scope, the name takes it."""
    scope: Dict[str, Optional[str]] = {}
    stack: list = []                             # (end, scope)
    for name, s, e, op_name in sorted(recs, key=lambda r: (r[1], -r[2])):
        while stack and stack[-1][0] <= s:
            stack.pop()
        own = scope_of(op_name) or (stack[-1][1] if stack else None)
        if scope.get(name) is None:
            scope[name] = own
        stack.append((e, own))
    return scope


def scopes_of_events(ev: dict, op_names: Dict[str, Optional[str]]
                     ) -> Dict[str, Optional[str]]:
    """The scope of each op of ``devtrace`` records, over their chips."""
    scope: Dict[str, Optional[str]] = {}
    for recs in ev["ops"].values():
        got = resolve([(n, s, e, op_names.get(n)) for n, s, e in recs])
        for name, sc in got.items():
            if scope.get(name) is None:
                scope[name] = sc
    return scope


def load_scopes(path: str, device_ids: Sequence[int]
                ) -> Dict[str, Optional[str]]:
    """The scope of each device op of the trace at ``path``, on the
    chips ``device_ids``."""
    return scopes_of_events(devtrace.load_events(path, device_ids),
                            hlo_op_names(path))


def scope_s(summary: devtrace.TraceSummary,
            scope: Dict[str, Optional[str]]) -> Dict[str, float]:
    """Self time per scope, clipped to the window and averaged over
    chips, with the ops of no scope under ``"unscoped"``."""
    out: Dict[str, float] = {}
    for name, t in summary.op_s.items():
        key = scope.get(name) or UNSCOPED
        out[key] = out.get(key, 0.0) + t
    return out


def export_events(path: str, device_ids: Sequence[int], out: str) -> None:
    """Write a small recorded trace for the tests, as
    ``devtrace.export_events`` does, but over the window's first two
    whole steps (a local step, then a share step) and with the scope of
    every op in it beside the HLO text."""
    ev = devtrace.load_events(path, device_ids)
    scope = scopes_of_events(ev, hlo_op_names(path))
    lo = min(s for n, s, e in ev["spans"] if n == devtrace.WINDOW_SPAN)
    hi = sorted(s for n, s, e in ev["spans"] if n == "bench.batch")[2]
    ops = {chip: sorted((r for r in recs if r[2] > lo and r[1] < hi),
                        key=lambda r: r[1])
           for chip, recs in ev["ops"].items()}
    spans = [[n, max(s, lo), min(e, hi)] for n, s, e in ev["spans"]
             if e > lo and s < hi]
    names = sorted({r[0] for recs in ops.values() for r in recs})
    hlo = {n: t[:200] for n, t in ev["hlo"].items() if n in names and (
        "custom-call" in t or devtrace.COLLECTIVE.search(n))}
    with open(out, "w") as f:
        json.dump({"ops": ops, "hlo": hlo, "spans": spans,
                   "scope": {n: scope.get(n) for n in names}}, f)


def trace_scopes(chips: int) -> Dict[str, Optional[str]]:
    """The scope map of the trace that the harness wrote, whose chips
    are the first ``chips`` devices."""
    return load_scopes(devtrace.find_xspace(harness.TRACE_DIR),
                       range(chips))


def read(ctx) -> Optional[Dict[str, float]]:
    """The traced run's time per scope, kept in the run's ``ctx`` for
    the next reader and logged to standard error; None where no op of
    the trace has a scope (an untraced run, or a program that names
    none)."""
    t = ctx["trace"]
    if t is None:
        return None
    if "scope_s" not in ctx:
        times = scope_s(t, trace_scopes(ctx["chips"]))
        for k, v in sorted(times.items(), key=lambda kv: -kv[1]):
            print(f"bench: scope {k} {v:.6f} s, "
                  f"{100.0 * v / t.busy_s:.2f}% of busy", file=sys.stderr,
                  flush=True)
        ctx["scope_s"] = times if set(times) - {UNSCOPED} else None
    return ctx["scope_s"]


def per_step_ms(ctx, name: str, steps: int) -> Optional[float]:
    """Milliseconds of scope ``name`` per step, over ``steps`` steps;
    None where the trace has no scopes or this scope ran no op."""
    times = read(ctx)
    if not times or name not in times or not steps:
        return None
    return 1e3 * times[name] / steps
