"""Device time by the train step's named scopes (``scopes.py``), on
hand-made records, on a hand-made profiler trace with the programs'
HLO, and on a small trace
recorded on a TPU v5e (``testdata/trace_scopes_small.json``: the first
two steps of a ``train.mamba2-780m.share2`` window, a local step and a
share step, exported by ``scopes.export_events``)."""
import os

import pytest

import devtrace
import harness
import scopes

SCOPED = os.path.join(harness.BENCH_DIR, "testdata",
                      "trace_scopes_small.json")
SMALL = os.path.join(harness.BENCH_DIR, "testdata", "trace_small.json")
READERS = ("grad_ms.train", "sketch_ms.train", "window_ms.train",
           "optimizer_ms.train", "combine_ms.train")


def reader(name):
    return harness.load_module(
        os.path.join(harness.BENCH_DIR, "metrics", name + ".py"),
        "bench_metric_" + name.replace(".", "_"))


def test_innermost_ddal_scope_names_an_op():
    assert scopes.scope_of("jit(train_step)/ddal.exchange/cond/"
                           "branch_1_fun/ddal.combine/reduce_sum") \
        == "ddal.combine"
    assert scopes.scope_of("jit(train_step)/ddal.grad/"
                           "vmap(transpose(jvp()))/dot_general") \
        == "ddal.grad"
    assert scopes.scope_of("jit(train_step)/jit(remainder)/rem") is None
    assert scopes.scope_of(None) is None


# a share step in miniature: the exchange's conditional, with a combine
# op and a copy the compiler added inside it, then ops of no scope
RECS = [("conditional.1", 0, 10,
         "jit(train_step)/ddal.exchange/cond"),
        ("fusion.2", 2, 5, "jit(train_step)/ddal.exchange/cond/"
                           "branch_1_fun/ddal.combine/div"),
        ("copy.3", 6, 7, None),
        ("fusion.4", 10, 12, "jit(train_step)/jit(remainder)/rem"),
        ("copy.5", 12, 13, None),
        ("fusion.6", 13, 20, "jit(train_step)/ddal.grad/tanh")]


def test_conditional_and_its_body_keep_their_own_scopes():
    scope = scopes.resolve(RECS)
    assert scope == {"conditional.1": "ddal.exchange",
                     "fusion.2": "ddal.combine",
                     "copy.3": "ddal.exchange", "fusion.4": None,
                     "copy.5": None, "fusion.6": "ddal.grad"}
    ev = {"ops": {"/device:TPU:0": [list(r[:3]) for r in RECS]},
          "hlo": {}, "spans": [["bench.window", 0, 20]]}
    t = devtrace.summarize_events(ev)
    got = scopes.scope_s(t, scope)
    # the conditional's own time is 10 less its body's 3 + 1
    assert got == pytest.approx({"ddal.exchange": 7e-9,
                                 "ddal.combine": 3e-9,
                                 "unscoped": 3e-9, "ddal.grad": 7e-9})
    assert sum(got.values()) == pytest.approx(sum(t.op_s.values()))


def test_a_name_takes_the_scope_of_either_program():
    """Two programs can both have a ``fusion.2``: the scoped one wins,
    whichever ran first."""
    recs = [("fusion.2", 0, 1, None),
            ("fusion.2", 5, 9, "jit(train_step)/ddal.window/add")]
    assert scopes.resolve(recs) == {"fusion.2": "ddal.window"}
    assert scopes.resolve(recs[::-1]) == {"fusion.2": "ddal.window"}


def _field(num, payload):
    """One length-delimited protobuf field, or a varint one for an
    int (small numbers only)."""
    if isinstance(payload, int):
        return bytes([num << 3, payload])
    return bytes([num << 3 | 2]) + _varint(len(payload)) + payload


def _varint(n):
    out = b""
    while n >= 0x80:
        out, n = out + bytes([n & 0x7F | 0x80]), n >> 7
    return out + bytes([n])


def _hlo_proto(*instructions):
    """An ``HloProto`` of one computation holding ``(name, op_name)``
    instructions (an op_name of None leaves the metadata out)."""
    comp = b"".join(_field(2, _field(1, n.encode()) + (
        _field(7, _field(1, b"op") + _field(2, o.encode()))
        if o else b"")) for n, o in instructions)
    return _field(1, _field(1, b"m") + _field(3, _field(1, b"c") + comp))


def _bytes_text(data):
    return '"' + "".join(f"\\{b:03o}" for b in data) + '"'


def _xspace(step_hlo, batch_hlo):
    """A trace of two programs: the device's "XLA Ops" line (a share
    step in miniature, then an op of the batch program whose name the
    step's HLO also has) and the HLO of both in ``/host:metadata``."""
    ops = [("conditional.1", 0, 10000), ("fusion.2", 2000, 3000),
           ("copy.3", 6000, 1000), ("fusion.4", 10000, 2000)]
    events = "".join(f"events {{ metadata_id: {i} offset_ps: {s} "
                     f"duration_ps: {d} }}\n"
                     for i, (_, s, d) in enumerate(ops, 1))
    names = "".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                    f'name: "%{n} = f32[2] op(...)" }} }}\n'
                    for i, (n, _, _) in enumerate(ops, 1))
    return f"""
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 1000 {events} }}
  {names} }}
planes {{ id: 2 name: "/host:metadata"
  event_metadata {{ key: 1 value {{ id: 1 name: "jit_train_step(1)"
    stats {{ metadata_id: 7 bytes_value: {_bytes_text(step_hlo)} }} }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "jit_batch(2)"
    stats {{ metadata_id: 7 bytes_value: {_bytes_text(batch_hlo)} }} }} }}
  stat_metadata {{ key: 7 value {{ id: 7 name: "Hlo Proto" }} }} }}
"""


def test_scopes_read_from_the_hlo_in_the_trace(tmp_path):
    from jax.profiler import ProfileData
    step = _hlo_proto(
        ("conditional.1", "jit(train_step)/ddal.exchange/cond"),
        ("fusion.2", "jit(train_step)/ddal.exchange/cond/branch_1_fun/"
                     "ddal.combine/div"),
        ("copy.3", None), ("fusion.4", "jit(train_step)/ddal.grad/tanh"))
    batch = _hlo_proto(("fusion.4", "jit(batch)/xor"))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(
        _xspace(step, batch)))
    assert scopes.hlo_op_names(str(path)) == {
        "conditional.1": "jit(train_step)/ddal.exchange/cond",
        "fusion.2": "jit(train_step)/ddal.exchange/cond/branch_1_fun/"
                    "ddal.combine/div",
        "copy.3": None, "fusion.4": "jit(train_step)/ddal.grad/tanh"}
    assert scopes.load_scopes(str(path), [0]) == {
        "conditional.1": "ddal.exchange", "fusion.2": "ddal.combine",
        "copy.3": "ddal.exchange", "fusion.4": "ddal.grad"}


@pytest.fixture(scope="module")
def scoped():
    return harness.load_json(SCOPED)


@pytest.fixture
def ctx_of(monkeypatch):
    """A reader's context for a recorded trace: the summary, its scope
    map where the harness would read it from the profiler's file, and
    the recorded steps."""
    def make(ev, steps, shares):
        monkeypatch.setattr(scopes, "trace_scopes",
                            lambda chips: ev.get("scope", {}))
        return {"trace": devtrace.summarize_events(ev), "chips": 1,
                "counters": {"steps": steps, "share_s": [0.1] * shares}}
    return make


def test_recorded_scope_times_add_up_to_the_ops(scoped):
    t = devtrace.summarize_events(scoped)
    got = scopes.scope_s(t, scoped["scope"])
    assert set(got) >= {"ddal.grad", "ddal.window", "ddal.sketch",
                        "ddal.exchange", "ddal.combine", "ddal.optimizer"}
    assert sum(got.values()) == pytest.approx(sum(t.op_s.values()),
                                              rel=1e-9)


def test_recorded_sketch_kernel_is_in_the_sketch_scope(scoped):
    kernels = {r[0] for recs in scoped["ops"].values() for r in recs
               if r[0].startswith("sketch_flat")}
    assert kernels
    assert all(scoped["scope"][n] == "ddal.sketch" for n in kernels)


def test_readers_read_the_recorded_steps(scoped, ctx_of):
    ctx = ctx_of(scoped, steps=2, shares=1)
    t = ctx["trace"]
    values = {n: reader(n).read(ctx) for n in READERS}
    assert all(v is not None and v > 0 for v in values.values()), values
    # the sketch scope holds the kernel that sketch_roofline reads
    assert values["sketch_ms.train"] * 2 / 1e3 >= t.kernel_s(r"sketch")


def test_readers_find_nothing_in_a_trace_without_scopes(ctx_of):
    small = harness.load_json(SMALL)
    ctx = ctx_of(small, steps=1, shares=0)
    assert "scope" not in small
    assert {n: reader(n).read(ctx) for n in READERS} == dict.fromkeys(
        READERS)
