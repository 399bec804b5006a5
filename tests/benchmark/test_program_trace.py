"""The readers of the program's own names (benchmark/program_trace.py
and the seven ``layer_metrics`` that use it): the ``XSpace`` wire reader
against the recorded TPU traces, hand-worked interval cases for time
per scope (a union, never a sum) and idle time inside a span, how this
process's trace is found, and every new reader's number on a small
trace recorded on the chip from a scoped two-layer fit step
(benchmark/testdata/record_scoped_trace.py).

CPU only; no rehearsal runs here (tests/benchmark/test_benchmark.py
runs the traced rehearsal that holds every declared metric to its
source: a float from ``program_span``, null from ``device_trace``).
"""
import importlib.util
import os
import struct
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
BENCH = os.path.join(ROOT, "benchmark")
SMALL = os.path.join(BENCH, "testdata", "small_tpu.xplane.pb")
SCOPED = os.path.join(BENCH, "testdata", "scoped_tpu.xplane.pb")
NEW_METRICS = ["fit_prepare_ms.train", "fit_dispatch_ms.train",
               "readback_idle_ms.train", "programs_per_step.train",
               "update_ms.train", "pallas_ms.train", "scoped_share.train"]


@pytest.fixture
def pt(monkeypatch):
    """benchmark/program_trace.py, importable the way run.py makes it."""
    monkeypatch.syspath_prepend(ROOT)
    monkeypatch.syspath_prepend(BENCH)
    for m in ("program_trace", "trace_reduce"):
        monkeypatch.delitem(sys.modules, m, raising=False)
    import program_trace
    yield program_trace
    program_trace._CACHE.clear()


def _reader(name):
    path = os.path.join(BENCH, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _read_all(pt, monkeypatch, path, facts):
    monkeypatch.setattr(pt, "find_trace", lambda: path)
    pt._CACHE.clear()
    return {n: _reader(n).read(facts) for n in NEW_METRICS}


# ----------------------------------------------------------------------
# the wire format
# ----------------------------------------------------------------------
def test_wire_fields_hand_worked(pt):
    # field 1 varint 150 (0x96 0x01), field 2 bytes "abc", field 3
    # fixed64 double 1.5, field 4 fixed32, field 5 varint -2 (int64)
    buf = (b"\x08\x96\x01" + b"\x12\x03abc" + b"\x19" + struct.pack("<d", 1.5)
           + b"\x25\x01\x00\x00\x00"
           + b"\x28\xfe\xff\xff\xff\xff\xff\xff\xff\xff\x01")
    got = list(pt.fields(memoryview(buf), 0, len(buf)))
    assert [f for f, _ in got] == [1, 2, 3, 4, 5]
    assert got[0][1] == 150
    assert got[1][1] == (5, 8) and buf[5:8] == b"abc"
    assert struct.unpack("<d", got[2][1])[0] == 1.5
    assert bytes(got[3][1]) == b"\x01\x00\x00\x00"
    assert got[4][1] == (1 << 64) - 2
    with pytest.raises(ValueError):
        list(pt.fields(memoryview(b"\x0b"), 0, 1))      # a group: refused
    # one XStat of each kind the metadata uses, by hand
    names = {7: "flops", 9: "tf_op", 11: "jit(f)/dot_general:"}
    assert pt._stat(memoryview(b"\x08\x07\x20\x05"), (0, 4), names) \
        == ("flops", 5)
    assert pt._stat(memoryview(b"\x08\x09\x2a\x02ab"), (0, 6), names) \
        == ("tf_op", "ab")
    assert pt._stat(memoryview(b"\x08\x09\x38\x0b"), (0, 4), names) \
        == ("tf_op", "jit(f)/dot_general:")
    neg = b"\x08\x07\x20" + b"\xff" * 9 + b"\x01"
    assert pt._stat(memoryview(neg), (0, len(neg)), names) == ("flops", -1)


def test_xspace_reader_on_recorded_tpu_trace(pt):
    """small_tpu.xplane.pb: the matmul fusion's metadata, the programs
    the chip ran, and the same clock as ``ProfileData`` gives."""
    import trace_reduce as tr
    planes = pt.read_device_planes(SMALL)
    assert list(planes) == ["/device:TPU:0"]
    ops = planes["/device:TPU:0"]["XLA Ops"]
    mods = planes["/device:TPU:0"]["XLA Modules"]
    assert len(ops) == 18 and len(mods) == 9
    assert [e["name"] for e in ops[:3]] == ["copy-start", "copy-done",
                                            "fusion"]
    fusion = ops[2]
    assert fusion["tf_op"] == "jit(<lambda>)/dot_general"
    assert fusion["category"] == "convolution fusion"
    assert fusion["flops"] == 2153775104        # 2 * 1024^3 + 1024^2 * 6
    assert fusion["bytes_accessed"] == 6291456  # 3 * 1024^2 * 2
    assert (fusion["start_ns"], fusion["dur_ns"]) == (50972489.078,
                                                      12609.922)
    assert ops[0]["tf_op"] == "" and ops[0]["flops"] == 0
    assert sorted({e["name"].split("(")[0] for e in mods}) == [
        "jit__lambda", "jit_dynamic_slice", "jit_squeeze"]
    assert [e["name"].split("(")[0] for e in mods[:3]] == [
        "jit__lambda", "jit_dynamic_slice", "jit_squeeze"]
    # ProfileData rounds to whole ns; same events, same order
    ref = tr.read_events(SMALL)["devices"]["/device:TPU:0"]
    assert [e["name"] for e in ops] == [n for n, _, _ in ref]
    assert all(abs(e["start_ns"] - s) <= 1.0 and abs(e["dur_ns"] - d) <= 1.0
               for e, (_, s, d) in zip(ops, ref))
    assert pt.read_device_planes(SMALL, lines=("XLA Modules",))[
        "/device:TPU:0"].keys() == {"XLA Modules"}


# ----------------------------------------------------------------------
# scopes and intervals, by hand
# ----------------------------------------------------------------------
OPS = frozenset(["FullyConnected", "LayerNorm", "transpose", "softmax",
                 "SoftmaxOutput"])


def test_scopes_of_hand_worked(pt):
    def s(path, ops):
        return list(pt.scopes_of(path, ops))
    assert s("jit(step)/jvp(FullyConnected)/fc1/dot_general", OPS) == [
        ("op.FullyConnected", "fwd")]
    assert s("jit(step)/transpose(jvp(FullyConnected))/fc1/dot_general",
             OPS) == [("op.FullyConnected", "bwd")]
    assert s("jit(step)/fit.update/cond/branch_1_fun/mul", OPS) == [
        ("fit.update", "fwd")]
    assert s("jit(step)/jvp(LayerNorm)/ln1/pallas.layernorm_fused/"
             "pallas_call", OPS) == [("op.LayerNorm", "fwd"),
                                     ("pallas.layernorm_fused", "fwd")]
    # backward from the first transpose(...) inwards
    assert s("jit(step)/transpose(jvp(LayerNorm))/ln1/"
             "pallas.layernorm_fused_bwd/pallas_call",
             OPS) == [("op.LayerNorm", "bwd"),
                      ("pallas.layernorm_fused_bwd", "bwd")]
    # a node may be called like an operator; the path's last element
    # is the primitive, never a scope
    assert s("jit(step)/jvp(SoftmaxOutput)/softmax/exp", OPS) == [
        ("op.SoftmaxOutput", "fwd"), ("op.softmax", "fwd")]
    assert s("jit(_fwd_eval)/transpose/transpose0/transpose", OPS) == [
        ("op.transpose", "fwd")]
    assert s("jit(f)/transpose", OPS) == []
    # jax's own wrappers are not the program's names
    assert s("jit(step)/jit(softmax)/exp", OPS) == []
    assert s("jit(<lambda>)/dot_general", OPS) == []
    assert s("", OPS) == []


def _ev(name, tf_op, start, dur, nbytes=None, category=None):
    return {"name": name, "tf_op": tf_op, "start_ns": float(start),
            "dur_ns": float(dur), "bytes_accessed": nbytes,
            "category": category, "flops": None}


# a cond of 40 ns with two instructions inside it, all under fit.update;
# a forward matmul, its backward twice (overlapping), a Pallas kernel
# inside an operator, and a compiler-made copy with no tf_op
EVENTS = [
    _ev("fusion.1", "jit(step)/jvp(FullyConnected)/fc1/dot_general", 0, 10),
    _ev("ln", "jit(step)/jvp(LayerNorm)/ln1/pallas.layernorm_fused/"
        "pallas_call", 10, 5),
    _ev("copy.7", "", 15, 5),
    _ev("fusion.2", "jit(step)/transpose(jvp(FullyConnected))/fc1/"
        "dot_general", 20, 10),
    _ev("fusion.3", "jit(step)/transpose(jvp(FullyConnected))/fc1/"
        "reduce_sum", 25, 10),
    _ev("cond.9", "jit(step)/fit.update/cond", 40, 40,
        category="conditional"),
    _ev("fusion.4", "jit(step)/fit.update/cond/branch_1_fun/mul", 42, 18),
    _ev("fusion.5", "jit(step)/fit.update/cond/branch_1_fun/sub", 60, 15),
]


def test_time_per_scope_is_a_union(pt):
    by = pt.time_by_scope(EVENTS, OPS)
    # the cond covers 40-80 and its instructions lie inside: 40, not 73
    assert by[("fit.update", "fwd")] == 40.0
    assert by[("op.FullyConnected", "fwd")] == 10.0
    # backward: 20-30 and 25-35 overlap: 15, not 20
    assert by[("op.FullyConnected", "bwd")] == 15.0
    # the kernel counts under its operator (the outermost scope) ...
    assert by[("op.LayerNorm", "fwd")] == 5.0
    assert ("pallas.layernorm_fused", "fwd") not in by
    # ... unless the kernels alone are asked for
    assert pt.time_by_scope(EVENTS, OPS, ("pallas.",)) == {
        ("pallas.layernorm_fused", "fwd"): 5.0}
    assert pt.scoped_ns(EVENTS, OPS, "fit.update") == 40.0
    assert pt.scoped_ns(EVENTS, OPS, "pallas.") == 5.0
    # every scope: 0-15, 20-35, 40-80 = 70 of 75 busy (the copy is not)
    assert pt.scoped_ns(EVENTS, OPS) == 70.0
    import trace_reduce as tr
    assert tr.busy_ns(pt.intervals(EVENTS)) == 75.0


def test_idle_inside_a_span_hand_worked(pt):
    # busy 0-35 and 40-80 in the window 0-100: gaps 35-40 and 80-100
    readback = [("metric.readback", 30.0, 8.0),
                ("metric.readback", 78.0, 12.0)]
    # 35-38 of the first gap, 80-90 of the second
    assert pt.idle_inside(EVENTS, readback, 0.0, 100.0) == 13.0
    # overlapping spans are not counted twice
    twice = readback + [("metric.readback", 85.0, 10.0)]
    assert pt.idle_inside(EVENTS, twice, 0.0, 100.0) == 18.0
    assert pt.idle_inside(EVENTS, [], 0.0, 100.0) == 0.0
    # the window cuts: only 80-85 is left of the second gap
    assert pt.idle_inside(EVENTS, readback, 0.0, 85.0) == 8.0


# ----------------------------------------------------------------------
# this process's trace
# ----------------------------------------------------------------------
def test_find_trace_newest_not_older_than_the_process(pt, tmp_path):
    assert pt.find_trace(root=str(tmp_path)) is None
    made = []
    for i, cell in enumerate(["cell_a", "cell_b"]):
        d = tmp_path / ".bench_scratch" / cell / "trace" / "plugins" / "p"
        d.mkdir(parents=True)
        f = d / "host.xplane.pb"
        f.write_bytes(b"")
        os.utime(f, (1000.0 + i, 1000.0 + i))
        made.append(str(f))
    assert pt.find_trace(root=str(tmp_path), not_before=0.0) == made[1]
    assert pt.find_trace(root=str(tmp_path), not_before=1002.5) is None
    # written before this process started: not this process's trace
    assert pt.find_trace(root=str(tmp_path)) is None
    os.utime(made[0], None)                     # now
    assert pt.find_trace(root=str(tmp_path)) == made[0]
    assert 0 < pt.process_start_s() <= os.path.getmtime(made[0])


def test_trace_is_read_once_per_process(pt, monkeypatch):
    calls = []
    real = pt.read_device_planes
    monkeypatch.setattr(pt, "read_device_planes",
                        lambda p: calls.append(p) or real(p))
    monkeypatch.setattr(pt, "find_trace", lambda: SMALL)
    assert pt.current() is pt.current()
    assert calls == [SMALL]
    monkeypatch.setattr(pt, "find_trace", lambda: None)
    assert pt.current() is None


def test_program_without_names_reads_as_nothing(pt, monkeypatch):
    """small_tpu.xplane.pb comes from a program without the spans and
    scopes (as the parent commit is): every reader that needs them
    returns None and none raises; the programs are still counted."""
    facts = {"kind": "train", "steps": 3}
    got = _read_all(pt, monkeypatch, SMALL, facts)
    # 9 programs in the window: jit__lambda + two eager ones a step
    assert got.pop("programs_per_step.train") == 3.0
    assert got == {n: None for n in NEW_METRICS
                   if n != "programs_per_step.train"}
    assert all(v is None for v in _read_all(
        pt, monkeypatch, SMALL, {"kind": "serve", "steps": 3}).values())
    assert all(v is None for v in _read_all(
        pt, monkeypatch, None, facts).values())


# ----------------------------------------------------------------------
# a scoped fit step recorded on the chip: every reader's number
# ----------------------------------------------------------------------
def test_every_reader_on_the_recorded_scoped_step(pt, monkeypatch):
    """benchmark/testdata/scoped_tpu.xplane.pb: three steps of a
    FullyConnected + Pallas LayerNorm under a softmax, bfloat16, Adam
    under the loss scaler's ``cond``, on a TPU v5 lite
    (record_scoped_trace.py).  The numbers below were worked out apart
    from the code under test: the file parsed with the protobuf
    library, unions by a sweep over interval ends."""
    tr = pt.Trace(SCOPED)
    assert len(tr.ops) == 255 and len(tr.modules) == 9     # 85 + 3 a step
    assert (tr.t1 - tr.t0) / 1e6 == pytest.approx(10.41625)
    assert [tr.span_count(n) for n in pt.HOST_SPANS] == [3, 3, 3, 3]
    assert sorted({e["name"].split("(")[0] for e in tr.modules}) == [
        "jit_convert_element_type", "jit_step"]
    kernel = [e for e in tr.ops if e["name"] == "pallas.layernorm_fused.1"]
    assert len(kernel) == 3
    assert kernel[0]["dur_ns"] == pytest.approx(1480.078)    # 1.5 us
    assert kernel[0]["tf_op"] == ("jit(step)/jvp(LayerNorm)/ln1/"
                                  "pallas.layernorm_fused/pallas_call")
    # on the chip the conditional itself carries no tf_op; what runs
    # under it does, so the scope's time is the union of those
    conds = [e for e in tr.ops if e["category"] == "conditional"]
    assert len(conds) == 3 and {e["tf_op"] for e in conds} == {""}
    inner = [e for e in tr.ops if e["tf_op"].startswith(
        "jit(step)/fit.update/cond/branch_1_fun/")]
    assert inner and all(
        c["start_ns"] <= e["start_ns"] and e["start_ns"] + e["dur_ns"]
        <= c["start_ns"] + c["dur_ns"] for e in inner
        for c in conds if c["start_ns"] <= e["start_ns"]
        < c["start_ns"] + c["dur_ns"])
    by = tr.table(3)
    assert [r[:2] for r in by["pallas"]] == [
        ["pallas.layernorm_fused", "fwd"],
        ["pallas.layernorm_fused_bwd", "bwd"]]
    assert {r[0] for r in by["op"]} == {
        "op.LayerNorm", "op.FullyConnected", "op.Cast", "op.SoftmaxOutput"}
    assert tr.busy_ns() / 3e6 == pytest.approx(0.018719322667)
    # the whole of every readback falls in a device gap: a step of 19 us
    assert tr.idle_inside_ns("metric.readback") == pytest.approx(
        tr.span_ns("metric.readback"))
    got = _read_all(pt, monkeypatch, SCOPED, {"kind": "train", "steps": 3})
    assert got == {
        "fit_prepare_ms.train": pytest.approx(1.3310633333),
        "fit_dispatch_ms.train": pytest.approx(0.8057766667),
        "readback_idle_ms.train": pytest.approx(1.0758833333),
        "programs_per_step.train": 3.0,     # the step + 2 eager converts
        "update_ms.train": pytest.approx(0.0078453907),
        "pallas_ms.train": pytest.approx(0.0028587760),
        "scoped_share.train": pytest.approx(87.5442038786),
    }
