"""The readers of the program's step timeline
(benchmark/step_timeline.py and the four ``layer_metrics`` over it):
hand-worked records and device programs; every reader's number on a
three-step trace recorded on the chip with the program's own stamps of
the same steps beside it
(benchmark/testdata/record_step_gap_trace.py), each worked out here
apart from the code; no number for a program without the timeline, a
serving cell or a lone step's launch lead; and one cell's rehearsal,
which reports the three ``program_counter`` metrics as floats.
"""
import importlib.util
import json
import os
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
BENCH = os.path.join(ROOT, "benchmark")
TRACE = os.path.join(BENCH, "testdata", "step_gap_tpu.xplane.pb")
STAMPS = os.path.join(BENCH, "testdata", "step_gap_tpu.steps.json")
COUNTED = ["readback_transfer_ms.train", "step_outside_ms.train",
           "longest_step_over_median.train"]
NEW = COUNTED + ["launch_lead_ms.train"]
CELL = "zaya1_8b_train_ep2"


@pytest.fixture
def st(monkeypatch):
    """benchmark/step_timeline.py, importable the way run.py makes it."""
    monkeypatch.syspath_prepend(ROOT)
    monkeypatch.syspath_prepend(BENCH)
    for m in ("step_timeline", "program_trace", "trace_reduce"):
        monkeypatch.delitem(sys.modules, m, raising=False)
    import step_timeline
    yield step_timeline
    step_timeline.program_trace._CACHE.clear()


def _reader(name):
    path = os.path.join(BENCH, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _rec(entry, d0=None, d1=None, r1=None, w0=None, w1=None, t1=None,
         nxt=None, open_=False):
    """One record of ``tracing.steps()``, times in microseconds."""
    us = lambda t: None if t is None else int(t * 1000)
    return {"step": 1, "fused": r1 is not None, "open": open_,
            "entry": us(entry), "dispatch0": us(d0), "dispatch1": us(d1),
            "rebind1": us(r1), "wait0": us(w0), "wait1": us(w1),
            "transfer1": us(t1), "next_entry": us(nxt),
            "cpu_ns": 0, "gc2": 0, "builds": 0}


def _program(name, start_us, dur_us):
    return {"name": name, "start_ns": start_us * 1e3, "dur_ns": dur_us * 1e3}


# three steps on the host's clock (us), the third still open (closed by
# the call at 40 000), the second not read back:
#         entry  d0    d1    r1    w0     w1     t1     next
RECS = [(0,     500,  3000, 3400, 3500,  9000,  11000, 11200),
        (11200, 11600, 14000, 14300, None, None, None,  20000),
        (20000, 20700, 23000, 23300, 23400, 29000, 31500, 40000)]


def _recs():
    return [_rec(*r, open_=(i == 2)) for i, r in enumerate(RECS)]


# ----------------------------------------------------------------------
# hand-worked
# ----------------------------------------------------------------------
def test_transfer_is_the_mean_over_the_steps_read_back(st):
    # (11000 - 9000) and (31500 - 29000) us; the second step has none
    assert st.transfer_ms(_recs()) == pytest.approx((2.0 + 2.5) / 2)
    assert st.transfer_ms(_recs()[1:2]) is None


def test_outside_leaves_the_open_step_out_and_falls_back_to_rebind(st):
    # step 1: 11200 - 11000; step 2, not read back: 20000 - 14300; the
    # open third step's end is the call's, not the loop's
    assert st.outside_ms(_recs()) == pytest.approx((0.2 + 5.7) / 2)
    # alone, the open step is all there is: 40000 - 31500
    assert st.outside_ms(_recs()[2:]) == pytest.approx(8.5)
    # a step that left the fused path: its whole interval
    assert st.outside_ms([_rec(0, nxt=700)]) == pytest.approx(0.7)


def test_longest_over_median_of_the_ended_steps(st):
    # intervals 11.2 and 8.8 ms; the median of two is their mean
    assert st.longest_over_median(_recs()) == pytest.approx(11.2 / 10.0)
    assert st.longest_over_median(_recs()[:1]) == 1.0
    five = [_rec(t, nxt=t + d) for t, d in
            ((0, 100), (100, 104), (204, 96), (300, 3600), (3900, 100))]
    assert st.longest_over_median(five) == pytest.approx(36.0)


def test_the_stamps_own_means_of_the_three_spans(st):
    got = st.interval_means_ms(_recs())
    assert got["prepare"] == pytest.approx((0.5 + 0.4 + 0.7) / 3)
    assert got["dispatch"] == pytest.approx((2.5 + 2.4 + 2.3) / 3)
    assert got["rebind"] == pytest.approx((0.4 + 0.3 + 0.3) / 3)


def test_idle_between_programs_less_what_else_ran(st):
    # the device's clock, 1 000 000 us ahead of the host's
    L = 1_000_000
    mods = [_program("jit_step(7)", L + 1000, 7900),     # ends L + 8900
            _program("jit_convert", L + 9500, 100),      # an eager program
            _program("jit_step(7)", L + 12400, 7000),    # ends L + 19400
            _program("jit_step(7)", L + 21500, 7400)]
    assert st.idle_between(mods) == pytest.approx(
        [(12400 - 8900 - 100) * 1e3, (21500 - 19400) * 1e3])


def test_launch_lead_is_the_gap_less_the_host_s_own_stretch(st):
    L = 1_000_000
    mods = [_program("jit_step(7)", L + 1000, 7900),
            _program("jit_step(7)", L + 12400, 7000),
            _program("jit_step(7)", L + 21500, 7400)]
    # pair 1 -> 2: the device idles 12400 - 8900 = 3500 us; the host
    # went from wait1 9000 to dispatch0 11600: 2600; the lead 900 us
    # (100 of wake-up: 9000 - 8900; 800 of launch: 12400 - 11600).
    # pair 2 -> 3: step 2 was not read back, so the pair is left out
    assert st.launch_lead_ms(_recs(), mods) == pytest.approx(0.9)
    # the clocks' offset is nowhere in it
    shifted = [dict(m, start_ns=m["start_ns"] + 5e6) for m in mods]
    assert st.launch_lead_ms(_recs(), shifted) == pytest.approx(0.9)
    # a pair is needed, and a program a record
    assert st.launch_lead_ms(_recs()[:1], mods[:1]) is None
    assert st.launch_lead_ms(_recs(), mods[:2]) is None


def test_the_lead_s_bounds_from_a_trace_s_own_planes(st):
    # device stamps lead the host's by 1 500 us
    L = 1500
    mods = [_program("jit_step", L + 1000, 7900),       # true end 8900
            _program("jit_step", L + 12400, 7000)]      # true end 19400
    waits = [("metric.wait", 3500e3, (9000 - 3500) * 1e3),      # wakes 100 late
             ("metric.wait", 14400e3, (19450 - 14400) * 1e3)]   # wakes 50 late
    calls = [("fit.fused_dispatch", 500e3, 2500e3),     # starts 500 early
             ("fit.fused_dispatch", 11600e3, 2400e3)]   # starts 800 early
    lower, upper = st.lead_bounds(mods, waits, calls)
    assert lower == pytest.approx((L - 50) * 1e3)
    assert upper == pytest.approx((L + 500) * 1e3)
    assert lower <= L * 1e3 <= upper


# ----------------------------------------------------------------------
# the pair recorded on the chip (benchmark/testdata/record_step_gap_trace.py)
# ----------------------------------------------------------------------
# the window's three steps as the program stamped them, ns after the
# first entry: entry, dispatch0, dispatch1, rebind1, wait0, wait1,
# transfer1, next_entry (the third step was still open when read)
STAMPED = [
    (0, 155889, 1089709, 1187629, 1320389, 1682109, 2636329, 2688339),
    (2688339, 2830719, 3736689, 3887839, 4013249, 4357959, 5270069,
     5327329),
    (5327329, 5443689, 6356679, 6424259, 6470289, 6832119, 7688639, None)]
# the fit-step programs on the device's line, (start, duration) in ns
# after the window opened.  The first began 3 us BEFORE the window by
# the two clocks as they are (the device's stamps lag the host's on
# this machine) and is clipped to it; its end is its own
PROGRAMS = [(0.0, 28262.922), (2681370.578, 31027.344),
            (5292019.25, 31484.922)]
# on the trace's host plane, ns after the window opened: (start,
# duration) of the three ``fit.fused_dispatch`` and ``metric.wait``
CALLS = [(204370.0, 953420.0), (2879759.0, 922360.0),
         (5493569.0, 924540.0)]
WAITS = [(1376870.0, 356980.0), (4070209.0, 340240.0),
         (6526569.0, 358190.0)]


@pytest.fixture
def pair(st, monkeypatch):
    monkeypatch.setattr(st.program_trace, "program_op_classes",
                        lambda: frozenset())
    return json.load(open(STAMPS)), st.program_trace.Trace(TRACE)


def test_the_pair_is_what_the_recorder_wrote(st, pair):
    recs, tr = pair
    e0 = recs[0]["entry"]
    assert [r["step"] for r in recs] == [6, 7, 8]   # after 3 + 2 warm steps
    assert [r["open"] for r in recs] == [False, False, True]
    for r, want in zip(recs, STAMPED):
        got = [r[k] - e0 for k in ("entry", "dispatch0", "dispatch1",
                                   "rebind1", "wait0", "wait1",
                                   "transfer1")]
        assert got == list(want[:7])
        assert r["fused"] and r["gc2"] == 0 and r["builds"] == 0
    assert recs[0]["next_entry"] - e0 == 2688339
    assert recs[1]["next_entry"] - e0 == 5327329
    assert recs[2]["next_entry"] > recs[2]["transfer1"]    # the call's time
    assert all(m["name"].startswith("jit_step(") for m in tr.modules)
    assert [(m["start_ns"] - tr.t0, m["dur_ns"]) for m in tr.modules] \
        == [pytest.approx(p, abs=1e-2) for p in PROGRAMS]
    host = st.trace_reduce.clip(st.trace_reduce.read_events(
        TRACE, ("fit.fused_dispatch", "metric.wait"))["host"], tr.t0, tr.t1)
    for name, want in (("fit.fused_dispatch", CALLS), ("metric.wait", WAITS)):
        assert [(t - tr.t0, d) for n, t, d in host if n == name] == want


def _on_the_pair(st, monkeypatch, recs, steps):
    """Every reader over the recorded pair, for a window of ``steps``."""
    from mxnet_tpu.telemetry import tracing
    monkeypatch.setattr(tracing, "steps", lambda last=None: recs[-last:])
    monkeypatch.setattr(st.program_trace, "find_trace", lambda: TRACE)
    st.program_trace._CACHE.clear()
    facts = {"kind": "train", "steps": steps}
    return {n: _reader(n).read(facts) for n in NEW}


def test_every_reader_on_the_recorded_window(st, monkeypatch, pair):
    recs, _ = pair
    got = _on_the_pair(st, monkeypatch, recs, 3)
    # transfer1 - wait1 of the three steps, ns
    assert got["readback_transfer_ms.train"] == pytest.approx(
        (954220 + 912110 + 856520) / 3 / 1e6)
    # next_entry - transfer1 of the two steps an entry ended
    assert got["step_outside_ms.train"] == pytest.approx(
        (52010 + 57260) / 2 / 1e6)
    # intervals 2688339 and 2638990 ns; the median of two is their mean
    assert got["longest_step_over_median.train"] == pytest.approx(
        2688339 / 2663664.5)
    # the device idles 28262.922 -> 2681370.578 (2653107.656 ns) while
    # the host goes from wait1 1682109 to dispatch0 2830719 (1148610),
    # then 2712397.922 -> 5292019.25 (2579621.328) against 4357959 ->
    # 5443689 (1085730): 1504497.656 and 1493891.328 ns of lead
    assert got["launch_lead_ms.train"] == pytest.approx(
        (1504497.656 + 1493891.328) / 2 / 1e6, abs=1e-6)
    assert got["launch_lead_ms.train"] == pytest.approx(1.499194, abs=1e-6)


def test_a_window_of_two_steps_has_one_pair(st, monkeypatch, pair):
    recs, _ = pair
    got = _on_the_pair(st, monkeypatch, recs, 2)
    # the window's two programs are the trace's last two
    st.program_trace._CACHE.clear()
    tr = st.program_trace.Trace(TRACE)
    assert st.launch_lead_ms(recs[1:], tr.modules[1:]) == pytest.approx(
        1.493891328, abs=1e-6)
    # (the whole trace holds three programs for the window's two
    # records, so the reader, which cannot tell which is whose, says so)
    assert got["launch_lead_ms.train"] is None
    assert got["readback_transfer_ms.train"] == pytest.approx(
        (912110 + 856520) / 2 / 1e6)
    assert got["step_outside_ms.train"] == pytest.approx(0.05726)
    assert got["longest_step_over_median.train"] == 1.0


def test_a_window_of_one_step(st, monkeypatch, pair):
    recs, tr = pair
    got = _on_the_pair(st, monkeypatch, recs, 1)
    assert got["launch_lead_ms.train"] is None      # it needs a pair
    assert st.launch_lead_ms(recs[2:], tr.modules[2:]) is None
    assert got["longest_step_over_median.train"] == 1.0
    assert got["readback_transfer_ms.train"] == pytest.approx(0.85652)
    # the open step alone: closed by the call that read it
    assert got["step_outside_ms.train"] == pytest.approx(
        (recs[2]["next_entry"] - recs[2]["transfer1"]) / 1e6)
    assert got["step_outside_ms.train"] > 1.0


def test_the_stamps_and_the_trace_s_annotations_tell_one_story(st, pair):
    """Two clocks' accounts of the same boundaries: the jit call by the
    stamps (``dispatch1 - dispatch0``) is the span ``fit.fused_dispatch``
    less what opening and closing the span costs under a running trace,
    step for step."""
    recs, tr = pair
    spans = [d for n, _, d in tr.spans if n == "fit.fused_dispatch"]
    assert spans == [d for _, d in CALLS]
    stamped = [r["dispatch1"] - r["dispatch0"] for r in recs]
    assert stamped == [933820, 905970, 912990]
    for a, b in zip(spans, stamped):
        assert 0 < a - b < 25_000           # 11.6 .. 19.6 us here
    assert st.interval_means_ms(recs)["dispatch"] == pytest.approx(
        (933820 + 905970 + 912990) / 3 / 1e6)


def test_by_hand_the_tool_bounds_the_clocks_offset(st, capsys, pair):
    """The program's end precedes the wait's return: the largest of
    28262.922 - 1733850, 2712397.922 - 4410449, 5323504.172 - 6884759
    bounds the lead from below; its start follows the jit call's: the
    smallest of 0 - 204370, 2681370.578 - 2879759, 5292019.25 - 5493569
    from above.  Here both are negative: the device's stamps LAG."""
    assert st.main(["step_timeline", TRACE]) == 0
    out = json.loads(capsys.readouterr().out)
    assert (out["steps"], out["metric_wait_spans"],
            out["fused_dispatch_spans"]) == (3, 3, 3)
    assert out["clock_lead_lower_ms"] == pytest.approx(-1.561254828)
    assert out["clock_lead_upper_ms"] == pytest.approx(-0.204370)
    assert out["bounds_cross"] is False
    assert out["device_gap_ms"] == pytest.approx(
        (2653107.656 + 2579621.328) / 2 / 1e6)
    # the same two differences from the trace's own annotations: the
    # gaps less 2879759 - 1733850 and 5493569 - 4410449
    assert out["launch_lead_ms_by_annotations"] == pytest.approx(
        (2653107.656 - 1145909 + 2579621.328 - 1083120) / 2 / 1e6)
    assert out["latest_step"]["step"] in (0, 1, 2)


# ----------------------------------------------------------------------
# no number where there is nothing to read
# ----------------------------------------------------------------------
def test_none_without_the_timeline_and_for_a_serving_cell(st, monkeypatch):
    from mxnet_tpu.telemetry import tracing
    train = {"kind": "train", "steps": 3}
    for name in NEW:
        assert _reader(name).read({"kind": "serve", "steps": 3}) is None
        assert _reader(name).read({"kind": "train", "steps": 0}) is None
    # an empty ring is an empty window
    tracing.clear_steps()
    for name in NEW:
        assert _reader(name).read(train) is None
    # a program from before the timeline
    monkeypatch.delattr(tracing, "steps")
    assert st.records(train) is None
    for name in NEW:
        assert _reader(name).read(train) is None


def test_the_window_is_the_last_steps_of_the_ring(st, monkeypatch):
    from mxnet_tpu.telemetry import tracing
    asked = []

    def steps(last=None):
        asked.append(last)
        return _recs()[-last:]
    monkeypatch.setattr(tracing, "steps", steps)
    facts = {"kind": "train", "steps": 2}
    assert st.records(facts) == _recs()[1:]
    assert asked == [2]
    assert _reader("readback_transfer_ms.train").read(facts) \
        == pytest.approx(2.5)
    assert _reader("step_outside_ms.train").read(facts) \
        == pytest.approx(5.7)
    assert _reader("longest_step_over_median.train").read(facts) == 1.0
    # no trace in this process: the launch lead has no device side
    monkeypatch.setattr(st.program_trace, "find_trace", lambda: None)
    assert _reader("launch_lead_ms.train").read(facts) is None


def test_the_entries_list_the_training_cells_the_frozen_tests_let_them():
    """Four ``per_layer`` entries, appended last, each a reader's file,
    each on the seven training cells that can take one.  The two they
    leave out are kept out by frozen tests that no PR but a
    ``benchmark`` one may edit (PERF.md section 7, "Open from PR 50"):
    the LM cell (tests/benchmark/test_sdar_cell.py,
    test_smallthinker_cell.py and test_kimi_linear_cell.py hold the
    metrics that list it to 19) and the Kimi-Linear cell (its own test
    holds it to 27 declared metrics)."""
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    last = bench["per_layer"][-4:]
    assert [m["name"] for m in last] == [
        "readback_transfer_ms.train", "step_outside_ms.train",
        "launch_lead_ms.train", "longest_step_over_median.train"]
    train = bench["end_to_end"][0]["workloads"]
    assert len(train) == 9
    for m in last:
        assert m["moves"] == "train_samples_per_s"
        assert m["workloads"] == [
            w for w in train if w not in ("cgpt13b_train_s2048",
                                          "kimilinear_48b_train_ep32")]
        assert "zaya1_8b_train_ep2" in m["workloads"]
        assert os.path.exists(os.path.join(BENCH, "layer_metrics",
                                           m["name"] + ".py"))
    by = {m["name"]: m for m in last}
    assert by["launch_lead_ms.train"]["source"] == "device_trace"
    assert {by[n]["source"] for n in COUNTED} == {"program_counter"}
    assert by["readback_transfer_ms.train"]["layer"] \
        == "metric readback (metric.py)"
    layers = {m["layer"] for m in bench["per_layer"][:-4]}
    assert {m["layer"] for m in last} <= layers


# ----------------------------------------------------------------------
# one cell's rehearsal: the ring is read, not the trace
# ----------------------------------------------------------------------
@pytest.fixture
def run(monkeypatch):
    """benchmark/run.py with the benchmark's modules importable the way
    it makes them."""
    monkeypatch.syspath_prepend(ROOT)
    monkeypatch.syspath_prepend(BENCH)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       os.environ.get("JAX_COMPILATION_CACHE_DIR", ""))
    for m in [m for m in sys.modules if m.split(".")[0] in (
            "common", "counts", "trace_reduce", "reference", "run",
            "program_trace", "operator_time", "step_timeline")]:
        monkeypatch.delitem(sys.modules, m)
    spec = importlib.util.spec_from_file_location(
        "bench_run", os.path.join(BENCH, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_a_rehearsal_reports_the_three_counted_metrics_as_floats(
        run, capsys):
    from mxnet_tpu.telemetry import tracing
    assert run.main(["--workload", CELL, "--seed",
                     "3000000050", "--seconds", "0.5", "--trace", "1",
                     "--rehearse"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    steps = line["notes"]["steps"]
    got = {n: line["metrics"][n] for n in NEW}
    for name in COUNTED:
        assert isinstance(got[name]["value"], float), name
    assert got["launch_lead_ms.train"] == {"value": None, "unit": "ms"}
    assert got["longest_step_over_median.train"]["unit"] == "ratio"
    assert got["longest_step_over_median.train"]["value"] >= 1.0
    assert 0.0 < got["readback_transfer_ms.train"]["value"] < 50.0
    assert 0.0 < got["step_outside_ms.train"]["value"]
    # the ring holds the window's steps last, every one read back, and
    # the stamps' account of the two spans agrees with the trace's (to
    # 0.02-0.04 ms on the chip, PERF.md; a loaded CI host gets room)
    recs = tracing.steps(last=steps)
    assert len(recs) == steps and all(r["wait1"] for r in recs)
    sys.path.insert(0, BENCH)
    try:
        import step_timeline
    finally:
        sys.path.remove(BENCH)
    mine = step_timeline.interval_means_ms(recs)
    assert mine["prepare"] == pytest.approx(
        line["metrics"]["fit_prepare_ms.train"]["value"], abs=0.3)
    assert mine["dispatch"] == pytest.approx(
        line["metrics"]["fit_dispatch_ms.train"]["value"], abs=0.3)
