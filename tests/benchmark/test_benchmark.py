"""The benchmark's own tests: CPU, tiny sizes, seconds each.

They hold the yardstick still: the counts against hand-worked values at
the cells' real sizes, the trace reduction against hand-computed answers
and a small recorded TPU trace, the harness finding every piece by name,
the rehearsal printing the contract's line, each reference agreeing with
the system and disagreeing with a lower precision, the control coming
out as not correct, and a run with the timed path broken underneath
coming out as not correct.  No topology call, here or at import.
"""
import argparse
import importlib.util
import json
import os
import shutil
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
BENCH = os.path.join(ROOT, "benchmark")
TRAIN, SERVE = "cgpt13b_train_s2048", "cgpt13b_serve_chat_c32"
RESNET = "resnet50_train_b256"


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def bench_path(monkeypatch):
    """The benchmark's modules importable the way run.py makes them."""
    monkeypatch.syspath_prepend(ROOT)
    monkeypatch.syspath_prepend(BENCH)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       os.environ.get("JAX_COMPILATION_CACHE_DIR", ""))
    for m in [m for m in sys.modules if m.split(".")[0] in (
            "common", "counts", "trace_reduce", "reference", "run")]:
        monkeypatch.delitem(sys.modules, m)
    yield _load(os.path.join(BENCH, "run.py"), "bench_run")


def _serve_entries():
    """The entries the serving cell will have in BENCHMARK.json once
    the engine serves right tokens under load (PERF.md, Open questions
    row 1).  Until then its driver, readers and reference are driven
    only from here, at tiny size."""
    e2e = [("serve_tok_per_s", "tokens/s", "higher"),
           ("ttft_p90_ms", "ms", "lower"), ("gap_p95_ms", "ms", "lower")]
    layers = [
        ("dispatches_per_step.serve", "1/step", "program_counter",
         "host loop (decode/engine.py)", "serve_tok_per_s"),
        ("slot_occupancy.serve", "%", "program_counter",
         "host loop (decode/engine.py)", "serve_tok_per_s"),
        ("prefill_chunks_per_iter.serve", "1/step", "program_counter",
         "scheduler (decode/scheduler.py)", "ttft_p90_ms"),
        ("pallas_fallbacks.serve", "count", "program_counter",
         "kernel selection (pallas/dispatch.py)", "gap_p95_ms"),
        ("step_roofline_share.serve", "%", "device_trace",
         "kernels (pallas/, XLA fusions)", "gap_p95_ms"),
        ("device_idle_share.serve", "%", "device_trace", "device",
         "serve_tok_per_s")]
    return {
        "configs": [{"name": "cerebras_gpt_1p3b_serve", "source":
                     "https://huggingface.co/cerebras/Cerebras-GPT-1.3B",
                     "file": "benchmark/configs/cerebras_gpt_1p3b_serve.json",
                     "reduced": [], "why": "the same model served whole"}],
        "workloads": [{"name": SERVE, "config": "cerebras_gpt_1p3b_serve",
                       "traffic": "chat_c32", "chips": 1,
                       "why": "closed loop of 32 clients on 32 slots"}],
        "end_to_end": [{"name": n, "unit": u, "better": b, "bound": 0.1,
                        "source": "host_clock", "workloads": [SERVE]}
                       for n, u, b in e2e],
        "per_layer": [{"name": n, "unit": u, "better": "lower",
                       "source": src, "layer": layer, "moves": moves,
                       "workloads": [SERVE]}
                      for n, u, src, layer, moves in layers]}


def _bench():
    """BENCHMARK.json with the serving cell's entries laid in."""
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for group, rows in _serve_entries().items():
        bench[group] = bench[group] + rows
    return bench


def _cell(run, workload, seed=5, seconds=0.3, trace=0):
    ns = argparse.Namespace(workload=workload, seed=seed, seconds=seconds,
                            trace=trace, rehearse=True)
    return run.Cell(_bench(), ns)


def _execute(run, cell):
    return run.execute(cell, run.device_info(cell))


# ----------------------------------------------------------------------
# counts and peaks: hand-worked values at the cells' real sizes
# ----------------------------------------------------------------------
LM9 = dict(num_classes=50257, num_layers=9, d_model=2048, num_heads=16,
           ffn_dim=8192, seq_len=2048)
LM24 = dict(LM9, num_layers=24)


def test_lm_train_flops_hand_worked(bench_path):
    import counts
    # per layer: 8*2048^2 + 4*2048*8192 + 2*2048*2048 = 109,051,904
    # 9 layers 981,467,136; head 2*2048*50257 = 205,852,672
    assert counts.lm_forward_flops_per_token(LM9, 2048) == 1_187_319_808
    assert counts.lm_train_flops_per_token(LM9, 2048) == 3_561_959_424
    # a step of 2 x 2048 tokens: 14.59 TFLOP, 74.06 ms at 197 TFLOP/s
    step = counts.lm_train_flops_per_token(LM9, 2048) * 4096
    assert step == 14_589_785_800_704
    assert abs(step / 197e12 - 0.074060) < 1e-6


def test_lm_serve_bytes_hand_worked(bench_path):
    import counts
    # a layer: qkv 12,582,912+6,144; proj 4,194,304+2,048; ffn
    # 33,554,432+8,192+2,048; two LayerNorms 8,192 = 50,358,272 values
    # 24 layers 1,208,598,528; ln_f 4,096; head 102,926,336+50,257
    assert counts.lm_weight_bytes(LM24) == 2 * 1_311_579_217
    assert counts.lm_kv_bytes_per_row(LM24) == 2 * 24 * 2048 * 2 == 196_608
    # 10,000 live rows, 96 tokens computed
    assert counts.lm_serve_iter_bytes(LM24, 10_000, 96) == (
        2_623_158_434 + 1_966_080_000 + 2 * 96 * 2048 * 4)


def test_resnet50_flops_hand_worked(bench_path):
    import counts
    # 4.09 G multiply-adds forward: the published figure for ResNet-50
    # at 224 x 224 with the 1000-way classifier (v2 strides the 3x3)
    f = counts.resnet_forward_flops_per_image(50, 224, 1000)
    assert f == 2 * 4_089_184_256
    assert counts.resnet_train_flops_per_image(50, 224, 1000) == 3 * f


@pytest.mark.parametrize("reference,kw,want", [
    ("gpt2", LM9, 3_561_959_424 * 2048),
    ("resnet", {"num_layers": 50, "image_shape": [3, 224, 224],
                "num_classes": 1000}, 6 * 4_089_184_256)])
def test_reference_gives_the_step_count(bench_path, reference, kw, want):
    """The roofline reader asks the configuration's reference module,
    found by name, so a new family brings its own count as a file."""
    import common
    reader = bench_path.load_module("layer_metrics",
                                    "step_roofline_share.train")
    cfg = {"reference": reference, "kwargs": kw}
    assert common.reference_model(cfg).train_flops_per_sample(kw) == want
    facts = {"kind": "train", "config": cfg, "batch": 2, "steps": 10,
             "trace": {"busy_s": 1.0, "host_span_n": {"fit_step": 10}},
             "peaks": {"bf16_flops_per_s": 197e12}}
    # 10 steps busy for 1 s: 0.1 s a step against 2 * want / peak
    assert reader.read(facts) == pytest.approx(
        100.0 * (2 * want / 197e12) / 0.1)


def test_declared_roofline_without_a_count_raises(bench_path, monkeypatch):
    import common
    reader = bench_path.load_module("layer_metrics",
                                    "step_roofline_share.train")
    monkeypatch.setattr(common, "reference_model", lambda cfg: object())
    facts = {"kind": "train", "config": {"reference": "none", "kwargs": {}},
             "batch": 2, "steps": 10,
             "trace": {"busy_s": 1.0, "host_span_n": {}}, "peaks": {}}
    with pytest.raises(SystemExit):
        reader.read(facts)


def test_peaks_known_and_unknown(bench_path):
    import counts
    p = counts.peaks("TPU v5 lite")
    assert (p["bf16_flops_per_s"], p["hbm_bytes_per_s"], p["hbm_bytes"]) \
        == (197e12, 819e9, 16e9)
    with pytest.raises(KeyError):
        counts.peaks("TPU v5")
    with pytest.raises(KeyError):
        counts.peaks("cpu")


# ----------------------------------------------------------------------
# trace reduction: one hand-computed answer per function
# ----------------------------------------------------------------------
EVENTS = [("a", 0.0, 10.0), ("b", 5.0, 10.0), ("a", 30.0, 5.0),
          ("c", 50.0, 20.0)]
SPANS = [("input", 14.0, 10.0), ("fit_step", 24.0, 8.0),
         ("readback", 40.0, 30.0)]


def test_busy_union(bench_path):
    import trace_reduce as tr
    assert tr.merged(EVENTS) == [[0.0, 15.0], [30.0, 35.0], [50.0, 70.0]]
    assert tr.busy_ns(EVENTS) == 40.0


def test_clip_and_idle_share(bench_path):
    import trace_reduce as tr
    assert tr.clip(EVENTS, 8.0, 60.0) == [("a", 8.0, 2.0), ("b", 8.0, 7.0),
                                          ("a", 30.0, 5.0), ("c", 50.0, 10.0)]
    # window [0, 80]: busy 40 of 80
    assert tr.idle_share(EVENTS, 0.0, 80.0) == 0.5
    # window [8, 60]: busy 7 + 5 + 10 = 22 of 52
    assert abs(tr.idle_share(EVENTS, 8.0, 60.0) - 30.0 / 52.0) < 1e-12


def test_op_sums(bench_path):
    import trace_reduce as tr
    assert tr.op_sums(EVENTS) == [("c", 20.0), ("a", 15.0), ("b", 10.0)]
    assert tr.op_sums(EVENTS, top=1) == [("c", 20.0)]
    assert tr.op_name("%fusion.3 = bf16[8,128]{1,0} fusion(%p0)") \
        == "fusion.3"


def test_gaps_and_attribution(bench_path):
    import trace_reduce as tr
    g = tr.gaps(EVENTS, 0.0, 80.0)
    assert g == [(15.0, 30.0), (35.0, 50.0), (70.0, 80.0)]
    # gap 15-30: input 14-24 covers 9, fit_step 24-32 covers 6
    # gap 35-50: readback 40-70 covers 10, 5 uncovered
    # gap 70-80: nothing covers it
    assert tr.attribute_gaps(g, SPANS) == [
        ("unattributed", 15.0), ("readback", 10.0), ("input", 9.0),
        ("fit_step", 6.0)]


def test_recorded_tpu_trace(bench_path):
    """benchmark/testdata/small_tpu.xplane.pb: three steps of one
    1024 x 1024 bf16 matmul fusion on a TPU v5 lite, with the
    benchmark's host spans (record_small_trace.py)."""
    import trace_reduce as tr
    path = os.path.join(BENCH, "testdata", "small_tpu.xplane.pb")
    ev = tr.read_events(path, ["input", "fit_step", "readback",
                               "bench_window"])
    assert list(ev["devices"]) == ["/device:TPU:0"]
    ops = ev["devices"]["/device:TPU:0"]
    assert len(ops) == 18
    assert ops[0][0] == "copy-start" and ops[2][0] == "fusion"
    assert ops[2][1:] == (50972489.0, 12609.0)      # as the profiler wrote it
    names = [h[0] for h in ev["host"]]
    assert names.count("fit_step") == 3 and names.count("bench_window") == 1
    s = tr.summarize(path, ["input", "fit_step", "readback"],
                     window_name="bench_window")
    win = [h for h in ev["host"] if h[0] == "bench_window"][0]
    assert abs(s["window_s"] - win[2] / 1e9) < 1e-12
    busy = tr.busy_ns(tr.clip(ops, win[1], win[1] + win[2]))
    assert abs(s["busy_s"] - busy / 1e9) < 1e-12
    assert 0.0 < s["busy_s"] < s["window_s"]
    assert abs(s["idle_share"] - (1 - busy / win[2])) < 1e-12
    assert s["device_ops"][0][0] == "fusion"
    assert s["host_span_n"] == {"input": 3, "fit_step": 3, "readback": 3}
    assert sum(d for _, d in s["idle_gaps"]) == pytest.approx(
        s["window_s"] - s["busy_s"])


def test_trace_without_device_plane_raises(bench_path, tmp_path):
    import trace_reduce as tr
    with pytest.raises(FileNotFoundError):
        tr.find_xplane(str(tmp_path))


def test_trace_without_device_ops_fails_a_measured_run(bench_path,
                                                        monkeypatch):
    """Only a rehearsal may go on without device metrics."""
    import common
    import trace_reduce as tr

    def no_device(*a, **kw):
        raise RuntimeError("the trace holds no device operation")

    monkeypatch.setattr(tr, "find_xplane", lambda d: d)
    monkeypatch.setattr(tr, "summarize", no_device)
    assert common.reduce_trace("x", ["fit_step"], rehearse=True) is None
    with pytest.raises(RuntimeError):
        common.reduce_trace("x", ["fit_step"])


# ----------------------------------------------------------------------
# found by name: a file added beside the others, nothing edited
# ----------------------------------------------------------------------
def test_added_files_are_found_by_name(bench_path, tmp_path, capsys):
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: open(os.path.join(dp, p), "rb").read()
              for dp, _, fs in os.walk(root / "benchmark") for p in fs}
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    (root / "benchmark/configs/toy.json").write_text(json.dumps(
        {"source": "none", "kwargs": {"n": 3}, "limits": {}}))
    (root / "benchmark/workloads/echo_mix.json").write_text(json.dumps(
        {"driver": "echo", "repeat": 4}))
    (root / "benchmark/drivers/echo.py").write_text(
        "import common\n"
        "def run(cell):\n"
        "    n = cell.config['kwargs']['n'] * cell.traffic['repeat']\n"
        "    return {'end_to_end': {'setup_s': 1.0, 'echo_per_s': n},\n"
        "            'checks': [common.check('echo', 0, 0)],\n"
        "            'attempted': n, 'failed': 0, 'facts': {'n': n}}\n")
    (root / "benchmark/layer_metrics/echo_count.py").write_text(
        "def read(facts):\n    return facts['n']\n")
    bench["configs"].append({"name": "toy", "source": "none",
                             "file": "benchmark/configs/toy.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "toy.echo", "config": "toy",
                               "traffic": "echo_mix", "chips": 1,
                               "why": "test"})
    bench["end_to_end"].append({"name": "echo_per_s", "unit": "1/s",
                                "better": "higher", "bound": 0.01,
                                "source": "host_clock",
                                "workloads": ["toy.echo"]})
    bench["per_layer"].append({"name": "echo_count", "unit": "count",
                               "better": "higher",
                               "source": "program_counter", "layer": "toy",
                               "moves": "echo_per_s",
                               "workloads": ["toy.echo"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    for m in ("common", "counts", "trace_reduce"):
        sys.modules.pop(m, None)
    run = _load(str(root / "benchmark/run.py"), "bench_run_copy")
    for trace, want in ((0, {"echo_per_s": 12.0, "setup_s": 1.0}),
                        (1, {"echo_count": 12.0,
                             "compile_cache_misses": None})):
        assert run.main(["--workload", "toy.echo", "--rehearse",
                         "--trace", str(trace)]) == 0
        line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert line["correct"] is True and line["attempted"] == 12
        got = {k: v["value"] for k, v in line["metrics"].items()}
        if trace == 0:
            assert got == want
        else:
            assert got["echo_count"] == 12.0
    after = {p: open(os.path.join(dp, p), "rb").read()
             for dp, _, fs in os.walk(root / "benchmark") for p in fs
             if p in before and "__pycache__" not in dp}
    assert after == {p: before[p] for p in after}


def test_unknown_cell_and_no_tpu_refuse(bench_path):
    run = bench_path
    with pytest.raises(SystemExit):
        run.main(["--workload", "no_such_cell", "--rehearse"])
    # no TPU here and no --rehearse: non-zero, no result
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", TRAIN, "--seconds", "0.1"])
    assert e.value.code not in (0, None)


# ----------------------------------------------------------------------
# rehearsals: the contract's line, every declared metric, no device name
# ----------------------------------------------------------------------
@pytest.mark.parametrize("workload,trace", [
    (TRAIN, 0), (TRAIN, 1), (SERVE, 0), (SERVE, 1), (RESNET, 0)])
def test_rehearsal_prints_the_contract_line(bench_path, capsys, workload,
                                            trace):
    run = bench_path
    if workload == SERVE:       # not an entry of BENCHMARK.json yet
        line = _execute(run, _cell(run, workload, seed=3000000019,
                                   seconds=0.5, trace=trace))
    else:
        assert run.main(["--workload", workload, "--seed", "3000000019",
                         "--seconds", "0.5", "--trace", str(trace),
                         "--rehearse"]) == 0
        line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert {"correct", "attempted", "failed", "metrics", "device"} \
        <= set(line)
    assert line["correct"] is True and line["failed"] == 0
    bench = _bench()
    group = "per_layer" if trace else "end_to_end"
    declared = {m["name"]: m for m in bench[group]
                if workload in m.get("workloads", [workload])}
    assert set(line["metrics"]) == set(declared)
    for name, m in declared.items():
        got = line["metrics"][name]
        assert got["unit"] == m["unit"]
        if m["source"] == "device_trace":
            assert got["value"] is None         # no CPU number under it
        else:
            assert isinstance(got["value"], float)
    dev = line["device"]
    assert dev["rehearsal"] is True and dev["kind"] == "rehearsal"
    assert "tpu" not in json.dumps(dev).lower()
    assert "busy_s" not in dev


# ----------------------------------------------------------------------
# references: agree with the system, disagree with a lower precision
# ----------------------------------------------------------------------
def test_training_reference_catches_bfloat16_system(bench_path, capsys):
    """The float32 rehearsal agrees with the float32 reference (the test
    above: ``correct``); the same system run in bfloat16 does not."""
    run = bench_path
    cell = _cell(run, TRAIN)
    cell.config["kwargs"]["dtype"] = "bfloat16"
    cell.config["multi_precision"] = True
    line = _execute(run, cell)
    assert line["correct"] is False
    out = capsys.readouterr().out
    assert "first_grad_norm_worst_leaf_gap" in out and "FAILED" in out


@pytest.mark.parametrize("reference,name,shape", [
    ("gpt2", "layer0_qkv_weight", (96, 32)),
    ("resnet", "stage1_unit1_conv1_weight", (16, 1, 1, 64))])
def test_seeded_leaves_are_bfloat16_exact(bench_path, reference, name,
                                          shape):
    """A seeded float32 leaf equals its own bfloat16 copy, also when it
    is made inside a compiled program (reduce_precision: the TPU's
    compiler keeps the excess precision of a cast there and back)."""
    import jax.numpy as jnp
    import numpy as np
    import common
    from reference import train as ref_train
    model = common.reference_model({"reference": reference})
    w = ref_train.seeded_leaf(model, model.seed_key(7), name, shape)
    assert w.dtype == jnp.float32 and float(jnp.std(w)) > 0
    back = w.astype(jnp.bfloat16).astype(jnp.float32)
    assert np.array_equal(np.asarray(w), np.asarray(back))


@pytest.mark.parametrize("workload", [TRAIN, SERVE, RESNET])
def test_control_is_not_correct(bench_path, capsys, workload):
    """The reference in fp8 in the program's place fails the cell's own
    comparison (benchmark/control.py runs this on the chip at the
    cell's size)."""
    run = bench_path
    cell = _cell(run, workload, seed=11)
    cell.traffic["control_seconds"] = 1.5    # enough tokens for a near-tie
    cell.traffic["check_requests"] = 16
    rows = run.load_module("drivers", cell.traffic["driver"]).control(cell)
    control = [r for r in rows if not r["name"].startswith("served_")]
    assert control and not all(r["ok"] for r in control)
    served = [r for r in rows if r["name"].startswith("served_")]
    assert all(r["ok"] for r in served)     # the program itself passes


# ----------------------------------------------------------------------
# the timed path broken underneath: ``correct`` comes out false
# ----------------------------------------------------------------------
def test_step_that_leaves_state_unchanged_is_not_correct(bench_path,
                                                         monkeypatch,
                                                         capsys):
    """A fit step that returns its state unchanged (the optimizer's rate
    forced to 0 under the harness): the parameters' change is 0 against
    the reference's, and the run is not correct."""
    import mxnet_tpu as mx
    run = bench_path
    real = mx.Module.init_optimizer

    def frozen(self, *a, **kw):
        kw["optimizer_params"] = dict(kw["optimizer_params"],
                                      learning_rate=0.0)
        return real(self, *a, **kw)

    monkeypatch.setattr(mx.Module, "init_optimizer", frozen)
    line = _execute(run, _cell(run, TRAIN))
    assert line["correct"] is False
    assert "param_change_norm_worst_leaf_gap" in capsys.readouterr().out


@pytest.mark.parametrize("leaf", ["stage3_unit2_conv2_weight",
                                  "stage2_unit1_bn2_gamma", "fc1_weight"])
def test_one_leaf_updated_wrongly_is_not_correct(bench_path, monkeypatch,
                                                 capsys, leaf):
    """ResNet under the cell's REAL limits (not the rehearsal's): one
    leaf, a convolution's weight, a BatchNorm gain or the classifier's
    weight, updated at three times its rate while every other leaf is
    right.  The worst-leaf comparison has to see it, and name it.  (A
    leaf whose norm lies under the median leaf's is measured against
    the median, so it has to be wrong by more to show.)"""
    import mxnet_tpu as mx
    run = bench_path
    real = mx.Module.init_optimizer

    def planted(self, *a, **kw):
        out = real(self, *a, **kw)
        self._optimizer.set_lr_mult({leaf: 3.0})
        return out

    cell = _cell(run, RESNET)
    cell.config["limits"] = json.load(open(os.path.join(
        BENCH, "configs", "resnet50_b256.json")))["limits"]
    assert _execute(run, cell)["correct"] is True   # sound under them
    capsys.readouterr()
    monkeypatch.setattr(mx.Module, "init_optimizer", planted)
    line = _execute(run, cell)
    assert line["correct"] is False
    failed = [l for l in capsys.readouterr().out.splitlines()
              if "FAILED" in l]
    assert any("worst_leaf_gap" in l and leaf in l for l in failed)


def test_altered_token_is_not_correct(bench_path, monkeypatch, capsys):
    """A served token altered where the engine emits it: its logit lies
    far below the reference's best, and the run is not correct."""
    import mxnet_tpu as mx
    run = bench_path
    real = mx.decode.DecodeEngine._emit
    n = {"i": 0}

    def altered(self, seq, tok):
        n["i"] += 1
        return real(self, seq, (tok + 1) % 96 if n["i"] % 3 == 0 else tok)

    monkeypatch.setattr(mx.decode.DecodeEngine, "_emit", altered)
    line = _execute(run, _cell(run, SERVE))
    assert line["correct"] is False
    assert "served_logit_gap_widest" in capsys.readouterr().out
