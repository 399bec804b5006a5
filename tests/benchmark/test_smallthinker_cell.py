"""The cell ``smallthinker_21b_train_s16k`` at its rehearsal size on the
CPU: the harness finds every file of it by name, the traced rehearsal
comes out ``correct`` with every declared metric, the fp8 control does
not, the three new readers give nothing (and do not raise) for a program
without what they read, and the family's counts agree with a brute-force
count of the reference's own matrix products at a tiny size and with
hand-worked values at the cell's real size.  The entries are checked by
membership and properties only: where an entry stands in its list, and
which later cells stand beside this one, is not this cell's to say.  No
topology call, here or at import."""
import argparse
import importlib.util
import json
import math
import os
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
BENCH = os.path.join(ROOT, "benchmark")
CELL = "smallthinker_21b_train_s16k"
CONFIG = "smallthinker_21b_train"
NEW = ["gqa_ms.train", "window_attention_roofline_share.train",
       "window_block_share.train"]
TRACED = NEW[:2]
JOINED = ["moe_ms.train", "expert_product_roofline_share.train",
          "expert_load_max_over_mean.train",
          "grouped_matmul_roofline_share.train"]
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


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
            "program_trace", "operator_time", "dsa_time")]:
        monkeypatch.delitem(sys.modules, m)
    spec = importlib.util.spec_from_file_location(
        "bench_run", os.path.join(BENCH, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _config():
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        return json.load(f)


# ----------------------------------------------------------------------
# the entries and the file
# ----------------------------------------------------------------------
def test_the_cell_reports_the_train_metrics_the_expert_four_and_its_three():
    bench = _bench()
    cell = [w for w in bench["workloads"] if w["name"] == CELL]
    assert len(cell) == 1 and cell[0]["chips"] == 1
    assert cell[0]["config"] == CONFIG
    assert cell[0]["traffic"] == "fit_b1_pool8"
    why = cell[0]["why"]
    assert "16384-token" in why and "window-4096" in why
    assert "1536 tokens" in why and "6144" in why and "4x" in why
    assert len(why) <= 200
    by_name = {m["name"]: m for m in bench["per_layer"]}
    everyones = [m for m in bench["per_layer"]
                 if "cgpt13b_train_s2048" in m.get("workloads", [])]
    assert len(everyones) == 19         # thirteen of a step, six of set-up
    for m in everyones + [by_name[n] for n in JOINED]:
        assert CELL in m["workloads"], m["name"]
    for other in ("cca_ms.train", "gdn_ms.train", "gated_attn_ms.train",
                  "gdn_scan_roofline_share.train", "mla_ms.train",
                  "mla_attention_roofline_share.train", "dsa_ms.train",
                  "dsa_live_block_share.train"):
        assert CELL not in by_name[other]["workloads"]
    for name in NEW:
        m = by_name[name]
        assert m["workloads"] == [CELL]
        assert m["moves"] == "train_samples_per_s"
        assert m["layer"] == by_name["moe_ms.train"]["layer"]
        assert os.path.exists(os.path.join(BENCH, "layer_metrics",
                                           name + ".py"))
    for name in TRACED:
        assert by_name[name]["source"] == "device_trace"
    assert by_name["window_block_share.train"]["source"] == "program_counter"
    assert by_name["window_block_share.train"]["better"] == "lower"
    assert by_name["window_attention_roofline_share.train"]["unit"] == "%"
    assert by_name["window_attention_roofline_share.train"]["better"] \
        == "higher"
    assert by_name["gqa_ms.train"]["unit"] == "ms"
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert CELL in e2e["train_samples_per_s"]["workloads"]
    row = [c for c in bench["configs"] if c["name"] == CONFIG][0]
    assert row["why"].startswith("drawn by the driver:")
    assert len(row["why"]) <= 200
    # one configuration and one cell: no cell in which the band does little
    assert [w["name"] for w in bench["workloads"]
            if w["config"] == CONFIG] == [CELL]


def test_the_file_keeps_the_published_widths_and_states_its_cut():
    """Every key of the source's config.json is in the file under its
    own name; only the three keys in ``reduced`` differ, and the
    published counts stand beside them."""
    cfg = _config()
    row = [c for c in _bench()["configs"] if c["name"] == CONFIG][0]
    assert row["source"] == cfg["source"] and len(row["source"]) <= 200
    assert row["file"] == "benchmark/configs/%s.json" % CONFIG
    assert sorted(row["reduced"]) == sorted(cfg["reduced"]) \
        == ["moe_num_primary_experts", "num_hidden_layers", "vocab_size"]
    src = cfg["source_config"]
    for k, v in src.items():
        if k not in cfg["reduced"]:
            assert cfg[k] == v, k
    assert (cfg["num_hidden_layers"], cfg["moe_num_primary_experts"],
            cfg["vocab_size"]) == (4, 16, 18992)
    assert (src["num_hidden_layers"], src["moe_num_primary_experts"],
            src["vocab_size"]) == (52, 64, 151936)
    kw = cfg["kwargs"]
    assert (kw["d_model"], kw["q_heads"], kw["kv_heads"], kw["head_dim"],
            kw["rope_theta"], kw["window"]) == (
        src["hidden_size"], src["num_attention_heads"],
        src["num_key_value_heads"], src["head_dim"], src["rope_theta"],
        src["sliding_window_size"])
    assert (kw["expert_dim"], kw["num_experts"], kw["top_k"]) == (
        src["moe_ffn_hidden_size"], src["moe_num_primary_experts"],
        src["moe_num_active_primary_experts"])
    assert src["norm_topk_prob"] is True
    assert src["moe_primary_router_apply_softmax"] is True
    assert src["tie_word_embeddings"] is False
    # one period of the two published lists, which have period 4
    L = kw["num_layers"]
    assert L == 4 and len(src["rope_layout"]) == src["num_hidden_layers"]
    for key, mine in (("sliding_window_layout", "window_layout"),
                      ("rope_layout", "rope_layout")):
        assert kw[mine] == src[key][:L] == [0, 1, 1, 1]
        assert src[key] == src[key][:L] * (src["num_hidden_layers"] // L)
    assert (kw["experts_held"], kw["num_classes"]) == ([0, 16], 18992)
    assert 4 * kw["experts_held"][1] == src["moe_num_primary_experts"]
    assert kw["seq_len"] == src["max_position_embeddings"] == 16384
    assert kw["seq_len"] > kw["window"]         # the band bites
    assert 8 * kw["num_classes"] == src["vocab_size"]
    assert cfg["moe_num_primary_experts"] == kw["experts_held"][1]
    for key in ("router_input", "window", "no_qk_norm", "not_built", "share",
                "optimizer", "init", "precision", "router_stream_flag",
                "max_position_embeddings"):
        assert cfg["assumed"][key].endswith("."), key
    assert "Four chips share each layer" in cfg["deployment"]
    assert "1536 tokens" in cfg["deployment"]
    assert "four times their share" in cfg["deployment"]
    for key in cfg["limits"]:
        assert 0 < cfg["limits"][key] < 1, key
    assert "loss_rel_gap" in cfg["limits"]
    assert len(cfg["limits_why"]) > 200 and len(cfg["reduced_why"]) > 200
    with open(os.path.join(BENCH, "configs", "zaya1_8b_train.json")) as f:
        opt = json.load(f)
    assert (cfg["optimizer"], cfg["optimizer_params"]) \
        == (opt["optimizer"], opt["optimizer_params"])


# ----------------------------------------------------------------------
# the rehearsal and the control
# ----------------------------------------------------------------------
def test_rehearsal_is_correct_with_every_declared_metric(run, capsys):
    assert run.main(["--workload", CELL, "--seed", "4000000019",
                     "--seconds", "0.5", "--trace", "1", "--rehearse"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    bench = _bench()
    declared = {m["name"]: m for m in bench["per_layer"]
                if CELL in m.get("workloads", [CELL])}
    assert set(NEW + JOINED) <= set(declared)
    # on the CPU the step builds no banded kernel: the counter's reader is
    # silent unless this process built one before (another test's)
    counter = "window_block_share.train"
    assert set(declared) - {counter} <= set(line["metrics"]) <= set(declared)
    for name, got in line["metrics"].items():
        if declared[name]["source"] == "device_trace":
            assert got["value"] is None         # no CPU number under it
        else:
            assert isinstance(got["value"], float)
    if counter in line["metrics"]:
        assert 0.0 < line["metrics"][counter]["value"] <= 100.0
    assert line["metrics"]["dispatches_per_step.train"]["value"] == 1.0
    load = line["metrics"]["expert_load_max_over_mean.train"]["value"]
    assert 1.0 <= load <= 4.0                   # 4 experts held
    assert line["device"]["rehearsal"] is True


def test_fp8_control_is_not_correct(run):
    ns = argparse.Namespace(workload=CELL, seed=11, seconds=0.3, trace=0,
                            rehearse=True)
    cell = run.Cell(_bench(), ns)
    rows = run.load_module("drivers", "train_fit").control(cell)
    assert rows and not all(r["ok"] for r in rows)


def test_the_reference_gives_the_harness_its_interface(run):
    import common
    model = common.reference_model(_config())
    for name in ("param_specs", "seed_key", "device_batch", "data_shapes",
                 "make_batch", "leaf_kind", "leaf_value", "leaf_key",
                 "init_leaf", "loss", "train_flops_per_sample",
                 "forward_flops_per_sample", "expert_product_flops",
                 "window_attention_flops", "window_attention_bytes"):
        assert callable(getattr(model, name)), name
    assert not hasattr(model, "init_aux")       # no auxiliary state
    assert not hasattr(model, "loss_scale")     # a mean over the batch
    kw = _config()["rehearse"]["kwargs"]
    names = [n for n, _ in model.param_specs(kw)]
    assert len(names) == 3 + 10 * kw["num_layers"]
    assert all(n.endswith(("_weight", "_gamma")) for n in names)
    with open(os.path.join(BENCH, "reference", "smallthinker.py")) as f:
        assert "mxnet_tpu" not in f.read().replace(
            "mxnet_tpu/models", "").replace('"mxnet_tpu"', "") \
            .replace("``mxnet_tpu``", "")


def test_the_references_mask_is_the_issues(run):
    """``allowed(t, s)``: every s <= t, and in a window layer only t - s
    < window: the query's own position counts."""
    import numpy as np
    import common
    model = common.reference_model(_config())
    t, s = np.arange(12)[:, None], np.arange(12)[None, :]
    full = np.asarray(model.allowed(t, s, 0))
    band = np.asarray(model.allowed(t, s, 4))
    assert np.array_equal(full, s <= t)
    assert band.sum(1).tolist() == [1, 2, 3] + [4] * 9
    assert band[7].nonzero()[0].tolist() == [4, 5, 6, 7]


# ----------------------------------------------------------------------
# the three new readers
# ----------------------------------------------------------------------
def test_readers_give_nothing_for_a_program_without_the_operator(run,
                                                                 monkeypatch):
    """What a program from before this family gives the new readers: no
    trace of the scopes, so None and no raise; and no trace at all
    likewise; and a reference without the counts (every other cell's)
    likewise; and a program without the counters, or with none booked."""
    import program_trace
    facts = {"kind": "train", "steps": 3, "batch": 1, "config": _config(),
             "peaks": PEAKS}

    class NoSuchOperator:
        op_classes = frozenset()
        modules = [{"name": "jit_step(1)", "start_ns": 0.0, "dur_ns": 1e6}]
        ops = [{"name": "fusion.1", "start_ns": 0.0, "dur_ns": 5e5,
                "tf_op": "jit(step)/jvp(FullyConnected)/h/dot_general"}]

        def has_scopes(self):
            return True

        def scope_ns(self, prefix):
            return 0.0

    for trace in (NoSuchOperator(), None):
        program_trace.train_trace = lambda f, t=trace: t
        for name in TRACED:
            assert run.load_module("layer_metrics", name).read(facts) is None
    with open(os.path.join(BENCH, "configs", "zaya1_8b_train.json")) as f:
        other = dict(facts, config=json.load(f))
    program_trace.train_trace = lambda f: NoSuchOperator()
    assert run.load_module(
        "layer_metrics", TRACED[1]).read(other) is None
    # the counters: none booked, then no such names at all
    from mxnet_tpu.pallas import dispatch
    reader = run.load_module("layer_metrics", "window_block_share.train")

    class NothingBooked:
        def children(self):
            return []

    monkeypatch.setattr(dispatch, "FLASH_BLOCKS_CAUSAL", NothingBooked())
    assert reader.read(facts) is None
    monkeypatch.delattr(dispatch, "FLASH_BLOCKS_CAUSAL")
    assert reader.read(facts) is None


def test_the_block_share_is_walked_over_causal_all_kernels(run):
    from mxnet_tpu.pallas import dispatch
    reader = run.load_module("layer_metrics", "window_block_share.train")
    walked = sum(c.value for c in dispatch.FLASH_BLOCKS_WALKED.children())
    causal = sum(c.value for c in dispatch.FLASH_BLOCKS_CAUSAL.children())
    dispatch.FLASH_BLOCKS_WALKED.labels(
        kernel="flash_attention_window_bwd").inc(252)
    dispatch.FLASH_BLOCKS_CAUSAL.labels(
        kernel="flash_attention_window_bwd").inc(528)
    dispatch.FLASH_BLOCKS_WALKED.labels(
        kernel="flash_attention_window").inc(280)
    dispatch.FLASH_BLOCKS_CAUSAL.labels(
        kernel="flash_attention_window").inc(544)
    assert reader.read({}) == pytest.approx(
        100.0 * (walked + 532) / (causal + 1072))


def test_shares_are_the_larger_need_over_the_time_under_their_scopes(run):
    """Two steps in the window.  Under ``gqa.window`` 60 ms forward and
    140 ms backward in all (100 ms a step); under ``gqa.full`` 120 ms,
    ``gqa.proj`` 60 ms and ``gqa.rope`` 20 ms (200 ms a step with the
    window's); an instruction of another operator."""
    import common
    import program_trace
    ev = lambda t0, dur, tf_op: {
        "name": "fusion", "start_ns": t0, "dur_ns": dur, "tf_op": tf_op,
        "category": None, "flops": None, "bytes_accessed": None}
    gqa = "_contrib_GroupedQueryAttention)/layer1_attn/"

    class Two:
        op_classes = frozenset(["_contrib_GroupedQueryAttention"])
        ops = [ev(0.0, 60e6, "jit(step)/jvp(" + gqa
                  + "gqa.window/pallas.flash_attention_window/pallas_call"),
               ev(100e6, 140e6, "jit(step)/transpose(jvp(" + gqa
                  + "gqa.window))/pallas.flash_attention_window/pallas_call"),
               ev(300e6, 120e6, "jit(step)/jvp(" + gqa.replace("1", "0")
                  + "gqa.full/pallas.flash_attention/pallas_call"),
               ev(500e6, 60e6, "jit(step)/jvp(" + gqa
                  + "gqa.proj/dot_general"),
               ev(600e6, 20e6, "jit(step)/transpose(jvp(" + gqa
                  + "gqa.rope))/mul"),
               ev(700e6, 5e6, "jit(step)/jvp(FullyConnected)/h/dot_general")]

        def has_scopes(self):
            return True

    program_trace.train_trace = lambda facts: Two()
    cfg = _config()
    facts = {"kind": "train", "steps": 2, "batch": 1, "config": cfg,
             "peaks": PEAKS}
    model = common.reference_model(cfg)
    kw = cfg["kwargs"]
    need_s = max(model.window_attention_flops(kw) / 197e12,
                 model.window_attention_bytes(kw) / 819e9)
    read = lambda name: run.load_module("layer_metrics", name).read(facts)
    assert read("window_attention_roofline_share.train") \
        == pytest.approx(100.0 * need_s / 0.100)
    assert 0 < read("window_attention_roofline_share.train") < 100
    assert read("gqa_ms.train") == pytest.approx(200.0)


# ----------------------------------------------------------------------
# the counts
# ----------------------------------------------------------------------
def test_counts_hand_worked_at_the_cells_size(run):
    import common
    cfg = _config()
    model = common.reference_model(cfg)
    kw = cfg["kwargs"]
    f = model.forward_flops_per_sample(kw)
    S, d, W = 16384, 2560, 4096
    causal, band = model.pairs(kw)
    assert causal == S * (S + 1) // 2 == 134_225_920
    assert band == W * (W + 1) // 2 + (S - W) * W == 58_722_304
    assert 0.437 < band / causal < 0.438         # the issue's 44 %
    assert model.layer_pairs(kw) == [causal, band, band, band]
    assert f["head"] == 2 * S * d * 18992
    assert f["projections"] == 4 * 2 * S * d * 128 * (28 + 4 + 4 + 28)
    assert f["attention"] == (causal + 3 * band) * 2 * 28 * 256
    assert f["router"] == 4 * 2 * S * d * 64
    held = 16
    assert f["experts"] == 4 * (S * 6 * held / 64) * 3 * 2 * d * 768
    assert model.window_attention_flops(kw) == 3 * 3 * band * 2 * 28 * 256
    assert model.window_attention_bytes(kw) \
        == 3 * 2 * S * 128 * (28 + 4 + 4 + 28) * 2
    assert model.train_flops_per_sample(kw) == 3 * sum(f.values())
    # the issue's forward TFLOP by part
    assert 2.74e12 < f["projections"] < 2.76e12
    assert 1.92e12 < 2 * causal * 28 * 256 < 1.93e12
    assert 0.84e12 < 2 * band * 28 * 256 < 0.85e12
    assert 4.44e12 < f["attention"] < 4.46e12
    assert 1.58e12 < f["head"] < 1.60e12
    assert 1.15e12 < f["experts"] < 1.17e12
    assert 29e12 < model.train_flops_per_sample(kw) < 31e12
    # bound by compute: the band's need over the peak is the larger
    assert model.window_attention_flops(kw) / 197e12 \
        > model.window_attention_bytes(kw) / 819e9
    # the kernels' whole blocks: 66.1 M pairs executed for 58.7 M needed
    executed = 252 * 512 * 512
    assert 0.888 < band / executed < 0.890
    # the parameters, as the issue counts them
    specs = dict(model.param_specs(kw))
    n = lambda names: sum(math.prod(specs[k]) for k in names)
    attn = [k for k in specs if k.startswith("layer1_attn_")]
    assert n(attn) == 2 * 28 * 128 * d + 2 * 4 * 128 * d == 20_971_520
    assert n(["layer1_moe_router_weight"]) == 163_840
    assert n(["layer1_moe_gate_weight", "layer1_moe_up_weight",
              "layer1_moe_down_weight"]) == held * 5_898_240
    assert n(["tok_embed_weight", "lm_head_weight"]) == 2 * 48_619_520
    assert 559.0e6 < n(specs) < 559.6e6     # the issue's 559.3 M
    assert model.expert_product_flops(kw, S * 6 * held // 64 * 4) \
        == 3 * f["experts"]


def _dot_flops(jaxpr, times=1):
    """2 x multiply-adds of every ``dot_general`` in a jaxpr, following
    sub-jaxprs (a scan's body times its length)."""
    from jax.extend import core
    total = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            (lc, _), _ = eqn.params["dimension_numbers"]
            lhs = eqn.invars[0].aval.shape
            total += times * 2 * math.prod(eqn.outvars[0].aval.shape) \
                * math.prod(lhs[i] for i in lc)
        inner = times * (eqn.params["length"]
                         if eqn.primitive.name == "scan" else 1)
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                if isinstance(sub, core.ClosedJaxpr):
                    total += _dot_flops(sub.jaxpr, inner)
                elif isinstance(sub, core.Jaxpr):
                    total += _dot_flops(sub, inner)
    return total


def test_counts_agree_with_a_brute_force_count(run):
    """Every matrix product the reference's forward pass really makes,
    counted from its jaxpr at a tiny size with every expert held.  The
    reference multiplies the whole square in every layer and runs every
    expert over every token: the count takes the band's pairs in the
    window layers, the triangle's in the full one, and ``top_k`` experts
    a token."""
    import jax
    import jax.numpy as jnp
    import common
    model = common.reference_model({"reference": "smallthinker"})
    S = 128
    kw = dict(num_classes=96, num_layers=4, d_model=32, q_heads=7, kv_heads=1,
              head_dim=8, window=32, window_layout=[0, 1, 1, 1],
              rope_layout=[0, 1, 1, 1], expert_dim=16, num_experts=8,
              experts_held=[0, 8], top_k=2, seq_len=S)
    params = {n: jax.ShapeDtypeStruct(s, jnp.float32)
              for n, s in model.param_specs(kw)}
    tok = jax.ShapeDtypeStruct((1, S), jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda p, t, l: model.loss(p, {}, t, l, kw)[0])(params, tok, tok)
    brute = _dot_flops(jaxpr.jaxpr)
    f = model.forward_flops_per_sample(kw)
    causal, band = model.pairs(kw)
    assert causal == S * (S + 1) // 2
    assert band == 32 * 33 // 2 + (S - 32) * 32
    squares = 4 * S * S * 2 * 7 * 2 * 8
    want = sum(f.values()) - f["attention"] + squares \
        + (8 // 2 - 1) * f["experts"]
    assert brute == pytest.approx(want, rel=1e-12)
    assert f["attention"] == (causal + 3 * band) * 2 * 7 * 2 * 8
    # a window no shorter than the sequence is the triangle
    assert model.pairs(dict(kw, window=4 * S)) == (causal, causal)
    # with a quarter of the experts held, a quarter of the pairs
    part = model.forward_flops_per_sample(dict(kw, experts_held=[2, 2]))
    assert part["experts"] * 4 == f["experts"]
    assert {k: v for k, v in part.items() if k != "experts"} \
        == {k: v for k, v in f.items() if k != "experts"}


def test_seeded_leaves_follow_the_assumed_initialisation(run):
    import jax.numpy as jnp
    import numpy as np
    import common
    from reference import train as ref_train
    model = common.reference_model({"reference": "smallthinker"})
    key = model.seed_key(4000000019)
    leaf = lambda name, shape: ref_train.seeded_leaf(model, key, name, shape)
    for name, shape, std in [("layer1_attn_q_weight", (96, 64), 0.02),
                             ("layer3_moe_gate_weight", (4, 48, 64), 0.02),
                             ("layer0_moe_router_weight", (64, 96), 0.02),
                             ("tok_embed_weight", (512, 64), 1.0)]:
        w = leaf(name, shape)
        assert w.dtype == jnp.float32
        assert 0.9 * std < float(jnp.std(w)) < 1.1 * std
        back = w.astype(jnp.bfloat16).astype(jnp.float32)
        assert np.array_equal(np.asarray(w), np.asarray(back))
    for name in ("layer0_in_norm_gamma", "layer2_post_norm_gamma",
                 "final_norm_gamma"):
        assert float(jnp.abs(leaf(name, (64,)) - 1.0).max()) == 0.0
