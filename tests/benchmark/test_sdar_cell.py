"""The cell ``sdar_30b_train_bd4_s8k`` at its rehearsal size on the CPU:
the harness finds every file of it by name, the traced rehearsal comes
out ``correct`` with every declared metric, the fp8 control does not,
the three new readers give nothing (and do not raise) for a program
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
CELL = "sdar_30b_train_bd4_s8k"
CONFIG = "sdar_30b_a3b_train"
NEW = ["bd_gqa_ms.train", "blockdiff_attention_roofline_share.train",
       "blockdiff_block_share.train"]
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
    assert len(why) <= 200
    for part in ("clean and noised copy in one pass", "288 of 1024",
                 "1024 rows an expert against 8192", "8x"):
        assert part in why, part
    by_name = {m["name"]: m for m in bench["per_layer"]}
    everyones = [m for m in bench["per_layer"]
                 if "cgpt13b_train_s2048" in m.get("workloads", [])]
    assert len(everyones) == 19                 # thirteen and the set-up six
    for m in everyones + [by_name[n] for n in JOINED]:
        assert CELL in m["workloads"], m["name"]
    for other in ("cca_ms.train", "gqa_ms.train", "window_block_share.train",
                  "dsa_ms.train", "mla_ms.train", "gdn_ms.train"):
        assert CELL not in by_name[other]["workloads"]
    for name in NEW:
        m = by_name[name]
        assert m["workloads"] == [CELL]
        assert m["moves"] == "train_samples_per_s"
        assert m["layer"] == by_name["gqa_ms.train"]["layer"]
        assert m["source"] == ("program_counter" if "block_share" in name
                               else "device_trace")
        assert os.path.exists(os.path.join(BENCH, "layer_metrics",
                                           name + ".py"))
    assert by_name["blockdiff_attention_roofline_share.train"]["unit"] == "%"
    assert by_name["blockdiff_block_share.train"]["better"] == "lower"
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert CELL in e2e["train_samples_per_s"]["workloads"]
    assert "workloads" not in e2e["setup_s"]
    row = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert len(row) == 1 and row[0]["why"].startswith("drawn by the driver:")
    assert len(row[0]["why"]) <= 200
    # one configuration and one cell: no cell in which the mask does little
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
        == ["num_experts", "num_hidden_layers", "vocab_size"]
    src = cfg["source_config"]
    assert len(src) == 24 and src["rope_scaling"] is None
    assert (src["intermediate_size"], src["max_window_layers"],
            src["decoder_sparse_step"], src["mlp_only_layers"]) \
        == (6144, 48, 1, [])
    for k, v in src.items():
        if k not in cfg["reduced"]:
            assert cfg[k] == v, k
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (4, 16, 18992)
    assert (src["num_hidden_layers"], src["num_experts"],
            src["vocab_size"]) == (48, 128, 151936)
    kw = cfg["kwargs"]
    assert (kw["d_model"], kw["q_heads"], kw["kv_heads"], kw["head_dim"],
            kw["rope_theta"]) == (
        src["hidden_size"], src["num_attention_heads"],
        src["num_key_value_heads"], src["head_dim"], src["rope_theta"])
    assert (kw["expert_dim"], kw["num_experts"], kw["top_k"]) == (
        src["moe_intermediate_size"], src["num_experts"],
        src["num_experts_per_tok"])
    assert src["norm_topk_prob"] is True and src["model_type"] == "sdar_moe"
    assert src["tie_word_embeddings"] is False
    assert src["use_sliding_window"] is False
    assert (kw["experts_held"], kw["num_classes"], kw["num_layers"]) \
        == ([0, 16], 18992, 4)
    assert 8 * kw["experts_held"][1] == src["num_experts"]
    assert 8 * kw["num_classes"] == src["vocab_size"]
    assert (kw["seq_len"], kw["block_length"], kw["dtype"]) \
        == (8192, 4, "bfloat16")
    assert 2 * kw["seq_len"] <= src["max_position_embeddings"]
    assert cfg["num_experts"] == kw["experts_held"][1]
    assert len(cfg["assumed"]) == 8             # the issue's points (a)-(h)
    for key, text in cfg["assumed"].items():
        assert text.startswith("(") and text.endswith("."), key
    assert sorted(t[1] for t in cfg["assumed"].values()) == list("abcdefgh")
    assert "Eight chips share each layer" in cfg["deployment"]
    assert "1024 rows" in cfg["deployment"]
    assert "eight times their share" in cfg["deployment"]
    # every row the harness reads is held, none by a placeholder: a
    # wrong mask reads ~1 and a step without the 1 / p weights 0.35-0.5
    # on EVERY leaf, which the worst other leaf catches; the two rows a
    # router's draw moves lie between their largest sound reading and 1
    assert set(cfg["limits"]) == {
        "loss_rel_gap", "grad_norm_gap.weights", "grad_norm_gap.others",
        "grad_norm_mean_weight_gap", "delta_norm_gap.weights",
        "delta_norm_gap.others", "delta_norm_mean_weight_gap"}
    assert all(0 < v <= 0.5 for v in cfg["limits"].values())
    assert cfg["limits"]["grad_norm_gap.others"] <= 4e-3
    assert cfg["limits"]["grad_norm_gap.others"] \
        < cfg["limits"]["grad_norm_mean_weight_gap"] \
        < cfg["limits"]["grad_norm_gap.weights"]
    assert "float32 residual stream" in cfg["limits_why"]
    assert "NOT PROVEN" in cfg["limits_why"]
    assert "residual stream" in cfg["assumed"]["training"].lower()
    assert len(cfg["limits_why"]) > 200 and len(cfg["reduced_why"]) > 200
    with open(os.path.join(BENCH, "configs", "zaya1_8b_train.json")) as f:
        opt = json.load(f)
    assert (cfg["optimizer"], cfg["optimizer_params"]) \
        == (opt["optimizer"], opt["optimizer_params"])


# ----------------------------------------------------------------------
# the rehearsal and the control
# ----------------------------------------------------------------------
def test_rehearsal_is_correct_with_every_declared_metric(run, capsys):
    assert run.main(["--workload", CELL, "--seed", "4400000019",
                     "--seconds", "0.5", "--trace", "1", "--rehearse"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    bench = _bench()
    declared = {m["name"]: m for m in bench["per_layer"]
                if CELL in m.get("workloads", [CELL])}
    assert set(NEW + JOINED) <= set(declared)
    # on the CPU the step builds no mask kernel: the counter's reader is
    # silent unless this process built one before (another test's)
    counter = "blockdiff_block_share.train"
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
    # the value compared is ce over ALL noised rows: under the uniform
    # mask probability about half of log(96) at random weights
    assert all(0.2 * math.log(96) < x < 0.9 * math.log(96)
               for x in line["notes"]["losses"])


def test_fp8_control_is_not_correct(run):
    """At the rehearsal's sizes and limits (float32 against fp8).  At the
    cell's own size the control is run on the chip
    (``benchmark/control.py``) and comes out not correct on about half of
    its 22 seeds only: ``limits_why`` has its readings beside the program's, and
    says why no limits of this harness tell the two apart there."""
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
                 "blockdiff_attention_flops", "blockdiff_attention_bytes"):
        assert callable(getattr(model, name)), name
    assert not hasattr(model, "init_aux")       # no auxiliary state
    assert not hasattr(model, "loss_scale")     # a mean over the rows
    kw = _config()["rehearse"]["kwargs"]
    names = [n for n, _ in model.param_specs(kw)]
    assert len(names) == 3 + 12 * kw["num_layers"]
    assert all(n.endswith(("_weight", "_gamma")) for n in names)
    assert model.data_shapes(kw, 2) == ((2, 3, kw["seq_len"]),
                                        (2 * kw["seq_len"],))
    with open(os.path.join(BENCH, "reference", "sdar_moe.py")) as f:
        assert "mxnet_tpu" not in f.read().replace(
            "mxnet_tpu/models", "").replace('"mxnet_tpu"', "") \
            .replace("``mxnet_tpu``", "")


def test_the_references_mask_is_the_issues(run):
    """``allowed(t, s)`` over ``[clean; noised]`` rows at L 8, Bk 4: a
    clean row sees the clean blocks up to its own (its own both ways), a
    noised row the clean blocks strictly before its own and its own
    noised block both ways, and a clean row never a noised one."""
    import numpy as np
    import common
    model = common.reference_model(_config())
    t, s = np.arange(16)[:, None], np.arange(16)[None, :]
    on = np.asarray(model.allowed(t, s, 8, 4))
    assert on[:8, :8].tolist() == (np.arange(8)[None, :] // 4
                                   <= np.arange(8)[:, None] // 4).tolist()
    assert not on[:8, 8:].any()
    assert on[8:, :8].tolist() == (np.arange(8)[None, :] // 4
                                   < np.arange(8)[:, None] // 4).tolist()
    assert on[8:, 8:].tolist() == (np.arange(8)[None, :] // 4
                                   == np.arange(8)[:, None] // 4).tolist()
    assert on[9].nonzero()[0].tolist() == [8, 9, 10, 11]     # a first block
    assert on[13].nonzero()[0].tolist() == [0, 1, 2, 3, 12, 13, 14, 15]
    assert int(on.sum()) == 8 * 8 + 8 * 4


# ----------------------------------------------------------------------
# the three new readers
# ----------------------------------------------------------------------
def test_readers_give_nothing_for_a_program_without_the_operator(run,
                                                                 monkeypatch):
    """What a program from before this family gives the new readers: no
    trace of the scopes, so None and no raise; and no trace at all
    likewise; and a reference without the counts (every other cell's)
    likewise; and a program without the counters, or with none booked
    under the mask kernels' labels."""
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
    with open(os.path.join(BENCH, "configs",
                           "smallthinker_21b_train.json")) as f:
        other = dict(facts, config=json.load(f))
    program_trace.train_trace = lambda f: NoSuchOperator()
    assert run.load_module(
        "layer_metrics", TRACED[1]).read(other) is None
    from mxnet_tpu.pallas import dispatch
    reader = run.load_module("layer_metrics", "blockdiff_block_share.train")

    class OnlyTheBand:
        def children(self):
            class Child:
                label_values, value = ("flash_attention_window_bwd",), 528.0
            return [Child()]

    monkeypatch.setattr(dispatch, "FLASH_BLOCKS_CAUSAL", OnlyTheBand())
    assert reader.read(facts) is None
    monkeypatch.delattr(dispatch, "FLASH_BLOCKS_CAUSAL")
    assert reader.read(facts) is None


def test_the_block_share_reads_the_mask_kernels_labels_only(run):
    from mxnet_tpu.pallas import dispatch
    reader = run.load_module("layer_metrics", "blockdiff_block_share.train")
    mine = ("flash_attention_blocks", "flash_attention_blocks_bwd")
    of = lambda c: sum(x.value for x in c.children()
                       if x.label_values[0] in mine)
    walked, causal = (of(dispatch.FLASH_BLOCKS_WALKED),
                      of(dispatch.FLASH_BLOCKS_CAUSAL))
    for kernel, w, c in (("flash_attention_blocks_bwd", 288, 528),
                         ("flash_attention_blocks", 320, 544),
                         ("flash_attention_window_bwd", 252, 528)):
        dispatch.FLASH_BLOCKS_WALKED.labels(kernel=kernel).inc(w)
        dispatch.FLASH_BLOCKS_CAUSAL.labels(kernel=kernel).inc(c)
    assert reader.read({}) == pytest.approx(
        100.0 * (walked + 608) / (causal + 1072))
    if not walked:
        assert reader.read({}) == pytest.approx(56.7, abs=0.05)


def test_shares_are_the_larger_need_over_the_time_under_their_scopes(run):
    """Two steps in the window.  Under ``gqa.blockdiff`` 80 ms forward
    and 160 ms backward in all (120 ms a step); under ``gqa.proj`` 60
    ms and ``gqa.norm`` 20 ms (160 ms a step with the cores'); an
    instruction under another family's ``gqa.full`` and one of another
    operator, which neither reader counts."""
    import common
    import program_trace
    ev = lambda t0, dur, tf_op: {
        "name": "fusion", "start_ns": t0, "dur_ns": dur, "tf_op": tf_op,
        "category": None, "flops": None, "bytes_accessed": None}
    gqa = "_contrib_GroupedQueryAttention)/layer1_attn/"

    class Two:
        op_classes = frozenset(["_contrib_GroupedQueryAttention"])
        ops = [ev(0.0, 80e6, "jit(step)/jvp(" + gqa
                  + "gqa.blockdiff/pallas.flash_attention_blocks/pallas_call"),
               ev(100e6, 160e6, "jit(step)/transpose(jvp(" + gqa
                  + "gqa.blockdiff))/pallas.flash_attention_blocks/"
                  "pallas_call"),
               ev(300e6, 60e6, "jit(step)/jvp(" + gqa
                  + "gqa.proj/dot_general"),
               ev(400e6, 20e6, "jit(step)/transpose(jvp(" + gqa
                  + "gqa.norm))/mul"),
               ev(500e6, 40e6, "jit(step)/jvp(" + gqa
                  + "gqa.full/pallas.flash_attention/pallas_call"),
               ev(700e6, 5e6, "jit(step)/jvp(FullyConnected)/h/dot_general")]

        def has_scopes(self):
            return True

    program_trace.train_trace = lambda facts: Two()
    cfg = _config()
    facts = {"kind": "train", "steps": 2, "batch": 1, "config": cfg,
             "peaks": PEAKS}
    model = common.reference_model(cfg)
    kw = cfg["kwargs"]
    need_s = max(model.blockdiff_attention_flops(kw) / 197e12,
                 model.blockdiff_attention_bytes(kw) / 819e9)
    read = lambda name: run.load_module("layer_metrics", name).read(facts)
    assert read("blockdiff_attention_roofline_share.train") \
        == pytest.approx(100.0 * need_s / 0.120)
    assert 0 < read("blockdiff_attention_roofline_share.train") < 100
    assert read("bd_gqa_ms.train") == pytest.approx(160.0)


# ----------------------------------------------------------------------
# the counts
# ----------------------------------------------------------------------
def test_counts_hand_worked_at_the_cells_size(run):
    import common
    cfg = _config()
    model = common.reference_model(cfg)
    kw = cfg["kwargs"]
    f = model.forward_flops_per_sample(kw)
    L, R, d = 8192, 16384, 2048
    pairs = model.pairs(kw)
    assert pairs == L * L + 4 * L == 67_141_632
    assert f["head"] == 2 * L * d * 18992             # the noised half only
    assert f["projections"] == 4 * 2 * R * d * 128 * (32 + 4 + 4 + 32)
    assert f["attention"] == 4 * pairs * 2 * 32 * 256
    assert f["router"] == 4 * 2 * R * d * 128
    held = 16
    assert f["experts"] == 4 * (R * 8 * held / 128) * 3 * 2 * d * 768
    assert model.blockdiff_attention_flops(kw) == 3 * f["attention"]
    assert model.blockdiff_attention_bytes(kw) \
        == 4 * 2 * R * 128 * (32 + 4 + 4 + 32) * 2
    assert model.train_flops_per_sample(kw) == 3 * sum(f.values())
    # the issue's forward TFLOP by part
    assert 2.46e12 < f["projections"] < 2.48e12
    assert 4.39e12 < f["attention"] < 4.41e12
    assert 0.63e12 < f["head"] < 0.65e12
    assert 0.61e12 < f["experts"] < 0.63e12
    assert 24e12 < model.train_flops_per_sample(kw) < 25e12
    assert 0.53 < f["attention"] / sum(f.values()) < 0.55   # ~54 %
    # bound by compute: the mask's need over the peak is the larger
    assert model.blockdiff_attention_flops(kw) / 197e12 \
        > model.blockdiff_attention_bytes(kw) / 819e9
    # the kernels' whole blocks: 75.5 M pairs executed for 67.1 M needed
    executed = 288 * 512 * 512
    assert 0.888 < pairs / executed < 0.890
    # against the causal kernels over the same rows: 48.5 %
    assert 0.484 < pairs / (528 * 512 * 512) < 0.486
    # the parameters, as the issue counts them
    specs = dict(model.param_specs(kw))
    n = lambda names: sum(math.prod(specs[k]) for k in names)
    attn = [k for k in specs if k.startswith("layer1_attn_")]
    assert n(attn) == 2 * 32 * 128 * d + 2 * 4 * 128 * d + 2 * 128
    assert n(["layer1_moe_router_weight"]) == 262_144
    assert n(["layer1_moe_gate_weight", "layer1_moe_up_weight",
              "layer1_moe_down_weight"]) == held * 4_718_592
    assert n(["tok_embed_weight", "lm_head_weight"]) == 2 * 38_895_616
    assert 456.3e6 < n(specs) < 456.5e6     # the issue's 456.4 M
    assert model.expert_product_flops(kw, R * 8 * held // 128 * 4) \
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
    reference multiplies the whole (2L, 2L) square in every layer and
    runs every expert over every row: the count takes the mask's pairs
    and ``top_k`` experts a row; the head runs over the L noised rows in
    both."""
    import jax
    import jax.numpy as jnp
    import common
    model = common.reference_model({"reference": "sdar_moe"})
    L = 64
    kw = dict(num_classes=96, num_layers=3, d_model=32, q_heads=8, kv_heads=2,
              head_dim=8, block_length=4, expert_dim=16, num_experts=8,
              experts_held=[0, 8], top_k=2, seq_len=L)
    params = {n: jax.ShapeDtypeStruct(s, jnp.float32)
              for n, s in model.param_specs(kw)}
    data = jax.ShapeDtypeStruct((1, 3, L), jnp.float32)
    lab = jax.ShapeDtypeStruct((1, L), jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda p, t, l: model.loss(p, {}, t, l, kw)[0])(params, data, lab)
    brute = _dot_flops(jaxpr.jaxpr)
    f = model.forward_flops_per_sample(kw)
    assert model.pairs(kw) == L * L + 4 * L
    squares = 3 * (2 * L) ** 2 * 2 * 8 * 2 * 8
    want = sum(f.values()) - f["attention"] + squares \
        + (8 // 2 - 1) * f["experts"]
    assert brute == pytest.approx(want, rel=1e-12)
    # with an eighth of the experts held, an eighth of the pairs
    part = model.forward_flops_per_sample(dict(kw, experts_held=[3, 1]))
    assert part["experts"] * 8 == f["experts"]
    assert {k: v for k, v in part.items() if k != "experts"} \
        == {k: v for k, v in f.items() if k != "experts"}


def test_seeded_leaves_follow_the_assumed_initialisation(run):
    import jax.numpy as jnp
    import numpy as np
    import common
    from reference import train as ref_train
    model = common.reference_model({"reference": "sdar_moe"})
    key = model.seed_key(4400000019)
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
    for name in ("layer0_in_norm_gamma", "layer2_attn_q_norm_gamma",
                 "layer1_attn_k_norm_gamma", "final_norm_gamma"):
        assert float(jnp.abs(leaf(name, (64,)) - 1.0).max()) == 0.0
