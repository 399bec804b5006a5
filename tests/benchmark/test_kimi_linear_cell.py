"""The cell ``kimilinear_48b_train_ep32`` at its rehearsal size on the
CPU: the harness finds every file of it by name, the rehearsal comes out
``correct`` with every declared metric, the fp8 control does not, the
two new readers give nothing (and do not raise) for a program without
what they read, and the family's counts agree with hand-worked values at
the cell's real size.  Membership and properties only: where an entry
stands in its list is not this cell's to say.  No topology call, here or
at import."""
import argparse
import importlib.util
import json
import math
import os
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
BENCH = os.path.join(ROOT, "benchmark")
CELL = "kimilinear_48b_train_ep32"
NEW = ["kda_ms.train", "kda_scan_roofline_share.train"]
JOINED = ["moe_ms.train", "expert_product_roofline_share.train",
          "expert_load_max_over_mean.train",
          "grouped_matmul_roofline_share.train", "mla_ms.train",
          "mla_attention_roofline_share.train"]


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
            "program_trace", "operator_time")]:
        monkeypatch.delitem(sys.modules, m)
    spec = importlib.util.spec_from_file_location(
        "bench_run", os.path.join(BENCH, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _bench():
    return json.load(open(os.path.join(ROOT, "BENCHMARK.json")))


def _config():
    return json.load(open(os.path.join(BENCH, "configs",
                                       "kimi_linear_48b_train.json")))


# ----------------------------------------------------------------------
# the entries and the file
# ----------------------------------------------------------------------
def test_the_cell_reports_the_train_metrics_the_expert_four_the_latent_two_and_its_two():
    bench = _bench()
    cell = [w for w in bench["workloads"] if w["name"] == CELL]
    assert len(cell) == 1 and cell[0]["chips"] == 1
    assert cell[0]["config"] == "kimi_linear_48b_train"
    assert cell[0]["traffic"] == "fit_b1_pool8"
    why = cell[0]["why"]
    assert len(why) <= 200 and "256 rows" in why and "32x" in why
    assert "thirty-second" in why
    by_name = {m["name"]: m for m in bench["per_layer"]}
    everyones = [m for m in bench["per_layer"]
                 if "cgpt13b_train_s2048" in m.get("workloads", [])]
    assert len(everyones) == 19             # thirteen and the set-up six
    for m in everyones + [by_name[n] for n in JOINED]:
        assert CELL in m["workloads"], m["name"]
    for other in ("cca_ms.train", "gdn_ms.train", "gqa_ms.train",
                  "gdn_scan_roofline_share.train", "dsa_ms.train"):
        assert CELL not in by_name[other]["workloads"]
    for name in NEW:
        m = by_name[name]
        assert m["workloads"] == [CELL]
        assert m["moves"] == "train_samples_per_s"
        assert m["source"] == "device_trace"
        assert m["layer"] == by_name["gdn_ms.train"]["layer"]
        assert os.path.exists(os.path.join(BENCH, "layer_metrics",
                                           name + ".py"))
    assert by_name["kda_scan_roofline_share.train"]["unit"] == "%"
    assert by_name["kda_ms.train"]["unit"] == "ms"
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert CELL in e2e["train_samples_per_s"]["workloads"]
    assert "workloads" not in e2e["setup_s"]


def test_the_file_keeps_the_published_widths_and_states_its_cut():
    """Every number of the source's config.json is in the file under
    its own key; only the three keys in ``reduced`` differ, and the
    published counts stand beside them."""
    cfg = _config()
    row = [c for c in _bench()["configs"]
           if c["name"] == "kimi_linear_48b_train"][0]
    assert row["source"] == cfg["source"] and len(row["source"]) <= 200
    assert row["why"].startswith("drawn by the driver:")
    assert len(row["why"]) <= 200
    assert row["file"] == "benchmark/configs/kimi_linear_48b_train.json"
    assert sorted(row["reduced"]) == sorted(cfg["reduced"]) \
        == ["num_experts", "num_hidden_layers", "vocab_size"]
    src = cfg["source_config"]
    for k, v in src.items():
        if k not in cfg["reduced"]:
            assert cfg[k] == v, k
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (5, 8, 20480)
    assert (src["num_hidden_layers"], src["num_experts"],
            src["vocab_size"]) == (27, 256, 163840)
    kw, lin = cfg["kwargs"], src["linear_attn_config"]
    assert (kw["d_model"], kw["heads"], kw["head_dim"], kw["conv_kernel"]) \
        == (src["hidden_size"], lin["num_heads"], lin["head_dim"],
            lin["short_conv_kernel_size"])
    assert kw["heads"] == src["num_attention_heads"]
    assert (kw["kda_layers"], kw["full_attn_layers"]) \
        == (lin["kda_layers"], lin["full_attn_layers"])
    assert (kw["nope_dim"], kw["rope_dim"], kw["v_dim"], kw["kv_rank"]) == (
        src["qk_nope_head_dim"], src["qk_rope_head_dim"], src["v_head_dim"],
        src["kv_lora_rank"])
    assert src["mla_use_nope"] is True and src["q_lora_rank"] is None
    assert (kw["dense_layers"], kw["dense_dim"], kw["expert_dim"],
            kw["num_experts"], kw["top_k"], kw["route_scale"],
            kw["shared_dim"]) == (
        src["first_k_dense_replace"], src["intermediate_size"],
        src["moe_intermediate_size"], src["num_experts"],
        src["num_experts_per_token"], src["routed_scaling_factor"],
        src["num_shared_experts"] * src["moe_intermediate_size"])
    assert (kw["num_layers"], kw["experts_held"], kw["num_classes"],
            kw["seq_len"], kw["dtype"]) == (5, [0, 8], 20480, 8192,
                                            "bfloat16")
    assert 8 * kw["num_classes"] == src["vocab_size"]
    assert 32 * kw["experts_held"][1] == src["num_experts"]
    for key in ("kda_layer", "no_position", "groups", "bias_and_recipe",
                "which_layers", "positions", "share"):
        assert cfg["assumed"][key].endswith(".")
    for mark, key in zip("abcdef", ("kda_layer", "no_position", "groups",
                                    "bias_and_recipe", "which_layers",
                                    "positions")):
        assert cfg["assumed"][key].startswith("(%s)" % mark)
    assert "Thirty-two chips share each layer" in cfg["deployment"]
    assert "256 rows" in cfg["deployment"]
    assert "32 times their share" in cfg["deployment"]
    for key in ("reduced_why", "limits_why"):
        assert len(cfg[key]) > 200 and "TODO" not in cfg[key]
    assert set(cfg["limits"]) >= {"grad_norm_gap.weights",
                                  "delta_norm_gap.weights"}
    kanana = json.load(open(os.path.join(BENCH, "configs",
                                         "kanana2_30b_train.json")))
    assert (cfg["optimizer"], cfg["optimizer_params"]) \
        == (kanana["optimizer"], kanana["optimizer_params"])
    re_kw = cfg["rehearse"]["kwargs"]
    assert set(re_kw) == set(kw) and re_kw["num_layers"] == 5


# ----------------------------------------------------------------------
# the rehearsal and the control
# ----------------------------------------------------------------------
def test_rehearsal_is_correct_with_every_declared_metric(run, capsys):
    assert run.main(["--workload", CELL, "--seed", "3000000047",
                     "--seconds", "0.3", "--trace", "1", "--rehearse"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    bench = _bench()
    declared = {m["name"]: m for m in bench["per_layer"]
                if CELL in m.get("workloads", [CELL])}
    assert set(line["metrics"]) == set(declared)
    assert set(NEW) <= set(declared) and len(declared) == 27
    for name, m in declared.items():
        got = line["metrics"][name]
        if m["source"] == "device_trace":
            assert got["value"] is None         # no CPU number under it
        else:
            assert isinstance(got["value"], float)
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


# ----------------------------------------------------------------------
# the two new readers
# ----------------------------------------------------------------------
def test_readers_give_nothing_for_a_program_without_the_operator(run):
    """What the parent commit's program gives the new readers: no trace
    of the operator class or of the scope, so None and no raise; and no
    trace at all likewise."""
    import program_trace
    facts = {"kind": "train", "steps": 3, "batch": 1, "config": _config(),
             "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}

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
        for name in NEW:
            assert run.load_module("layer_metrics", name).read(facts) is None


def test_scan_share_is_the_larger_need_over_the_time_under_the_scope(run):
    """Two steps in the window; under ``kda.scan`` 60 ms forward and
    140 ms backward in all (100 ms a step: the kernels' own scope inside
    it), an instruction of the same operator outside the scope (the
    gates, made again in the backward pass), the scalar rule's scope and
    another operator.  The need of a step at the cell's size is the
    larger of FLOPs over the peak and bytes over the bandwidth."""
    import common
    import program_trace
    ev = lambda t0, dur, tf_op: {
        "name": "fusion", "start_ns": t0, "dur_ns": dur, "tf_op": tf_op,
        "category": None, "flops": None, "bytes_accessed": None}
    kda = "_contrib_KimiDeltaAttention)/layer0_kda/"

    class Two:
        op_classes = frozenset(["_contrib_KimiDeltaAttention"])
        ops = [ev(0.0, 60e6, "jit(step)/jvp(" + kda + "checkpoint/kda.scan/"
                  "pallas.kda_delta_rule/_run_forward/pallas_call"),
               ev(70e6, 140e6, "jit(step)/transpose(jvp(" + kda
                  + "checkpoint/kda.scan/pallas.kda_delta_rule))/"
                  "_run_backward/pallas_call"),
               ev(220e6, 9e6, "jit(step)/transpose(jvp(" + kda
                  + "checkpoint))/rematted_computation/kda.gate/softplus"),
               ev(230e6, 7e6, "jit(step)/jvp(_contrib_GatedDeltaNet)/l/"
                  "gdn.scan/pallas.gated_delta_rule/pallas_call"),
               ev(240e6, 5e6, "jit(step)/jvp(FullyConnected)/h/dot_general")]

        def has_scopes(self):
            return True

    program_trace.train_trace = lambda facts: Two()
    cfg = _config()
    facts = {"kind": "train", "steps": 2, "batch": 1, "config": cfg,
             "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    model = common.reference_model(cfg)
    need = max(model.kda_scan_flops(cfg["kwargs"]) / 197e12,
               model.kda_scan_bytes(cfg["kwargs"]) / 819e9)
    got = run.load_module("layer_metrics",
                          "kda_scan_roofline_share.train").read(facts)
    assert got == pytest.approx(100.0 * need / 0.100)
    assert 0 < got < 100


# ----------------------------------------------------------------------
# the counts
# ----------------------------------------------------------------------
def test_counts_hand_worked_at_the_cells_size(run):
    import common
    cfg = _config()
    model = common.reference_model(cfg)
    kw = cfg["kwargs"]
    f = model.forward_flops_per_sample(kw)
    S, d, HD = 8192, 2304, 4096
    assert f["head"] == 2 * S * d * 20480
    assert f["attention"] == S * S * 32 * (192 + 128)
    assert f["dense_ffn"] == S * 3 * 2 * d * 9216
    assert f["experts"] == 4 * (S * 8 * 8 // 256) * 3 * 2 * d * 1024
    assert f["shared_expert"] == 4 * S * 3 * 2 * d * 1024
    assert f["router"] == 4 * 2 * S * d * 256
    assert f["kda_projections"] == 4 * 2 * S * (
        4 * d * HD + 2 * (d * 128 + 128 * HD) + d * 32)
    assert f["mla_projections"] == 2 * S * (
        d * 32 * 192 + d * 576 + 512 * 32 * 256 + HD * d)
    # the chunked rule: 128 chunks of 64 tokens, 32 heads, as the scalar
    # gate's with one value head a key head
    head = 5 * 2 * 64 * 64 * 128 + 3 * 2 * 64 * 128 * 128
    assert f["kda_scan"] == 4 * 128 * 32 * head == 188_978_561_024
    assert model.kda_scan_flops(kw) == 3 * f["kda_scan"]
    # q, k, v, o in bf16, g (as large as k) and beta in float32, values
    # and gradients, four layers
    assert model.kda_scan_bytes(kw) == 4 * 2 * S * 32 * (
        4 * 128 * 2 + 128 * 4 + 4) == 3_229_614_080
    assert model.mla_attention_flops(kw) == 2 * (S * S // 2) * 32 * 3 * 320
    assert model.mla_attention_bytes(kw) == 2 * S * 32 * 2 * 320 * 2
    total = model.train_flops_per_sample(kw)
    assert total == 3 * sum(f.values())
    assert 18.0e12 < total < 20.0e12        # the issue's ~19 TFLOP a step
    # the parameters, as the issue counts them
    specs = dict(model.param_specs(kw))
    n = lambda names: sum(math.prod(specs[k]) for k in names)
    kda = [k for k in specs if k.startswith("layer0_kda_")]
    assert n(kda) == 4 * 9_437_184 + 2 * (294_912 + 524_288) + 73_728 \
        + 49_152 + 32 + 2 * 4096 + 128
    attn = [k for k in specs if k.startswith("layer3_attn_")]
    assert n(attn) == 14_155_776 + 1_327_104 + 512 + 4_194_304 + 9_437_184
    assert n(["layer0_ffn_gate_weight", "layer0_ffn_up_weight",
              "layer0_ffn_down_weight"]) == 63_700_992
    assert n(["layer1_moe_gate_weight", "layer1_moe_up_weight",
              "layer1_moe_down_weight"]) == 8 * 7_077_888
    assert n(["tok_embed_weight", "lm_head_weight"]) == 2 * 47_185_920
    assert 602.0e6 < n(specs) < 603.0e6     # the issue's 602.4 M
    assert model.expert_product_flops(kw, S * 8 * 8 // 256 * 4) \
        == 3 * f["experts"]
    # the reference's scan is the program's chunk and no chunk of its own
    assert model.CHUNK == 64
    assert not hasattr(model, "gdn_scan_flops")


def test_seeded_leaves_follow_the_assumed_initialisation(run):
    import jax.numpy as jnp
    import numpy as np
    import common
    from reference import train as ref_train
    model = common.reference_model({"reference": "kimi_linear"})
    key = model.seed_key(3000000047)
    leaf = lambda name, shape: ref_train.seeded_leaf(model, key, name, shape)
    for name, shape, std in [("layer1_kda_q_weight", (96, 64), 0.02),
                             ("layer0_kda_fb_weight", (256, 16), 0.02),
                             ("tok_embed_weight", (512, 64), 1.0)]:
        w = leaf(name, shape)
        assert w.dtype == jnp.float32
        assert 0.9 * std < float(jnp.std(w)) < 1.1 * std
        back = w.astype(jnp.bfloat16).astype(jnp.float32)
        assert np.array_equal(np.asarray(w), np.asarray(back))
    assert float(leaf("layer0_in_norm_gamma", (64,)).min()) == 1.0
    assert float(leaf("layer0_kda_norm_gamma", (16,)).min()) == 1.0
    assert float(jnp.abs(leaf("layer0_kda_gb_bias", (64,))).max()) == 0.0
    # exp(g) = exp(-A dt) at a = 0: a chunk of 64 tokens holds channels
    # that keep e^-0.001 and channels that keep e^-100
    A = jnp.exp(leaf("layer0_kda_A_log", (32,)))
    dt = jnp.log1p(jnp.exp(leaf("layer0_kda_dt_bias", (4096,))))
    assert 0.0 < float(A.min()) and float(A.max()) <= 16.0
    assert 0.9e-3 < float(dt.min()) and float(dt.max()) < 0.11
    chunk = 64 * np.asarray(A)[:, None] * np.asarray(dt).reshape(32, 128)
    assert chunk.max() > 20.0 and chunk.min() < 0.5
