"""The cell ``kanana2_30b_train_ep8`` at its rehearsal size on the CPU:
the harness finds every file of it by name, the traced rehearsal comes
out ``correct`` with every declared metric, the fp8 control does not,
the two new readers give nothing (and do not raise) for a program
without what they read, and the family's counts agree with a
brute-force count of the reference's own matrix products at a tiny size
and with hand-worked values at the cell's real size.  The entries are
checked by membership and properties only: where an entry stands in its
list, and which later cells stand beside this one, is not this cell's to
say.  No topology call, here or at import."""
import argparse
import importlib.util
import json
import math
import os
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
BENCH = os.path.join(ROOT, "benchmark")
CELL = "kanana2_30b_train_ep8"
CONFIG = "kanana2_30b_train"
NEW = ["mla_ms.train", "mla_attention_roofline_share.train"]
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
            "program_trace", "operator_time")]:
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
def test_the_cell_reports_the_train_metrics_the_expert_four_and_its_two():
    bench = _bench()
    cell = [w for w in bench["workloads"] if w["name"] == CELL]
    assert len(cell) == 1 and cell[0]["chips"] == 1
    assert cell[0]["config"] == CONFIG
    assert cell[0]["traffic"] == "fit_b1_pool8"
    assert "384 tokens" in cell[0]["why"] and "8x" in cell[0]["why"]
    by_name = {m["name"]: m for m in bench["per_layer"]}
    everyones = [m for m in bench["per_layer"]
                 if "cgpt13b_train_s2048" in m.get("workloads", [])]
    assert everyones
    for m in everyones + [by_name[n] for n in JOINED]:
        assert CELL in m["workloads"], m["name"]
    for other in ("cca_ms.train", "gdn_ms.train", "gated_attn_ms.train",
                  "gdn_scan_roofline_share.train"):
        assert CELL not in by_name[other]["workloads"]
    for name in NEW:
        m = by_name[name]
        assert CELL in m["workloads"]
        assert m["moves"] == "train_samples_per_s"
        assert m["source"] == "device_trace"
        assert m["layer"] == by_name["moe_ms.train"]["layer"]
        assert os.path.exists(os.path.join(BENCH, "layer_metrics",
                                           name + ".py"))
    assert by_name["mla_attention_roofline_share.train"]["unit"] == "%"
    assert by_name["mla_attention_roofline_share.train"]["better"] == "higher"
    assert by_name["mla_ms.train"]["unit"] == "ms"
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert CELL in e2e["train_samples_per_s"]["workloads"]
    row = [c for c in bench["configs"] if c["name"] == CONFIG][0]
    assert "drawn by the driver" in row["why"] and len(row["why"]) <= 200


def test_the_file_keeps_the_published_widths_and_states_its_cut():
    """Every number of the source's config.json is in the file under
    its own key; only the three keys in ``reduced`` differ, and the
    published counts stand beside them."""
    cfg = _config()
    row = [c for c in _bench()["configs"] if c["name"] == CONFIG][0]
    assert row["source"] == cfg["source"] and len(row["source"]) <= 200
    assert row["file"] == "benchmark/configs/%s.json" % CONFIG
    assert sorted(row["reduced"]) == sorted(cfg["reduced"]) \
        == ["n_routed_experts", "num_hidden_layers", "vocab_size"]
    src = cfg["source_config"]
    for k, v in src.items():
        if k not in cfg["reduced"]:
            assert cfg[k] == v, k
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (5, 16, 16032)
    assert (src["num_hidden_layers"], src["n_routed_experts"],
            src["vocab_size"]) == (48, 128, 128256)
    kw = cfg["kwargs"]
    assert (kw["d_model"], kw["heads"], kw["nope_dim"], kw["rope_dim"],
            kw["v_dim"], kw["kv_rank"], kw["rope_theta"]) == (
        src["hidden_size"], src["num_attention_heads"],
        src["qk_nope_head_dim"], src["qk_rope_head_dim"], src["v_head_dim"],
        src["kv_lora_rank"], src["rope_theta"])
    assert kw["nope_dim"] + kw["rope_dim"] == src["qk_head_dim"] == 192
    assert src["q_lora_rank"] is None and src["rope_interleave"] is True
    assert (kw["dense_layers"], kw["dense_dim"], kw["expert_dim"],
            kw["num_experts"], kw["top_k"], kw["route_scale"]) == (
        src["first_k_dense_replace"], src["intermediate_size"],
        src["moe_intermediate_size"], src["n_routed_experts"],
        src["num_experts_per_tok"], src["routed_scaling_factor"])
    assert kw["shared_dim"] \
        == src["n_shared_experts"] * src["moe_intermediate_size"]
    assert (src["scoring_func"], src["topk_method"], src["n_group"],
            src["topk_group"], src["norm_topk_prob"]) == (
        "sigmoid", "noaux_tc", 1, 1, True)
    assert (kw["num_layers"], kw["experts_held"], kw["num_classes"],
            kw["seq_len"]) == (5, [0, 16], 16032, 8192)
    assert kw["num_layers"] - kw["dense_layers"] >= 4       # the floor
    assert 8 * kw["num_classes"] == src["vocab_size"]
    assert 8 * kw["experts_held"][1] == src["n_routed_experts"]
    for key in ("bias_update", "bias_init", "balancing", "groups",
                "rotary_layout", "projections", "head_dim", "share",
                "optimizer", "init", "precision", "max_position_embeddings"):
        assert cfg["assumed"][key].endswith("."), key
    assert "Eight chips share each layer" in cfg["deployment"]
    assert "384 tokens" in cfg["deployment"]
    assert "eight times its share" in cfg["deployment"]
    for key in ("loss_rel_gap", "grad_norm_gap.weights",
                "grad_norm_gap.others", "delta_norm_gap.weights",
                "delta_norm_gap.others"):
        assert 0 < cfg["limits"][key] < 1
    assert len(cfg["limits_why"]) > 200 and len(cfg["reduced_why"]) > 200
    with open(os.path.join(BENCH, "configs", "zaya1_8b_train.json")) as f:
        opt = json.load(f)
    assert (cfg["optimizer"], cfg["optimizer_params"]) \
        == (opt["optimizer"], opt["optimizer_params"])


# ----------------------------------------------------------------------
# the rehearsal and the control
# ----------------------------------------------------------------------
def test_rehearsal_is_correct_with_every_declared_metric(run, capsys):
    assert run.main(["--workload", CELL, "--seed", "3200000019",
                     "--seconds", "0.5", "--trace", "1", "--rehearse"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    bench = _bench()
    declared = {m["name"]: m for m in bench["per_layer"]
                if CELL in m.get("workloads", [CELL])}
    assert set(NEW + JOINED) <= set(declared)
    assert set(line["metrics"]) == set(declared)
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


def test_the_reference_gives_the_harness_its_interface(run):
    import common
    model = common.reference_model(_config())
    for name in ("param_specs", "seed_key", "device_batch", "data_shapes",
                 "make_batch", "leaf_kind", "leaf_value", "leaf_key",
                 "init_leaf", "init_aux", "loss", "train_flops_per_sample",
                 "expert_product_flops", "mla_attention_flops",
                 "mla_attention_bytes"):
        assert callable(getattr(model, name)), name
    kw = _config()["rehearse"]["kwargs"]
    aux = model.init_aux(kw)
    assert sorted(aux) == ["layer1_moe_router_bias", "layer2_moe_router_bias"]
    for b in aux.values():
        assert b.shape == (kw["num_experts"],) and str(b.dtype) == "float32"
        assert float(abs(b).max()) > 0
    again = model.init_aux(kw)
    assert all((aux[n] == again[n]).all() for n in aux)
    assert not (aux["layer1_moe_router_bias"]
                == aux["layer2_moe_router_bias"]).all()


# ----------------------------------------------------------------------
# the two new readers
# ----------------------------------------------------------------------
def test_readers_give_nothing_for_a_program_without_the_operator(run):
    """What the parent commit's program gives the new readers: no trace
    of the operator class or of the scope, so None and no raise; and no
    trace at all likewise; and a reference without the counts (every
    other cell's) likewise."""
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
        for name in NEW:
            assert run.load_module("layer_metrics", name).read(facts) is None
    with open(os.path.join(BENCH, "configs", "zaya1_8b_train.json")) as f:
        other = dict(facts, config=json.load(f))
    program_trace.train_trace = lambda f: NoSuchOperator()
    assert run.load_module(
        "layer_metrics", "mla_attention_roofline_share.train").read(other) \
        is None


def test_attention_share_is_the_larger_need_over_the_time_under_the_scope(
        run):
    """Two steps in the window; under ``mla.attention`` 60 ms forward
    and 140 ms backward in all (100 ms a step), an instruction of the
    same operator outside the scope, and one of another operator.  The
    need of a step at the cell's size is the larger of FLOPs over the
    peak and bytes over the bandwidth: the FLOPs, 52 ms."""
    import common
    import program_trace
    ev = lambda t0, dur, tf_op: {
        "name": "fusion", "start_ns": t0, "dur_ns": dur, "tf_op": tf_op,
        "category": None, "flops": None, "bytes_accessed": None}
    mla = "_contrib_LatentAttention)/layer0_attn/"

    class Two:
        op_classes = frozenset(["_contrib_LatentAttention"])
        ops = [ev(0.0, 60e6, "jit(step)/jvp(" + mla
                  + "mla.attention/pallas.flash_attention/vmap(splash)"),
               ev(70e6, 140e6, "jit(step)/transpose(jvp(" + mla
                  + "mla.attention))/pallas.flash_attention/vmap(splash)"),
               ev(220e6, 7e6, "jit(step)/jvp(" + mla
                  + "mla.proj/dot_general"),
               ev(230e6, 5e6, "jit(step)/jvp(FullyConnected)/h/dot_general")]

        def has_scopes(self):
            return True

        def scope_ns(self, prefix):
            assert prefix == "op._contrib_LatentAttention"
            return 207e6

    program_trace.train_trace = lambda facts: Two()
    cfg = _config()
    facts = {"kind": "train", "steps": 2, "batch": 1, "config": cfg,
             "peaks": PEAKS}
    model = common.reference_model(cfg)
    flops_s = model.mla_attention_flops(cfg["kwargs"]) / 197e12
    bytes_s = model.mla_attention_bytes(cfg["kwargs"]) / 819e9
    assert flops_s > bytes_s and 0.050 < flops_s < 0.054
    got = run.load_module(
        "layer_metrics", "mla_attention_roofline_share.train").read(facts)
    assert got == pytest.approx(100.0 * flops_s / 0.100)
    assert 0 < got < 100
    assert run.load_module("layer_metrics", "mla_ms.train").read(facts) \
        == pytest.approx(103.5)


# ----------------------------------------------------------------------
# the counts
# ----------------------------------------------------------------------
def test_counts_hand_worked_at_the_cells_size(run):
    import common
    cfg = _config()
    model = common.reference_model(cfg)
    kw = cfg["kwargs"]
    f = model.forward_flops_per_sample(kw)
    S, d, H = 8192, 2048, 32
    assert f["head"] == 2 * S * d * 16032
    assert f["attention"] == 5 * S * S * H * (192 + 128)
    assert f["mla_projections"] == 5 * 2 * S * (
        d * H * 192 + d * 576 + 512 * H * 256 + H * 128 * d)
    assert f["dense_ffn"] == S * 3 * 2 * d * 6144
    assert f["experts"] == 4 * (S * 6 // 8) * 3 * 2 * d * 768
    assert f["shared_expert"] == 4 * S * 3 * 2 * d * 1536
    assert f["router"] == 4 * 2 * S * d * 128
    total = model.train_flops_per_sample(kw)
    assert total == 3 * sum(f.values())
    assert 22.0e12 < total < 23.5e12        # the issue's 22.9 TFLOP a step
    # the attention cores: 33.55 M causal pairs x 32 heads x (3 x 192 +
    # 3 x 128) multiply-adds a layer, five layers
    assert model.mla_attention_flops(kw) == 5 * 2 * (S * S // 2) * H * 960 \
        == 3 * f["attention"]
    assert 10.2e12 < model.mla_attention_flops(kw) < 10.4e12
    # q, k (192) and v, o (128) of 32 heads in bf16, values and gradients
    assert model.mla_attention_bytes(kw) \
        == 5 * 2 * S * H * (192 + 192 + 128 + 128) * 2 == 3_355_443_200
    # the parameters, as the issue counts them
    specs = dict(model.param_specs(kw))
    n = lambda names: sum(math.prod(specs[k]) for k in names)
    attn = [k for k in specs if k.startswith("layer1_attn_")]
    assert n(attn) == 12_582_912 + 1_179_648 + 512 + 4_194_304 + 8_388_608
    assert n(["layer0_ffn_gate_weight", "layer0_ffn_up_weight",
              "layer0_ffn_down_weight"]) == 3 * 2048 * 6144
    assert n([k for k in specs if k.startswith("layer0_")]) \
        == 26_345_984 + 2 * 2048 + 37_748_736            # 64.10 M
    assert n(["layer1_moe_gate_weight", "layer1_moe_up_weight",
              "layer1_moe_down_weight"]) == 16 * 4_718_592
    assert n(["layer1_moe_shared_gate_weight", "layer1_moe_shared_up_weight",
              "layer1_moe_shared_down_weight"]) == 9_437_184
    assert n(["tok_embed_weight", "lm_head_weight"]) == 2 * 32_833_536
    assert 575.0e6 < n(specs) < 577.5e6     # the issue's 576 M
    assert not [k for k in specs if k.endswith("router_bias")]
    assert model.expert_product_flops(kw, S * 6 // 8 * 4) \
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
    reference multiplies the whole attention square and runs every
    expert over every token: the count takes half the square and
    ``top_k`` experts a token."""
    import jax
    import jax.numpy as jnp
    import common
    model = common.reference_model({"reference": "kanana2"})
    kw = dict(num_classes=96, num_layers=3, d_model=32, heads=4, nope_dim=8,
              rope_dim=4, v_dim=8, kv_rank=16, dense_layers=1, dense_dim=48,
              expert_dim=16, num_experts=8, experts_held=[0, 8], top_k=2,
              route_scale=2.448, shared_dim=32, seq_len=128)
    params = {n: jax.ShapeDtypeStruct(s, jnp.float32)
              for n, s in model.param_specs(kw)}
    aux = {n: jax.ShapeDtypeStruct(a.shape, a.dtype)
           for n, a in model.init_aux(kw).items()}
    tok = jax.ShapeDtypeStruct((1, 128), jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda p, a, t, l: model.loss(p, a, t, l, kw)[0])(params, aux, tok,
                                                          tok)
    brute = _dot_flops(jaxpr.jaxpr)
    f = model.forward_flops_per_sample(kw)
    want = sum(f.values()) + f["attention"] + (8 // 2 - 1) * f["experts"]
    assert brute == want
    # and with a quarter of the experts held, a quarter of the pairs
    part = model.forward_flops_per_sample(dict(kw, experts_held=[2, 2]))
    assert part["experts"] * 4 == f["experts"]
    assert {k: v for k, v in part.items() if k != "experts"} \
        == {k: v for k, v in f.items() if k != "experts"}


def test_seeded_leaves_follow_the_assumed_initialisation(run):
    import jax.numpy as jnp
    import numpy as np
    import common
    from reference import train as ref_train
    model = common.reference_model({"reference": "kanana2"})
    key = model.seed_key(3200000019)
    leaf = lambda name, shape: ref_train.seeded_leaf(model, key, name, shape)
    for name, shape, std in [("layer1_attn_q_weight", (96, 64), 0.02),
                             ("layer3_moe_gate_weight", (4, 48, 64), 0.02),
                             ("layer0_ffn_down_weight", (64, 96), 0.02),
                             ("tok_embed_weight", (512, 64), 1.0)]:
        w = leaf(name, shape)
        assert w.dtype == jnp.float32
        assert 0.9 * std < float(jnp.std(w)) < 1.1 * std
        back = w.astype(jnp.bfloat16).astype(jnp.float32)
        assert np.array_equal(np.asarray(w), np.asarray(back))
    for name in ("layer0_in_norm_gamma", "layer2_attn_kv_norm_gamma",
                 "final_norm_gamma"):
        assert float(jnp.abs(leaf(name, (64,)) - 1.0).max()) == 0.0
    # the bias: a fixed small draw a layer, whatever the seed
    kw = _config()["kwargs"]
    aux = model.init_aux(kw)
    assert len(aux) == 4
    for b in aux.values():
        assert b.shape == (128,)
        assert 0.5 * model.BIAS_STD < float(jnp.std(b)) < 1.5 * model.BIAS_STD
