"""The cell ``qwen3next_80b_train_ep16`` at its rehearsal size on the
CPU: the harness finds every file of it by name, the rehearsal comes out
``correct`` with every declared metric, the fp8 control does not, the
three new readers give nothing (and do not raise) for a program without
what they read, and the family's counts agree with a brute-force count
of the reference's own matrix products at a tiny size and with
hand-worked values at the cell's real size.  No topology call, here or
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
CELL = "qwen3next_80b_train_ep16"
NEW = ["gdn_ms.train", "gated_attn_ms.train", "gdn_scan_roofline_share.train"]
JOINED = ["moe_ms.train", "expert_product_roofline_share.train",
          "expert_load_max_over_mean.train",
          "grouped_matmul_roofline_share.train"]


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
                                       "qwen3_next_80b_train.json")))


# ----------------------------------------------------------------------
# the entries and the file
# ----------------------------------------------------------------------
def test_the_cell_reports_the_train_metrics_the_expert_four_and_its_three():
    """Membership and properties only: where an entry stands in its
    list, and which later cells stand beside this one, is not this
    cell's to say."""
    bench = _bench()
    cell = [w for w in bench["workloads"] if w["name"] == CELL]
    assert len(cell) == 1 and cell[0]["chips"] == 1
    assert cell[0]["config"] == "qwen3_next_80b_train"
    assert cell[0]["traffic"] == "fit_b1_pool8"
    assert "160 tokens" in cell[0]["why"] and "16x" in cell[0]["why"]
    by_name = {m["name"]: m for m in bench["per_layer"]}
    everyones = [m for m in bench["per_layer"]
                 if "cgpt13b_train_s2048" in m.get("workloads", [])]
    assert everyones
    for m in everyones + [by_name[n] for n in JOINED]:
        assert CELL in m["workloads"], m["name"]
    assert CELL not in by_name["cca_ms.train"]["workloads"]
    for name in NEW:
        m = by_name[name]
        assert CELL in m["workloads"]
        assert m["moves"] == "train_samples_per_s"
        assert m["source"] == "device_trace"
        assert os.path.exists(os.path.join(BENCH, "layer_metrics",
                                           name + ".py"))
    assert by_name["gdn_scan_roofline_share.train"]["unit"] == "%"
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert CELL in e2e["train_samples_per_s"]["workloads"]


def test_the_file_keeps_the_published_widths_and_states_its_cut():
    """Every number of the source's config.json is in the file under
    its own key; only the three keys in ``reduced`` differ, and the
    published counts stand beside them."""
    cfg = _config()
    row = [c for c in _bench()["configs"]
           if c["name"] == "qwen3_next_80b_train"][0]
    assert row["source"] == cfg["source"] and len(row["source"]) <= 200
    assert sorted(row["reduced"]) == sorted(cfg["reduced"]) \
        == ["num_experts", "num_hidden_layers", "vocab_size"]
    src = cfg["source_config"]
    for k, v in src.items():
        if k not in cfg["reduced"]:
            assert cfg[k] == v, k
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (4, 32, 18992)
    assert (src["num_hidden_layers"], src["num_experts"],
            src["vocab_size"]) == (48, 512, 151936)
    kw = cfg["kwargs"]
    assert (kw["d_model"], kw["q_heads"], kw["kv_heads"], kw["head_dim"],
            kw["rotary_frac"], kw["rope_theta"]) == (
        src["hidden_size"], src["num_attention_heads"],
        src["num_key_value_heads"], src["head_dim"],
        src["partial_rotary_factor"], src["rope_theta"])
    assert (kw["gdn_k_heads"], kw["gdn_v_heads"], kw["gdn_k_dim"],
            kw["gdn_v_dim"], kw["conv_kernel"]) == (
        src["linear_num_key_heads"], src["linear_num_value_heads"],
        src["linear_key_head_dim"], src["linear_value_head_dim"],
        src["linear_conv_kernel_dim"])
    assert (kw["expert_dim"], kw["num_experts"], kw["top_k"],
            kw["shared_dim"], kw["full_attention_interval"]) == (
        src["moe_intermediate_size"], src["num_experts"],
        src["num_experts_per_tok"], src["shared_expert_intermediate_size"],
        src["full_attention_interval"])
    assert (kw["num_layers"], kw["experts_held"], kw["num_classes"],
            kw["seq_len"]) == (4, [0, 32], 18992, 8192)
    assert 8 * kw["num_classes"] == src["vocab_size"]
    assert 16 * kw["experts_held"][1] == src["num_experts"]
    for key in ("mtp", "balancing", "intermediate_size", "qkvz_order", "scan",
                "share", "optimizer", "init", "precision"):
        assert cfg["assumed"][key].endswith(".")
    assert "Sixteen chips share each layer" in cfg["deployment"]
    assert "160 tokens" in cfg["deployment"]
    assert "sixteen times their share" in cfg["deployment"]
    opt = json.load(open(os.path.join(BENCH, "configs",
                                      "zaya1_8b_train.json")))
    assert (cfg["optimizer"], cfg["optimizer_params"]) \
        == (opt["optimizer"], opt["optimizer_params"])


# ----------------------------------------------------------------------
# the rehearsal and the control
# ----------------------------------------------------------------------
def test_rehearsal_is_correct_with_every_declared_metric(run, capsys):
    assert run.main(["--workload", CELL, "--seed", "3000000019",
                     "--seconds", "0.5", "--trace", "1", "--rehearse"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    bench = _bench()
    declared = {m["name"]: m for m in bench["per_layer"]
                if CELL in m.get("workloads", [CELL])}
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


# ----------------------------------------------------------------------
# the three new readers
# ----------------------------------------------------------------------
def test_readers_give_nothing_for_a_program_without_the_operators(run):
    """What the parent commit's program gives the new readers: no trace
    of the operator classes or of the scope, so None and no raise; and
    no trace at all likewise."""
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
    """Two steps in the window; under ``gdn.scan`` 30 ms forward, 20 ms
    recomputed and 40 ms backward in all (45 ms a step), an instruction
    of the same operator outside the scope, and one of another
    operator.  The need of a step at the cell's size is the larger of
    FLOPs over the peak and bytes over the bandwidth."""
    import common
    import program_trace
    ev = lambda t0, dur, tf_op: {
        "name": "fusion", "start_ns": t0, "dur_ns": dur, "tf_op": tf_op,
        "category": None, "flops": None, "bytes_accessed": None}
    gdn = "_contrib_GatedDeltaNet)/layer0_gdn/"

    class Two:
        op_classes = frozenset(["_contrib_GatedDeltaNet"])
        ops = [ev(0.0, 30e6, "jit(step)/jvp(" + gdn
                  + "gdn.scan/checkpoint/while/body/dot_general"),
               ev(40e6, 20e6, "jit(step)/transpose(jvp(" + gdn
                  + "gdn.scan))/rematted_computation/while/body/dot_general"),
               ev(60e6, 40e6, "jit(step)/transpose(jvp(" + gdn
                  + "gdn.scan))/while/body/transpose/dot_general"),
               ev(100e6, 7e6, "jit(step)/jvp(" + gdn
                  + "gdn.proj/dot_general"),
               ev(110e6, 5e6, "jit(step)/jvp(FullyConnected)/h/dot_general")]

        def has_scopes(self):
            return True

    program_trace.train_trace = lambda facts: Two()
    cfg = _config()
    facts = {"kind": "train", "steps": 2, "batch": 1, "config": cfg,
             "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    model = common.reference_model(cfg)
    need = max(model.gdn_scan_flops(cfg["kwargs"]) / 197e12,
               model.gdn_scan_bytes(cfg["kwargs"]) / 819e9)
    got = run.load_module("layer_metrics",
                          "gdn_scan_roofline_share.train").read(facts)
    assert got == pytest.approx(100.0 * need / 0.045)
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
    S, d = 8192, 2048
    assert f["head"] == 2 * S * d * 18992
    assert f["attention"] == 2 * S * S * 4096
    assert f["experts"] == 4 * (S * 10 // 16) * 3 * 2 * d * 512
    assert f["shared_expert"] == 4 * S * (3 * 2 * d * 512 + 2 * d)
    assert f["router"] == 4 * 2 * S * d * 512
    assert f["gdn_projections"] == 3 * 2 * S * d * (12288 + 64 + 4096)
    assert f["attn_projections"] == 2 * S * d * (8192 + 512 + 512 + 4096)
    # the chunked rule: 128 chunks of 64 tokens, 16 key and 32 value heads
    key_head = 2 * 2 * 64 * 64 * 128
    value_head = 3 * 2 * 64 * 64 * 128 + 3 * 2 * 64 * 128 * 128
    assert f["gdn_scan"] == 3 * 128 * (16 * key_head + 32 * value_head) \
        == 128_849_018_880
    assert model.gdn_scan_flops(kw) == 3 * f["gdn_scan"]
    # q, k (16 x 128), v, o (32 x 128) in bf16, g and beta in float32,
    # values and gradients, three layers
    assert model.gdn_scan_bytes(kw) == 3 * 2 * (
        S * (2 * 2048 + 2 * 4096) * 2 + S * 2 * 32 * 4) == 1_220_542_464
    total = model.train_flops_per_sample(kw)
    assert total == 3 * sum(f.values())
    assert 11.0e12 < total < 12.5e12        # the issue's ~11.7 TFLOP a step
    # the parameters, as the issue counts them
    specs = dict(model.param_specs(kw))
    n = lambda names: sum(math.prod(specs[k]) for k in names)
    gdn = [k for k in specs if k.startswith("layer0_gdn_")]
    assert n(gdn) == 25_165_824 + 131_072 + 32_768 + 8_388_608 + 32 + 32 + 128
    attn = [k for k in specs if k.startswith("layer3_attn_")]
    assert n(attn) == 16_777_216 + 2 * 1_048_576 + 8_388_608 + 2 * 256
    assert n(["layer0_moe_gate_weight", "layer0_moe_up_weight",
              "layer0_moe_down_weight"]) == 32 * 3_145_728
    assert n(["tok_embed_weight", "lm_head_weight"]) == 2 * 38_895_616
    assert 625.0e6 < n(specs) < 626.5e6     # the issue's 625.7 M
    assert model.expert_product_flops(kw, S * 10 // 16 * 4) \
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
    reference multiplies the whole attention square, runs every expert
    over every token and applies the delta rule token by token (two
    Dk x Dv products a token and value head): the count takes half the
    square, ``top_k`` experts a token, and the chunked rule's products."""
    import jax
    import jax.numpy as jnp
    import common
    model = common.reference_model({"reference": "qwen3_next"})
    kw = dict(num_classes=96, num_layers=4, d_model=32,
              full_attention_interval=4, q_heads=4, kv_heads=2, head_dim=8,
              gdn_k_heads=2, gdn_v_heads=4, gdn_k_dim=8, gdn_v_dim=8,
              conv_kernel=4, expert_dim=16, num_experts=8,
              experts_held=[0, 8], top_k=2, shared_dim=16, seq_len=128)
    params = {n: jax.ShapeDtypeStruct(s, jnp.float32)
              for n, s in model.param_specs(kw)}
    tok = jax.ShapeDtypeStruct((1, 128), jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda p, t, l: model.loss(p, {}, t, l, kw)[0])(params, tok, tok)
    brute = _dot_flops(jaxpr.jaxpr)
    f = model.forward_flops_per_sample(kw)
    token_rule = 3 * 128 * 4 * 2 * (2 * 8 * 8)
    want = sum(f.values()) - f["gdn_scan"] + token_rule + f["attention"] \
        + (8 // 2 - 1) * f["experts"]
    assert brute == want
    # the chunked rule at this size, by hand: 2 chunks, 2 key heads, 4
    # value heads of 8 x 8
    assert f["gdn_scan"] == 3 * 2 * (2 * 2 * (2 * 64 * 64 * 8)
                                     + 4 * (3 * 2 * 64 * 64 * 8
                                            + 3 * 2 * 64 * 8 * 8))
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
    model = common.reference_model({"reference": "qwen3_next"})
    key = model.seed_key(3000000019)
    leaf = lambda name, shape: ref_train.seeded_leaf(model, key, name, shape)
    for name, shape, std in [("layer1_gdn_qkvz_weight", (96, 64), 0.02),
                             ("layer3_moe_gate_weight", (4, 48, 64), 0.02),
                             ("tok_embed_weight", (512, 64), 1.0)]:
        w = leaf(name, shape)
        assert w.dtype == jnp.float32
        assert 0.9 * std < float(jnp.std(w)) < 1.1 * std
        back = w.astype(jnp.bfloat16).astype(jnp.float32)
        assert np.array_equal(np.asarray(w), np.asarray(back))
    assert float(jnp.abs(leaf("layer0_in_norm_gamma", (64,))).max()) == 0.0
    assert float(jnp.abs(leaf("layer3_attn_q_norm_gamma", (8,))).max()) == 0.0
    assert float(leaf("layer0_gdn_norm_gamma", (8,)).min()) == 1.0
    # exp(g) = exp(-A dt) at a = 0: mostly in (0.2, 1), so the state
    # carries across chunks
    A = jnp.exp(leaf("layer0_gdn_A_log", (4096,)))
    dt = jnp.log1p(jnp.exp(leaf("layer0_gdn_dt_bias", (4096,))))
    assert 0.0 < float(A.min()) and float(A.max()) <= 16.0
    assert 0.9e-3 < float(dt.min()) and float(dt.max()) < 0.11
    keep = np.asarray(jnp.exp(-A * dt))
    assert keep.min() > 0.19 and np.mean(keep > 0.5) > 0.8
