"""The cell ``zaya1_8b_train_ep2`` at its rehearsal size on the CPU: the
harness finds every file of it by name, the rehearsal comes out
``correct`` with every declared metric, the fp8 control does not, and
the family's counts agree with a brute-force count of the reference's
own matrix products at a tiny size and with hand-worked values at the
cell's real size.  No topology call, here or at import."""
import argparse
import importlib.util
import json
import math
import os
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
BENCH = os.path.join(ROOT, "benchmark")
CELL = "zaya1_8b_train_ep2"
NEW = ["cca_ms.train", "moe_ms.train", "expert_product_roofline_share.train",
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
                                       "zaya1_8b_train.json")))


# ----------------------------------------------------------------------
# the entries and the file
# ----------------------------------------------------------------------
def test_the_cell_reports_every_train_metric_and_its_own_five():
    bench = _bench()
    cell = [w for w in bench["workloads"] if w["name"] == CELL]
    assert len(cell) == 1 and cell[0]["chips"] == 1
    assert cell[0]["config"] == "zaya1_8b_train"
    assert cell[0]["traffic"] == "fit_b1_pool8"
    for m in bench["per_layer"]:
        if m["name"].endswith(".train") and m["name"] not in NEW:
            assert CELL in m["workloads"], m["name"]
    for name in NEW:
        (m,) = [m for m in bench["per_layer"] if m["name"] == name]
        assert m["workloads"] == [CELL]
        assert m["moves"] == "train_samples_per_s"
        assert os.path.exists(os.path.join(BENCH, "layer_metrics",
                                           name + ".py"))
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert CELL in e2e["train_samples_per_s"]["workloads"]


def test_the_file_keeps_the_published_widths_and_states_its_cut():
    """Every number of the source's config.json is in the file under
    its own key; only the three keys in ``reduced`` differ, and the
    published counts stand beside them."""
    cfg = _config()
    row = [c for c in _bench()["configs"] if c["name"] == "zaya1_8b_train"][0]
    assert row["source"] == cfg["source"]
    assert sorted(row["reduced"]) == sorted(cfg["reduced"]) \
        == ["num_experts", "num_hidden_layers", "vocab_size"]
    published = {"hidden_size": 2048, "num_attention_heads": 8,
                 "num_key_value_heads": 2, "head_dim": 128,
                 "moe_intermediate_size": 2048, "num_experts_per_tok": 1,
                 "router_hidden_size": 256, "cca_time0": 2, "cca_time1": 2,
                 "partial_rotary_factor": 0.5, "rms_norm_eps": 1e-05,
                 "max_position_embeddings": 131072}
    for k, v in published.items():
        assert cfg[k] == v, k
    assert cfg["rope_parameters"]["hybrid"]["rope_theta"] == 5000000
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (4, 8, 32784)
    src = cfg["source_config"]
    assert (src["num_hidden_layers"], src["num_experts"],
            src["vocab_size"]) == (40, 16, 262272)
    kw = cfg["kwargs"]
    assert (kw["d_model"], kw["q_heads"], kw["kv_heads"], kw["head_dim"],
            kw["expert_dim"], kw["router_hidden"], kw["num_experts"]) \
        == (2048, 8, 2, 128, 2048, 256, 16)
    assert (kw["num_layers"], kw["experts_held"], kw["num_classes"],
            kw["seq_len"]) == (4, [0, 8], 32784, 8192)
    assert 8 * kw["num_classes"] == src["vocab_size"]
    for key in ("temperature", "residual_scale", "convolutions",
                "depth_averaging", "balancing", "skip_route", "optimizer",
                "init"):
        assert cfg["assumed"][key].endswith(".")
    assert "Two chips share each transformer layer" in cfg["deployment"]


# ----------------------------------------------------------------------
# the rehearsal and the control
# ----------------------------------------------------------------------
@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_is_correct_with_every_declared_metric(run, capsys, trace):
    assert run.main(["--workload", CELL, "--seed", "3000000019",
                     "--seconds", "0.5", "--trace", str(trace),
                     "--rehearse"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    bench = _bench()
    group = "per_layer" if trace else "end_to_end"
    declared = {m["name"]: m for m in bench[group]
                if CELL in m.get("workloads", [CELL])}
    assert set(line["metrics"]) == set(declared)
    for name, m in declared.items():
        got = line["metrics"][name]
        if m["source"] == "device_trace":
            assert got["value"] is None         # no CPU number under it
        else:
            assert isinstance(got["value"], float)
    if trace:
        assert line["metrics"]["dispatches_per_step.train"]["value"] == 1.0
        load = line["metrics"]["expert_load_max_over_mean.train"]["value"]
        assert 1.0 <= load <= 4.0               # 4 experts held
    assert line["device"]["rehearsal"] is True


def test_fp8_control_is_not_correct(run):
    ns = argparse.Namespace(workload=CELL, seed=11, seconds=0.3, trace=0,
                            rehearse=True)
    cell = run.Cell(_bench(), ns)
    rows = run.load_module("drivers", "train_fit").control(cell)
    assert rows and not all(r["ok"] for r in rows)


def test_readers_give_nothing_for_a_program_without_the_operators(run):
    """What the parent commit's program gives the new readers: no trace
    of the operator classes and no counter, so None and no raise."""
    import operator_time
    import program_trace
    facts = {"kind": "train", "steps": 3, "config": _config(),
             "peaks": {"bf16_flops_per_s": 197e12}}

    class NoSuchOperator:
        op_classes = frozenset()
        modules = [{"name": "jit_step(1)", "start_ns": 0.0, "dur_ns": 1e6}]
        ops = []

        def has_scopes(self):
            return True

        def scope_ns(self, prefix):
            return 0.0

    program_trace.train_trace = lambda f: NoSuchOperator()
    operator_time.expert_tokens = lambda: None
    for name in NEW:
        assert run.load_module("layer_metrics", name).read(facts) is None


def test_roofline_shares_pair_the_last_steps_tokens_with_its_time(run):
    """Two steps in the window: the first spent 30 ms under the expert
    operator, the last 20 ms (8 of them in the kernel).  The counts are
    the last step's, so the shares are over the LAST step's times."""
    import numpy as np
    import operator_time
    import program_trace
    ev = lambda name, t0, dur, tf_op="": {
        "name": name, "start_ns": t0, "dur_ns": dur, "tf_op": tf_op,
        "category": None, "flops": None, "bytes_accessed": None}
    moe = "jit(step)/jvp(_contrib_RoutedExperts)/layer0_moe/"
    kernel = moe + "moe.experts/pallas.grouped_matmul/pallas_call"

    class Two:
        op_classes = frozenset(["_contrib_RoutedExperts"])
        modules = [ev("jit_step(1)", 0.0, 100e6), ev("jit_step(1)", 200e6, 90e6),
                   ev("jit_convert_element_type(2)", 195e6, 1e3)]
        ops = [ev("fusion.1", 10e6, 30e6, moe + "moe.router/dot_general"),
               ev("fusion.1", 210e6, 12e6, moe + "moe.router/dot_general"),
               ev("gmm.2", 230e6, 8e6, kernel),
               ev("fusion.9", 250e6, 5e6, "jit(step)/jvp(FullyConnected)/h/dot")]

        def has_scopes(self):
            return True

    program_trace.train_trace = lambda facts: Two()
    counts = np.zeros((4, 16), np.int64)
    counts[:, :8] = 512                     # even routing: half here
    counts[:, 8:] = 512
    operator_time.expert_tokens = lambda: {
        "counts": counts, "held_first": 0, "held_count": 8}
    facts = {"kind": "train", "steps": 2, "config": _config(),
             "peaks": {"bf16_flops_per_s": 197e12}}
    # 16384 (token, layer) pairs x 3 products x 2*2048*2048 x 3 (fwd+bwd)
    flops = 16384 * 3 * 2 * 2048 * 2048 * 3
    assert flops == 1_236_950_581_248
    assert operator_time.last_step_scope_ms(
        facts, "op._contrib_RoutedExperts") == pytest.approx(20.0)
    layer = run.load_module("layer_metrics",
                            "expert_product_roofline_share.train")
    assert layer.read(facts) == pytest.approx(
        100.0 * flops / 197e12 / 0.020)
    kern = run.load_module("layer_metrics",
                           "grouped_matmul_roofline_share.train")
    assert kern.read(facts) == pytest.approx(100.0 * flops / 197e12 / 0.008)


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
    assert f["head"] == 2 * S * d * 32784 == 1_100_048_498_688
    assert f["attention"] == 4 * 2 * S * S * 1024 == 549_755_813_888
    assert f["experts"] == 4 * (S // 2) * 3 * 2 * d * 2048 \
        == 412_316_860_416
    assert f["cca_projections"] == 4 * (2 * S * d * 1536 + 2 * S * 1024 * d)
    assert f["cca_convolutions"] == 4 * (2 * S * 1280 * 2
                                         + 2 * S * 10 * 128 * 128 * 2)
    assert f["router"] == 4 * 2 * S * (d * 256 + 2 * 256 * 256 + 256 * 16)
    total = model.train_flops_per_sample(kw)
    assert total == 3 * sum(f.values())
    assert 7.3e12 < total < 7.5e12          # the issue's 7.4 TFLOP a step
    # the parameters a layer, as the issue counts them
    specs = dict(model.param_specs(kw))
    n = lambda names: sum(math.prod(specs[k]) for k in names)
    attn = [k for k in specs if k.startswith("layer1_attn_")
            and k not in ("layer1_attn_norm_gamma", "layer1_attn_scale")]
    assert n(attn) == 5_573_122
    rout = [k for k in specs if k.startswith("layer1_moe_router_")]
    assert n(rout) == 659_713
    assert n(["layer1_moe_gate_weight", "layer1_moe_up_weight",
              "layer1_moe_down_weight"]) == 8 * 12_582_912
    assert n(["tok_embed_weight"]) == 67_141_632
    assert model.expert_product_flops(kw, S // 2 * 4) \
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
    counted from its jaxpr at a tiny size with every expert held (so
    that each token's expert is computed).  The reference multiplies
    the whole attention square and runs every expert over every token:
    the count takes half the square (causal) and one expert a token."""
    import jax
    import jax.numpy as jnp
    import common
    model = common.reference_model({"reference": "zaya"})
    kw = dict(num_classes=96, num_layers=3, d_model=32, q_heads=4,
              kv_heads=2, head_dim=8, expert_dim=48, num_experts=4,
              experts_held=[0, 4], router_hidden=16, conv_k0=2, conv_k1=2,
              rotary_frac=0.5, rope_theta=5e6, seq_len=16)
    params = {n: jax.ShapeDtypeStruct(s, jnp.float32)
              for n, s in model.param_specs(kw)}
    tok = jax.ShapeDtypeStruct((1, 16), jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda p, t, l: model.loss(p, {}, t, l, kw)[0])(params, tok, tok)
    brute = _dot_flops(jaxpr.jaxpr)
    f = model.forward_flops_per_sample(kw)
    # the depthwise convolution is an elementwise product there, no dot
    depthwise = 3 * 2 * 16 * (4 + 2) * 8 * 2
    want = sum(f.values()) + f["attention"] + 3 * f["experts"] - depthwise
    assert brute == want
    # and with a quarter of the experts held, a quarter of the tokens
    part = model.forward_flops_per_sample(dict(kw, experts_held=[1, 1]))
    assert part["experts"] * 4 == f["experts"]
    assert {k: v for k, v in part.items() if k != "experts"} \
        == {k: v for k, v in f.items() if k != "experts"}


def test_seeded_leaves_are_bfloat16_exact(run):
    import jax.numpy as jnp
    import numpy as np
    import common
    from reference import train as ref_train
    model = common.reference_model({"reference": "zaya"})
    for name, shape in [("layer1_attn_conv1_weight", (6, 8, 8, 2)),
                        ("layer1_moe_gate_weight", (4, 48, 32)),
                        ("layer1_attn_conv0_weight", (48, 2))]:
        w = ref_train.seeded_leaf(model, model.seed_key(7), name, shape)
        assert w.dtype == jnp.float32 and float(jnp.std(w)) > 0
        back = w.astype(jnp.bfloat16).astype(jnp.float32)
        assert np.array_equal(np.asarray(w), np.asarray(back))
    # a convolution's taps are sized to their fan-in, the rest to 0.02
    conv = ref_train.seeded_leaf(model, model.seed_key(7),
                                 "layer1_attn_conv1_weight", (6, 32, 32, 2))
    assert 0.11 < float(jnp.std(conv)) < 0.14       # 1/sqrt(64)
    assert float(ref_train.seeded_leaf(
        model, model.seed_key(7), "layer2_moe_router_carry", (1,))[0]) == 0.5
