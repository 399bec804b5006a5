"""The cell ``keyevl2_30b_train_ep8`` at its rehearsal size on the CPU:
the harness finds every file of it by name, the traced rehearsal comes
out ``correct`` with every declared metric, the fp8 control does not,
the five new readers give nothing (and do not raise) for a program
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
CELL = "keyevl2_30b_train_ep8"
CONFIG = "keye_vl2_30b_train"
NEW = ["dsa_ms.train", "dsa_select_ms.train",
       "dsa_attention_roofline_share.train",
       "dsa_indexer_roofline_share.train", "dsa_live_block_share.train"]
TRACED = NEW[:4]
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
def test_the_cell_reports_the_train_metrics_the_expert_four_and_its_five():
    bench = _bench()
    cell = [w for w in bench["workloads"] if w["name"] == CELL]
    assert len(cell) == 1 and cell[0]["chips"] == 1
    assert cell[0]["config"] == CONFIG
    assert cell[0]["traffic"] == "fit_b1_pool8"
    assert "16384-token" in cell[0]["why"] and "1024 tokens" in cell[0]["why"]
    assert "8x" in cell[0]["why"] and len(cell[0]["why"]) <= 200
    by_name = {m["name"]: m for m in bench["per_layer"]}
    everyones = [m for m in bench["per_layer"]
                 if "cgpt13b_train_s2048" in m.get("workloads", [])]
    assert everyones
    for m in everyones + [by_name[n] for n in JOINED]:
        assert CELL in m["workloads"], m["name"]
    # the set-up metric without a list has one now, with every cell
    misses = by_name["compile_cache_misses"]["workloads"]
    assert CELL in misses and "resnet50_train_b256" in misses
    assert set(misses) >= {w["name"] for w in bench["workloads"]
                           if w["name"] in by_name["bind_s"]["workloads"]}
    for other in ("cca_ms.train", "gdn_ms.train", "gated_attn_ms.train",
                  "gdn_scan_roofline_share.train", "mla_ms.train",
                  "mla_attention_roofline_share.train"):
        assert CELL not in by_name[other]["workloads"]
    for name in NEW:
        m = by_name[name]
        assert CELL in m["workloads"]
        assert m["moves"] == "train_samples_per_s"
        assert m["layer"] == by_name["moe_ms.train"]["layer"]
        assert os.path.exists(os.path.join(BENCH, "layer_metrics",
                                           name + ".py"))
    for name in TRACED:
        assert by_name[name]["source"] == "device_trace"
    assert by_name["dsa_live_block_share.train"]["source"] \
        == "program_counter"
    for name in NEW[2:]:
        assert by_name[name]["unit"] == "%"
    assert by_name["dsa_ms.train"]["unit"] == "ms"
    assert by_name["dsa_attention_roofline_share.train"]["better"] == "higher"
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
        == ["num_experts", "num_hidden_layers", "vocab_size"]
    src = cfg["source_config"]
    for k, v in src.items():
        if k not in cfg["reduced"]:
            assert cfg[k] == v, k
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (4, 16, 18992)
    assert (src["num_hidden_layers"], src["num_experts"],
            src["vocab_size"]) == (48, 128, 151936)
    kw, sa = cfg["kwargs"], src["sa_config"]
    assert (kw["d_model"], kw["q_heads"], kw["kv_heads"], kw["head_dim"],
            kw["rope_theta"]) == (
        src["hidden_size"], src["num_attention_heads"],
        src["num_key_value_heads"], src["head_dim"], src["rope_theta"])
    assert (kw["idx_heads"], kw["idx_dim"], kw["topk"], kw["q_chunk"],
            kw["kv_chunk"]) == (
        sa["indexer_num_heads"], sa["indexer_head_dim"], sa["topk"],
        sa["q_chunk_size"], sa["kv_chunk_size"])
    assert sa["indexer_num_kv_heads"] == 1
    assert (kw["expert_dim"], kw["num_experts"], kw["top_k"]) == (
        src["moe_intermediate_size"], src["num_experts"],
        src["num_experts_per_tok"])
    assert src["norm_topk_prob"] is True
    assert src["tie_word_embeddings"] is False
    assert (kw["num_layers"], kw["experts_held"], kw["num_classes"]) \
        == (4, [0, 16], 18992)
    assert kw["seq_len"] in (16384, 8192) and kw["num_layers"] >= 4
    assert kw["seq_len"] > kw["topk"]           # the choice bites
    assert 8 * kw["num_classes"] == src["vocab_size"]
    assert 8 * kw["experts_held"][1] == src["num_experts"]
    for key in ("scorer_queries", "scorer_form", "scorer_rotary", "chunks",
                "qk_norm", "mrope", "not_built", "two_objectives", "share",
                "optimizer", "init", "precision", "num_local_experts",
                "max_position_embeddings"):
        assert cfg["assumed"][key].endswith("."), key
    assert "Eight chips share each layer" in cfg["deployment"]
    assert "1024 tokens" in cfg["deployment"]
    assert "eight times their share" in cfg["deployment"]
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
    live = line["metrics"]["dsa_live_block_share.train"]["value"]
    assert 0.0 < live <= 100.0
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
                 "expert_product_flops", "dsa_attention_flops",
                 "dsa_attention_bytes", "dsa_indexer_flops",
                 "dsa_indexer_bytes"):
        assert callable(getattr(model, name)), name
    assert not hasattr(model, "init_aux")       # no auxiliary state
    kw = _config()["rehearse"]["kwargs"]
    names = [n for n, _ in model.param_specs(kw)]
    scorer = [n for n in names if model.is_scorer(n)]
    assert len(scorer) == 5 * kw["num_layers"]
    # the harness's leaf classes find the scorer's leaves by their ends
    assert all(n.endswith(("_weight", "_gamma", "_beta")) for n in scorer)


def test_the_references_value_is_the_cross_entropy_and_its_gradient_both(run):
    """``loss`` returns ``ce`` as its value and the gradient of ``ce +
    L^I``: the scorer's leaves get their whole gradient from ``L^I``."""
    import jax
    import jax.numpy as jnp
    import common
    import numpy as np
    model = common.reference_model(_config())
    kw = _config()["rehearse"]["kwargs"]
    key = model.seed_key(5)
    p = {n: model.init_leaf(key, n, s) for n, s in model.param_specs(kw)}
    tok, lab = model.device_batch(*model.make_batch(
        np.random.default_rng(0), kw, 2))
    (value, _), g = jax.value_and_grad(
        lambda p: model.loss(p, {}, tok, lab, kw), has_aux=True)(p)
    ce, li = model.losses(p, tok, lab, kw)
    assert float(value) == pytest.approx(float(ce), rel=1e-6)
    assert float(li) > 0
    g_ce = jax.grad(lambda p: model.losses(p, tok, lab, kw)[0])(p)
    g_li = jax.grad(lambda p: model.losses(p, tok, lab, kw)[1])(p)
    for n in p:
        if model.is_scorer(n):
            assert float(jnp.abs(g_ce[n]).max()) == 0.0, n
            assert float(jnp.abs(g[n]).max()) > 0.0, n
        else:
            assert float(jnp.abs(g_li[n]).max()) == 0.0, n
        np.testing.assert_allclose(np.asarray(g[n]),
                                   np.asarray(g_ce[n] + g_li[n]),
                                   rtol=1e-5, atol=1e-9)


# ----------------------------------------------------------------------
# the five new readers
# ----------------------------------------------------------------------
def test_readers_give_nothing_for_a_program_without_the_operator(run,
                                                                 monkeypatch):
    """What the parent commit's program gives the new readers: no trace
    of the operator class or of the scopes, so None and no raise; and no
    trace at all likewise; and a reference without the counts (every
    other cell's) likewise; and a program without the counter's module."""
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
    for name in TRACED[2:]:
        assert run.load_module("layer_metrics", name).read(other) is None
    # the counter: no step noted, then no such module at all
    from mxnet_tpu.telemetry import dsa
    monkeypatch.setattr(dsa, "_last", None)
    reader = run.load_module("layer_metrics", "dsa_live_block_share.train")
    assert reader.read(facts) is None
    monkeypatch.setitem(sys.modules, "mxnet_tpu.telemetry.dsa", None)
    assert reader.read(facts) is None


def test_shares_are_the_larger_need_over_the_time_under_their_scopes(run):
    """Two steps in the window.  Under ``dsa.attention`` 600 ms forward
    and 1400 ms backward in all (1000 ms a step); under ``dsa.indexer``
    100 ms and under ``dsa.index_loss`` 300 ms (200 ms a step); under
    ``dsa.select`` 80 ms (40 ms a step); an instruction of the same
    operator outside the scopes, and one of another operator."""
    import common
    import program_trace
    ev = lambda t0, dur, tf_op: {
        "name": "fusion", "start_ns": t0, "dur_ns": dur, "tf_op": tf_op,
        "category": None, "flops": None, "bytes_accessed": None}
    dsa = "_contrib_SparseIndexedAttention)/layer0_attn/"

    class Two:
        op_classes = frozenset(["_contrib_SparseIndexedAttention"])
        ops = [ev(0.0, 600e6, "jit(step)/jvp(" + dsa
                  + "dsa.attention/while/body/dot_general"),
               ev(700e6, 1400e6, "jit(step)/transpose(jvp(" + dsa
                  + "dsa.attention))/while/body/dot_general"),
               ev(2200e6, 100e6, "jit(step)/jvp(" + dsa
                  + "dsa.indexer/while/body/dot_general"),
               ev(2300e6, 300e6, "jit(step)/transpose(jvp(" + dsa
                  + "dsa.index_loss))/while/body/dot_general"),
               ev(2700e6, 80e6, "jit(step)/jvp(" + dsa
                  + "dsa.select/while/body/reduce_sum"),
               ev(2800e6, 7e6, "jit(step)/jvp(" + dsa
                  + "dsa.proj/dot_general"),
               ev(2900e6, 5e6, "jit(step)/jvp(FullyConnected)/h/dot_general")]

        def has_scopes(self):
            return True

        def scope_ns(self, prefix):
            assert prefix == "op._contrib_SparseIndexedAttention"
            return 2487e6

    program_trace.train_trace = lambda facts: Two()
    cfg = _config()
    facts = {"kind": "train", "steps": 2, "batch": 1, "config": cfg,
             "peaks": PEAKS}
    model = common.reference_model(cfg)
    kw = cfg["kwargs"]
    attn_s = max(model.dsa_attention_flops(kw) / 197e12,
                 model.dsa_attention_bytes(kw) / 819e9)
    idx_s = max(model.dsa_indexer_flops(kw) / 197e12,
                model.dsa_indexer_bytes(kw) / 819e9)
    read = lambda name: run.load_module("layer_metrics", name).read(facts)
    assert read("dsa_attention_roofline_share.train") \
        == pytest.approx(100.0 * attn_s / 1.000)
    assert read("dsa_indexer_roofline_share.train") \
        == pytest.approx(100.0 * idx_s / 0.200)
    assert 0 < read("dsa_attention_roofline_share.train") < 100
    assert 0 < read("dsa_indexer_roofline_share.train") < 100
    assert read("dsa_select_ms.train") == pytest.approx(40.0)
    assert read("dsa_ms.train") == pytest.approx(1243.5)


# ----------------------------------------------------------------------
# the counts
# ----------------------------------------------------------------------
def test_counts_hand_worked_at_the_cells_size(run):
    import common
    cfg = _config()
    model = common.reference_model(cfg)
    kw = dict(cfg["kwargs"], seq_len=16384)
    f = model.forward_flops_per_sample(kw)
    S, d, K = 16384, 2048, 2048
    causal, chosen = model.pairs(kw)
    assert causal == S * (S + 1) // 2
    assert chosen == K * (K + 1) // 2 + (S - K) * K
    assert 0.233 < chosen / causal < 0.235       # the issue's 23.4 %
    assert f["head"] == 2 * S * d * 18992
    assert f["projections"] == 4 * 2 * S * d * 128 * (32 + 4 + 4 + 32)
    assert f["scorer_projections"] == 4 * 2 * S * d * (1024 + 64 + 16)
    assert f["scorer"] == 4 * 2 * causal * 16 * 64
    assert f["attention"] == 4 * 2 * chosen * 32 * 256
    assert f["router"] == 4 * 2 * S * d * 128
    assert f["experts"] == 4 * S * 3 * 2 * d * 768      # one pair a token
    assert model.dsa_attention_flops(kw) == 3 * f["attention"]
    assert model.dsa_indexer_flops(kw) \
        == 4 * 2 * 1024 * (causal + 2 * chosen)
    assert model.train_flops_per_sample(kw) \
        == 3 * (sum(f.values()) - f["scorer"]) + model.dsa_indexer_flops(kw)
    # the need of the sparse cores: 6.2 TFLOP a step = 31 ms at the peak
    assert 6.1e12 < model.dsa_attention_flops(kw) < 6.3e12
    assert 1.5e12 < model.dsa_indexer_flops(kw) < 1.7e12
    assert model.dsa_attention_bytes(kw) \
        == 4 * 2 * S * 128 * (32 + 4 + 4 + 32) * 2
    assert model.dsa_indexer_bytes(kw) \
        == 4 * (2 * S * (1024 + 64 + 16) * 4 + 2 * S * S // 8)
    # the parameters, as the issue counts them
    specs = dict(model.param_specs(kw))
    n = lambda names: sum(math.prod(specs[k]) for k in names)
    main = [k for k in specs if k.startswith("layer1_attn_")
            and not model.is_scorer(k)]
    assert n(main) == 2 * 8_388_608 + 2 * 1_048_576 + 2 * 128
    scorer = [k for k in specs if k.startswith("layer1_") and
              model.is_scorer(k)]
    assert n(scorer) == 2_097_152 + 131_072 + 32_768 + 2 * 64
    assert n(["layer1_moe_gate_weight", "layer1_moe_up_weight",
              "layer1_moe_down_weight"]) == 16 * 4_718_592
    assert n(["layer1_moe_router_weight"]) == 262_144
    assert n(["tok_embed_weight", "lm_head_weight"]) == 2 * 38_895_616
    assert 465.0e6 < n(specs) < 466.0e6     # the issue's 465.4 M
    assert model.expert_product_flops(kw, S * 4) == 3 * f["experts"]


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
    counted from its jaxpr at a tiny size with every expert held and
    ``topk`` at the sequence's length (every causal pair chosen).  The
    reference multiplies the whole square, of scorer and heads alike,
    and runs every expert over every token: the count takes the causal
    pairs (a half and half a diagonal) and ``top_k`` experts a token."""
    import jax
    import jax.numpy as jnp
    import common
    model = common.reference_model({"reference": "keye_vl2"})
    S = 128
    kw = dict(num_classes=96, num_layers=2, d_model=32, q_heads=4, kv_heads=2,
              head_dim=8, idx_heads=4, idx_dim=8, topk=S, expert_dim=16,
              num_experts=8, experts_held=[0, 8], top_k=2, seq_len=S)
    params = {n: jax.ShapeDtypeStruct(s, jnp.float32)
              for n, s in model.param_specs(kw)}
    tok = jax.ShapeDtypeStruct((1, S), jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda p, t, l: model.loss(p, {}, t, l, kw)[0])(params, tok, tok)
    brute = _dot_flops(jaxpr.jaxpr)
    f = model.forward_flops_per_sample(kw)
    causal, chosen = model.pairs(kw)
    assert causal == chosen == S * (S + 1) // 2
    square = S * S / causal
    want = sum(f.values()) + (square - 1) * (f["attention"] + f["scorer"]) \
        + (8 // 2 - 1) * f["experts"]
    assert brute == pytest.approx(want, rel=1e-12)
    # with a quarter of the keys chosen the sparse cores' need falls,
    # the scorer's forward need does not
    less = model.forward_flops_per_sample(dict(kw, topk=S // 4))
    assert less["attention"] < f["attention"]
    assert less["scorer"] == f["scorer"]
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
    model = common.reference_model({"reference": "keye_vl2"})
    key = model.seed_key(3200000019)
    leaf = lambda name, shape: ref_train.seeded_leaf(model, key, name, shape)
    for name, shape, std in [("layer1_attn_q_weight", (96, 64), 0.02),
                             ("layer3_moe_gate_weight", (4, 48, 64), 0.02),
                             ("layer0_attn_idx_q_weight", (64, 96), 0.02),
                             ("tok_embed_weight", (512, 64), 1.0)]:
        w = leaf(name, shape)
        assert w.dtype == jnp.float32
        assert 0.9 * std < float(jnp.std(w)) < 1.1 * std
        back = w.astype(jnp.bfloat16).astype(jnp.float32)
        assert np.array_equal(np.asarray(w), np.asarray(back))
    for name in ("layer0_in_norm_gamma", "layer2_attn_q_norm_gamma",
                 "layer1_attn_idx_k_norm_gamma", "final_norm_gamma"):
        assert float(jnp.abs(leaf(name, (64,)) - 1.0).max()) == 0.0
    assert float(jnp.abs(leaf("layer1_attn_idx_k_norm_beta", (64,))).max()) \
        == 0.0
