"""A loss head's output, deferred (mxnet_tpu/loss_head.py,
docs/TRAINING.md "What a fused step returns").

The fused fit program returns the head's stem (the logits as their
producer wrote them) where the metric folds on the device or there is
none; ``get_outputs()`` builds the head's value on the first read.  Pins:
what is read equals the eager ``forward_backward`` path's outputs for the
same weights and batch, in float32; one tail program a step however often
it is read; the metrics that read the probabilities at the labels only
(``ce``, ``nll_loss``, ``perplexity``) equal the host metric over the
materialised outputs; a metric that accumulates on the host keeps the
program that returns the outputs; one dispatch a step and no retrace
after the first; and the lowered program's results hold no float32 array
of tokens x vocabulary elements.  (That the compiled program writes no
such array either is a statement about the chip's compiler:
tests/test_chip_compile.py.)
"""
import re

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import loss_head, models, nd, profiler, sym, telemetry
from mxnet_tpu.module import fused_fit

V, S, B = 96, 16, 2


def _count(name):
    return telemetry.REGISTRY.get(name).value


def _lm(dtype):
    return models.get_symbol("transformer", num_classes=V, num_layers=1,
                             d_model=32, num_heads=2, ffn_dim=64, seq_len=S,
                             dtype=dtype)


def _conv():
    net = sym.Convolution(sym.Variable("data"), kernel=(3, 3), num_filter=4,
                          name="conv1")
    net = sym.BatchNorm(net, name="bn1")
    net = sym.Pooling(sym.Activation(net, act_type="relu"), kernel=(2, 2),
                      stride=(2, 2), pool_type="max")
    return sym.SoftmaxOutput(sym.FullyConnected(sym.Flatten(net),
                                                num_hidden=5, name="fc"),
                             name="softmax")


def _case(kind, seed=0):
    """(symbol, data shape, label shape, classes, [batches])."""
    rng = np.random.RandomState(seed)
    if kind == "conv":
        net, dshape, lshape, classes = _conv(), (4, 1, 8, 8), (4,), 5
        draw = lambda: rng.rand(*dshape).astype(np.float32)
    else:
        net = _lm({"lm_f32": "float32", "lm_bf16": "bfloat16"}[kind])
        dshape, lshape, classes = (B, S), (B * S,), V
        draw = lambda: rng.randint(0, V, dshape).astype(np.float32)
    batches = [mx.io.DataBatch(
        data=[nd.array(draw())],
        label=[nd.array(rng.randint(0, classes, lshape).astype(np.float32))])
        for _ in range(3)]
    return net, dshape, lshape, batches


def _module(net, dshape, lshape, fused=True, like=None):
    mod = mx.Module(net, context=mx.cpu())
    mod._fused_fit_enabled = fused
    mod.bind(data_shapes=[("data", dshape)],
             label_shapes=[("softmax_label", lshape)])
    if like is None:
        mod.init_params(mx.init.Xavier())
    else:
        mod.init_params(arg_params=like[0], aux_params=like[1])
    mod.init_optimizer(optimizer="adam",
                       optimizer_params={"learning_rate": 1e-3})
    return mod


KINDS = ["lm_f32", "lm_bf16", "conv"]


@pytest.mark.parametrize("kind", KINDS)
def test_plans_find_the_head_behind_its_cheap_chain(kind):
    net = _case(kind)[0]
    (plan,) = loss_head.plans(net)
    assert plan.index == 0 and plan.head.op.name == "SoftmaxOutput"
    assert plan.label == "softmax_label"
    assert [n.name for n in plan.chain] == {
        "lm_f32": ["logits_2d"], "lm_bf16": ["cast_out", "logits_2d"],
        "conv": []}[kind]
    assert plan.stem[0].name == {"conv": "fc"}.get(kind, "lm_head")


def test_plans_skip_a_head_something_else_reads():
    x = sym.FullyConnected(sym.Variable("data"), num_hidden=4, name="fc")
    head = sym.SoftmaxOutput(x, name="softmax")
    both = sym.Group([head, sym.MakeLoss(sym.sum(head), name="extra")])
    assert loss_head.plans(both) == ()
    # and a chain node with a second reader is where the stem is
    cast = sym.Cast(x, dtype="float32", name="cast")
    two = sym.Group([sym.SoftmaxOutput(cast, name="softmax"),
                     sym.BlockGrad(cast, name="tap")])
    (plan,) = loss_head.plans(two)
    assert plan.chain == () and plan.stem[0].name == "cast"


@pytest.mark.parametrize("kind", KINDS)
def test_outputs_after_a_fused_step_equal_the_eager_paths(kind):
    net, dshape, lshape, batches = _case(kind)
    fused = _module(net, dshape, lshape)
    eager = _module(net, dshape, lshape, fused=False,
                    like=fused.get_params())
    d0, m0 = _count("fit_outputs_deferred"), _count("fit_outputs_materialized")

    eager.forward_backward(batches[0])
    want = [o.asnumpy() for o in eager.get_outputs()]
    assert fused.fit_step(batches[0], mx.metric.create("ce"))
    assert _count("fit_outputs_deferred") == d0 + 1
    assert _count("fit_outputs_materialized") == m0     # nothing read yet
    first = fused.get_outputs()
    assert _count("fit_outputs_materialized") == m0 + 1
    assert len(first) == len(want)
    for got, ref in zip(first, want):
        assert got.dtype == np.float32 and got.shape == ref.shape
        # the tail program runs the chain's and the head's own operators
        # over what the producer wrote: the eager program's value, to
        # the ulp two programs of one softmax agree to
        np.testing.assert_allclose(got.asnumpy(), ref, rtol=2e-6, atol=1e-9)

    # read twice: the same arrays, no second program
    again = fused.get_outputs()
    assert all(a is b for a, b in zip(first, again))
    assert _count("fit_outputs_materialized") == m0 + 1

    # the next step has a value of its own
    assert fused.fit_step(batches[1], mx.metric.create("ce"))
    second = fused.get_outputs()
    assert _count("fit_outputs_materialized") == m0 + 2
    assert second[0] is not first[0]
    assert not np.array_equal(second[0].asnumpy(), first[0].asnumpy())
    np.testing.assert_allclose(second[0].asnumpy().sum(axis=-1), 1.0,
                               rtol=1e-5)


def _host_value(name, kwargs, labels, outputs):
    host = mx.metric.create(name, **kwargs)
    host.update(labels, outputs)
    return host.get()[1]


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("name,kwargs", [
    ("ce", {}), ("ce", {"eps": 0.05}), ("nll_loss", {}),
    ("nll_loss", {"eps": 0.05}), ("perplexity", {}),
    ("perplexity", {"ignore_label": 3})])
def test_metrics_at_the_labels_equal_the_host_metric(kind, name, kwargs):
    """Folded on the device from the stem, against the host metric over
    the outputs the same step materialises."""
    net, dshape, lshape, batches = _case(kind, seed=1)
    mod = _module(net, dshape, lshape)
    metric = mx.metric.create(name, **kwargs)
    d0 = _count("fit_outputs_deferred")
    for batch in batches[:2]:
        metric.reset()
        assert mod.fit_step(batch, metric)
        mod.update_metric(metric, batch.label)      # consumed: no read
        got = metric.get()[1]
        want = _host_value(name, kwargs, batch.label, mod.get_outputs())
        assert got == pytest.approx(want, rel=1e-6)
    assert _count("fit_outputs_deferred") == d0 + 2


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("name,kwargs", [("acc", {}),
                                         ("top_k_accuracy", {"top_k": 3})])
def test_metrics_over_the_whole_value_are_unchanged(kind, name, kwargs):
    """``acc`` and ``top_k_accuracy`` ask for the head's full value and
    get it, built inside the program."""
    net, dshape, lshape, batches = _case(kind, seed=2)
    mod = _module(net, dshape, lshape)
    metric = mx.metric.create(name, **kwargs)
    assert mod.fit_step(batches[0], metric)
    got = metric.get()[1]
    assert got == _host_value(name, kwargs, batches[0].label,
                              mod.get_outputs())


@pytest.mark.parametrize("kind", KINDS)
def test_a_host_metric_keeps_the_program_that_returns_the_outputs(kind):
    net, dshape, lshape, batches = _case(kind)
    mod = _module(net, dshape, lshape)
    eager = _module(net, dshape, lshape, fused=False, like=mod.get_params())
    host = mx.metric.np(lambda label, pred: float(pred.sum()), name="mass")
    assert host.device_fn() is None
    d0, m0 = _count("fit_outputs_deferred"), _count("fit_outputs_materialized")
    # (the eager step first: on the CPU the two modules' initial weights
    # share buffers, and the fused step donates them)
    eager.forward_backward(batches[0])
    assert mod.fit_step(batches[0], host)
    mod.update_metric(host, batches[0].label)
    np.testing.assert_allclose(mod.get_outputs()[0].asnumpy(),
                               eager.get_outputs()[0].asnumpy(),
                               rtol=2e-6, atol=1e-9)
    assert host.get()[1] == pytest.approx(mod.get_outputs()[0].shape[0],
                                          rel=1e-5)
    assert _count("fit_outputs_deferred") == d0
    assert _count("fit_outputs_materialized") == m0
    # a step with no metric at all defers: nothing reads its outputs
    assert mod.fit_step(batches[1])
    assert _count("fit_outputs_deferred") == d0 + 1


@pytest.mark.parametrize("kind", KINDS)
def test_one_dispatch_a_step_and_no_retrace_after_the_first(kind):
    net, dshape, lshape, batches = _case(kind)
    mod = _module(net, dshape, lshape)
    metric = mx.metric.create("ce")
    assert mod.fit_step(batches[0], metric)
    mod.get_outputs()                       # compiles the tail program
    traces = fused_fit.TRACE_COUNT
    retraces = _count("executor_retraces")
    m0 = _count("fit_outputs_materialized")
    d0 = profiler.DEVICE_DISPATCHES.value
    steps = 4
    for i in range(steps):
        assert mod.fit_step(batches[i % 3], metric)
        mod.update_metric(metric, batches[i % 3].label)
    assert profiler.DEVICE_DISPATCHES.value - d0 == steps
    assert _count("fit_outputs_materialized") == m0
    # a read costs one more dispatch, and no compile
    mod.get_outputs()
    assert profiler.DEVICE_DISPATCHES.value - d0 == steps + 1
    assert fused_fit.TRACE_COUNT == traces
    assert _count("executor_retraces") == retraces


def _lowered(kind, metric):
    net, dshape, lshape, batches = _case(kind)
    mod = _module(net, dshape, lshape)
    fn, args, _ = mod._get_fused_fit()._prepare(batches[0], metric)
    return fn.lower(*args).as_text()


@pytest.mark.parametrize("kind", ["lm_f32", "lm_bf16"])
def test_lowered_program_returns_no_float32_tokens_by_vocab(kind):
    """With ``ce`` folded, no result of the lowered fit program is the
    (tokens, vocab) float32 probabilities; the stem is a result in its
    producer's dtype and shape (so a bfloat16 model returns no float32
    array of that size at all), and nothing gathers from an array of
    that size; the program of a host metric returns the probabilities."""
    wide = r"tensor<%dx%dxf32>" % (B * S, V)
    if kind == "lm_bf16":
        wide += r"|tensor<%dx%dx%dxf32>" % (B, S, V)
    results = lambda text: re.search(
        r"func\.func public @main\(.*?\)\s*->\s*\((.*?)\)\s*\{", text,
        re.S).group(1)

    text = _lowered(kind, mx.metric.create("ce"))
    assert not re.search(wide, results(text))
    stem = "tensor<%dx%dx%dx%s>" % (
        B, S, V, {"lm_f32": "f32", "lm_bf16": "bf16"}[kind])
    assert stem in results(text)
    for line in text.splitlines():
        if "stablehlo.gather" in line or "stablehlo.dynamic_slice" in line:
            assert not re.search(r"x%dx(f32|bf16)>" % V, line), line

    parent = _lowered(kind, mx.metric.np(lambda l, p: 0.0))
    assert re.search(r"tensor<%dx%dxf32>" % (B * S, V), results(parent))
