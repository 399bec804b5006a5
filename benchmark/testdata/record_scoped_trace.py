"""Record benchmark/testdata/scoped_tpu.xplane.pb (run on the chip):
three fit steps of a two-layer bfloat16 network (a FullyConnected and a
Pallas LayerNorm under a softmax), Adam with float32 masters under the
loss scaler's ``cond``, the ``acc`` metric read back every step; the
way benchmark/drivers/train_fit.py drives a step, with the benchmark's
window and span names around the program's own.

The profiler's file also holds every program's HLO (the
``/host:metadata`` plane, 200 KB here) and lines no reader asks for;
:func:`trim` copies the two planes and the lines the readers use, byte
for byte, so that the test data stays under 100 KB."""
import glob
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))
sys.path.insert(0, os.path.dirname(HERE))
os.environ["MXNET_LN_IMPL"] = "pallas"

import jax
import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import sym
import program_trace

KEEP_PLANES = ("/device:TPU:0", "/host:CPU")
DROP_LINES = ("Async XLA Ops", "Steps", "TC Overlay")


def _varint(n):
    out = bytearray()
    while n >= 0x80:
        out.append(n & 0x7F | 0x80)
        n >>= 7
    out.append(n)
    return bytes(out)


def trim(raw):
    """``raw`` (an XSpace) with only KEEP_PLANES, without DROP_LINES
    and, on the host plane, without the lines that hold none of the
    program's or the benchmark's spans; everything kept is copied byte
    for byte."""
    buf, out = memoryview(raw), bytearray()
    fields = program_trace.fields
    wanted = set(program_trace.HOST_SPANS) | {program_trace.WINDOW}
    for f, plane in fields(buf, 0, len(buf)):
        if f != 1:
            continue
        parts = list(fields(buf, *plane))
        name = next(program_trace._text(buf, v) for g, v in parts if g == 2)
        if name not in KEEP_PLANES:
            continue
        names = {}          # event metadata id -> name (host plane)
        for g, v in parts:
            if g == 4:
                entry = dict(fields(buf, *v))
                md = dict(fields(buf, *entry[2]))
                if 2 in md:
                    names[entry[1]] = program_trace._text(buf, md[2])
        body = bytearray()
        for g, v in parts:
            if g == 3:
                line, _, events = program_trace._line(buf, v)
                if line in DROP_LINES or (
                        name == "/host:CPU" and not any(
                            names.get(mid) in wanted for mid, _, _ in events)):
                    continue
            if isinstance(v, tuple):
                body += _varint(g << 3 | 2) + _varint(v[1] - v[0]) \
                    + bytes(buf[v[0]:v[1]])
            else:
                body += _varint(g << 3) + _varint(v)
        out += b"\x0a" + _varint(len(body)) + bytes(body)
    return bytes(out)


out = "chiprun_out/testdata"
tmp = os.path.join(out, "_rec")
shutil.rmtree(tmp, ignore_errors=True)
ctx = mx.tpu(0)
B, D = 64, 256
x = sym.Cast(sym.Variable("data"), dtype="bfloat16", name="cast_in")
x = sym.FullyConnected(x, num_hidden=128, name="fc1")
x = sym.LayerNorm(x, name="ln1")
net = sym.SoftmaxOutput(sym.Cast(x, dtype="float32", name="cast_out"),
                        name="softmax")
mod = mx.Module(net, context=ctx)
mod.bind(data_shapes=[("data", (B, D))],
         label_shapes=[("softmax_label", (B,))])
np.random.seed(0)
mod.init_params(mx.initializer.Xavier())
mod.init_optimizer(optimizer="adam", optimizer_params={
    "learning_rate": 1e-3, "multi_precision": True})
rng = np.random.default_rng(0)
host = mx.io.DataBatch(
    data=[mx.nd.array(rng.standard_normal((B, D)).astype(np.float32))],
    label=[mx.nd.array(rng.integers(0, 128, (B,)).astype(np.float32))])
metric = mx.metric.create("acc")


def step():
    with jax.profiler.TraceAnnotation("input"):
        batch = mx.io.DataBatch(
            data=[a.as_in_context(ctx) for a in host.data],
            label=[a.as_in_context(ctx) for a in host.label])
    with jax.profiler.TraceAnnotation("fit_step"):
        assert mod.fit_step(batch, metric)
        mod.update_metric(metric, batch.label)
    with jax.profiler.TraceAnnotation("readback"):
        value = float(metric.get()[1])
        metric.reset()
    return value


for _ in range(3):
    step()                      # compile and warm outside the trace
opts = jax.profiler.ProfileOptions()
opts.python_tracer_level = 0
opts.host_tracer_level = 2
jax.profiler.start_trace(tmp, profiler_options=opts)
with jax.profiler.TraceAnnotation("bench_window"):
    for _ in range(3):
        step()
jax.profiler.stop_trace()
path = glob.glob(tmp + "/**/*.xplane.pb", recursive=True)[0]
with open(path, "rb") as f:
    raw = f.read()
small = trim(raw)
with open(os.path.join(out, "scoped_tpu.xplane.pb"), "wb") as f:
    f.write(small)
shutil.rmtree(tmp, ignore_errors=True)
print("trace bytes", len(raw), "kept", len(small))
