"""Record benchmark/testdata/step_gap_tpu.xplane.pb and, beside it,
step_gap_tpu.steps.json (run on the chip): three fit steps of a
two-layer bfloat16 network, Adam with float32 masters, the ``acc``
metric read back every step, driven the way
benchmark/drivers/train_fit.py drives a step; the trace of the window
and the program's own step timeline of the same three steps
(``mxnet_tpu.telemetry.tracing.steps(last=3)``, read where the harness
reads it: right after the window, the third step still open).

The readers of benchmark/step_timeline.py are held to the pair by
tests/benchmark/test_timeline_readers.py, every number worked out
apart from the code.  :func:`trim` keeps the device's ``XLA Modules``
line and the host lines that hold the program's or the benchmark's
spans, byte for byte (record_scoped_trace.py's rule, with the
readback's two children among the names)."""
import glob
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))
sys.path.insert(0, os.path.dirname(HERE))

import jax
import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import sym
from mxnet_tpu.telemetry import tracing
import program_trace
import step_timeline

KEEP_PLANES = ("/device:TPU:0", "/host:CPU")
DROP_LINES = ("Async XLA Ops", "Steps", "TC Overlay", "XLA Ops",
              "XLA TraceMe")
HOST_NAMES = set(program_trace.HOST_SPANS) | {
    program_trace.WINDOW, "metric.wait", "metric.transfer"}


def _varint(n):
    out = bytearray()
    while n >= 0x80:
        out.append(n & 0x7F | 0x80)
        n >>= 7
    out.append(n)
    return bytes(out)


def trim(raw):
    """``raw`` (an XSpace) with only KEEP_PLANES, without DROP_LINES
    and, on the host plane, without the lines that hold none of
    HOST_NAMES; everything kept is copied byte for byte."""
    buf, out = memoryview(raw), bytearray()
    fields = program_trace.fields
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
                            names.get(mid) in HOST_NAMES
                            for mid, _, _ in events)):
                    continue
            if isinstance(v, tuple):
                body += _varint(g << 3 | 2) + _varint(v[1] - v[0]) \
                    + bytes(buf[v[0]:v[1]])
            else:
                body += _varint(g << 3) + _varint(v)
        out += b"\x0a" + _varint(len(body)) + bytes(body)
    return bytes(out)


def main():
    out = "chiprun_out/testdata"
    tmp = os.path.join(out, "_rec_gap")
    shutil.rmtree(tmp, ignore_errors=True)
    os.makedirs(out, exist_ok=True)
    ctx = mx.tpu(0)
    B, D = 64, 256
    x = sym.Cast(sym.Variable("data"), dtype="bfloat16", name="cast_in")
    x = sym.FullyConnected(x, num_hidden=512, name="fc1")
    x = sym.Activation(x, act_type="relu", name="relu1")
    x = sym.FullyConnected(x, num_hidden=128, name="fc2")
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
    for _ in range(2):  # the device's tracer misses a program this early
        step()
    with jax.profiler.TraceAnnotation("bench_window"):
        for _ in range(3):
            step()
    jax.profiler.stop_trace()
    recs = tracing.steps(last=3)
    path = glob.glob(tmp + "/**/*.xplane.pb", recursive=True)[0]
    with open(path, "rb") as f:
        raw = f.read()
    small = trim(raw)
    kept = os.path.join(out, "step_gap_tpu.xplane.pb")
    with open(kept, "wb") as f:
        f.write(small)
    with open(os.path.join(out, "step_gap_tpu.steps.json"), "w") as f:
        json.dump(recs, f, indent=1)
    shutil.rmtree(tmp, ignore_errors=True)
    print("trace bytes", len(raw), "kept", len(small))
    programs = [m for m in program_trace.Trace(kept).modules
                if m["name"].startswith(step_timeline.FIT_PROGRAM)]
    if len(programs) != 3:
        raise SystemExit("the window holds %d fit-step programs for its "
                         "3 steps: record again" % len(programs))
    # what the readers make of the pair just written
    tr = program_trace.Trace(kept)
    print(json.dumps({
        "modules": [(m["name"], m["start_ns"], m["dur_ns"])
                    for m in tr.modules],
        "readback_transfer_ms": step_timeline.transfer_ms(recs),
        "step_outside_ms": step_timeline.outside_ms(recs),
        "longest_step_over_median": step_timeline.longest_over_median(recs),
        "launch_lead_ms": step_timeline.launch_lead_ms(recs, tr.modules)}))
    step_timeline.main(["step_timeline", kept])


if __name__ == "__main__":
    main()
