#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof the system still starts on the chip.

Drives the training and serving main paths once, end to end, through the
public API (``import mxnet_tpu as mx``) at the repo's own full widths, in
ONE process that owns the chip, and checks what comes out by the repo's
own means.  Weights and data are random, made from a seed; nothing here
is a benchmark.

    python chip_smoke.py            # one TPU chip (what the driver runs)
    python chip_smoke.py --chips 4  # only the multi-chip phase, on 4 chips

Phases (each prints one JSON line; the first failure exits non-zero at
once, and nothing catches it):

1. devices      jax must report a TPU.  Without one the script stops
                here, before any result line.
2. train-lm     Module.fit_step (module/fused_fit.py, the user's path)
                on the transformer LM, L12 d2048 h16 S1024 B4 vocab
                16384 bf16.
3. train-resnet Module.fit on ResNet-50 b256 NHWC bf16.
4. serve-lm     the LM's weights by name in a DecodeEngine behind a
                ModelServer, real HTTP POST /generate requests, checked
                against the same engine built on the XLA paged path.

The last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
import argparse
import gc
import json
import os
import threading
import time
import urllib.request

import numpy as np

# ----------------------------------------------------------------------
# full-width configurations (BASELINE.json)
# ----------------------------------------------------------------------
LM = dict(num_classes=16384, num_layers=12, d_model=2048, num_heads=16,
          seq_len=1024, dtype="bfloat16")
LM_TRAIN = dict(batch=4, steps=6,
                kernels=("flash_attention", "layernorm_fused",
                         "layernorm_fused_bwd"))
RESNET = dict(num_layers=50, image_shape=(3, 224, 224), batch=256,
              steps=3, dtype="bfloat16", layout="NHWC")
# K/V cache: L12 x (K,V) x 2048 blocks x 16 rows x H16 x D128 bf16 = 3.2 GB
SERVE = dict(capacity=32, block_size=16, num_blocks=2048, chunk_tokens=64,
             prompt_lens=(5, 37, 100, 211), max_new_tokens=12,
             kernels=("paged_decode_attend", "paged_chunk_prefill_attend"),
             impl="pallas")
# bf16 logits of two attention implementations (f32 softmax statistics
# in both; the XLA path rounds scores and probabilities to bf16, the
# kernel keeps scores in f32) through 12 layers: agreement is asked to
# this many units of the reference's largest |logit|
LOGIT_TOL = 0.05
# --chips 4 cuts depth (never width): four compiles at 4x the charge
LM_4CHIP = dict(LM, num_layers=4)
SEED = 0


_CACHE_SEEN = [0, 0]


def emit(phase, **fields):
    """One JSON line per phase, with the persistent compile cache's
    hits and misses since the previous line."""
    import mxnet_tpu as mx
    st = mx.aot.stats()
    now = [int(st["cache_hits"]), int(st["cache_misses"])]
    fields.update(compile_cache_hits=now[0] - _CACHE_SEEN[0],
                  compile_cache_misses=now[1] - _CACHE_SEEN[1])
    _CACHE_SEEN[:] = now
    print(json.dumps(dict(phase=phase, **fields)), flush=True)


def check(ok, what):
    if not ok:
        raise SystemExit("chip_smoke: FAILED — %s" % what)


# ----------------------------------------------------------------------
# 1. devices
# ----------------------------------------------------------------------
def phase_devices(want_count):
    import jax
    import jaxlib
    devs = jax.devices()
    check(devs[0].platform == "tpu",
          "jax found no TPU (platform=%r, kind=%r)"
          % (devs[0].platform, devs[0].device_kind))
    check(len(devs) == want_count,
          "need %d chip(s), jax reports %d" % (want_count, len(devs)))
    import mxnet_tpu as mx
    from mxnet_tpu import _native
    try:
        from importlib.metadata import version
        libtpu = version("libtpu")
    except Exception:                       # noqa: BLE001 — a version string
        libtpu = None
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    emit("devices", device=device, jax=jax.__version__,
         jaxlib=jaxlib.__version__, libtpu=libtpu,
         compile_cache_dir=mx.aot.cache_dir(),
         compile_cache_from_env=bool(
             os.environ.get("JAX_COMPILATION_CACHE_DIR")),
         native_reader=_native.get_lib() is not None,
         num_tpus=mx.num_tpus())
    return device


# ----------------------------------------------------------------------
# training phases
# ----------------------------------------------------------------------
def _kernel_counts():
    from mxnet_tpu.pallas.dispatch import PALLAS_FALLBACKS, PALLAS_LAUNCHES
    return ({c.label_values[0]: c.value
             for c in PALLAS_LAUNCHES.children()},
            sum(c.value for c in PALLAS_FALLBACKS.children()))


def _kernel_delta(before, want):
    """Kernels built since ``before``; fails unless every kernel of
    ``want`` was, and unless ``auto`` never fell back to XLA."""
    launches0, fallbacks0 = before
    launches, fallbacks = _kernel_counts()
    built = {k: v - launches0.get(k, 0) for k, v in launches.items()
             if v - launches0.get(k, 0)}
    for k in want:
        check(built.get(k, 0) > 0,
              "kernel %s was not built into the program (built: %s)"
              % (k, built))
    check(fallbacks == fallbacks0,
          "pallas_fallbacks grew by %d: auto chose the XLA path"
          % (fallbacks - fallbacks0))
    return built


def _on_devices(arrays, devices):
    """Every array lives on exactly ``devices`` (jax Device set)."""
    devices = set(devices)
    return all(set(getattr(a, "_data", a).devices()) == devices
               for a in arrays)


def _trainable(mod):
    exe = mod._exec_group._exec
    return [exe.arg_dict[n] for n in mod._exec_group.param_names
            if n in exe.arg_dict]


def _fit_steps(mod, batch, metric, steps):
    """``steps`` fused fit steps on one repeated batch.  Returns per
    step: loss, wall seconds (the metric readback is the sync), device
    dispatches and fit-program traces."""
    from mxnet_tpu import profiler
    from mxnet_tpu.module import fused_fit
    rows = []
    for _ in range(steps):
        metric.reset()
        d0 = profiler.DEVICE_DISPATCHES.value
        r0 = fused_fit.TRACE_COUNT
        t0 = time.perf_counter()
        check(mod.fit_step(batch, metric),
              "fit_step fell back to the eager path")
        mod.update_metric(metric, batch.label)
        loss = float(metric.get()[1])
        rows.append({"loss": loss,
                     "seconds": time.perf_counter() - t0,
                     "dispatches": profiler.DEVICE_DISPATCHES.value - d0,
                     "traces": fused_fit.TRACE_COUNT - r0})
    return rows


def _check_training(rows, what):
    losses = [r["loss"] for r in rows]
    check(all(np.isfinite(losses)), "%s: non-finite loss %s" % (what, losses))
    check(all(r["dispatches"] == 1 for r in rows),
          "%s: train_dispatches_per_step != 1 (%s)"
          % (what, [r["dispatches"] for r in rows]))
    check(not any(r["traces"] for r in rows[2:]),
          "%s: fit program retraced after step 2 (%s)"
          % (what, [r["traces"] for r in rows]))


def _lm_module(mx, cfg, batch, ctx, arg_params=None, **sym_kwargs):
    from mxnet_tpu import models
    sym = models.get_symbol("transformer", **cfg, **sym_kwargs)
    S = cfg["seq_len"]
    mod = mx.Module(sym, context=ctx)
    mod.bind(data_shapes=[("data", (batch, S))],
             label_shapes=[("softmax_label", (batch * S,))])
    mx.random.seed(SEED)
    np.random.seed(SEED)
    mod.init_params(mx.init.Xavier(rnd_type="gaussian", factor_type="in",
                                   magnitude=2), arg_params=arg_params)
    mod.init_optimizer(
        kvstore="tpu", optimizer="sgd",
        optimizer_params={"learning_rate": 0.01, "momentum": 0.9,
                          "multi_precision": cfg["dtype"] != "float32"})
    return mod


def _lm_batch(mx, cfg, batch):
    rng = np.random.RandomState(SEED)
    S, V = cfg["seq_len"], cfg["num_classes"]
    tok = rng.randint(0, V, (batch, S)).astype(np.float32)
    # next-token labels of the same sequence: something a model can fit
    lab = np.roll(tok, -1, axis=1).reshape(batch * S)
    return mx.io.DataBatch(data=[mx.nd.array(tok)],
                           label=[mx.nd.array(lab)])


def phase_train_lm(ctx, cfg=LM, train=LM_TRAIN):
    """The user's training path on the LM.  Returns the trained
    parameters (host copies, by name) for the serve phase."""
    import mxnet_tpu as mx
    t_phase = time.perf_counter()
    before = _kernel_counts()
    mod = _lm_module(mx, cfg, train["batch"], ctx)
    metric = mx.metric.create("ce")
    rows = _fit_steps(mod, _lm_batch(mx, cfg, train["batch"]), metric,
                      train["steps"])
    _check_training(rows, "train-lm")
    check(rows[-1]["loss"] < rows[0]["loss"],
          "train-lm: loss did not fall on a repeated batch (%s)"
          % [r["loss"] for r in rows])
    built = _kernel_delta(before, train["kernels"])
    check(_on_devices(_trainable(mod), [ctx.jax_device]),
          "train-lm: parameters do not live on %s" % ctx)
    arg_params, _ = mod.get_params()
    n_params = sum(int(np.prod(v.shape)) for v in arg_params.values())
    emit("train-lm", seconds=time.perf_counter() - t_phase,
         compile_seconds=rows[0]["seconds"],
         step_seconds=[r["seconds"] for r in rows[1:]],
         config="L%d d%d h%d S%d B%d vocab%d %s" % (
             cfg["num_layers"], cfg["d_model"], cfg["num_heads"],
             cfg["seq_len"], train["batch"], cfg["num_classes"],
             cfg["dtype"]),
         params=n_params, losses=[r["loss"] for r in rows],
         train_dispatches_per_step=1, retraces_after_step_2=0,
         pallas_kernels_built=built, pallas_fallbacks=0,
         param_device=str(ctx.jax_device))
    return arg_params


def phase_train_resnet(ctx, cfg=RESNET):
    """ResNet-50 through ``Module.fit`` itself (NDArrayIter, callbacks)."""
    import mxnet_tpu as mx
    from mxnet_tpu import models, profiler
    from mxnet_tpu.module import fused_fit
    t_phase = time.perf_counter()
    c, h, w = cfg["image_shape"]
    B, steps = cfg["batch"], cfg["steps"]
    shape = (B, h, w, c) if cfg["layout"] == "NHWC" else (B, c, h, w)
    sym = models.get_symbol("resnet", num_classes=1000,
                            num_layers=cfg["num_layers"],
                            image_shape=cfg["image_shape"],
                            dtype=cfg["dtype"], layout=cfg["layout"])
    rng = np.random.RandomState(SEED)
    one = rng.uniform(-1, 1, shape).astype(np.float32)
    lab = rng.randint(0, 1000, (B,)).astype(np.float32)
    it = mx.io.NDArrayIter(np.concatenate([one] * steps),
                           np.concatenate([lab] * steps), batch_size=B)
    rows = []
    mark = {"t": time.perf_counter(),
            "d": profiler.DEVICE_DISPATCHES.value,
            "r": fused_fit.TRACE_COUNT}

    def on_batch(param):
        # the metric readback is the step's sync; reset gives per-step loss
        loss = float(param.eval_metric.get()[1])
        param.eval_metric.reset()
        now = time.perf_counter()
        rows.append({"loss": loss, "seconds": now - mark["t"],
                     "dispatches": profiler.DEVICE_DISPATCHES.value
                     - mark["d"],
                     "traces": fused_fit.TRACE_COUNT - mark["r"]})
        mark.update(t=now, d=profiler.DEVICE_DISPATCHES.value,
                    r=fused_fit.TRACE_COUNT)

    mod = mx.Module(sym, context=ctx)
    mx.random.seed(SEED)
    np.random.seed(SEED)
    mod.fit(it, num_epoch=1, eval_metric="ce", kvstore="tpu",
            optimizer="sgd",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9,
                              "wd": 1e-4,
                              "multi_precision": cfg["dtype"] != "float32"},
            initializer=mx.init.Xavier(rnd_type="gaussian",
                                       factor_type="in", magnitude=2),
            batch_end_callback=on_batch)
    check(len(rows) == steps, "train-resnet: %d steps ran, wanted %d"
          % (len(rows), steps))
    check(mod._fused_fit is not None,
          "train-resnet: Module.fit did not take the fused fit step")
    _check_training(rows, "train-resnet")
    check(_on_devices(_trainable(mod), [ctx.jax_device]),
          "train-resnet: parameters do not live on %s" % ctx)
    emit("train-resnet", seconds=time.perf_counter() - t_phase,
         compile_seconds=rows[0]["seconds"],
         step_seconds=[r["seconds"] for r in rows[1:]],
         config="resnet%d b%d %s %s" % (cfg["num_layers"], B,
                                        cfg["layout"], cfg["dtype"]),
         losses=[r["loss"] for r in rows], train_dispatches_per_step=1,
         retraces_after_step_2=0, param_device=str(ctx.jax_device))


# ----------------------------------------------------------------------
# serving
# ----------------------------------------------------------------------
def _prompts(cfg, serve):
    rng = np.random.RandomState(SEED + 1)
    return [rng.randint(0, cfg["num_classes"], n).tolist()
            for n in serve["prompt_lens"]]


def _engine(params, cfg, serve, ctx, impl=None, build=None):
    """A warmed DecodeEngine.  ``impl`` forces MXNET_PAGED_ATTN_IMPL
    while the step program is built (the knob is read at trace time)
    and puts the environment back; None leaves ``auto`` to choose."""
    import mxnet_tpu as mx
    build = build or mx.decode.DecodeEngine
    prev = os.environ.get("MXNET_PAGED_ATTN_IMPL")
    if impl is not None:
        os.environ["MXNET_PAGED_ATTN_IMPL"] = impl
    try:
        t0 = time.perf_counter()
        eng = build(params, dict(cfg), capacity=serve["capacity"],
                    block_size=serve["block_size"],
                    num_blocks=serve["num_blocks"],
                    chunk_tokens=serve["chunk_tokens"], ctx=ctx,
                    warmup=True)
        return eng, time.perf_counter() - t0
    finally:
        if impl is not None:
            if prev is None:
                del os.environ["MXNET_PAGED_ATTN_IMPL"]
            else:
                os.environ["MXNET_PAGED_ATTN_IMPL"] = prev


def _generate_all(eng, prompts, n_new):
    """All prompts in flight at once; (tokens, per-step logits) each."""
    handles = [eng.submit(p, max_new_tokens=n_new, collect_logits=True)
               for p in prompts]
    return [(h.result(timeout=600), [np.asarray(l) for l in h.logits])
            for h in handles]


def _compare_streams(ref, got, what, tol=LOGIT_TOL):
    """``ref``/``got``: [(tokens, logits|None)] per request.  Per-step
    logits must agree within ``tol`` x the reference's largest |logit|,
    and tokens must be equal wherever the reference's top-2 margin is
    wider than twice that (random weights give near-ties; past a flip
    the two streams see different contexts and are not compared)."""
    worst, flips, compared = 0.0, 0, 0
    for i, ((rt, rl), (gt, gl)) in enumerate(zip(ref, got)):
        check(len(gt) == len(rt), "%s: request %d answered %d tokens, "
              "reference %d" % (what, i, len(gt), len(rt)))
        for step, (a, b) in enumerate(zip(rt, gt)):
            row = rl[step]
            band = tol * float(np.abs(row).max())
            if gl is not None:
                diff = float(np.abs(gl[step] - row).max())
                worst = max(worst, diff / float(np.abs(row).max()))
                check(np.isfinite(gl[step]).all() and diff <= band,
                      "%s: request %d step %d logits differ by %.4g "
                      "(allowed %.4g)" % (what, i, step, diff, band))
            compared += 1
            if a != b:
                top2 = np.sort(row)[-2:]
                check(top2[1] - top2[0] <= 2 * band,
                      "%s: request %d step %d token %d != reference %d "
                      "at top-2 margin %.4g > %.4g"
                      % (what, i, step, b, a, top2[1] - top2[0], 2 * band))
                flips += 1
                break
    return {"steps_compared": compared, "near_tie_flips": flips,
            "worst_logit_diff_rel": worst, "tolerance_rel": tol}


def _post(url, doc):
    req = urllib.request.Request(
        url, json.dumps(doc).encode(), {"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=600) as resp:
        return resp.status, resp.read().decode()


def _http_generate(server, prompts, n_new):
    """Real POST /generate requests, all in flight at once; the last one
    streams.  Returns (tokens per request, seconds per request)."""
    host, port = server.start_http(port=0)
    url = "http://%s:%d/generate" % (host, port)
    out = [None] * len(prompts)

    def one(i):
        stream = i == len(prompts) - 1
        t0 = time.perf_counter()
        status, body = _post(url, {"tokens": prompts[i],
                                   "max_new_tokens": n_new,
                                   "stream": stream})
        check(status == 200, "POST /generate %d answered %d" % (i, status))
        if stream:
            lines = [json.loads(l) for l in body.splitlines() if l.strip()]
            check(lines and lines[-1].get("done") and
                  "error" not in lines[-1],
                  "streamed /generate did not finish cleanly: %s"
                  % lines[-1:])
            toks = [l["token"] for l in lines[:-1]]
        else:
            toks = json.loads(body)["tokens"]
        out[i] = (toks, time.perf_counter() - t0)

    threads = [threading.Thread(target=one, args=(i,))
               for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    check(all(o is not None for o in out), "a /generate request died")
    return [o[0] for o in out], [o[1] for o in out]


def _tiny_forward():
    """ModelServer wants a forward model beside the decode engine."""
    import mxnet_tpu as mx
    sym = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=2,
                                name="fc")
    return sym, {"fc_weight": mx.nd.zeros((2, 4)),
                 "fc_bias": mx.nd.zeros((2,))}


def phase_serve_lm(ctx, params, cfg=LM, serve=SERVE):
    import mxnet_tpu as mx
    t_phase = time.perf_counter()
    prompts = _prompts(cfg, serve)
    n_new = serve["max_new_tokens"]
    check(max(serve["prompt_lens"]) > serve["chunk_tokens"],
          "serve-lm: no prompt longer than chunk_tokens")

    # the in-repo reference: the same engine on the XLA paged path
    ref_eng, ref_compile = _engine(params, cfg, serve, ctx, impl="xla")
    check(ref_eng.stats()["attn_impl"] == "xla", "reference is not XLA")
    ref = _generate_all(ref_eng, prompts, n_new)
    ref_eng.stop()
    del ref_eng
    gc.collect()

    before = _kernel_counts()
    eng, compile_s = _engine(params, cfg, serve, ctx)
    built = _kernel_delta(before, serve["kernels"])
    check(eng.stats()["attn_impl"] == serve["impl"],
          "serve-lm: auto chose %r, expected %r"
          % (eng.stats()["attn_impl"], serve["impl"]))
    direct = _compare_streams(ref, _generate_all(eng, prompts, n_new),
                              "serve-lm engine")

    sym, fwd_params = _tiny_forward()
    server = mx.serving.ModelServer(sym, fwd_params, {}, {"data": (4,)},
                                    contexts=[ctx], max_batch_size=1,
                                    warmup=False, decode_engine=eng)
    toks, secs = _http_generate(server, prompts, n_new)
    http = _compare_streams(ref, [(t, None) for t in toks],
                            "serve-lm /generate")
    server.stop()
    st = eng.stats()
    eng.stop()
    check(st["completed"] == 2 * len(prompts) and not st["failed"],
          "serve-lm: %d completed, %d failed" % (st["completed"],
                                                 st["failed"]))
    check(st["dispatches_per_step"] == 1.0,
          "serve-lm: dispatches_per_step %s" % st["dispatches_per_step"])
    check(st["steady_state_retraces"] == 0,
          "serve-lm: %d steady-state retraces" % st["steady_state_retraces"])
    check(st["prefill_chunks"] > 2 * len(prompts),
          "serve-lm: the long prompts were not chunked")
    cache_bytes = mx.fleet.per_device_cache_bytes(eng)
    emit("serve-lm", seconds=time.perf_counter() - t_phase,
         compile_seconds=compile_s, reference_compile_seconds=ref_compile,
         request_seconds=secs,
         config="L%d d%d h%d ctx%d %s capacity%d block%d blocks%d chunk%d"
         % (cfg["num_layers"], cfg["d_model"], cfg["num_heads"],
            cfg["seq_len"], cfg["dtype"], serve["capacity"],
            serve["block_size"], serve["num_blocks"],
            serve["chunk_tokens"]),
         prompt_lens=list(serve["prompt_lens"]), max_new_tokens=n_new,
         attn_impl=st["attn_impl"], pallas_kernels_built=built,
         pallas_fallbacks=0, cache_donation=st["cache_donation"],
         kv_cache_bytes=cache_bytes, engine_vs_xla=direct,
         http_vs_xla=http, steps=st["steps"],
         prefill_chunks=st["prefill_chunks"],
         dispatches_per_step=st["dispatches_per_step"],
         steady_state_retraces=st["steady_state_retraces"])
    del eng, server
    gc.collect()


# ----------------------------------------------------------------------
# --chips 4: the multi-chip path and what it is compared with
# ----------------------------------------------------------------------
def _bytes_per_device(arrays):
    """{device id: bytes} over ``addressable_shards`` of every array."""
    out = {}
    for a in arrays:
        for s in getattr(a, "_data", a).addressable_shards:
            out[s.device.id] = out.get(s.device.id, 0) + int(s.data.nbytes)
    return out


def _check_spread(per_dev, n, what):
    check(len(per_dev) == n and all(per_dev.values()),
          "%s sits on %d of %d devices: %s" % (what, len(per_dev), n,
                                               per_dev))


def phase_multichip(cfg=LM_4CHIP, train=LM_TRAIN, serve=SERVE,
                    mesh_axes=(("dp", 2), ("mp", 2)), tp=2, ctx_of=None):
    import mxnet_tpu as mx
    ctx_of = ctx_of or mx.tpu
    n_dev = int(np.prod([n for _, n in mesh_axes]))
    steps = train["steps"]

    # (a) the dp x mp fused fit step against the one-chip trajectory
    t0 = time.perf_counter()
    one = _lm_module(mx, cfg, train["batch"], ctx_of(0))
    # host snapshots: both arms start from the same weights
    init = {k: v.asnumpy() for k, v in one.get_params()[0].items()}
    batch = _lm_batch(mx, cfg, train["batch"])
    rows1 = _fit_steps(one, batch, mx.metric.create("ce"), steps)
    _check_training(rows1, "one-chip fit")
    del one
    gc.collect()
    mx.sharding.set_mesh(dict(mesh_axes))
    try:
        mod = _lm_module(mx, cfg, train["batch"],
                         [ctx_of(i) for i in range(n_dev)],
                         arg_params={k: mx.nd.array(v, dtype=v.dtype)
                                     for k, v in init.items()},
                         tensor_parallel="mp")
        rows4 = _fit_steps(mod, batch, mx.metric.create("ce"), steps)
        _check_training(rows4, "dp x mp fit")
        l1 = np.array([r["loss"] for r in rows1])
        l4 = np.array([r["loss"] for r in rows4])
        check(np.allclose(l4, l1, rtol=2e-2),
              "dp x mp loss trajectory %s leaves the one-chip one %s"
              % (l4.tolist(), l1.tolist()))
        param_bytes = _bytes_per_device(_trainable(mod))
        _check_spread(param_bytes, n_dev, "dp x mp parameters")
        emit("multichip-fit", seconds=time.perf_counter() - t0,
             mesh=dict(mesh_axes), kvstore="tpu",
             config="L%d d%d h%d S%d B%d %s" % (
                 cfg["num_layers"], cfg["d_model"], cfg["num_heads"],
                 cfg["seq_len"], train["batch"], cfg["dtype"]),
             one_chip_losses=l1.tolist(), mesh_losses=l4.tolist(),
             one_chip_compile_seconds=rows1[0]["seconds"],
             mesh_compile_seconds=rows4[0]["seconds"],
             one_chip_step_seconds=[r["seconds"] for r in rows1[1:]],
             mesh_step_seconds=[r["seconds"] for r in rows4[1:]],
             param_bytes_per_device=param_bytes,
             train_dispatches_per_step=1, retraces_after_step_2=0)
        del mod
    finally:
        mx.sharding.clear_mesh()
    gc.collect()

    # (b) tensor-parallel decode against the one-device engine
    t0 = time.perf_counter()
    params = init
    prompts = _prompts(cfg, serve)
    n_new = serve["max_new_tokens"]
    base, base_compile = _engine(params, cfg, serve, ctx_of(0))
    ref = _generate_all(base, prompts, n_new)
    base_bytes = _bytes_per_device(base._cache_arrs)
    base.stop()
    del base
    gc.collect()
    try:
        eng, tp_compile = _engine(
            params, cfg, serve, ctx_of(0),
            build=lambda p, c, **kw: mx.fleet.make_tp_engine(
                p, c, tensor_parallel=tp, **kw))
        got = _compare_streams(ref, _generate_all(eng, prompts, n_new),
                               "tensor-parallel engine")
        st = eng.stats()
        cache_bytes = _bytes_per_device(eng._cache_arrs)
        eng.stop()
        del eng
    finally:
        mx.sharding.clear_mesh()
    _check_spread(cache_bytes, tp, "tensor-parallel K/V cache")
    check(max(cache_bytes.values()) * tp == max(base_bytes.values()),
          "K/V cache per device %s is not 1/%d of the one-device %s"
          % (cache_bytes, tp, base_bytes))
    check(st["dispatches_per_step"] == 1.0
          and st["steady_state_retraces"] == 0,
          "tensor-parallel engine: dispatches_per_step %s, retraces %d"
          % (st["dispatches_per_step"], st["steady_state_retraces"]))
    emit("multichip-decode", seconds=time.perf_counter() - t0,
         tensor_parallel=tp, attn_impl=st["attn_impl"],
         compile_seconds=tp_compile, one_device_compile_seconds=base_compile,
         tp_vs_one_device=got, kv_cache_bytes_per_device=cache_bytes,
         one_device_kv_cache_bytes=base_bytes,
         dispatches_per_step=st["dispatches_per_step"],
         steady_state_retraces=st["steady_state_retraces"])


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the multi-chip phase (the driver "
                         "runs 1)")
    args = ap.parse_args(argv)
    device = phase_devices(args.chips)
    import mxnet_tpu as mx
    if args.chips == 4:
        phase_multichip()
    else:
        ctx = mx.tpu(0)
        params = phase_train_lm(ctx)
        gc.collect()
        phase_train_resnet(ctx)
        gc.collect()
        phase_serve_lm(ctx, params)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
