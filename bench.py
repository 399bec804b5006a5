"""Headline benchmark: ResNet-50 ImageNet training + transformer-LM MFU.

Reference baseline (BASELINE.md / docs/faq/perf.md:205-215): MXNet 1.2
ResNet-50 training, batch 32, fp32, 1x V100 = 298.51 img/s.

The whole training step — forward, backward, gradient scale, SGD momentum
update — is ONE XLA computation (parallel/trainer.py TrainStep) running
bf16 on the MXU with fp32 master weights (the multi-precision
configuration the reference exposes as optimizer.py SGD multi_precision).
The ResNet trunk runs channel-last (NHWC) end-to-end with the one-pass
fused BatchNorm schedule (ops/nn.py _bn_train_fused) — see docs/PERF.md
for the roofline analysis of why ResNet-50/224 is HBM-bandwidth-bound.

The default run prints ONE JSON line: the ResNet-50 img/s headline plus
``transformer_*`` fields from the arithmetic-intensity-dense
transformer-LM benchmark (models/transformer.py), which demonstrates the
framework reaches MXU-bound MFU when the model is not bandwidth-bound.
Use ``--model resnet|transformer|all`` to select.
"""
import argparse
import json
import os
import time

import numpy as np


BASELINE_IMG_PER_SEC = 298.51


def _step_hist():
    """A fine-grained (factor-1.25 buckets) mx.telemetry Histogram for
    per-step wall times — the latency-distribution source behind the
    ``step_ms_p50``/``step_ms_p99`` JSON fields (docs/OBSERVABILITY.md)."""
    from mxnet_tpu import telemetry
    return telemetry.Histogram(
        "bench_step_ms", unit="ms",
        bounds=telemetry.exponential_buckets(0.01, 1.25, 72))


def _round_opt(v, digits=3):
    return None if v is None else round(v, digits)


def _latency_fields(hist, compile_ms):
    """step_ms_p50 / step_ms_p99 / compile_ms fields every bench mode
    folds into its JSON line. ``compile_ms`` is first-trace wall time
    (trace + XLA compile + first run of the measurement program)."""
    have = hist is not None and hist.count > 0
    return {
        "step_ms_p50": _round_opt(hist.quantile(0.5)) if have else None,
        "step_ms_p99": _round_opt(hist.quantile(0.99)) if have else None,
        "compile_ms": _round_opt(compile_ms, 1),
    }

def _check_sane(achieved, peak):
    """Refuse to report throughput above the chip's physical peak — a
    failed launch (OOM) can make the timing loop "complete" instantly."""
    if achieved and peak and achieved > peak:
        raise SystemExit(
            "bench: achieved %.1f TFLOP/s exceeds the %.0f TF peak — "
            "the timing loop did not actually execute (a failed "
            "launch); refusing to report garbage" % (achieved, peak))


def _peak_tflops(device_kind):
    """Peak bf16 TFLOP/s — the one table lives in the compiled-program
    registry (telemetry/programs.py PEAKS)."""
    from mxnet_tpu import telemetry
    return telemetry.programs.peak_tflops(device_kind)


def _mfu_fields(flops_hand, flops_measured, iters, dt, device_kind):
    """The hand-math vs compiler-measured MFU pair every training bench
    folds into its JSON: ``mfu`` from the analytic FLOP count (the
    numerator docs/PERF.md derives by hand — known to drop attention
    matmuls on the transformer arm), ``mfu_measured`` from XLA
    ``cost_analysis()`` via the compiled-program registry.  A >10%
    FLOP-count disagreement warns on stderr (time cancels, so the
    check runs on the CPU container too) — the measured number is the
    trustworthy one.  Also refreshes the ``mfu_measured`` gauge."""
    import sys
    from mxnet_tpu import telemetry

    peak = _peak_tflops(device_kind)
    sec = dt / iters if iters else None
    ach_hand = (flops_hand / sec / 1e12
                if flops_hand and sec else None)
    ach_meas = (flops_measured / sec / 1e12
                if flops_measured and sec else None)
    _check_sane(ach_meas if ach_meas is not None else ach_hand, peak)
    mfu_hand = (ach_hand / peak) if ach_hand and peak else None
    mfu_meas = (ach_meas / peak) if ach_meas and peak else None
    if flops_hand and flops_measured \
            and abs(flops_hand - flops_measured) > 0.10 * flops_measured:
        print("bench: WARNING hand-math FLOPs/step %.3g disagree with "
              "compiler-measured %.3g by %.0f%% — trust mfu_measured "
              "(the hand numerator is known to drop attention matmuls)"
              % (flops_hand, flops_measured,
                 100.0 * abs(flops_hand - flops_measured)
                 / flops_measured), file=sys.stderr)
    if flops_measured and sec:
        telemetry.programs.mfu_measured(flops_measured, sec, device_kind)
    ach = ach_meas if ach_meas is not None else ach_hand
    mfu = mfu_hand if mfu_hand is not None else mfu_meas
    return {
        "achieved_tflops": round(ach, 2) if ach else None,
        "peak_bf16_tflops": peak,
        "mfu": round(mfu, 4) if mfu else None,
        "mfu_measured": round(mfu_meas, 4) if mfu_meas else None,
        "flops_per_step_hand": flops_hand,
        "flops_per_step_measured": flops_measured,
    }


def _make_pipeline_stream(args, image_shape):
    """Endless DataBatch stream from a generated .rec of JPEG images
    (PrefetchingIter over ImageRecordIter with the native decode path)."""
    import io as _pyio
    import tempfile

    import mxnet_tpu as mx
    from mxnet_tpu import recordio
    from PIL import Image

    c, h, w = image_shape
    n_images = max(2 * args.batch, 256)
    d = tempfile.mkdtemp(prefix="bench_rec_")
    rec_path = d + "/bench.rec"
    idx_path = d + "/bench.idx"
    rec = recordio.MXIndexedRecordIO(idx_path, rec_path, "w")
    rng = np.random.RandomState(0)
    for i in range(n_images):
        img = rng.randint(0, 255, (h, w, c), dtype=np.uint8)
        buf = _pyio.BytesIO()
        Image.fromarray(img.squeeze() if c == 1 else img).save(
            buf, "JPEG", quality=90)
        header = recordio.IRHeader(0, float(i % 1000), i, 0)
        rec.write_idx(i, recordio.pack(header, buf.getvalue()))
    rec.close()

    it = mx.io.ImageRecordIter(
        path_imgrec=rec_path, path_imgidx=idx_path,
        data_shape=image_shape, batch_size=args.batch, shuffle=True,
        rand_mirror=True, mean_r=127.0, mean_g=127.0, mean_b=127.0,
        std_r=64.0, std_g=64.0, std_b=64.0,
        preprocess_threads=args.decode_threads)
    it = mx.io.PrefetchingIter(it)

    def stream():
        while True:
            it.reset()
            for batch in it:
                yield batch

    return stream()


def _timed_steps(ts, next_batch, warmup, iters):
    """Host-fed timing loop (pipeline mode): warm up, time ``iters``
    python-dispatched steps. The synthetic benches use _fori_timed
    instead (see there for why). Returns ``(dt, info)`` where info
    carries compile_ms (first warm-up step = trace+compile wall time)
    and a per-step latency histogram (host step times incl. data)."""
    import jax
    from mxnet_tpu import telemetry

    compile_ms = None
    for i in range(max(1, warmup)):   # >=1: keep compile out of the
        t0 = time.perf_counter()      # measured (histogrammed) steps
        ts.step(next_batch(i))
        if i == 0:
            jax.block_until_ready(ts.params)
            compile_ms = (time.perf_counter() - t0) * 1e3
            telemetry.JIT_COMPILE_MS.observe(compile_ms)
    jax.block_until_ready(ts.params)

    hist = _step_hist()
    t0 = time.perf_counter()
    for i in range(iters):
        t_s = time.perf_counter()
        ts.step(next_batch(i))
        hist.observe((time.perf_counter() - t_s) * 1e3)
    jax.block_until_ready(ts.params)
    dt = time.perf_counter() - t0

    # liveness guard: force a real readback; a failed launch (OOM) can
    # otherwise report instant "completion" and absurd throughput
    import jax.numpy as jnp
    probe_w = float(jnp.asarray(
        next(iter(ts.params.values())).ravel()[0]))
    if not np.isfinite(probe_w):
        raise SystemExit("bench: non-finite weights after timing loop")
    return dt, {"compile_ms": compile_ms, "hist": hist}


def _cost_flops(ts, flops_probe, site="bench_train_step"):
    """Per-step FLOPs from XLA cost analysis (abstract-probe lowering,
    run after timing, so no second live executable sits beside the
    timing loop).  The compiled
    probe registers in the compiled-program registry
    (``telemetry.programs()``), which is also where the FLOP number is
    read back from — one analysis pipeline for bench, roofline and the
    flight recorder."""
    if flops_probe is None:
        return None
    try:
        compiled = ts._step_fn.lower(*flops_probe).compile()
    except Exception:
        return None
    try:
        from mxnet_tpu import telemetry
        entry = telemetry.programs.register_compiled(
            site, compiled, fn_name="train_step")
        return float(entry.get("flops") or 0.0) or None
    except Exception:
        try:
            cost = compiled.cost_analysis()
            if isinstance(cost, (list, tuple)):
                cost = cost[0]
            return float(cost.get("flops", 0.0)) or None
        except Exception:
            return None


def _flash_attention_flops(args):
    """Analytic FLOPs of the Pallas flash-attention kernels per step —
    XLA's cost analysis reports 0 for custom calls, so without this the
    MFU numerator silently drops the attention matmuls when the fused
    kernel is active (ops/nn.py _use_flash_attention). Counted causally
    (half the S^2 blocks): forward = QK^T + PV = 2 matmuls, backward =
    score recompute + dV + dP + dQ + dK = 5 matmuls.
    """
    import jax.numpy as jnp
    from mxnet_tpu.ops.nn import _use_flash_attention
    B, S = args.lm_batch, args.lm_seq
    H, D = args.lm_heads, args.lm_d_model // args.lm_heads
    dtype = jnp.bfloat16 if args.dtype == "bfloat16" else \
        jnp.dtype(args.dtype)
    if not _use_flash_attention(S, D, dtype):
        return 0.0  # XLA path: cost analysis already counts these
    per_matmul = 2.0 * B * H * S * S * D
    causal = 0.5
    return args.lm_layers * (2 + 5) * per_matmul * causal


def _fori_timed(ts, batches, iters, lr, warmup=1):
    """Time ``iters`` training steps as the DIFFERENCE between one
    (n0+iters)-step and one n0-step program, each a single launch with
    the step chain inside ``lax.fori_loop``.

    One launch per measurement with a forced scalar readback, and the
    differential cancels the launch + readback round trip.  Whether a
    plain python dispatch loop gives the same number on the attached
    chip is for the benchmark PR to measure (ROADMAP speed queue 1).
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    if ts._step_fn is None:
        ts._step_fn = ts._build_step()
    step = ts._step_fn
    lr = jnp.float32(lr)

    # the two batches stack into one argument; each step gathers only
    # its slice (a per-step jnp.where select would read both batches
    # and write a copy — measurable extra HBM traffic in an HBM-bound
    # loop). Arguments, not closure constants: baked-in ImageNet
    # batches blow the remote-compile size limit.
    stacked = jax.tree.map(lambda a, b: jnp.stack([a, b]),
                           batches[0], batches[1])

    def make(n):
        @jax.jit
        def run(params, states, auxs, bstack):
            def body(i, carry):
                p, s, a = carry
                batch = jax.tree.map(
                    lambda v: lax.dynamic_index_in_dim(
                        v, i % 2, 0, keepdims=False), bstack)
                p, s, a, _outs = step(p, s, a, batch, lr,
                                      (i + 1).astype(jnp.uint32))
                return (p, s, a)
            return lax.fori_loop(0, n, body, (params, states, auxs))
        return run

    n0 = 2
    short = make(n0)
    long_ = make(n0 + iters)

    def timed(fn):
        t0 = time.perf_counter()
        p, s, a = fn(ts.params, ts.states, ts.auxs, stacked)
        w = float(jnp.asarray(next(iter(p.values())).ravel()[0]))
        if not np.isfinite(w):
            raise SystemExit("bench: non-finite weights in timing loop")
        return time.perf_counter() - t0

    # compile + warm both programs (>= --warmup repetitions), measure.
    # The first calls trace+compile: their wall time is the compile_ms
    # witness (observed into the jit_compile_ms registry histogram too)
    from mxnet_tpu import telemetry
    compile_ms = None
    for i in range(max(1, warmup)):
        t_s = timed(short)
        t_l = timed(long_)
        if i == 0:
            compile_ms = (t_s + t_l) * 1e3
            telemetry.JIT_COMPILE_MS.observe(compile_ms)
    shorts = [timed(short) for _ in range(2)]
    longs = [timed(long_) for _ in range(2)]
    t_short = min(shorts)
    t_long = min(longs)
    # per-step latency distribution: each long-program repetition gives
    # one per-step estimate against the best short baseline (few samples
    # by design — the method times whole programs, see above)
    hist = _step_hist()
    for t_l in longs:
        est = (t_l - t_short) / iters * 1e3
        if est > 0:
            hist.observe(est)
    dt = t_long - t_short
    if dt <= 0:
        raise SystemExit(
            "bench: non-positive timing differential (%.4fs long vs "
            "%.4fs short) — wall-clock noise exceeded the measured "
            "work; rerun with more --iters" % (t_long, t_short))
    return dt, {"compile_ms": compile_ms, "hist": hist}


def bench_pipeline_scaling(args):
    """Host-side decode-pipeline throughput at 1/2/4/8 threads
    (VERDICT r2 item 5): iterator-only timing (ImageRecordIter native
    libjpeg decode + augment), no device in the loop, so the number
    isolates the input pipeline. On a 1-core harness the curve is flat
    by construction; on a real multi-core TPU host it scales."""
    import mxnet_tpu as mx

    image_shape = tuple(int(x) for x in args.image_shape.split(","))
    saved = args.decode_threads
    rates = {}
    for nthreads in (1, 2, 4, 8):
        args.decode_threads = nthreads
        stream = _make_pipeline_stream(args, image_shape)
        # warm one batch (thread spin-up), then time
        next(stream)
        n_batches = 4
        t0 = time.perf_counter()
        for _ in range(n_batches):
            next(stream)
        dt = time.perf_counter() - t0
        rates[str(nthreads)] = round(args.batch * n_batches / dt, 1)
    args.decode_threads = saved
    best = max(rates.values())
    return {"metric": "pipeline_decode_img_per_sec", "value": best,
            "unit": "img/s", "threads": rates,
            "note": "host decode only; flat on 1-core harnesses"}


def bench_resnet(args):
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu import models
    from mxnet_tpu.parallel import TrainStep

    image_shape = tuple(int(x) for x in args.image_shape.split(","))
    c, h, w = image_shape
    data_shape = ((args.batch, h, w, c) if args.layout == "NHWC"
                  else (args.batch,) + image_shape)
    sym = models.get_symbol("resnet", num_classes=1000,
                            num_layers=args.num_layers,
                            image_shape=image_shape, dtype=args.dtype,
                            layout=args.layout)
    n_fused = 0
    if args.fuse:
        # BN→ReLU→Conv1×1 Pallas fusion (symbol/fuse.py); matches only
        # channel-last 1×1 sites, so it no-ops on NCHW — n_fused is
        # reported so a silent no-op can't masquerade as an A/B arm
        from mxnet_tpu.symbol.fuse import count_fused, fuse_conv_bn
        sym = fuse_conv_bn(sym)
        n_fused = count_fused(sym)
    opt = mx.optimizer.SGD(learning_rate=0.1, momentum=0.9, wd=1e-4,
                           multi_precision=(args.dtype != "float32"),
                           rescale_grad=1.0 / args.batch)
    ts = TrainStep(sym, opt,
                   data_shapes={"data": data_shape},
                   label_shapes={"softmax_label": (args.batch,)})
    ts.init_params(mx.init.Xavier(rnd_type="gaussian", factor_type="in",
                                  magnitude=2))

    rng = np.random.RandomState(0)
    if args.pipeline:
        # real input pipeline: generated .rec of JPEGs through the native
        # threaded decode + augment + prefetch path (NCHW batches per the
        # iterator contract; relayout to NHWC is part of the measured cost)
        stream = _make_pipeline_stream(args, image_shape)

        def next_batch(_i):
            b = next(stream)
            d = b.data[0].asnumpy()
            if args.layout == "NHWC":
                d = np.transpose(d, (0, 2, 3, 1))
            return {"data": d, "softmax_label": b.label[0].asnumpy()}
        dt, lat = _timed_steps(ts, next_batch, args.warmup, args.iters)
        flops_measured = None
    else:
        # Synthetic device-resident batches (the reference's perf.md
        # numbers are synthetic-data benchmarks of the training step).
        batches = []
        for _ in range(2):
            data = jnp.asarray(rng.uniform(-1, 1, data_shape)
                               .astype(np.float32))
            label = jnp.asarray(rng.randint(0, 1000, (args.batch,))
                                .astype(np.float32))
            batches.append({"data": data, "softmax_label": label})
        jax.block_until_ready(batches)

        dt, lat = _fori_timed(ts, batches, args.iters, lr=0.1,
                              warmup=args.warmup)
        # abstract probe: lowering must not touch live (donated) buffers
        probe = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
            (ts.params, ts.states, ts.auxs, batches[0],
             jnp.float32(0.1), jnp.uint32(0)))
        flops_measured = _cost_flops(ts, probe, site="bench_resnet")
    # hand numerator (docs/PERF.md): ResNet-50 fwd ≈ 4.1 GMACs =
    # 8.2 GFLOP/img; training ≈ 3x fwd — `mfu` reports this, the
    # compiler-measured count reports as `mfu_measured` beside it
    flops_hand = 24.6e9 * args.batch if args.num_layers == 50 else None

    img_per_sec = args.batch * args.iters / dt
    dev = jax.devices()[0]
    return {
        "metric": ("resnet50_train_img_per_sec_pipeline" if args.pipeline
                   else "resnet50_train_img_per_sec"),
        "value": round(img_per_sec, 2),
        "unit": "img/s",
        "vs_baseline": round(img_per_sec / BASELINE_IMG_PER_SEC, 3),
        "device_kind": dev.device_kind,
        "layout": args.layout,
        "fused": n_fused,
        **_mfu_fields(flops_hand, flops_measured, args.iters, dt,
                      dev.device_kind),
        **_latency_fields(lat["hist"], lat["compile_ms"]),
    }


def bench_transformer(args):
    """Decoder-only LM training throughput (models/transformer.py):
    the MXU-bound benchmark. No reference baseline exists (MXNet 1.2
    predates transformers) — the target is absolute MFU."""
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu import models
    from mxnet_tpu.parallel import TrainStep

    B, S = args.lm_batch, args.lm_seq
    sym = models.get_symbol("transformer", num_classes=args.lm_vocab,
                            num_layers=args.lm_layers,
                            d_model=args.lm_d_model,
                            num_heads=args.lm_heads, seq_len=S,
                            dtype=args.dtype)
    opt = mx.optimizer.SGD(learning_rate=0.01, momentum=0.9,
                           multi_precision=(args.dtype != "float32"),
                           rescale_grad=1.0 / (B * S))
    ts = TrainStep(sym, opt, data_shapes={"data": (B, S)},
                   label_shapes={"softmax_label": (B * S,)})
    ts.init_params(mx.init.Xavier(rnd_type="gaussian", factor_type="in",
                                  magnitude=2))

    rng = np.random.RandomState(0)
    batches = []
    for _ in range(2):
        tok = jnp.asarray(rng.randint(0, args.lm_vocab, (B, S))
                          .astype(np.float32))
        lab = jnp.asarray(rng.randint(0, args.lm_vocab, (B * S,))
                          .astype(np.float32))
        batches.append({"data": tok, "softmax_label": lab})
    jax.block_until_ready(batches)
    probe = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
        (ts.params, ts.states, ts.auxs, batches[0],
         jnp.float32(0.01), jnp.uint32(0)))

    dt, lat = _fori_timed(ts, batches, args.iters, lr=0.01,
                          warmup=args.warmup)
    flops_measured = _cost_flops(ts, probe, site="bench_transformer")
    if flops_measured:
        # XLA reports 0 FLOPs for custom calls: when the Pallas flash-
        # attention kernel is active its matmuls are counted analytically
        flops_measured += _flash_attention_flops(args)
    # hand numerator: the classic 6 * params * tokens training estimate
    # — it DROPS the attention matmuls entirely (the known bug), which
    # is exactly what the >10% mfu-vs-mfu_measured warning surfaces
    n_params = sum(int(np.prod(p.shape)) for p in ts.params.values())
    flops_hand = 6.0 * n_params * B * S

    tok_per_sec = B * S * args.iters / dt
    dev = jax.devices()[0]
    return {
        "metric": "transformer_lm_tokens_per_sec",
        "value": round(tok_per_sec, 1),
        "unit": "tokens/s",
        "device_kind": dev.device_kind,
        "config": "L%d d%d h%d S%d B%d vocab%d" % (
            args.lm_layers, args.lm_d_model, args.lm_heads, S, B,
            args.lm_vocab),
        **_mfu_fields(flops_hand, flops_measured, args.iters, dt,
                      dev.device_kind),
        **_latency_fields(lat["hist"], lat["compile_ms"]),
    }


def bench_transformer_mp(args):
    """Tensor-parallel transformer fit on the 2-D dp×mp GSPMD mesh
    (mx.sharding, docs/SHARDING.md): the model-parallelism acceptance
    arm. Two arms of the SAME fused Module fit step on the SAME
    TP-annotated symbol — ``replicated`` (mesh cleared, so the
    ``__sharding__`` annotations stay latent and the step runs
    dp-only) and ``mp`` (dp×mp=2 mesh: Megatron column/row-parallel
    FFN + head-sharded attention partitioned INSIDE the one donated
    program). Hard gates (SystemExit): the mp arm must stay
    single-launch (``train_dispatches_per_step == 1.0``), retrace-free
    in steady state, and its per-device param bytes must be ≤ 60% of
    the replicated arm's — the matmul shards must actually halve, not
    silently replicate."""
    import os
    import sys
    if "jax" not in sys.modules \
            and os.environ.get("JAX_PLATFORMS") == "cpu":
        # standalone --mode transformer on the CPU container: force 8
        # virtual devices so the dp4×mp2 mesh exists (same knob
        # tests/conftest.py pins for tier-1)
        flags = os.environ.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import executor as _executor
    from mxnet_tpu import metric as metric_mod
    from mxnet_tpu import profiler, telemetry
    from mxnet_tpu.models import transformer
    from mxnet_tpu.module import fused_fit as _ff

    n_dev = len(jax.devices())
    if n_dev < 2 or n_dev % 2:
        return {"metric": "transformer_mp_dispatches_per_step",
                "value": None, "unit": "launches/step",
                "note": "%d visible device(s): the dp×mp=2 mesh needs "
                        "an even count >= 2" % n_dev}
    mp = 2
    dp = n_dev // mp
    B, S, V = 2 * dp, 32, 256
    steps = max(4, args.fit_steps)
    rng = np.random.RandomState(0)
    batches = [mx.io.DataBatch(
        data=[mx.nd.array(rng.randint(0, V, (B, S)).astype(np.float32))],
        label=[mx.nd.array(rng.randint(0, V, (B * S,))
                           .astype(np.float32))])
        for _ in range(steps + 2)]

    def run_arm(mesh_axes):
        mx.sharding.set_mesh(mesh_axes)
        try:
            sym = transformer.get_symbol(
                num_classes=V, num_layers=2, d_model=64, num_heads=4,
                seq_len=S, tensor_parallel="mp")
            mod = mx.Module(sym, context=[mx.tpu(i)
                                          for i in range(n_dev)])
            mod.bind(data_shapes=[("data", (B, S))],
                     label_shapes=[("softmax_label", (B * S,))])
            mod.init_params(mx.init.Xavier(rnd_type="gaussian",
                                           factor_type="in",
                                           magnitude=2))
            mod.init_optimizer(
                kvstore=mx.kv.create("device"), optimizer="sgd",
                optimizer_params={"learning_rate": 0.05,
                                  "momentum": 0.9})
            m = metric_mod.create("ce")
            t_c = time.perf_counter()
            mod.fit_step(batches[0], m)
            mod._fit_sync()
            compile_ms = (time.perf_counter() - t_c) * 1e3
            mod.fit_step(batches[1], m)     # steady-state entry
            mod._fit_sync()
            d0 = profiler.DEVICE_DISPATCHES.value
            h0 = metric_mod.HOST_SYNCS.value
            r0 = (_ff.FIT_RETRACES.value
                  + _executor.EXECUTOR_RETRACES.value)
            t0 = time.perf_counter()
            for b in batches[2:2 + steps]:
                mod.fit_step(b, m)
            mod._fit_sync()
            dt = time.perf_counter() - t0
            exe = mod._exec_group._exec
            params = [exe.arg_dict[n]
                      for n in mod._exec_group.param_names
                      if n in exe.arg_dict]
            snap = telemetry.memory_snapshot()
            return {
                "dispatches_per_step": round(
                    (profiler.DEVICE_DISPATCHES.value - d0) / steps, 2),
                "host_syncs_per_step": round(
                    (metric_mod.HOST_SYNCS.value - h0) / steps, 2),
                "steady_retraces": int(
                    _ff.FIT_RETRACES.value
                    + _executor.EXECUTOR_RETRACES.value - r0),
                "step_ms": round(dt / steps * 1000, 1),
                "compile_ms": _round_opt(compile_ms, 1),
                "param_bytes_per_device":
                    mx.sharding.per_device_param_bytes(params),
                "census_param_bytes_per_device":
                    snap["param_bytes_per_device"],
            }
        finally:
            mx.sharding.set_mesh(None)

    rep = run_arm(None)
    sharded = run_arm({"dp": dp, "mp": mp})
    sites = int(mx.sharding.CONSTRAINT_SITES.value)
    if sharded["dispatches_per_step"] != 1.0:
        raise SystemExit(
            "bench: transformer mp arm train_dispatches_per_step = %s "
            "(want 1.0) — model parallelism must stay inside the ONE "
            "donated program" % sharded["dispatches_per_step"])
    if sharded["steady_retraces"]:
        raise SystemExit(
            "bench: transformer mp arm retraced %d time(s) in steady "
            "state — mesh-fingerprint compile-cache regression"
            % sharded["steady_retraces"])
    ratio = sharded["param_bytes_per_device"] / max(
        1, rep["param_bytes_per_device"])
    if ratio > 0.60:
        raise SystemExit(
            "bench: mp arm per-device param bytes %d = %.0f%% of "
            "replicated %d (want <= 60%%) — the mp shards silently "
            "replicated" % (sharded["param_bytes_per_device"],
                            100 * ratio, rep["param_bytes_per_device"]))
    dev = jax.devices()[0]
    return {
        "metric": "transformer_mp_dispatches_per_step",
        "value": sharded["dispatches_per_step"],
        "unit": "launches/step",
        "device_kind": dev.device_kind,
        "config": "L2 d64 h4 S%d B%d vocab%d mesh=dp%dxmp%d" % (
            S, B, V, dp, mp),
        "transformer_mp": {"replicated": rep, "mp": sharded},
        "param_bytes_per_device": sharded["param_bytes_per_device"],
        "param_bytes_ratio_vs_replicated": round(ratio, 3),
        "sharding_constraint_sites": sites,
    }


def bench_quantized_inference(args):
    """Calibrated 8-bit ResNet-50 inference (VERDICT r3 item 5): the
    conv/FC stack runs int8(/uint8)×int8 with int32 accumulation
    (ops/quantization_ops.py), ranges pre-calibrated so no online max
    pass remains. Accuracy-delta vs fp32 is pinned by
    tests/test_quantization.py (agreement >= 99% on the trained
    fixture); this measures throughput on the chip."""
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu import models
    from mxnet_tpu.executor import _build_graph_fn
    from mxnet_tpu.contrib.quantization import quantize_symbol

    rng = np.random.RandomState(0)
    dev = jax.devices()[0]
    table = {}
    for qdtype in ("int8", "auto"):
        for batch in (32, 128):
            image_shape = (3, 224, 224)
            sym = models.get_symbol("resnet", num_classes=1000,
                                    image_shape=image_shape,
                                    dtype="float32")
            dshape = (batch,) + image_shape
            input_shapes = {"data": dshape, "softmax_label": (batch,)}
            arg_shapes, arg_types, aux_shapes, aux_types = \
                sym.infer_shape_type(input_shapes)
            arg_names = sym.list_arguments()
            shape_of = dict(zip(arg_names, arg_shapes))
            params = {}
            key = jax.random.key(0)
            for name, shp, dt in zip(arg_names, arg_shapes, arg_types):
                if name in input_shapes:
                    continue
                key, sub = jax.random.split(key)
                params[name] = (jax.random.normal(sub, shp, jnp.float32)
                                * 0.05).astype(dt)
            auxs = {}
            for name, shp, dt in zip(sym.list_auxiliary_states(),
                                     aux_shapes, aux_types):
                auxs[name] = (jnp.zeros(shp, dt) if name.endswith("_mean")
                              else jnp.ones(shp, dt))
            # pre-calibrated ranges for every conv/FC -> no online max
            calib = {n.name: (-4.0, 4.0) for n in sym._topo()
                     if not n.is_var
                     and n.op.name in ("Convolution", "FullyConnected")}
            offline = [n for n in arg_names
                       if n.endswith("_weight") and ("conv" in n
                                                     or "fc" in n
                                                     or "sc" in n)]
            qsym = quantize_symbol(
                sym, offline_params=offline, calib_ranges=calib,
                param_shapes={n: shape_of[n] for n in arg_names
                              if n not in input_shapes},
                quantized_dtype=qdtype)
            for name in offline:
                w = params.pop(name)
                lo = float(jnp.min(w))
                hi = float(jnp.max(w))
                from mxnet_tpu import nd as _nd
                qw, qlo, qhi = _nd.quantize(
                    _nd.NDArray(w), _nd.array(np.float32(lo)),
                    _nd.array(np.float32(hi)), out_type="int8")
                params[name + "_quantize"] = qw._data
                params[name + "_quantize_min"] = qlo._data
                params[name + "_quantize_max"] = qhi._data
            graph_fn = _build_graph_fn(qsym)

            def make_loop(n_iters):
                @jax.jit
                def fwd_loop(params, auxs, data):
                    def body(_, carry):
                        d, acc = carry
                        outs, _ = graph_fn(
                            {**params, "data": d,
                             "softmax_label": jnp.zeros((dshape[0],),
                                                        jnp.float32)},
                            auxs, np.uint32(0), False)
                        s = outs[0].sum()
                        patch = (s * 1e-30).astype(d.dtype).reshape(
                            (1,) * d.ndim)
                        d = jax.lax.dynamic_update_slice(
                            d, patch, (0,) * d.ndim)
                        return (d, acc + s)
                    _, acc = jax.lax.fori_loop(
                        0, n_iters, body, (data, jnp.float32(0)))
                    return acc
                return fwd_loop

            data = jnp.asarray(rng.uniform(-1, 1, dshape)
                               .astype(np.float32))
            n0 = 2
            short = make_loop(n0)
            long_ = make_loop(n0 + args.iters)
            float(short(params, auxs, data))
            float(long_(params, auxs, data))

            def timed(fn):
                t0 = time.perf_counter()
                float(fn(params, auxs, data))
                return time.perf_counter() - t0

            t_short = min(timed(short) for _ in range(2))
            t_long = min(timed(long_) for _ in range(2))
            dt_s = max(t_long - t_short, 1e-9)
            table["resnet50-%s-b%d" % (qdtype, batch)] = round(
                batch * args.iters / dt_s, 1)
    return {"metric": "quantized_inference_img_per_sec",
            "value": table.get("resnet50-int8-b128"),
            "unit": "img/s", "device_kind": dev.device_kind,
            "table": table}


def bench_inference(args):
    """Inference scoring (reference example/image-classification/
    benchmark_score.py + BASELINE.md inference tables): forward-only
    throughput per model at the reference's batch sizes. Weights are
    device-resident, data stays bound (the reference scores the same
    way: random fixed batch).

    Measurement: N forwards run CHAINED inside one ``lax.fori_loop``
    program (each iteration writes a tiny output-dependent patch into
    the data so XLA cannot hoist the loop-invariant forward), and the
    per-step time is the DIFFERENCE between an (n0+iters)-step and an
    n0-step program — cancelling launch/transfer round-trip overhead,
    which would otherwise weigh on millisecond-scale forwards."""
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu import models
    from mxnet_tpu.executor import _build_graph_fn

    configs = [
        ("resnet", {"num_layers": 50, "layout": args.layout}, 32),
        ("resnet", {"num_layers": 50, "layout": args.layout}, 128),
        ("resnet", {"num_layers": 152, "layout": args.layout}, 32),
        ("inception-bn", {}, 32),
        ("vgg", {"num_layers": 16}, 32),
        ("alexnet", {}, 32),
    ]
    rng = np.random.RandomState(0)
    table = {}
    dev = jax.devices()[0]
    for net, kw, batch in configs:
        image_shape = (3, 224, 224)
        sym = models.get_symbol(net, num_classes=1000,
                                image_shape=image_shape, dtype=args.dtype,
                                **kw)
        c, h, w = image_shape
        chlast = kw.get("layout") == "NHWC"
        dshape = (batch, h, w, c) if chlast else (batch,) + image_shape
        graph_fn = _build_graph_fn(sym)

        def make_loop(n_iters):
            @jax.jit
            def fwd_loop(params, auxs, data):
                def body(_, carry):
                    d, acc = carry
                    outs, _ = graph_fn(
                        {**params, "data": d,
                         "softmax_label": jnp.zeros((dshape[0],),
                                                    jnp.float32)},
                        auxs, np.uint32(0), False)
                    s = outs[0].sum()
                    patch = (s * 1e-30).astype(d.dtype).reshape(
                        (1,) * d.ndim)
                    d = jax.lax.dynamic_update_slice(
                        d, patch, (0,) * d.ndim)
                    return (d, acc + s)
                _, acc = jax.lax.fori_loop(
                    0, n_iters, body, (data, jnp.float32(0)))
                return acc
            return fwd_loop

        input_names = {"data", "softmax_label"}
        arg_shapes, arg_types, aux_shapes, aux_types = sym.infer_shape_type(
            {"data": dshape, "softmax_label": (batch,)},
            {"data": args.dtype} if args.dtype != "float32" else {})
        key = jax.random.key(0)
        params = {}
        for name, shp, dt in zip(sym.list_arguments(), arg_shapes,
                                 arg_types):
            if name in input_names:
                continue
            key, sub = jax.random.split(key)
            params[name] = (jax.random.normal(sub, shp, jnp.float32) * 0.05
                            ).astype(dt)
        auxs = {}
        for name, shp, dt in zip(sym.list_auxiliary_states(), aux_shapes,
                                 aux_types):
            auxs[name] = (jnp.zeros(shp, dt) if name.endswith("_mean")
                          else jnp.ones(shp, dt))
        data = jnp.asarray(rng.uniform(-1, 1, dshape).astype(np.float32)
                           ).astype(args.dtype)
        n0 = 2
        short = make_loop(n0)
        long_ = make_loop(n0 + args.iters)
        float(short(params, auxs, data))        # compile + warm
        float(long_(params, auxs, data))

        def timed(fn):
            t0 = time.perf_counter()
            float(fn(params, auxs, data))       # one launch, one readback
            return time.perf_counter() - t0

        t_short = min(timed(short) for _ in range(2))
        t_long = min(timed(long_) for _ in range(2))
        dt_s = max(t_long - t_short, 1e-9)
        label = "%s%s-b%d" % (net, kw.get("num_layers", ""), batch)
        table[label] = round(batch * args.iters / dt_s, 1)
    return {"metric": "inference_img_per_sec",
            "value": table.get("resnet50-b32"),
            "unit": "img/s", "device_kind": dev.device_kind,
            "dtype": args.dtype, "table": table,
            "vs_baseline_v100_fp32": round(
                table.get("resnet50-b32", 0) / 1076.81, 3)}


def bench_kvstore(args):
    """kvstore push/pull throughput on a ResNet-50-sized key set (the real
    param shapes from models.get_symbol, ``--kv-ndev`` simulated device
    gradient streams per key). Four arms: {eager per-key, compiled
    bucketed} x {dense f32, 2-bit compressed}. The headline
    ``kvstore_push_pull_gbps`` is bytes moved through push+pull per
    second on the bucketed dense path; ``speedup_vs_eager`` /
    ``speedup_vs_eager_2bit`` are the acceptance metrics (target >= 3x).

    What the bucketed path eliminates is per-key *dispatch*: the eager
    loop launches ~(2*ndev+1) device computations per key per step where
    the bucketed path launches one per bucket (``dispatches_per_step``
    in the output is the hardware-independent witness). What a launch
    costs on the attached chip is not measured yet; on a 1-core CPU
    smoke run both arms sit at the memory-bandwidth floor and the ratio
    compresses toward 1x — read the dispatch counts, not the CPU ratio. Timing uses min-of-blocks to damp
    scheduler noise, with a readback liveness probe per arm."""
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import models, nd
    from mxnet_tpu import kvstore_fused

    sym = models.get_symbol("resnet", num_classes=1000, num_layers=50,
                            image_shape=(3, 224, 224), dtype="float32")
    arg_shapes, _, _ = sym.infer_shape(data=(1, 3, 224, 224),
                                       softmax_label=(1,))
    keys, shapes = [], []
    for n, s in zip(sym.list_arguments(), arg_shapes):
        if n not in ("data", "softmax_label"):
            keys.append(n)
            shapes.append(s)
    total_bytes = sum(int(np.prod(s)) * 4 for s in shapes)
    ndev = args.kv_ndev
    rng = np.random.RandomState(0)
    weights_np = [rng.normal(0, 0.05, s).astype(np.float32) for s in shapes]
    grads_np = [[rng.normal(0, 0.01, s).astype(np.float32)
                 for _ in range(ndev)] for s in shapes]
    prios = [-i for i in range(len(keys))]
    blocks = max(2, args.iters // 4)

    def run(bucketed, compress, want_latency=False):
        kv = mx.kv.create("device")
        kv.set_bucketing(bucketed)
        if compress:
            kv.set_gradient_compression({"type": "2bit", "threshold": 0.5})
        kv.set_optimizer(mx.optimizer.SGD(
            learning_rate=0.05, momentum=0.9, wd=1e-4,
            rescale_grad=1.0 / args.batch))
        grads = [[nd.array(g) for g in gl] for gl in grads_np]
        outs = [nd.zeros(s) for s in shapes]
        for k, w in zip(keys, weights_np):
            kv.init(k, nd.array(w))

        def step():
            kv.push(keys, grads, priority=prios)
            kv.pull(keys, out=outs)

        def timed_block(n):
            t0 = time.perf_counter()
            for _ in range(n):
                step()
            jax.block_until_ready([o._data for o in outs])
            return (time.perf_counter() - t0) / n

        # first warm-up step traces + compiles every bucket program —
        # its wall time is the arm's compile_ms witness
        t0 = time.perf_counter()
        step()
        jax.block_until_ready([o._data for o in outs])
        compile_ms = (time.perf_counter() - t0) * 1e3
        for _ in range(max(1, args.warmup) - 1):
            step()
        jax.block_until_ready([o._data for o in outs])
        per_step = min(timed_block(blocks) for _ in range(3))
        # per-step latency distribution (headline arm only — the extra
        # block of steps is not free on a bandwidth-bound host): host
        # wall time of each push+pull pair with one block at the end
        # (bandwidth-bound on CPU — same caveat as the mean)
        hist = None
        if want_latency:
            hist = _step_hist()
            for _ in range(blocks):
                t_s = time.perf_counter()
                step()
                hist.observe((time.perf_counter() - t_s) * 1e3)
            jax.block_until_ready([o._data for o in outs])
        probe = float(outs[0].asnumpy().ravel()[0])
        if not np.isfinite(probe):
            raise SystemExit("bench: non-finite weights in kvstore loop")
        return per_step, kv, {"compile_ms": compile_ms, "hist": hist}

    eager_dt, _, _ = run(False, False)
    fused_dt, kv, lat = run(True, False, want_latency=True)
    eager2_dt, _, _ = run(False, True)
    fused2_dt, kvc, _ = run(True, True)
    # push (grad bytes in, per device stream) + pull (weight bytes out)
    step_bytes = total_bytes * (ndev + 1)
    gbps = lambda dt: step_bytes / dt / 1e9
    st = kv._engine.stats
    # streaming flush dispatches several chunks per step — buckets per
    # step is the total over the run divided by steps (pushes of the
    # full keyset)
    n_steps = st["keys"] // len(keys)
    buckets_per_step = round(st["buckets"] / max(n_steps, 1))
    # eager per key: ndev compressions (2bit arm) + (ndev-1) adds + 1
    # updater apply; bucketed: one program per bucket
    eager_disp = len(keys) * (ndev * 1 + (ndev - 1) + 1)
    dev = jax.devices()[0]
    mh = bench_kvstore_multihost(args) if args.kv_hosts > 1 else {
        "kvstore_hosts": 1, "crosshost_bytes_per_step": 0}
    return {
        "metric": "kvstore_push_pull_gbps",
        "value": round(gbps(fused_dt), 2),
        "unit": "GB/s",
        "device_kind": dev.device_kind,
        "num_keys": len(keys),
        "ndev": ndev,
        "param_bytes": total_bytes,
        "eager_gbps": round(gbps(eager_dt), 2),
        "compressed_gbps": round(gbps(fused2_dt), 2),
        "eager_compressed_gbps": round(gbps(eager2_dt), 2),
        "speedup_vs_eager": round(eager_dt / fused_dt, 2),
        "speedup_vs_eager_2bit": round(eager2_dt / fused2_dt, 2),
        # logical wire ratio (f32 -> 2-bit); nominal by construction —
        # the local store never materializes packed bytes
        "kvstore_compress_ratio": 32 / 2.0,
        "bucket_count": buckets_per_step,
        "mean_bucket_occupancy": round(st["keys"] / max(st["buckets"], 1), 2),
        "bigarray_bound_bytes": kvstore_fused.bucket_byte_cap(),
        "dispatches_per_step": {"eager_2bit": eager_disp,
                                "bucketed": buckets_per_step},
        **_latency_fields(lat["hist"], lat["compile_ms"]),
        **mh,
    }


def bench_kvstore_multihost(args):
    """Multi-host arm of ``--mode kvstore``: spawn a ``--kv-hosts``-
    process kvstore='tpu' world (tools/run_multihost.py env contract,
    CPU jax.distributed backend) pushing a bucketed 2-bit key set, and
    report what travels per step. CPU-container convention (CHANGES.md):
    the numbers that matter are the dispatch-count witnesses and
    ``crosshost_bytes_per_step`` — wall time on a 1-core host measures
    process contention, not the collective. On this backend the engine
    uses the host transport (2 launches + 1 coordination-service
    allgather per bucket); a real pod rides GSPMD at 1 launch.

    Runs the world TWICE — backward-overlapped (default) vs
    ``MXNET_KVSTORE_OVERLAP=0`` serial — under a bucket cap small
    enough that the streaming flush engages, and GATES the A/B
    (docs/KVSTORE.md "Overlapped push"): the overlapped arm must
    dispatch no more programs per step than serial (overlap reorders
    work, it never adds any) and its overlap witness must actually
    fire; either failure is a SystemExit, not a report field."""
    import os
    import subprocess
    import sys as _sys
    root = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)

    def arm(overlap):
        proc = subprocess.run(
            [_sys.executable,
             os.path.join(root, "tools", "run_multihost.py"),
             "-n", str(args.kv_hosts),
             # cap = the largest key (256 KiB): full buckets stream out
             # mid-push, the partial tail rides the sync point
             "--env", "MXNET_KVSTORE_BIGARRAY_BOUND=262144",
             "--env", "MXNET_KVSTORE_OVERLAP=%d" % overlap, "--",
             _sys.executable, os.path.join(root, "bench.py"),
             "--mode", "kvstore-mh-worker", "--iters", str(args.iters),
             "--batch", str(args.batch)],
            env=env, capture_output=True, text=True, timeout=600)
        if proc.returncode != 0:
            raise SystemExit("bench: multi-host kvstore arm failed:\n%s"
                             % proc.stderr[-2000:])
        line = next(l for l in proc.stdout.splitlines()
                    if l.startswith("{") and "kvstore_hosts" in l)
        return json.loads(line)

    ov, ser = arm(1), arm(0)
    if ov["kvstore_overlap_dispatches_per_step"] <= 0:
        raise SystemExit(
            "bench: overlap witness never fired — no bucket collective "
            "was dispatched before the final backward bucket landed")
    if ser["kvstore_overlap_dispatches_per_step"] != 0:
        raise SystemExit("bench: MXNET_KVSTORE_OVERLAP=0 arm still "
                         "ticked the overlap witness")
    if ov["kvstore_mh_dispatches_per_step"] > \
            ser["kvstore_mh_dispatches_per_step"]:
        raise SystemExit(
            "bench: overlapped push dispatched MORE programs per step "
            "than serial (%.2f > %.2f) — overlap must reorder work, "
            "not add any" % (ov["kvstore_mh_dispatches_per_step"],
                             ser["kvstore_mh_dispatches_per_step"]))
    ov["kvstore_mh_serial_dispatches_per_step"] = \
        ser["kvstore_mh_dispatches_per_step"]
    return ov


def bench_kvstore_mh_worker(args):
    """One rank of the multi-host kvstore arm (spawned by
    bench_kvstore_multihost under the MXTPU_* env contract; also runs
    standalone as a single-process world). Rank 0 prints the JSON."""
    import mxnet_tpu as mx
    from mxnet_tpu import nd, profiler, telemetry

    kv = mx.kv.create("tpu")
    kv.set_gradient_compression({"type": "2bit", "threshold": 0.5})
    kv.set_optimizer(mx.optimizer.SGD(learning_rate=0.05, momentum=0.9,
                                      wd=1e-4,
                                      rescale_grad=1.0 / args.batch))
    shapes = [(256, 256), (512, 128), (1000,), (64, 3, 3, 3), (256,)]
    keys = ["mh_p%d" % i for i in range(len(shapes))]
    rng = np.random.RandomState(0)          # same init on every rank
    for k, s in zip(keys, shapes):
        kv.init(k, nd.array(rng.normal(0, 0.05, s).astype(np.float32)))
    grng = np.random.RandomState(1 + kv.rank)   # per-rank gradients

    def step():
        kv.push(keys, [[nd.array(grng.normal(0, 0.01, s)
                                 .astype(np.float32))] for s in shapes])
    step()                                  # warmup: trace + compile
    kv._sync_engine()     # land the warmup's pipelined applies before
    steps = max(4, min(args.iters, 16))     # snapshotting the counters
    xb = telemetry.REGISTRY.get("kvstore_tpu_crosshost_bytes")
    wit = telemetry.REGISTRY.get("kvstore_overlap_dispatches")
    d0, x0, w0 = (profiler.DEVICE_DISPATCHES.value, xb.value,
                  wit.value)
    for _ in range(steps):
        step()
    kv._sync_engine()
    kv.barrier()
    if kv.rank == 0:
        print(json.dumps({
            "kvstore_hosts": kv.num_workers,
            "crosshost_bytes_per_step":
                int((xb.value - x0) / steps),
            "kvstore_mh_dispatches_per_step":
                round((profiler.DEVICE_DISPATCHES.value - d0) / steps, 2),
            "kvstore_overlap_dispatches_per_step":
                round((wit.value - w0) / steps, 2),
            "kvstore_mh_transport":
                "gspmd" if kv._gspmd_ok else "host",
            "kvstore_mh_keys": len(keys),
            "kvstore_mh_steps": steps,
        }))


def bench_dlrm_partition(args):
    """Multi-host arm of ``--mode dlrm``: spawn a ``--dlrm-hosts``-
    process kvstore='tpu' world where the stacked table row-partitions
    ACROSS hosts (docs/EMBEDDING.md "Multi-host partitioning") and GATE
    the pod-partitioning acceptance criteria: resident table bytes per
    host must scale as 1/W and the cross-host row_sparse apply must
    stay at ONE sparse dispatch per step (the replicated host transport
    needs two). Either failure is a SystemExit, not a report field."""
    import os
    import subprocess
    import sys as _sys
    root = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [_sys.executable, os.path.join(root, "tools", "run_multihost.py"),
         "-n", str(args.dlrm_hosts), "--",
         _sys.executable, os.path.join(root, "bench.py"),
         "--mode", "dlrm-part-worker", "--iters", str(args.iters)],
        env=env, capture_output=True, text=True, timeout=600)
    if proc.returncode != 0:
        raise SystemExit("bench: multi-host dlrm arm failed:\n%s"
                         % proc.stderr[-2000:])
    line = next(l for l in proc.stdout.splitlines()
                if l.startswith("{") and "dlrm_hosts" in l)
    out = json.loads(line)
    W = out["dlrm_hosts"]
    if not out["dlrm_partitioned"]:
        raise SystemExit("bench: table did not partition in a %d-host "
                         "world" % W)
    if out["table_bytes_per_host_ratio"] > 1.0 / W + 1e-6:
        raise SystemExit(
            "bench: table_bytes_per_host_ratio %.3f > 1/%d — the slab "
            "did not replace the replicated table"
            % (out["table_bytes_per_host_ratio"], W))
    if out["crosshost_sparse_dispatches_per_step"] != 1:
        raise SystemExit(
            "bench: partitioned sparse apply took %.2f dispatches/step "
            "(want exactly 1 — the single cross-host launch)"
            % out["crosshost_sparse_dispatches_per_step"])
    return out


def bench_dlrm_part_worker(args):
    """One rank of the pod-partitioned DLRM arm (spawned by
    bench_dlrm_partition under the MXTPU_* env contract). Rank 0
    prints the JSON line the parent parses and gates on."""
    import mxnet_tpu as mx
    from mxnet_tpu import nd, autograd, telemetry
    from mxnet_tpu.embedding import ShardedEmbedding
    from mxnet_tpu.embedding.engine import SPARSE_DISPATCHES
    from mxnet_tpu.embedding.lookup import LOOKUPS

    V, D, F, B = 64, 8, 4, 8
    kv = mx.kv.create("tpu")
    kv.set_optimizer(mx.optimizer.SGD(learning_rate=0.05,
                                      lazy_update=True,
                                      rescale_grad=1.0 / B))
    blk = ShardedEmbedding(F * V, D)
    blk.initialize()
    tbl = telemetry.REGISTRY.get("embedding_table_bytes_per_host")
    a2a = telemetry.REGISTRY.get("embedding_alltoall_bytes")
    key = blk.attach_to_kvstore(kv)
    part = kv._partitioned.get(key)
    rng = np.random.RandomState(11 + kv.rank)   # per-rank index stream
    offs = (np.arange(F) * V)[None, :]

    def step():
        idx = np.minimum(rng.zipf(1.2, size=(B, F)) - 1, V - 1) + offs
        with autograd.record():
            out = blk(nd.array(idx))
        out._grad = nd.array(rng.normal(0, 1, out.shape)
                             .astype(np.float32))
        blk.sparse_push(kv, key=key)

    step()                                  # warmup: trace + compile
    steps = max(4, min(args.iters, 12))
    s0, l0, a0 = SPARSE_DISPATCHES.value, LOOKUPS.value, a2a.value
    for _ in range(steps):
        step()
    kv.barrier()
    if kv.rank == 0:
        print(json.dumps({
            "dlrm_hosts": kv.num_workers,
            "dlrm_partitioned": part is not None,
            "table_bytes_per_host_ratio":
                round(tbl.value / (F * V * D * 4), 3),
            "crosshost_sparse_dispatches_per_step":
                round((SPARSE_DISPATCHES.value - s0) / steps, 2),
            "crosshost_lookup_dispatches_per_step":
                round((LOOKUPS.value - l0) / steps, 2),
            "embedding_alltoall_bytes_per_step":
                int((a2a.value - a0) / steps),
        }))


def bench_dlrm(args):
    """Recommendation-scale training (mx.embedding, docs/EMBEDDING.md):
    an embedding-dominated DLRM-style step — F categorical features
    share one stacked (F*V, D) ``ShardedEmbedding`` table via
    per-feature index offsets, indices drawn zipf(1.2) so traffic is
    heavy-tailed (a few hot rows, a long cold tail, ragged unique-row
    counts every step — the retrace stressor). Each step is ONE compiled
    lookup dispatch (B*F is power-of-two by construction, so no unpad
    slice) plus ONE compiled sparse-apply dispatch through ``kv.push``;
    ``sparse_dispatches_per_step <= 2`` and zero steady-state retraces
    across the ragged batches are asserted, not just reported. The
    parity arm replays the identical gradient stream through the EAGER
    row_sparse path (bucketing off) and compares final tables at
    rtol 2e-5 — the compiled pipeline must train the same model."""
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import nd, autograd
    from mxnet_tpu.embedding import ShardedEmbedding
    from mxnet_tpu.embedding.lookup import LOOKUPS, LOOKUP_RETRACES
    from mxnet_tpu.embedding.engine import (SPARSE_DISPATCHES,
                                            SPARSE_RETRACES)
    from mxnet_tpu import telemetry, profiler

    V, D, F, B = (args.dlrm_vocab, args.dlrm_dim,
                  args.dlrm_features, args.dlrm_batch)
    if (B * F) & (B * F - 1):
        raise SystemExit("bench: --dlrm-batch * --dlrm-features must be "
                         "a power of two (single-dispatch lookup)")
    rng = np.random.RandomState(7)
    steps = max(4, args.iters)
    # per-step (B, F) zipf indices, offset feature f into its own V rows
    offs = (np.arange(F) * V)[None, :]
    batches = [np.minimum(rng.zipf(1.2, size=(B, F)) - 1, V - 1) + offs
               for _ in range(args.warmup + steps)]
    upstream = [rng.normal(0, 1, (B, F, D)).astype(np.float32)
                for _ in range(args.warmup + steps)]
    w0 = rng.normal(0, 0.05, (F * V, D)).astype(np.float32)

    def run(bucketed):
        blk = ShardedEmbedding(F * V, D)
        blk.initialize()
        kv = mx.kv.create("local")
        kv.set_bucketing(bucketed)
        kv.set_optimizer(mx.optimizer.SGD(learning_rate=0.05,
                                          lazy_update=True,
                                          rescale_grad=1.0 / B))
        blk.attach_to_kvstore(kv)
        key = "embedding:%s" % blk.weight.name
        # both arms start from the same table
        kv._store[key]._set_data(jax.numpy.asarray(w0))

        def step(i):
            with autograd.record():
                out = blk(nd.array(batches[i]))
                # stand-in for the dense interaction tower: a weighted
                # sum whose gradient w.r.t. the lookup is upstream[i]
                loss = (out * nd.array(upstream[i])).sum()
            loss.backward()
            blk.sparse_push(kv, key=key)
        return blk, kv, key, step

    # -- compiled arm ---------------------------------------------------
    blk, kv, key, step = run(bucketed=True)
    t0 = time.perf_counter()
    step(0)
    jax.block_until_ready(kv._store[key]._data)
    compile_ms = (time.perf_counter() - t0) * 1e3
    for i in range(1, args.warmup):
        step(i)
    jax.block_until_ready(kv._store[key]._data)
    l0, s0 = LOOKUPS.value, SPARSE_DISPATCHES.value
    lr0, sr0 = LOOKUP_RETRACES.value, SPARSE_RETRACES.value
    hist = _step_hist()
    t0 = time.perf_counter()
    for i in range(steps):
        t_s = time.perf_counter()
        step(args.warmup + i)
        hist.observe((time.perf_counter() - t_s) * 1e3)
    jax.block_until_ready(kv._store[key]._data)
    dt = time.perf_counter() - t0
    retraces = (LOOKUP_RETRACES.value - lr0) + (SPARSE_RETRACES.value - sr0)
    sparse_per_step = (SPARSE_DISPATCHES.value - s0) / steps
    lookup_per_step = (LOOKUPS.value - l0) / steps
    if retraces:
        raise SystemExit("bench: %d embedding retraces across ragged "
                         "measured steps — the runtime/static split "
                         "leaked a shape into a trace" % retraces)
    if sparse_per_step > 2:
        raise SystemExit("bench: %.1f sparse dispatches/step > 2" %
                         sparse_per_step)
    compiled_w = np.asarray(kv._store[key]._data)

    # -- parity arm: identical stream through the EAGER rsp path --------
    _, kv_e, key_e, step_e = run(bucketed=False)
    for i in range(args.warmup + steps):
        step_e(i)
    eager_w = np.asarray(kv_e._store[key_e]._data)
    err = np.abs(compiled_w - eager_w).max() / max(
        np.abs(eager_w).max(), 1e-12)
    if err > 2e-5:
        raise SystemExit("bench: compiled-vs-eager sparse training "
                         "diverged (rel err %.2e > 2e-5)" % err)

    hbm = telemetry.REGISTRY.get("embedding_hbm_bytes")
    dev = jax.devices()[0]
    mh = bench_dlrm_partition(args) if args.dlrm_hosts > 1 else {
        "dlrm_hosts": 1, "table_bytes_per_host_ratio": 1.0,
        "crosshost_sparse_dispatches_per_step": 0}
    return {
        "metric": "dlrm_lookups_per_sec",
        "value": round(B * F * steps / dt, 1),
        "unit": "lookups/s",
        "device_kind": dev.device_kind,
        "dlrm_table_rows": F * V,
        "dlrm_dim": D,
        "dlrm_features": F,
        "dlrm_batch": B,
        "dlrm_steps": steps,
        "dlrm_lookups_per_sec": round(B * F * steps / dt, 1),
        "lookup_dispatches_per_step": round(lookup_per_step, 2),
        "sparse_dispatches_per_step": round(sparse_per_step, 2),
        "embedding_retraces": retraces,
        "embedding_hbm_bytes": int(hbm.value),
        "dlrm_parity_rel_err": float(err),
        **_latency_fields(hist, compile_ms),
        **mh,
    }


def bench_fit(args):
    """Module-fit step witnesses: the single-launch fused fit step
    (module/fused_fit.py) vs the eager fwd_bwd + bucketed-kvstore pair
    on a ResNet-50 fit configuration (SGD momentum + wd, device
    kvstore, Accuracy metric — the Module path's default shape), plus
    two fused-optimizer acceptance arms: f32 Adam and bf16
    multi-precision Adam (f32 masters + dynamic loss scaler inside the
    donated program; docs/TRAINING.md "Mixed precision"). Both must
    hold train_dispatches_per_step == 1, and the bf16 fit program must
    report fewer bytes_accessed than the f32 one — gated only on
    backends with native bf16 compute (XLA CPU emulates bf16 in f32
    and reports the opposite; the JSON carries a note instead).

    The headline numbers are hardware-independent launch/sync counters,
    not wall clock: ``train_dispatches_per_step`` (profiler
    DEVICE_DISPATCHES delta per step — fused target ≤ 2, eager ~32) and
    ``host_syncs_per_step`` (metric-layer blocking readbacks — fused
    target 0 between Speedometer/epoch boundaries). On the 1-core CPU
    container both arms sit at the memory-bandwidth floor so step_ms
    compresses toward 1x; what a launch costs on the attached chip is
    not measured yet."""
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import models, nd
    from mxnet_tpu import metric as metric_mod
    from mxnet_tpu import profiler

    from mxnet_tpu import telemetry

    image_shape = tuple(int(x) for x in args.fit_image_shape.split(","))
    batch = args.fit_batch
    steps = args.fit_steps
    syms = {dt: models.get_symbol("resnet", num_classes=1000,
                                  num_layers=args.num_layers,
                                  image_shape=image_shape, dtype=dt)
            for dt in ("float32", "bfloat16")}
    rng = np.random.RandomState(0)
    c, h, w = image_shape
    X = rng.uniform(-1, 1, (batch, c, h, w)).astype(np.float32)
    y = rng.randint(0, 1000, (batch,)).astype(np.float32)

    # arm -> (fused?, optimizer, optimizer_params, train dtype).  The
    # adam and bf16+MP arms are the PR's acceptance witnesses: strict
    # train_dispatches_per_step == 1, and the bf16 program must touch
    # fewer bytes than the f32 one (telemetry.programs cost analysis).
    sgd_params = {"learning_rate": 0.05, "momentum": 0.9, "wd": 1e-4}
    adam_params = {"learning_rate": 1e-3, "wd": 1e-4}
    # "fused" runs with the in-launch numerics sentinels ON (the
    # default); "fused_nosent" is the identical config with
    # MXNET_SENTINEL_NUMERICS=0 — the pair yields sentinel_overhead_pct
    # and the hard gate that the witnesses add ZERO dispatches/syncs
    arm_cfg = {
        "eager": (False, "sgd", sgd_params, "float32", True),
        "fused": (True, "sgd", sgd_params, "float32", True),
        "fused_nosent": (True, "sgd", sgd_params, "float32", False),
        "fused_adam": (True, "adam", adam_params, "float32", True),
        "fused_bf16": (True, "adam",
                       dict(adam_params, multi_precision=True),
                       "bfloat16", True),
    }

    arms = {}
    for arm, (fused, opt, opt_params, train_dtype,
              sentinels) in arm_cfg.items():
        prev_sent = os.environ.get("MXNET_SENTINEL_NUMERICS")
        os.environ["MXNET_SENTINEL_NUMERICS"] = "1" if sentinels else "0"
        n_programs = len(telemetry.programs(analyze=False))
        mod = mx.Module(syms[train_dtype])
        mod._fused_fit_enabled = fused
        mod.bind(data_shapes=[("data", X.shape)],
                 label_shapes=[("softmax_label", (batch,))])
        mod.init_params(mx.init.Xavier(rnd_type="gaussian",
                                       factor_type="in", magnitude=2))
        mod.init_optimizer(kvstore=mx.kv.create("device"), optimizer=opt,
                           optimizer_params=dict(opt_params))
        m = metric_mod.Accuracy()
        batch_nd = mx.io.DataBatch(data=[nd.array(X)], label=[nd.array(y)])

        def one_step():
            mod.fit_step(batch_nd, m)
            mod.update_metric(m, batch_nd.label)

        def block():
            mod._fit_sync()     # waits on a trainable param (step output)

        t_c = time.perf_counter()
        one_step()                       # compile + warm
        block()
        compile_ms = (time.perf_counter() - t_c) * 1e3
        d0 = profiler.DEVICE_DISPATCHES.value
        h0 = metric_mod.HOST_SYNCS.value
        hist = _step_hist()
        t0 = time.perf_counter()
        for _ in range(steps):
            t_s = time.perf_counter()
            one_step()
            hist.observe((time.perf_counter() - t_s) * 1e3)
        block()
        dt = time.perf_counter() - t0
        # capture the loop deltas BEFORE the boundary get() below — that
        # readback is the scheduled Speedometer-style sync, not a
        # per-batch one
        d_steps = profiler.DEVICE_DISPATCHES.value - d0
        h_steps = metric_mod.HOST_SYNCS.value - h0
        _name, val = m.get()             # boundary readback (liveness)
        if not np.isfinite(val):
            raise SystemExit("bench: non-finite fit metric (%s arm)" % arm)
        arms[arm] = {
            "dispatches_per_step": round(d_steps / steps, 2),
            "host_syncs_per_step": round(h_steps / steps, 2),
            "step_ms": round(dt / steps * 1000, 1),
            "train_dtype": train_dtype,
            "fused_optimizer": (type(mod._optimizer).__name__
                                if fused and mod._fused_fit is not None
                                else None),
            **_latency_fields(hist, compile_ms),
        }
        if fused and mod._fused_fit is None:
            raise SystemExit("bench: %s arm fell back to eager — "
                             "eligibility regression" % arm)
        # the fit program's compiler-reported cost (bytes moved is the
        # bf16 win on an HBM-bound model; flops feed mfu_measured)
        fit_rows = [r for r in telemetry.programs()[n_programs:]
                    if r["site"] == "fit_step"
                    and r.get("bytes_accessed")]
        arms[arm]["bytes_accessed"] = (
            max(r["bytes_accessed"] for r in fit_rows) if fit_rows
            else None)
        if arm == "fused_bf16":
            scaler = getattr(mod, "_loss_scaler", None)
            if scaler is not None:
                scaler.publish()
                arms[arm]["loss_scale_skips"] = scaler.skips
            else:
                arms[arm]["loss_scale_skips"] = None
        if prev_sent is None:
            os.environ.pop("MXNET_SENTINEL_NUMERICS", None)
        else:
            os.environ["MXNET_SENTINEL_NUMERICS"] = prev_sent
    # acceptance: the fused Adam arms are SINGLE-launch, f32 and bf16+MP
    for arm in ("fused_adam", "fused_bf16"):
        if arms[arm]["dispatches_per_step"] != 1:
            raise SystemExit(
                "bench: %s arm train_dispatches_per_step = %s (want 1)"
                % (arm, arms[arm]["dispatches_per_step"]))
    # acceptance: the in-launch sentinels ride the SAME program — with
    # them on the fused arm must stay single-launch and sync-free, and
    # the on/off dispatch counts must be IDENTICAL (the deterministic
    # overhead convention; wall clock is reported, not gated, because
    # the 1-core CPU container's p50 jitter exceeds any real delta)
    if arms["fused"]["dispatches_per_step"] != 1:
        raise SystemExit(
            "bench: sentinels-on fused arm train_dispatches_per_step = "
            "%s (want 1)" % arms["fused"]["dispatches_per_step"])
    if arms["fused"]["host_syncs_per_step"] != 0:
        raise SystemExit(
            "bench: sentinels-on fused arm host_syncs_per_step = %s "
            "(want 0)" % arms["fused"]["host_syncs_per_step"])
    if arms["fused"]["dispatches_per_step"] \
            != arms["fused_nosent"]["dispatches_per_step"]:
        raise SystemExit(
            "bench: sentinel witnesses changed the dispatch count "
            "(%s on vs %s off)"
            % (arms["fused"]["dispatches_per_step"],
               arms["fused_nosent"]["dispatches_per_step"]))
    p50_off = arms["fused_nosent"]["step_ms_p50"]
    sentinel_overhead_pct = (
        round((arms["fused"]["step_ms_p50"] - p50_off) / p50_off * 100, 2)
        if p50_off else None)
    from mxnet_tpu.telemetry import sentinel as _sentinel
    sentinel_alerts = int(
        _sentinel.SENTINEL_ALERTS.value
        + sum(c.value for c in _sentinel.SENTINEL_ALERTS.children()))
    dev = jax.devices()[0]
    # XLA CPU upcasts bf16 compute to f32 (a bf16 matmul *reports more*
    # bytes accessed than the f32 one), so the fewer-bytes acceptance
    # gate is meaningful only on backends with native low-precision
    # compute; on the CPU container the values are reported, not gated
    ba_f32 = arms["fused_adam"]["bytes_accessed"]
    ba_bf16 = arms["fused_bf16"]["bytes_accessed"]
    bytes_note = None
    if jax.default_backend() == "cpu":
        bytes_note = ("bytes_accessed gate skipped: XLA CPU emulates "
                      "bf16 in f32 (docs/TRAINING.md Mixed precision)")
    elif ba_f32 and ba_bf16 and not ba_bf16 < ba_f32:
        raise SystemExit(
            "bench: bf16 fit program moves %d bytes >= f32's %d — "
            "low-precision regression" % (ba_bf16, ba_f32))
    return {
        "metric": "train_dispatches_per_step",
        "value": arms["fused"]["dispatches_per_step"],
        "unit": "launches/step",
        "device_kind": dev.device_kind,
        "config": "resnet%d b%d %s sgd-mom+adam(f32/bf16-mp) kv=device "
                  "2bit=off" % (args.num_layers, batch,
                                args.fit_image_shape),
        "train_dispatches_per_step": {
            a: arms[a]["dispatches_per_step"] for a in arms},
        "host_syncs_per_step": {
            a: arms[a]["host_syncs_per_step"] for a in arms},
        "fit_step_ms": {a: arms[a]["step_ms"] for a in arms},
        "fused_optimizer": {a: arms[a]["fused_optimizer"] for a in arms},
        "train_dtype": {a: arms[a]["train_dtype"] for a in arms},
        "train_bytes_accessed": {a: arms[a]["bytes_accessed"]
                                 for a in arms},
        **({"train_bytes_note": bytes_note} if bytes_note else {}),
        "loss_scale_skips": arms["fused_bf16"]["loss_scale_skips"],
        "sentinel_overhead_pct": sentinel_overhead_pct,
        "sentinel_alerts": sentinel_alerts,
        "step_ms_p50": arms["fused"]["step_ms_p50"],
        "step_ms_p99": arms["fused"]["step_ms_p99"],
        "compile_ms": arms["fused"]["compile_ms"],
    }


def bench_checkpoint(args):
    """mx.checkpoint witnesses: async vs blocking save latency, bytes
    per checkpoint, and — the headline — the training-thread BLOCK time
    of an async save (``checkpoint_block_ms``: device→host snapshot +
    enqueue; serialization and IO run on the writer thread).

    Acceptance shape (docs/CHECKPOINT.md): ``checkpoint_block_ms`` p50
    stays under the fit-step p50 — checkpointing never costs a full
    step — and the fused-step / bucketed-kvstore retrace witnesses stay
    flat with checkpointing enabled. Measured on the bench_fit model
    (ResNet fit config, 2-bit compression ON so residual capture is
    priced in)."""
    import os
    import shutil
    import tempfile

    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import models, nd, telemetry
    from mxnet_tpu import checkpoint as ckpt

    image_shape = tuple(int(x) for x in args.fit_image_shape.split(","))
    batch = args.fit_batch
    sym = models.get_symbol("resnet", num_classes=1000,
                            num_layers=args.num_layers,
                            image_shape=image_shape, dtype="float32")
    rng = np.random.RandomState(0)
    c, h, w = image_shape
    X = rng.uniform(-1, 1, (batch, c, h, w)).astype(np.float32)
    y = rng.randint(0, 1000, (batch,)).astype(np.float32)
    mod = mx.Module(sym, compression_params={"type": "2bit",
                                             "threshold": 0.5})
    mod.bind(data_shapes=[("data", X.shape)],
             label_shapes=[("softmax_label", (batch,))])
    mod.init_params(mx.init.Xavier(rnd_type="gaussian",
                                   factor_type="in", magnitude=2))
    mod.init_optimizer(kvstore=mx.kv.create("device"), optimizer="sgd",
                       optimizer_params={"learning_rate": 0.05,
                                         "momentum": 0.9, "wd": 1e-4})
    batch_nd = mx.io.DataBatch(data=[nd.array(X)], label=[nd.array(y)])
    mod.fit_step(batch_nd)               # compile + warm
    mod._fit_sync()
    r_fit0 = telemetry.REGISTRY.get("fit_step_retraces").value
    r_kv0 = telemetry.REGISTRY.get("kvstore_bucket_retraces").value

    step_hist = _step_hist()
    for _ in range(args.fit_steps):
        t_s = time.perf_counter()
        mod.fit_step(batch_nd)
        step_hist.observe((time.perf_counter() - t_s) * 1e3)
    mod._fit_sync()

    tmp = tempfile.mkdtemp(prefix="mx-bench-ckpt-")
    n_saves = args.ckpt_saves
    save_hist = telemetry.REGISTRY.get("checkpoint_save_ms")
    bytes_ctr = telemetry.REGISTRY.get("checkpoint_bytes")
    try:
        mgr = ckpt.CheckpointManager(os.path.join(tmp, "ck"), module=mod,
                                     keep=2, install_preemption=False)
        # async arm: the training thread pays only the snapshot+enqueue
        block_ms, t_c = [], time.perf_counter()
        snap0, b0 = save_hist.snapshot(), bytes_ctr.value
        for i in range(n_saves):
            mod.fit_step(batch_nd)
            t0 = time.perf_counter()
            mgr.save(step=i + 1)
            block_ms.append((time.perf_counter() - t0) * 1e3)
        assert mgr.drain(600), "bench: checkpoint writer failed to drain"
        async_wall_ms = (time.perf_counter() - t_c) * 1e3
        async_save_p50 = telemetry.hist_quantile(
            save_hist.snapshot(), 0.5, since=snap0)
        per_save_bytes = (bytes_ctr.value - b0) // n_saves
        # blocking arm: serialize + write + fsync + rename inline
        sync_ms = []
        for i in range(n_saves):
            mod.fit_step(batch_nd)
            t0 = time.perf_counter()
            mgr.save(step=100 + i, block=True)
            sync_ms.append((time.perf_counter() - t0) * 1e3)
        mgr.close()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    block_ms.sort()
    sync_ms.sort()
    step_p50 = step_hist.quantile(0.5)
    block_p50 = block_ms[len(block_ms) // 2]
    retr_fit = telemetry.REGISTRY.get("fit_step_retraces").value - r_fit0
    retr_kv = telemetry.REGISTRY.get("kvstore_bucket_retraces").value \
        - r_kv0
    dev = jax.devices()[0]
    return {
        "metric": "checkpoint_block_ms",
        "value": _round_opt(block_p50),
        "unit": "ms",
        "device_kind": dev.device_kind,
        "config": "resnet%d b%d %s sgd-mom kv=device 2bit=on" % (
            args.num_layers, batch, args.fit_image_shape),
        "checkpoint_save_ms": {
            "async": _round_opt(async_save_p50),
            "blocking": _round_opt(sync_ms[len(sync_ms) // 2]),
        },
        "checkpoint_bytes": int(per_save_bytes),
        "checkpoint_async_wall_ms": _round_opt(async_wall_ms),
        "fit_step_ms_p50": _round_opt(step_p50),
        "block_lt_step_p50": bool(step_p50 is None
                                  or block_p50 < step_p50),
        "fit_step_retraces_delta": int(retr_fit),
        "kvstore_bucket_retraces_delta": int(retr_kv),
        "saves_per_arm": n_saves,
    }


def bench_serving(args):
    """mx.serving throughput: concurrent clients against the in-process
    ModelServer (dynamic micro-batching + bucket padding over a jitted
    ResNet forward). The headline is ``serving_qps`` — single-example
    requests served per second end to end (queue + batcher + device),
    NOT the raw batched-forward img/s, so it prices the batching control
    plane the way a traffic-serving deployment would see it."""
    import threading

    import jax
    import numpy as np
    from mxnet_tpu import models
    from mxnet_tpu.serving import ModelServer

    image_shape = tuple(int(x) for x in args.image_shape.split(","))
    sym = models.get_symbol("resnet", num_classes=1000,
                            num_layers=args.num_layers,
                            image_shape=image_shape, dtype="float32")
    arg_shapes, _, aux_shapes = sym.infer_shape(
        data=(1,) + image_shape, softmax_label=(1,))
    rng = np.random.RandomState(0)
    params = {n: (rng.normal(0, 0.05, s).astype(np.float32))
              for n, s in zip(sym.list_arguments(), arg_shapes)
              if n not in ("data", "softmax_label")}
    auxs = {}
    for n, s in zip(sym.list_auxiliary_states(), aux_shapes):
        auxs[n] = (np.zeros(s, np.float32) if n.endswith("_mean")
                   else np.ones(s, np.float32))

    from mxnet_tpu import telemetry

    n_req = args.serving_requests
    # construction compiles every bucket on every replica (warmup=True):
    # its wall time is the serving arm's compile_ms witness
    t_c = time.perf_counter()
    srv = ModelServer(sym, params, auxs, {"data": image_shape},
                      num_replicas=args.serving_replicas,
                      max_batch_size=args.serving_max_batch,
                      max_latency_ms=args.serving_latency_ms,
                      queue_capacity=n_req + args.serving_max_batch)
    compile_ms = (time.perf_counter() - t_c) * 1e3
    telemetry.JIT_COMPILE_MS.observe(compile_ms)
    try:
        xs = [rng.uniform(-1, 1, image_shape).astype(np.float32)
              for _ in range(8)]
        # warmup already compiled every bucket; a short served burst
        # warms the control plane too — then zero the stats so the
        # occupancy-1 warmup batches don't bias the reported metrics
        for x in xs:
            srv.predict({"data": x})
        srv.drain(timeout=600)
        srv.reset_stats()
        # registry latency histogram: percentiles over THIS run come
        # from the delta against the post-warmup snapshot
        lat_hist = telemetry.REGISTRY.get("serving_request_ms")
        lat_snap0 = lat_hist.snapshot()

        futs = []
        lock = threading.Lock()
        t0 = time.perf_counter()

        def client(k):
            local = []
            for i in range(n_req // args.serving_clients):
                local.append(srv.submit({"data": xs[(k + i) % len(xs)]}))
            with lock:
                futs.extend(local)

        threads = [threading.Thread(target=client, args=(k,))
                   for k in range(args.serving_clients)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        for f in futs:
            f.result(timeout=600)
        dt = time.perf_counter() - t0
        st = srv.stats()
    finally:
        srv.stop()
    dev = jax.devices()[0]
    return {
        "metric": "serving_qps",
        "value": round(len(futs) / dt, 1),
        "unit": "req/s",
        "device_kind": dev.device_kind,
        "replicas": args.serving_replicas,
        "max_batch_size": args.serving_max_batch,
        "max_latency_ms": args.serving_latency_ms,
        "mean_batch_occupancy": round(st["batches"]["mean_occupancy"], 2)
        if st["batches"]["mean_occupancy"] else None,
        "latency_p50_ms": st["latency_ms"]["p50"],
        "latency_p99_ms": st["latency_ms"]["p99"],
        # serving's "step" is one request end to end: percentiles from
        # the serving_request_ms registry histogram, this run only
        "step_ms_p50": _round_opt(
            telemetry.hist_quantile(lat_hist.snapshot(), 0.5,
                                    since=lat_snap0)),
        "step_ms_p99": _round_opt(
            telemetry.hist_quantile(lat_hist.snapshot(), 0.99,
                                    since=lat_snap0)),
        "compile_ms": round(compile_ms, 1),
    }


def bench_decode(args):
    """mx.decode generative serving: continuous batching vs static
    (run-to-completion) batching over the paged-KV-cache decode engine
    (docs/DECODE.md).  Headline is ``decode_tokens_per_sec`` for the
    continuous arm; the structural witnesses are
    ``decode_dispatches_per_step`` (exactly 1 compiled launch per
    decode iteration), ``decode_retraces_steady_state`` (0 across
    ragged prompt/output lengths) and ``decode_steps_ratio_vs_static``
    (static steps / continuous steps — the dispatch-bound speedup; on
    the 1-core CPU container read the ratios, not wall times, per the
    CHANGES.md convention).  A reduced pallas-vs-xla A/B arm
    (MXNET_PAGED_ATTN_IMPL forced per run, docs/KERNELS.md) gates on
    the kernel arm keeping the same dispatch contract.

    A chunked-vs-unchunked A/B arm runs a long-prompt heavy-tailed
    mix through the engine at ``--decode-chunk`` vs an unchunked
    oracle compiled at ``--decode-seq`` (whole context in one chunk).
    Every iteration runs the ONE mixed step compiled at the engine's
    chunk width, so per-launch device work is ``capacity +
    chunk_width`` token rows **whether or not a prompt is in
    flight** — the unchunked oracle pays whole-context chunk compute
    on every decode step forever.  TTFT is therefore compared in
    launch-work units (``ttft_steps_p99 * (capacity + chunk_width)``)
    — the dispatch-count-convention stand-in for wall-clock TTFT on
    hardware, where raw iteration counts would reward fat launches
    the container can't time honestly.  Both arms' raw step counts
    are published next to the gate so nothing hides in the
    normalization."""
    import os

    import jax
    from mxnet_tpu import profiler, telemetry
    from mxnet_tpu.decode import DecodeEngine
    from mxnet_tpu.models import transformer

    cfg = dict(num_classes=args.decode_vocab, num_layers=args.decode_layers,
               d_model=args.decode_d_model, num_heads=args.decode_heads,
               seq_len=args.decode_seq)
    tsym = transformer.get_symbol(**cfg)
    arg_shapes, _, _ = tsym.infer_shape(data=(1, args.decode_seq),
                                        softmax_label=(args.decode_seq,))
    rng = np.random.RandomState(0)
    params = {n: rng.normal(0, 0.05, s).astype(np.float32)
              for n, s in zip(tsym.list_arguments(), arg_shapes)
              if n not in ("data", "softmax_label")}
    n_req = args.decode_requests
    # every request opens with the same system-prompt-style preamble
    # (the production shape prefix sharing exists for: identical
    # few-shot headers across the fleet) followed by a random tail
    sys_prompt = list(rng.randint(0, args.decode_vocab,
                                  args.decode_block_size + 1))
    prompts = [sys_prompt
               + list(rng.randint(0, args.decode_vocab,
                                  rng.randint(4,
                                              args.decode_prompt_max + 1)))
               for _ in range(n_req)]
    # heavy-tailed output lengths (many short, few near-max) — the
    # production shape continuous batching exists for; run-to-completion
    # pins every slot to its batch's longest member
    new_tokens = [4 + int((args.decode_gen_max - 4) * rng.uniform() ** 2)
                  for _ in range(n_req)]

    step_hist = telemetry.REGISTRY.get("decode_step_ms")

    def run(admission, impl=None, n=None, gen_cap=None, chunk=None,
            workload=None, spec_k=None, prefix=False):
        """One engine lifetime.  ``impl`` forces MXNET_PAGED_ATTN_IMPL
        for the whole run (the dispatch decision is baked in at trace
        time, so the env must cover engine construction + warmup);
        ``n``/``gen_cap`` shrink the workload for the interpret-mode
        pallas A/B arm, which is orders of magnitude slower off-TPU;
        ``chunk`` overrides the prefill chunk budget (the
        chunked-vs-unchunked arm), ``workload`` swaps in a different
        ``(prompts, new_tokens)`` mix, and ``spec_k``/``prefix`` arm
        draft-verify spans / COW prefix sharing (the speculative A/B
        arm) — both pinned explicitly so a stray env knob can never
        flip an arm's baseline."""
        ps, nt = (prompts, new_tokens) if workload is None else workload
        if n is not None:
            ps = ps[:n]
            nt = [min(m, gen_cap) for m in nt[:n]]
        prev = os.environ.get("MXNET_PAGED_ATTN_IMPL")
        if impl is not None:
            os.environ["MXNET_PAGED_ATTN_IMPL"] = impl
        try:
            t_c = time.perf_counter()
            eng = DecodeEngine(params, cfg, capacity=args.decode_capacity,
                               block_size=args.decode_block_size,
                               num_blocks=args.decode_blocks,
                               max_waiting=n_req + 1, admission=admission,
                               chunk_tokens=(chunk if chunk is not None
                                             else args.decode_chunk),
                               spec_k=(spec_k if spec_k is not None else 0),
                               prefix_cache=prefix, warmup=True)
            compile_ms = (time.perf_counter() - t_c) * 1e3
            try:
                snap0 = (step_hist.snapshot()
                         if step_hist is not None else None)
                d0 = profiler.DEVICE_DISPATCHES.value
                t0 = time.perf_counter()
                handles = [eng.submit(p, max_new_tokens=m)
                           for p, m in zip(ps, nt)]
                streams = [h.result(timeout=600) for h in handles]
                toks = sum(len(s) for s in streams)
                dt = time.perf_counter() - t0
                st = eng.stats()
                st["_tokens"] = toks
                st["_streams"] = streams
                st["_dt"] = dt
                st["_dispatches"] = profiler.DEVICE_DISPATCHES.value - d0
                st["_compile_ms"] = compile_ms
                if step_hist is not None and snap0 is not None:
                    st["_p50"] = telemetry.hist_quantile(
                        step_hist.snapshot(), 0.5, since=snap0)
                    st["_p99"] = telemetry.hist_quantile(
                        step_hist.snapshot(), 0.99, since=snap0)
            finally:
                eng.stop()
            return st
        finally:
            if impl is not None:
                if prev is None:
                    os.environ.pop("MXNET_PAGED_ATTN_IMPL", None)
                else:
                    os.environ["MXNET_PAGED_ATTN_IMPL"] = prev

    cont = run("continuous")
    static = run("static")
    # pallas-vs-xla A/B arm on a reduced workload (same engine
    # geometry).  Forcing impl=pallas off-TPU is legal because the
    # kernels run interpret=True anywhere; wall-clock is meaningless
    # there, so the gate is structural: the kernel arm must keep the
    # one-launch-per-step contract and stay retrace-free.
    n_ab = min(6, n_req)
    ab_xla = run("continuous", impl="xla", n=n_ab, gen_cap=6)
    ab_pallas = run("continuous", impl="pallas", n=n_ab, gen_cap=6)
    if (ab_pallas["dispatches_per_step"] != 1.0
            or ab_pallas["steady_state_retraces"] != 0):
        raise SystemExit(
            "decode pallas arm broke the dispatch contract: "
            "dispatches_per_step=%r (want 1.0), "
            "steady_state_retraces=%r (want 0)"
            % (ab_pallas["dispatches_per_step"],
               ab_pallas["steady_state_retraces"]))
    # chunked-vs-unchunked A/B arm (docstring): a long-prompt
    # heavy-tailed mix — many short prompts, a heavy tail reaching
    # most of the context window — served at the production chunk
    # budget vs an unchunked oracle whose every launch carries a
    # max_context-wide chunk stream
    ab_rng = np.random.RandomState(7)
    ck_prompts, ck_gens = [], []
    long_lo = max(args.decode_seq // 2, 8)
    long_hi = max(args.decode_seq - 12, long_lo + 1)
    for _ in range(min(10, n_req)):
        plen = (ab_rng.randint(long_lo, long_hi)
                if ab_rng.uniform() < 0.4 else ab_rng.randint(4, 13))
        ck_prompts.append(list(ab_rng.randint(0, args.decode_vocab,
                                              plen)))
        ck_gens.append(2 + int(ab_rng.randint(0, 5)))
    ck_wl = (ck_prompts, ck_gens)
    ab_chunked = run("continuous", workload=ck_wl)
    ab_unchunked = run("continuous", chunk=args.decode_seq,
                       workload=ck_wl)
    if (ab_chunked["dispatches_per_step"] != 1.0
            or ab_chunked["steady_state_retraces"] != 0):
        raise SystemExit(
            "decode chunked arm broke the dispatch contract: "
            "dispatches_per_step=%r (want 1.0), "
            "steady_state_retraces=%r (want 0)"
            % (ab_chunked["dispatches_per_step"],
               ab_chunked["steady_state_retraces"]))
    if ab_chunked["_streams"] != ab_unchunked["_streams"]:
        raise SystemExit("chunked arm diverged from the unchunked "
                         "full-prefill oracle (greedy streams differ)")

    # speculative A/B arm (docs/DECODE.md): the SAME heavy-tailed mix
    # with draft-verify spans on vs off (the `cont` arm IS the spec-off
    # baseline — identical engine geometry and workload).  Greedy
    # acceptance must keep the streams oracle-identical; the structural
    # gates pin the one-launch / zero-retrace contract; and
    # tokens_per_launch > 1 is the feature's existence proof — the
    # n-gram drafter must land SOME accepted spans on this mix.
    spec_on = run("continuous", spec_k=args.decode_spec_k, prefix=True)
    if spec_on["_streams"] != cont["_streams"]:
        raise SystemExit("speculative arm diverged from the "
                         "non-speculative oracle (greedy streams differ)")
    if (spec_on["dispatches_per_step"] != 1.0
            or spec_on["steady_state_retraces"] != 0):
        raise SystemExit(
            "decode speculative arm broke the dispatch contract: "
            "dispatches_per_step=%r (want 1.0), "
            "steady_state_retraces=%r (want 0)"
            % (spec_on["dispatches_per_step"],
               spec_on["steady_state_retraces"]))
    if not (spec_on["tokens_per_launch"] or 0) > 1.0:
        raise SystemExit(
            "decode speculative arm committed no extra tokens: "
            "tokens_per_launch=%r (want > 1.0; accept_rate=%r, "
            "proposed=%r)" % (spec_on["tokens_per_launch"],
                              spec_on["accept_rate"],
                              spec_on["spec_proposed"]))
    if not spec_on["cache"]["prefix_hit_blocks"] > 0:
        raise SystemExit(
            "prefix sharing never hit: every request carries the same "
            "system preamble, so later admissions must adopt trie "
            "blocks (prefix_hit_blocks=%r)"
            % spec_on["cache"]["prefix_hit_blocks"])

    def _ttft_work(st):
        # per-launch token rows: C decode rows + the compiled chunk
        # width every launch carries, prompt in flight or not
        return st["ttft_steps_p99"] * (args.decode_capacity
                                       + st["chunk_tokens"])

    if not _ttft_work(ab_chunked) < _ttft_work(ab_unchunked):
        raise SystemExit(
            "chunked prefill did not improve launch-work TTFT p99 "
            "under the long-prompt mix: chunked %r (steps %r x width "
            "%r) vs unchunked %r (steps %r x width %r)"
            % (_ttft_work(ab_chunked), ab_chunked["ttft_steps_p99"],
               args.decode_capacity + ab_chunked["chunk_tokens"],
               _ttft_work(ab_unchunked),
               ab_unchunked["ttft_steps_p99"],
               args.decode_capacity + ab_unchunked["chunk_tokens"]))
    # the mixed-step compiled program, recognized by its block-table
    # feed [capacity, table_width] (recorded arg_shapes truncate at 8
    # entries and the donated order puts the cache arrays first, so
    # the (C, 1) token input can fall outside the recorded prefix —
    # the block table survives both argument orders); bytes_accessed
    # is the donation acceptance witness — the donated step no longer
    # pays the whole-cache in+out copy
    fn_want = ("_fwd_eval_donated" if cont.get("cache_donation")
               else "_fwd_eval")
    table_w = -(-args.decode_seq // args.decode_block_size)
    step_rows = [p for p in telemetry.programs(site="executor")
                 if p["fn_name"] == fn_want
                 and any(s.endswith("[%d, %d]" % (args.decode_capacity,
                                                  table_w))
                         for s in p["arg_shapes"])]
    decode_bytes = max((p["bytes_accessed"] for p in step_rows
                        if p["bytes_accessed"] is not None), default=None)
    dev = jax.devices()[0]
    out = {
        "metric": "decode_tokens_per_sec",
        "value": round(cont["_tokens"] / cont["_dt"], 1),
        "unit": "tok/s",
        "device_kind": dev.device_kind,
        "config": {"layers": args.decode_layers,
                   "d_model": args.decode_d_model,
                   "heads": args.decode_heads, "vocab": args.decode_vocab,
                   "capacity": args.decode_capacity,
                   "block_size": args.decode_block_size,
                   "num_blocks": args.decode_blocks,
                   "requests": n_req},
        "decode_ttft_p99_ms": _round_opt(cont["ttft_p99_ms"]),
        "decode_cache_occupancy": _round_opt(cont["mean_cache_occupancy"]),
        "decode_slot_occupancy": _round_opt(
            cont["mean_slot_occupancy"] / args.decode_capacity
            if cont["mean_slot_occupancy"] else None),
        "decode_dispatches_per_step": _round_opt(
            cont["dispatches_per_step"]),
        "decode_dispatches_per_token": _round_opt(
            cont["_dispatches"] / cont["_tokens"]),
        "decode_retraces_steady_state": cont["steady_state_retraces"],
        "decode_preemptions": cont["preemptions"],
        "decode_steps": cont["steps"],
        "decode_chunk_tokens": cont["chunk_tokens"],
        "decode_prefill_chunks_per_iter": _round_opt(
            cont["prefill_chunks_per_iter"]),
        "decode_ttft_steps_p99": cont["ttft_steps_p99"],
        "decode_chunked_ttft_steps_p99": ab_chunked["ttft_steps_p99"],
        "decode_unchunked_ttft_steps_p99":
            ab_unchunked["ttft_steps_p99"],
        "decode_chunked_ttft_work_p99": _ttft_work(ab_chunked),
        "decode_unchunked_ttft_work_p99": _ttft_work(ab_unchunked),
        "decode_attn_impl": cont.get("attn_impl"),
        "decode_cache_donation": cont.get("cache_donation"),
        "decode_bytes_accessed": decode_bytes,
        "decode_pallas_dispatches_per_step": _round_opt(
            ab_pallas["dispatches_per_step"]),
        "decode_pallas_retraces_steady_state":
            ab_pallas["steady_state_retraces"],
        "decode_ab_tokens_equal":
            ab_pallas["_streams"] == ab_xla["_streams"],
        # speculative arm: stream identity is gated above; steps ratio
        # is the dispatch-bound speedup speculation buys on this mix
        "decode_spec_k": args.decode_spec_k,
        "decode_spec_impl": spec_on.get("spec_impl"),
        "decode_accept_rate": _round_opt(spec_on["accept_rate"]),
        "decode_tokens_per_launch": _round_opt(
            spec_on["tokens_per_launch"]),
        "decode_spec_steps_ratio": round(
            cont["steps"] / max(spec_on["steps"], 1), 2),
        "decode_prefix_hit_blocks":
            spec_on["cache"]["prefix_hit_blocks"],
        "static_tokens_per_sec": round(
            static["_tokens"] / static["_dt"], 1),
        "static_steps": static["steps"],
        # wall-clock speedup (noisy on the 1-core container) AND the
        # dispatch-count form: each step is one launch, so where
        # launches bound the time the step ratio is the tokens/s ratio
        "decode_speedup_vs_static": round(
            (cont["_tokens"] / cont["_dt"])
            / (static["_tokens"] / static["_dt"]), 2),
        "decode_steps_ratio_vs_static": round(
            static["steps"] / max(cont["steps"], 1), 2),
    }
    out["step_ms_p50"] = _round_opt(cont.get("_p50"))
    out["step_ms_p99"] = _round_opt(cont.get("_p99"))
    out["compile_ms"] = _round_opt(cont["_compile_ms"], 1)
    return out


def bench_fleet(args):
    """mx.fleet disaggregated serving (docs/FLEET.md): three arms.

    * **Routing A/B** — the SAME shared-prefix request mix (three
      request families, each opening with its own system preamble,
      interleaved round-robin the way a fleet actually sees traffic)
      through a two-replica ``FleetRouter`` under ``affinity`` vs
      ``least_loaded``.  Hard gate: the affinity arm's summed
      ``prefix_hit_blocks`` must be STRICTLY higher — co-locating a
      family on one replica converts every repeat preamble into trie
      hits, while spreading makes each replica re-prefill it.
    * **TP arm** — ``make_tp_engine(tensor_parallel=2)`` over the mp
      mesh must keep the decode contract intact (1 dispatch/iteration,
      0 steady-state retraces, greedy streams bit-identical to the
      single-device baseline) while its per-device cache bytes drop to
      <= 0.6x replicated — TP buys memory, never different math.
    * **Scale-up arm** — a COLD replica (``warmup=False``) joins the
      ring via ``add_replica`` (which AOT-warms BEFORE the replica is
      routable) and serves its first routed request with ZERO
      serve-time compiles (``steady_state_retraces == 0``).

    Wall-clock is meaningless for routing on the 1-core container; the
    headline is the hit-block ratio, the dispatch-count convention's
    stand-in for the TTFT win prefix affinity buys on hardware."""
    import os
    import sys
    if "jax" not in sys.modules \
            and os.environ.get("JAX_PLATFORMS") == "cpu":
        # standalone --mode fleet on the CPU container: the TP arm
        # needs >= 2 visible devices (same knob tests/conftest.py pins)
        flags = os.environ.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()
    import jax
    from mxnet_tpu import sharding
    from mxnet_tpu.decode import DecodeEngine
    from mxnet_tpu.fleet import (FleetRouter, make_tp_engine,
                                 per_device_cache_bytes)
    from mxnet_tpu.models import transformer

    cfg = dict(num_classes=args.decode_vocab,
               num_layers=args.decode_layers, d_model=16,
               num_heads=2, seq_len=args.decode_seq)
    ek = dict(capacity=4, block_size=args.decode_block_size,
              num_blocks=args.decode_blocks, chunk_tokens=8,
              warmup=True, prefix_cache=True)
    tsym = transformer.get_symbol(**cfg)
    shapes, _, _ = tsym.infer_shape(data=(1, args.decode_seq),
                                    softmax_label=(args.decode_seq,))
    rng = np.random.RandomState(0)
    params = {n: rng.normal(0, 0.05, s).astype(np.float32)
              for n, s in zip(tsym.list_arguments(), shapes)
              if n not in ("data", "softmax_label")}

    # three request FAMILIES (distinct system preambles spanning > 3
    # full cache blocks) interleaved round-robin: the shape
    # prefix-affinity routing exists for — without stickiness or
    # affinity, consecutive arrivals from one family land on different
    # replicas and every one re-prefills the preamble
    fam_rng = np.random.RandomState(11)
    preambles = [list(fam_rng.randint(0, args.decode_vocab,
                                      3 * args.decode_block_size + 1))
                 for _ in range(3)]
    requests = []
    for turn in range(4):
        for fam, pre in enumerate(preambles):
            requests.append(pre + list(fam_rng.randint(
                0, args.decode_vocab, 2 + fam + turn)))

    def run_router_arm(policy):
        engs = {"r0": DecodeEngine(params, cfg, **ek),
                "r1": DecodeEngine(params, cfg, **ek)}
        try:
            router = FleetRouter(policy=policy, sticky=False,
                                 trie_blocks=4096)
            for name, eng in engs.items():
                router.add_replica(name, eng)
            placements = []
            for toks in requests:
                name, eng = router.route(toks)
                placements.append(name)
                eng.generate(toks, max_new_tokens=4, timeout=300)
            hit_blocks = sum(
                e.stats()["cache"]["prefix_hit_blocks"]
                for e in engs.values())
            return {"hit_blocks": int(hit_blocks),
                    "spread": len(set(placements)),
                    "router": router.stats()}
        finally:
            for eng in engs.values():
                eng.stop()

    affinity = run_router_arm("affinity")
    least = run_router_arm("least_loaded")
    if not affinity["hit_blocks"] > least["hit_blocks"]:
        raise SystemExit(
            "bench: affinity routing did not beat least_loaded on "
            "prefix_hit_blocks (%d vs %d) under the shared-prefix "
            "mix — cache-aware placement bought nothing"
            % (affinity["hit_blocks"], least["hit_blocks"]))

    # TP arm: same prompts single-device vs mp=2
    tp_prompts = [list(fam_rng.randint(0, args.decode_vocab,
                                       fam_rng.randint(4, 13)))
                  for _ in range(4)]
    base = DecodeEngine(params, cfg, **ek)
    try:
        base_streams = [base.generate(p, max_new_tokens=8, timeout=300)
                        for p in tp_prompts]
        base_bytes = per_device_cache_bytes(base)
    finally:
        base.stop()
    n_dev = len(jax.devices())
    if n_dev >= 2 and n_dev % 2 == 0:
        try:
            tp = make_tp_engine(params, cfg, tensor_parallel=2, **ek)
            try:
                tp_streams = [tp.generate(p, max_new_tokens=8,
                                          timeout=300)
                              for p in tp_prompts]
                tp_stats = tp.stats()
                tp_bytes = per_device_cache_bytes(tp)
            finally:
                tp.stop()
        finally:
            sharding.clear_mesh()
        if tp_streams != base_streams:
            raise SystemExit("bench: TP decode arm changed the greedy "
                             "streams vs the single-device baseline")
        if (tp_stats["dispatches_per_step"] != 1.0
                or tp_stats["steady_state_retraces"] != 0):
            raise SystemExit(
                "bench: TP decode arm broke the dispatch contract: "
                "dispatches_per_step=%r (want 1.0), "
                "steady_state_retraces=%r (want 0)"
                % (tp_stats["dispatches_per_step"],
                   tp_stats["steady_state_retraces"]))
        cache_ratio = round(tp_bytes / max(1, base_bytes), 3)
        if cache_ratio > 0.6:
            raise SystemExit(
                "bench: TP per-device cache bytes %d = %.0f%% of "
                "replicated %d (want <= 60%%) — the head shards "
                "silently replicated" % (tp_bytes, 100 * cache_ratio,
                                         base_bytes))
        tp_fields = {
            "fleet_tp_dispatches_per_step":
                tp_stats["dispatches_per_step"],
            "fleet_tp_retraces_steady_state":
                tp_stats["steady_state_retraces"],
            "fleet_tp_cache_bytes_ratio": cache_ratio,
        }
    else:
        tp_fields = {"fleet_tp_note":
                     "%d visible device(s): mp=2 needs an even "
                     "count >= 2" % n_dev}

    # scale-up arm: a cold replica joins and serves compile-free
    cold = DecodeEngine(params, cfg, capacity=4,
                        block_size=args.decode_block_size,
                        num_blocks=args.decode_blocks, chunk_tokens=8,
                        warmup=False, prefix_cache=True)
    try:
        router = FleetRouter(policy="affinity", sticky=False)
        warmed = router.add_replica("join", cold)
        name, eng = router.route(tp_prompts[0])
        eng.generate(tp_prompts[0], max_new_tokens=6, timeout=300)
        join_stats = eng.stats()
    finally:
        cold.stop()
    if warmed <= 0 or join_stats["steady_state_retraces"] != 0:
        raise SystemExit(
            "bench: scale-up first request compiled at serve time "
            "(warmed=%r, steady_state_retraces=%r — want > 0 / 0): "
            "add_replica must AOT-warm before ring insertion"
            % (warmed, join_stats["steady_state_retraces"]))

    dev = jax.devices()[0]
    out = {
        "metric": "fleet_affinity_hit_ratio",
        "value": round(affinity["hit_blocks"]
                       / max(1, least["hit_blocks"]), 2),
        "unit": "x",
        "device_kind": dev.device_kind,
        "config": {"replicas": 2, "requests": len(requests),
                   "families": len(preambles),
                   "block_size": args.decode_block_size,
                   "num_blocks": args.decode_blocks,
                   "vocab": args.decode_vocab,
                   "seq": args.decode_seq},
        "fleet_affinity_hit_blocks": affinity["hit_blocks"],
        "fleet_least_loaded_hit_blocks": least["hit_blocks"],
        "fleet_affinity_replicas_used": affinity["spread"],
        "fleet_least_loaded_replicas_used": least["spread"],
        "fleet_router_mirror_blocks": sum(
            r["mirror_blocks"]
            for r in affinity["router"]["replicas"].values()),
        "fleet_scale_up_warmed_programs": warmed,
        "fleet_scale_up_retraces_first_request":
            join_stats["steady_state_retraces"],
    }
    out.update(tp_fields)
    return out


def _device():
    """The device as jax reports it — every printed result names it."""
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _emit(result):
    print(json.dumps(dict(result, device=_device())))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", type=str, default="all",
                    choices=["all", "resnet", "transformer"])
    ap.add_argument("--mode", type=str, default="train",
                    choices=["train", "inference", "serving", "checkpoint",
                             "kvstore", "kvstore-mh-worker",
                             "fit", "decode", "dlrm", "dlrm-part-worker",
                             "transformer", "fleet"])
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--image-shape", type=str, default="3,224,224")
    ap.add_argument("--layout", type=str, default="NHWC",
                    choices=["NCHW", "NHWC"])
    ap.add_argument("--dtype", type=str, default="bfloat16",
                    choices=["float32", "bfloat16"])
    ap.add_argument("--num-layers", type=int, default=50)
    ap.add_argument("--warmup", type=int, default=3)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--pipeline", action="store_true",
                    help="feed the resnet step from a real ImageRecordIter "
                         "over a generated .rec of JPEGs (threaded native "
                         "decode + augment + prefetch) instead of "
                         "device-resident synthetic batches")
    ap.add_argument("--decode-threads", type=int, default=8)
    ap.add_argument("--fuse", dest="fuse", action="store_true", default=False,
                    help="apply the BN→ReLU→Conv1×1 Pallas fusion pass "
                         "(NHWC only; A/B flag — see docs/PERF.md for the "
                         "measured result)")
    ap.add_argument("--no-fuse", dest="fuse", action="store_false")
    ap.add_argument("--pipeline-scaling", action="store_true",
                    help="measure host decode throughput at 1/2/4/8 "
                         "threads (iterator only, no device)")
    ap.add_argument("--quantized", action="store_true",
                    help="with --mode inference: calibrated int8/uint8 "
                         "ResNet-50 scoring (ops/quantization_ops.py)")
    # mx.serving throughput (--mode serving; also folded into the default
    # run as serving_* fields so BENCH_* tracks it alongside training)
    ap.add_argument("--serving-requests", type=int, default=256)
    ap.add_argument("--serving-clients", type=int, default=4)
    ap.add_argument("--serving-replicas", type=int, default=1)
    ap.add_argument("--serving-max-batch", type=int, default=8)
    ap.add_argument("--serving-latency-ms", type=float, default=5.0)
    # kvstore bench (--mode kvstore; also folded into the default line)
    ap.add_argument("--kv-ndev", type=int, default=4,
                    help="simulated per-key device gradient streams for "
                         "the kvstore bench (the CommDevice reduce width)")
    ap.add_argument("--kv-hosts", type=int, default=2,
                    help="process count of the kvstore='tpu' multi-host "
                         "arm (spawned via tools/run_multihost.py; 1 "
                         "skips the arm)")
    # fused fit step witnesses (--mode fit; also folded into the default
    # line as train_dispatches_per_step / host_syncs_per_step)
    ap.add_argument("--fit-batch", type=int, default=4)
    ap.add_argument("--fit-image-shape", type=str, default="3,224,224")
    ap.add_argument("--fit-steps", type=int, default=4)
    ap.add_argument("--ckpt-saves", type=int, default=4,
                    help="checkpoint saves per arm in --mode checkpoint")
    # mx.decode generative-serving bench (--mode decode; also folded
    # into the default line as decode_* fields). NOTE: --decode-threads
    # above is the IMAGE-decode pipeline knob, unrelated.
    ap.add_argument("--decode-requests", type=int, default=32)
    ap.add_argument("--decode-capacity", type=int, default=8,
                    help="decode batch slots (compiled step batch dim)")
    ap.add_argument("--decode-block-size", type=int, default=8,
                    help="KV-cache tokens per block")
    ap.add_argument("--decode-blocks", type=int, default=64,
                    help="KV-cache blocks per layer")
    ap.add_argument("--decode-layers", type=int, default=2)
    ap.add_argument("--decode-d-model", type=int, default=64)
    ap.add_argument("--decode-heads", type=int, default=4)
    ap.add_argument("--decode-vocab", type=int, default=128)
    ap.add_argument("--decode-seq", type=int, default=64,
                    help="max context (position-embedding range)")
    ap.add_argument("--decode-prompt-max", type=int, default=12)
    ap.add_argument("--decode-gen-max", type=int, default=40)
    ap.add_argument("--decode-chunk", type=int, default=8,
                    help="prefill chunk budget (tokens/iteration); the "
                         "chunked-vs-unchunked A/B arm compares against "
                         "an oracle compiled at --decode-seq")
    ap.add_argument("--decode-spec-k", type=int, default=4,
                    help="draft tokens per slot for the speculative "
                         "A/B arm (spec-on vs spec-off under the same "
                         "heavy-tailed mix; stream-identity gated)")
    # transformer-LM config (sized for one v5e chip at bf16)
    ap.add_argument("--lm-batch", type=int, default=4)
    ap.add_argument("--lm-seq", type=int, default=1024)
    ap.add_argument("--lm-layers", type=int, default=12)
    ap.add_argument("--lm-d-model", type=int, default=2048)
    ap.add_argument("--lm-heads", type=int, default=16)
    ap.add_argument("--lm-vocab", type=int, default=16384)

    ap.add_argument("--dlrm-vocab", type=int, default=4096,
                    help="rows per categorical feature (the stacked "
                         "table is dlrm-features * dlrm-vocab rows)")
    ap.add_argument("--dlrm-dim", type=int, default=64)
    ap.add_argument("--dlrm-features", type=int, default=8)
    ap.add_argument("--dlrm-batch", type=int, default=128,
                    help="batch * features must be a power of two "
                         "(single-dispatch lookup)")
    ap.add_argument("--dlrm-hosts", type=int, default=2,
                    help="process count of the pod-partitioned "
                         "embedding arm (spawned via "
                         "tools/run_multihost.py; 1 skips the arm)")
    args = ap.parse_args()

    workers = ("kvstore-mh-worker", "dlrm-part-worker")
    if args.mode not in workers and not args.pipeline_scaling \
            and _device()["platform"] == "cpu":
        # a time, a rate or a utilisation comes from a chip run; the
        # two worker modes are CPU worlds their parent arm spawns and
        # names as such, and --pipeline-scaling is host-only by design
        raise SystemExit(
            "bench: jax found no accelerator (%s); a timing mode does "
            "not fall back to the CPU" % _device())

    if args.pipeline_scaling:
        _emit(bench_pipeline_scaling(args))
        return
    if args.mode == "serving":
        _emit(bench_serving(args))
        return
    if args.mode == "kvstore":
        _emit(bench_kvstore(args))
        return
    if args.mode == "kvstore-mh-worker":
        bench_kvstore_mh_worker(args)
        return
    if args.mode == "dlrm":
        _emit(bench_dlrm(args))
        return
    if args.mode == "dlrm-part-worker":
        bench_dlrm_part_worker(args)
        return
    if args.mode == "fit":
        _emit(bench_fit(args))
        return
    if args.mode == "transformer":
        _emit(bench_transformer_mp(args))
        return
    if args.mode == "decode":
        _emit(bench_decode(args))
        return
    if args.mode == "fleet":
        _emit(bench_fleet(args))
        return
    if args.mode == "checkpoint":
        _emit(bench_checkpoint(args))
        return
    if args.mode == "inference":
        if args.quantized:
            _emit(bench_quantized_inference(args))
            return
        _emit(bench_inference(args))
        return
    if args.pipeline and args.model == "transformer":
        raise SystemExit("--pipeline is the ResNet image-input mode; "
                         "combine it with --model resnet (or all)")
    if args.model == "transformer":
        _emit(bench_transformer(args))
        return
    if args.model == "resnet" or args.pipeline:
        _emit(bench_resnet(args))
        return
    # default: resnet headline + transformer_* + serving_* fields, one
    # JSON line (BENCH_* tracks serving throughput alongside training)
    out = bench_resnet(args)
    lm = bench_transformer(args)
    out["transformer_tokens_per_sec"] = lm["value"]
    out["transformer_mfu"] = lm["mfu"]
    out["transformer_mfu_measured"] = lm["mfu_measured"]
    out["transformer_achieved_tflops"] = lm["achieved_tflops"]
    out["transformer_config"] = lm["config"]
    sv = bench_serving(args)
    out["serving_qps"] = sv["value"]
    out["serving_mean_batch_occupancy"] = sv["mean_batch_occupancy"]
    out["serving_latency_p99_ms"] = sv["latency_p99_ms"]
    # this line is the chip's: the multi-host kvstore arm is a world of
    # JAX_PLATFORMS=cpu child processes, so it stays in --mode kvstore
    args.kv_hosts = 1
    kvb = bench_kvstore(args)
    out["kvstore_push_pull_gbps"] = kvb["value"]
    out["kvstore_speedup_vs_eager"] = kvb["speedup_vs_eager"]
    out["kvstore_compress_ratio"] = kvb["kvstore_compress_ratio"]
    fit = bench_fit(args)
    out["train_dispatches_per_step"] = fit["train_dispatches_per_step"]
    out["host_syncs_per_step"] = fit["host_syncs_per_step"]
    out["fit_step_ms"] = fit["fit_step_ms"]
    out["sentinel_overhead_pct"] = fit["sentinel_overhead_pct"]
    out["sentinel_alerts"] = fit["sentinel_alerts"]
    tmp = bench_transformer_mp(args)
    out["transformer_mp"] = tmp.get("transformer_mp")
    out["param_bytes_per_device"] = tmp.get("param_bytes_per_device")
    out["sharding_constraint_sites"] = tmp.get("sharding_constraint_sites")
    cp = bench_checkpoint(args)
    out["checkpoint_block_ms"] = cp["value"]
    out["checkpoint_save_ms"] = cp["checkpoint_save_ms"]
    out["checkpoint_bytes"] = cp["checkpoint_bytes"]
    dc = bench_decode(args)
    out["decode_tokens_per_sec"] = dc["value"]
    out["decode_ttft_p99_ms"] = dc["decode_ttft_p99_ms"]
    out["decode_chunk_tokens"] = dc["decode_chunk_tokens"]
    out["decode_prefill_chunks_per_iter"] = \
        dc["decode_prefill_chunks_per_iter"]
    out["decode_ttft_steps_p99"] = dc["decode_ttft_steps_p99"]
    out["decode_cache_occupancy"] = dc["decode_cache_occupancy"]
    out["decode_dispatches_per_step"] = dc["decode_dispatches_per_step"]
    out["decode_speedup_vs_static"] = dc["decode_speedup_vs_static"]
    out["decode_steps_ratio_vs_static"] = dc["decode_steps_ratio_vs_static"]
    out["decode_attn_impl"] = dc["decode_attn_impl"]
    out["decode_bytes_accessed"] = dc["decode_bytes_accessed"]
    out["decode_spec_k"] = dc["decode_spec_k"]
    out["decode_accept_rate"] = dc["decode_accept_rate"]
    out["decode_tokens_per_launch"] = dc["decode_tokens_per_launch"]
    _emit(out)


if __name__ == "__main__":
    main()
