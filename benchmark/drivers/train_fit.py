"""Driver ``train_fit``: ``Module.fit_step`` the way ``Module.fit``
drives it, on a pool of host batches made from the seed.

One loop body serves the checked first steps and the window: get the
next host batch and place it (span ``input``), ``fit_step`` +
``update_metric`` (span ``fit_step``), read the metric back (span
``readback``: the step's sync, what MXNet's Speedometer does).

Set-up builds ONE module; its first ``check_steps`` steps (which also
compile and warm the step) are held to the plain reference
(benchmark/reference/train.py), and the same module goes on into the
window.  The reference runs before the module exists, so that the
device's peak memory stays the program's.
"""
import time

import numpy as np

import common
from reference import train as ref_train


def make_pool(model, kw, traffic, seed):
    """``pool`` host batches (data, labels) as float32 numpy, every row
    different; the same seed gives the same pool."""
    rng = np.random.default_rng([int(seed), 0xBA7C4])
    return [model.make_batch(rng, kw, int(traffic["batch"]))
            for _ in range(int(traffic["pool"]))]


def optimizer_state(mod, name):
    """([optimizer state leaves], master weight) of parameter ``name``
    as jax arrays, as the fused step carries them.  With
    ``multi_precision`` a low-precision weight's state is MXNet's
    ``(inner state, float32 master)``; otherwise the weight is its own
    master."""
    upd = mod._kvstore._updater if mod._update_on_kvstore else mod._updater
    st = upd.states[name]
    w = mod._exec_group._exec.arg_dict[name]
    if mod._optimizer.multi_precision and str(w.dtype) != "float32":
        st, w = st
    inner = st if isinstance(st, (tuple, list)) else (st,)
    return [s._data for s in inner], w._data


def first_gradient_norms(mod, names, optimizer, opt):
    """Per leaf, the norm of the first rescaled gradient, worked out
    from the optimizer's state after ONE step (benchmark's arithmetic on
    the program's state, reduced on the device to one scalar a leaf)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def adam_norm(m, v, w, wd):
        # m1 = (1-b1) G, v1 = (1-b2) G^2 with G = rescale*g + wd*w0 and
        # w1 = w0 - lr1 * m1 / (sqrt(v1) + eps); lr1 = lr*sqrt(1-b2)/(1-b1)
        b1, b2 = opt["beta1"], opt["beta2"]
        lr1 = opt["learning_rate"] * (1.0 - b2) ** 0.5 / (1.0 - b1)
        w0 = w + lr1 * m / (jnp.sqrt(v) + opt.get("epsilon", 1e-8))
        g = m / (1.0 - b1) - wd * w0
        return jnp.sqrt(jnp.sum(jnp.square(g)))

    @jax.jit
    def sgd_norm(mom, w, wd):
        # mom1 = -lr (rescale*g + wd*w0); w1 = w0 + mom1
        w0 = w - mom
        g = -mom / opt["learning_rate"] - wd * w0
        return jnp.sqrt(jnp.sum(jnp.square(g)))

    out = {}
    for n in names:
        state, w = optimizer_state(mod, n)
        wd = opt.get("wd", 0.0) * ref_train.wd_mult(n)
        state = [s.astype(jnp.float32) for s in state]
        w = w.astype(jnp.float32)
        out[n] = (adam_norm(state[0], state[1], w, wd)
                  if optimizer == "adam" else sgd_norm(state[0], w, wd))
    return {n: float(v) for n, v in out.items()}


def seeded_initializer(mx, values):
    """An ``mx.init.Initializer`` that hands every parameter its seeded
    value BY NAME, device array to device array.  (``init_params(
    arg_params=...)`` would commit host copies to the CPU, and
    ``kvstore='tpu'`` pulls those into the executor as they are.)"""
    class Seeded(mx.init.Initializer):
        def __call__(self, desc, arr):
            arr[:] = mx.nd.NDArray(values[str(desc)], arr.context)

    return Seeded()


def checked_loss(mod, host_batch, value, traffic, scale):
    """A checked step's loss as the reference states it: the ``ce``
    metric's reading where that is the cell's metric, else the mean
    cross-entropy of the step's own outputs (the softmax the fused step
    left in the executor) under the batch's labels; times ``scale``
    (the reference's loss may be a sum over the batch)."""
    if traffic["metric"] == "ce":
        return value * scale
    prob = mod.get_outputs()[0].astype("float32").asnumpy()
    lab = host_batch.label[0].asnumpy().astype(np.int64).reshape(-1)
    prob = prob.reshape(lab.size, -1)
    return float(-np.mean(np.log(prob[np.arange(lab.size), lab]))) * scale


LEAF_CLASSES = {
    # convolution, matmul, embedding and classifier weights
    "weights": lambda n: n.endswith("_weight"),
    # gains, shifts and biases: in a bfloat16 network their gradients
    # carry the noise of every activation they touch
    "others": lambda n: not n.endswith("_weight"),
}


def compare(got_losses, got_grad, got_delta, ref, limits):
    """The rows that decide ``correct``: each number beside its limit.
    A configuration's ``limits`` names the numbers it is held to:
    ``loss_rel_gap`` (each checked step's loss), and the first
    gradient's and the parameter change's norm gap BY THE WORST LEAF,
    either over all leaves (``grad_norm_gap``, ``delta_norm_gap``) or
    over a class of leaves with a limit of its own
    (``grad_norm_gap.weights``, ``grad_norm_gap.others``, ...:
    :data:`LEAF_CLASSES`).  Every leaf falls under some limit, or the
    configuration is refused.  Beside them a steadier number, which a
    lower precision moves more than it moves a worst leaf: the mean of
    the weight leaves' gaps (``grad_norm_mean_weight_gap``,
    ``delta_norm_mean_weight_gap``).  A reading without a limit is
    printed and decides nothing."""
    rows = []
    for t, (a, b) in enumerate(zip(got_losses, ref["losses"]), 1):
        rows.append(common.check("loss_step%d_rel_gap" % t,
                                 abs(a - b) / abs(b),
                                 limits["loss_rel_gap"]))
    for what, key, got, want in (
            ("first_grad", "grad", got_grad, ref["grad_norms"]),
            ("param_change", "delta", got_delta, ref["delta_norms"])):
        base = key + "_norm_gap"
        classes = ["%s.%s" % (base, c) for c in LEAF_CLASSES]
        if base not in limits and not all(c in limits for c in classes):
            raise SystemExit("train_fit: the configuration's limits leave "
                             "some leaf's %s unheld (give %s, or each of %s)"
                             % (what, base, classes))
        gaps = ref_train.leaf_gaps(got, want)
        readings = []
        for suffix, pick in [("", lambda n: True)] + [
                ("." + c, pick) for c, pick in LEAF_CLASSES.items()]:
            gap, at = ref_train.worst_gap(gaps, [n for n in gaps if pick(n)])
            readings.append(("%s_norm_worst_leaf_gap%s[%s]"
                             % (what, suffix, at), gap, base + suffix))
        weights = [gaps[n] for n in gaps if LEAF_CLASSES["weights"](n)]
        readings.append(("%s_norm_mean_weight_leaf_gap" % what,
                         sum(weights) / len(weights),
                         key + "_norm_mean_weight_gap"))
        for name, value, limit_key in readings:
            if limit_key in limits:
                rows.append(common.check(name, value, limits[limit_key]))
            else:
                print("not held to: %s %.6g" % (name, value))
    return rows


def control(cell):
    """The reference in fp8 against the reference in float32, through
    the run's own comparison."""
    model = common.reference_model(cell.config)
    cfg, traffic = cell.config, cell.traffic
    kw, opt = dict(cfg["kwargs"]), dict(cfg["optimizer_params"])
    pool = make_pool(model, kw, traffic, cell.seed)
    n_check = int(traffic.get("check_steps", 3))
    key = model.seed_key(cell.seed)
    out = {}
    for precision in ("f32", "fp8"):
        batches = [model.device_batch(d, l) for d, l in pool[:n_check]]
        out[precision] = ref_train.first_steps(
            model, kw, cfg["optimizer"], opt, 1.0 / int(traffic["batch"]),
            key, batches, precision)
    low = out["fp8"]
    return compare(low["losses"], low["grad_norms"], low["delta_norms"],
                   out["f32"], cfg["limits"])


def run(cell):
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import models
    model = common.reference_model(cell.config)

    cfg, traffic = cell.config, cell.traffic
    kw = dict(cfg["kwargs"])
    opt = dict(cfg["optimizer_params"])
    optimizer = cfg["optimizer"]
    B = int(traffic["batch"])
    limits = cfg["limits"]
    n_check = int(traffic.get("check_steps", 3))
    compiles = common.CompileCounter()
    spans = common.Spans()
    key = model.seed_key(cell.seed)
    pool = make_pool(model, kw, traffic, cell.seed)
    rescale = 1.0 / B                   # Module.init_optimizer's rule

    # ---- the reference, before the program's state exists ------------
    t_ref = time.perf_counter()
    ref_batches = [model.device_batch(d, l) for d, l in pool[:n_check]]
    ref = ref_train.first_steps(model, kw, optimizer, opt, rescale, key,
                                ref_batches)
    del ref_batches
    ref_seconds = time.perf_counter() - t_ref
    ref_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in jax.local_devices())

    # ---- the program: one module, weights from the seed by name ------
    ctx = mx.cpu(0) if cell.rehearse else mx.tpu(0)
    sym = models.get_symbol(cfg["model"], **kw)
    mod = mx.Module(sym, context=ctx)
    dshape, lshape = model.data_shapes(kw, B)
    mod.bind(data_shapes=[("data", dshape)],
             label_shapes=[("softmax_label", lshape)])
    exe = mod._exec_group._exec
    specs = model.param_specs(kw)
    bound = {n: tuple(exe.arg_dict[n].shape) for n, _ in specs}
    if bound != {n: tuple(s) for n, s in specs}:
        raise SystemExit("train_fit: the reference's parameters differ from "
                         "the bound module's")
    dtypes = {n: exe.arg_dict[n].dtype for n, _ in specs}
    # made on the device from the seed; constants (ones, zeros) land on
    # jax's default device, so everything is put where the module is
    weights = jax.device_put(
        ref_train.init_params(model, key, specs, dtypes), ctx.jax_device)
    aux0 = model.init_aux(kw) if hasattr(model, "init_aux") else {}
    aux0 = {n: a.astype(exe.aux_dict[n].dtype) for n, a in aux0.items()}
    mod.init_params(seeded_initializer(mx, dict(weights, **aux0)))
    del weights
    mod.init_optimizer(kvstore="tpu", optimizer=optimizer,
                       optimizer_params=dict(opt, multi_precision=bool(
                           cfg.get("multi_precision", True))))
    metric = mx.metric.create(traffic["metric"])
    host_pool = [mx.io.DataBatch(data=[mx.nd.array(d)],
                                 label=[mx.nd.array(l)]) for d, l in pool]
    state = {"i": 0}

    def step():
        """The loop body: checked steps and window alike."""
        with spans.span("input"):
            hb = host_pool[state["i"] % len(host_pool)]
            batch = mx.io.DataBatch(
                data=[a.as_in_context(ctx) for a in hb.data],
                label=[a.as_in_context(ctx) for a in hb.label])
        with spans.span("fit_step"):
            fused = mod.fit_step(batch, metric)
            mod.update_metric(metric, batch.label)
        with spans.span("readback"):
            value = float(metric.get()[1])
            metric.reset()
        state["i"] += 1
        return fused, value, hb

    # ---- first steps: compile, warm, and the comparison --------------
    checks, got_losses, fused_all = [], [], True
    loss_scale = model.loss_scale(kw, B) if hasattr(model, "loss_scale") \
        else 1.0
    for t in range(1, n_check + 1):
        fused, value, hb = step()
        fused_all &= bool(fused)
        got_losses.append(checked_loss(mod, hb, value, traffic, loss_scale))
        if t == 1:
            got = first_gradient_norms(mod, [n for n, _ in specs],
                                       optimizer, opt)
    got_delta = {n: float(ref_train.delta_norm(
        key, n, tuple(s), optimizer_state(mod, n)[1], model))
        for n, s in specs}
    checks += compare(got_losses, got, got_delta, ref, limits)
    checks.append(common.check("fit_step_fused", 0.0 if fused_all else 1.0, 0))
    for _ in range(int(traffic.get("warm_steps", 2))):
        step()

    # ---- the window ---------------------------------------------------
    seconds = min(cell.seconds, float(traffic.get("trace_seconds", 3.0))) \
        if cell.trace else cell.seconds
    before = common.program_counters()
    compiles0 = compiles.n
    spans.seconds.clear()
    spans.count.clear()
    n_steps, failed, ends = 0, 0, []
    with common.traced(cell, cell.trace) as tr:
        t0 = time.perf_counter()
        setup_s = t0 - cell.t_process - ref_seconds
        t_end = t0
        while t_end - t0 < seconds:
            fused, value, _ = step()
            if not (fused and np.isfinite(value)):
                failed += 1
            n_steps += 1
            t_end = time.perf_counter()
            ends.append(t_end - t0)
    elapsed = t_end - t0
    after = common.program_counters()
    checks.append(common.check("compilations_in_window",
                               compiles.n - compiles0, 0))
    checks.append(common.check("failed_steps", failed, 0))

    facts = {
        "kind": "train", "config": cfg, "traffic": traffic, "steps": n_steps,
        "window_s": elapsed, "batch": B, "spans": dict(spans.seconds),
        "span_counts": dict(spans.count), "before": before, "after": after,
        "rehearse": cell.rehearse,
        "trace": common.reduce_trace(tr["dir"], ["input", "fit_step",
                                                 "readback"],
                                     cell.rehearse)
        if cell.trace else None,
    }
    if not cell.rehearse:
        import counts
        facts["peaks"] = counts.peaks(jax.devices()[0].device_kind)
    return {
        "end_to_end": {"train_samples_per_s": n_steps * B / elapsed,
                       "setup_s": setup_s},
        "checks": checks, "attempted": n_steps + n_check, "failed": failed,
        "facts": facts,
        "notes": {"steps": n_steps, "reference_seconds": ref_seconds,
                  "reference_peak_bytes": int(ref_peak),
                  "losses": got_losses, "reference_losses": ref["losses"],
                  "memory_stats": {k: int(v) for k, v in (
                      jax.local_devices()[0].memory_stats() or {}).items()},
                  "step_ms": 1e3 * elapsed / max(n_steps, 1),
                  # steps that ended in each quarter of the window: a
                  # run that reads low says whether all of it was slow
                  "steps_by_quarter": [
                      sum(1 for e in ends
                          if q * elapsed / 4 < e <= (q + 1) * elapsed / 4)
                      for q in range(4)]},
    }
