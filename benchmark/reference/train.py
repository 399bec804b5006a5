"""The reference's side of a training cell: the first three steps from
the seed in float32, and the numbers the program's steps are held to.

Generic over the model (``benchmark/reference/<model>.py`` gives
``param_specs``, ``init_leaf`` and ``loss``) and the optimizer (MXNet's
arithmetic, written out here).  Nothing of the program is imported.
"""
import functools

import jax
import jax.numpy as jnp


def wd_mult(name):
    """MXNet's rule (optimizer.py set_wd_mult): decay only names ending
    in _weight or _gamma."""
    return 1.0 if name.endswith(("_weight", "_gamma")) else 0.0


def adam(w, g, state, t, name, opt, rescale):
    """MXNet's Adam on the float32 master copy: coupled decay, bias
    correction folded into the rate.  ``t`` counts from 1."""
    m, v = state
    b1, b2 = opt["beta1"], opt["beta2"]
    g = g * rescale + opt.get("wd", 0.0) * wd_mult(name) * w
    m = b1 * m + (1.0 - b1) * g
    v = b2 * v + (1.0 - b2) * jnp.square(g)
    lr = opt["learning_rate"] * jnp.sqrt(1.0 - b2 ** t) / (1.0 - b1 ** t)
    return w - lr * m / (jnp.sqrt(v) + opt.get("epsilon", 1e-8)), (m, v)


def sgd(w, g, state, t, name, opt, rescale):
    """MXNet's SGD with momentum: mom = momentum*mom - lr*(g + wd*w)."""
    (mom,) = state
    g = g * rescale + opt.get("wd", 0.0) * wd_mult(name) * w
    mom = opt.get("momentum", 0.0) * mom - opt["learning_rate"] * g
    return w + mom, (mom,)


OPTIMIZERS = {"adam": (adam, 2), "sgd": (sgd, 1)}


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _seeded_leaf(k, kind, shape, leaf_value):
    return leaf_value(k, kind, shape)


def seeded_leaf(model, key, name, shape):
    """One parameter's seeded float32 value.  Every caller (the
    program's weights, the reference's, the parameter-change check that
    makes a leaf again) goes through this ONE compiled program per
    (kind, shape), so the bits are the same wherever a leaf is made:
    inside different programs the compiler may round a normal deviate's
    last bit differently, and a flipped bfloat16 bit is larger than a
    small learning rate's whole step."""
    return _seeded_leaf(model.leaf_key(key, name), model.leaf_kind(name),
                        tuple(shape), model.leaf_value)


def init_params(model, key, specs, dtypes=None):
    """{name: seeded value} for ``specs``, made on the device leaf by
    leaf; ``dtypes`` maps a name to the type its holder wants (default
    float32; the values are bfloat16-exact, so the cast loses nothing)."""
    dtypes = dtypes or {}
    return {n: seeded_leaf(model, key, n, s).astype(
        dtypes.get(n, jnp.float32)) for n, s in specs}


def leaf_norms(tree):
    return {n: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for n, x in tree.items()}


def first_steps(model, kw, optimizer, opt, rescale, key, batches,
                precision="f32"):
    """Follow ``len(batches)`` steps from the seeded weights.  Returns
    ``{"losses": [...], "grad_norms": {name: norm of the first step's
    rescaled gradient}, "delta_norms": {name: norm of the parameter's
    change after the last step}}`` as Python floats."""
    update, n_state = OPTIMIZERS[optimizer]
    specs = model.param_specs(kw)
    aux = model.init_aux(kw) if hasattr(model, "init_aux") else {}

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(params, states, aux, data, labels, t):
        def f(p):
            return model.loss(p, aux, data, labels, kw, precision)
        (l, new_aux), g = jax.value_and_grad(f, has_aux=True)(params)
        gn = leaf_norms({n: g[n] * rescale for n in g})
        new_p, new_s = {}, {}
        for n in params:
            new_p[n], new_s[n] = update(params[n], g[n], states[n], t, n,
                                        opt, rescale)
        return new_p, new_s, new_aux, l, gn

    params = init_params(model, key, specs)
    states = {n: tuple(jnp.zeros_like(params[n]) for _ in range(n_state))
              for n, _ in specs}
    losses, grad_norms = [], None
    for t, (data, labels) in enumerate(batches, 1):
        params, states, aux, l, gn = step(params, states, aux, data, labels,
                                          jnp.float32(t))
        losses.append(float(l))
        if t == 1:
            grad_norms = {n: float(x) for n, x in gn.items()}
    del states
    delta = {}
    for n, s in specs:
        delta[n] = float(delta_norm(key, n, tuple(s), params[n], model))
    return {"losses": losses, "grad_norms": grad_norms,
            "delta_norms": delta}


def delta_norm(key, name, shape, w, model):
    """||w - w0|| with w0 made again from the seed (one leaf at a time:
    the seeded copy of a whole model never sits beside the live one)."""
    return _diff_norm(w, seeded_leaf(model, key, name, shape))


@jax.jit
def _diff_norm(w, w0):
    return jnp.sqrt(jnp.sum(jnp.square(w.astype(jnp.float32) - w0)))


def leaf_gaps(got, ref):
    """{name: gap} between two {name: norm} readings: |got - ref| over
    the larger of the reference's norm of that leaf and of the median
    leaf (some gradients are all but zero)."""
    med = sorted(ref.values())[len(ref) // 2]
    return {n: abs(got[n] - ref[n]) / max(ref[n], med, 1e-30) for n in ref}


def worst_gap(gaps, names=None):
    """(gap, name) of the worst of ``names`` (default: all) in what
    :func:`leaf_gaps` gives; NaN counts as worst."""
    worst, at = 0.0, None
    for n in sorted(gaps if names is None else names):
        if not gaps[n] <= worst:
            worst, at = gaps[n], n
    return worst, at
