"""Symbolic control flow: foreach / while_loop / cond.

Reference parity: python/mxnet/symbol/contrib.py:37 (foreach), :157
(while_loop), and cond — the reference builds subgraph symbols executed
by dedicated control-flow operators. TPU-native: the body is traced into
a sub-Symbol whose free variables become extra inputs of ONE fused graph
node lowering to ``jax.lax.scan`` / ``lax.cond`` — exactly the
compiler-friendly control flow XLA wants (no Python loop in the compiled
step, gradients ride jax's scan/cond rules).

Graphs containing control-flow nodes execute, differentiate AND
serialize like any other: ``tojson`` emits the reference's nested
"subgraphs" field per node plus a ``cf_meta`` rebuild recipe, and
``load_json`` reconstructs the identical lax.scan/lax.cond lowering
(_rebuild_cf).
"""
from __future__ import annotations

from ..base import MXNetError
from ..ops.registry import OpDef
from .symbol import Symbol, _Node, Variable
from . import current_name_manager

__all__ = ["foreach", "while_loop", "cond"]


def _as_list(x):
    if isinstance(x, Symbol):
        return [x], True
    return list(x), False


def _subgraph_eval(entries_sym):
    """Build an evaluator running the sub-DAG on jax values inside the
    enclosing trace (op context/rng of the outer program applies)."""
    topo = entries_sym._topo()
    entries = list(entries_sym._entries)

    def run(env):
        vals = {}
        for node in topo:
            if node.is_var:
                if node.name not in env:
                    raise MXNetError("control-flow subgraph: unbound "
                                     "variable '%s'" % node.name)
                vals[(id(node), 0)] = env[node.name]
                continue
            ins = [vals[(id(i), oi)] for i, oi in node.inputs]
            raw = node.apply(ins)
            outs = list(raw) if isinstance(raw, (tuple, list)) else [raw]
            for i, v in enumerate(outs):
                vals[(id(node), i)] = v
        return [vals[(id(n), oi)] for n, oi in entries]

    return run


def _free_vars(sub, bound_names):
    names = (sub.list_arguments() + sub.list_auxiliary_states())
    return [n for n in names if n not in bound_names]


def _make_node(opname, fn, n_outputs, input_syms, name_hint, cf_meta=None):
    from .symbol import AttrScope

    opdef = OpDef(opname, fn, num_outputs=n_outputs,
                  num_visible_outputs=n_outputs)
    nm = current_name_manager().get(None, name_hint)
    entries = []
    for s in input_syms:
        if len(s._entries) != 1:
            raise MXNetError("control-flow inputs must be single-output "
                             "symbols")
        entries.append(s._entries[0])
    node = _Node(opdef, nm, {}, entries,
                 str_attrs=AttrScope.current_attrs(), cf_meta=cf_meta)
    return [Symbol([(node, i)]) for i in range(n_outputs)]


# ----------------------------------------------------------------------
# lowering builders — pure functions of (subgraph symbols + meta), so a
# node loaded from JSON rebuilds the exact same lax.scan/cond program
# ----------------------------------------------------------------------
def _foreach_lowering(sub, meta):
    import jax

    run = _subgraph_eval(sub)
    data_names = meta["data_names"]
    state_names = meta["state_names"]
    params = meta["params"]
    n_out, n_state = meta["n_out"], meta["n_state"]
    n_data = len(data_names)

    def fn(*inputs):
        xs = inputs[:n_data]
        carry0 = tuple(inputs[n_data:n_data + n_state])
        pvals = dict(zip(params, inputs[n_data + n_state:]))

        def step(carry, x_slices):
            env = dict(zip(data_names, x_slices))
            env.update(zip(state_names, carry))
            env.update(pvals)
            vals = run(env)
            return tuple(vals[n_out:]), tuple(vals[:n_out])

        final, ys = jax.lax.scan(step, carry0, tuple(xs))
        return tuple(ys) + tuple(final)

    return fn


def _while_lowering(sub, meta):
    import jax
    import jax.numpy as jnp

    run = _subgraph_eval(sub)
    var_names = meta["var_names"]
    params = meta["params"]
    n_out, n_var = meta["n_out"], meta["n_var"]
    max_iterations = meta["max_iterations"]

    def fn(*inputs):
        vars0 = tuple(inputs[:n_var])
        pvals = dict(zip(params, inputs[n_var:]))

        def body_all(vars_):
            env = dict(zip(var_names, vars_))
            env.update(pvals)
            vals = run(env)
            pred = jnp.squeeze(vals[0]).astype(bool)
            return pred, tuple(vals[1:1 + n_out]), tuple(vals[1 + n_out:])

        def step(carry, _):
            alive, vars_ = carry
            pred, outs, nvars = body_all(vars_)
            take = jnp.logical_and(alive, pred)
            new_vars = tuple(jnp.where(take, nv, v)
                             for nv, v in zip(nvars, vars_))
            outs = tuple(jnp.where(take, o, jnp.zeros_like(o))
                         for o in outs)
            return (take, new_vars), outs

        (alive, final_vars), ys = jax.lax.scan(
            step, (jnp.asarray(True), vars0), None, length=max_iterations)
        return tuple(ys) + tuple(final_vars)

    return fn


def _cond_lowering(t_sub, e_sub, meta):
    import jax
    import jax.numpy as jnp

    t_run = _subgraph_eval(t_sub)
    e_run = _subgraph_eval(e_sub)
    t_params, e_params = meta["t_params"], meta["e_params"]
    all_params = meta["all_params"]

    def fn(pred_v, *inputs):
        pvals = dict(zip(all_params, inputs))

        def t_branch(_):
            return tuple(t_run({n: pvals[n] for n in t_params}))

        def e_branch(_):
            return tuple(e_run({n: pvals[n] for n in e_params}))

        p = jnp.squeeze(pred_v).astype(bool)
        return jax.lax.cond(p, t_branch, e_branch, operand=None)

    return fn


def _rebuild_cf(opname, meta):
    """Rebuild (OpDef, n_outputs) for a control-flow node loaded from
    JSON (symbol._load_graph_dict)."""
    subs = meta["subgraphs"]
    if opname == "_foreach":
        n = meta["n_out"] + meta["n_state"]
        fn = _foreach_lowering(subs[0], meta)
    elif opname == "_while_loop":
        n = meta["n_out"] + meta["n_var"]
        fn = _while_lowering(subs[0], meta)
    elif opname == "_cond":
        n = meta["n_out"]
        fn = _cond_lowering(subs[0], subs[1], meta)
    else:
        raise MXNetError("unknown control-flow op '%s'" % opname)
    return OpDef(opname, fn, num_outputs=n, num_visible_outputs=n), n


def foreach(body, data, init_states, name="foreach"):
    """Scan ``body`` over axis 0 of ``data`` (reference
    symbol/contrib.py:37). ``body(data_slice, states) -> (outputs,
    states)``. Lowers to one ``jax.lax.scan``."""
    import jax

    datas, single_data = _as_list(data)
    states, single_state = _as_list(init_states)

    data_vars = [Variable("%s_data%d" % (name, i))
                 for i in range(len(datas))]
    state_vars = [Variable("%s_state%d" % (name, i))
                  for i in range(len(states))]
    outs, out_states = body(data_vars[0] if single_data else data_vars,
                            state_vars[0] if single_state else state_vars)
    out_syms, single_out = _as_list(outs)
    ostate_syms, _ = _as_list(out_states)
    if len(ostate_syms) != len(states):
        raise MXNetError("foreach body must return as many states as "
                         "init_states")

    sub = Symbol([e for s in (out_syms + ostate_syms) for e in s._entries])
    data_names = [v.name for v in data_vars]
    state_names = [v.name for v in state_vars]
    params = _free_vars(sub, set(data_names + state_names))
    n_out, n_state = len(out_syms), len(ostate_syms)

    meta = {"subgraphs": [sub], "data_names": data_names,
            "state_names": state_names, "params": params,
            "n_out": n_out, "n_state": n_state}
    fn = _foreach_lowering(sub, meta)
    out_all = _make_node("_foreach", fn, n_out + n_state,
                         datas + states + list(map(Variable, params)), name,
                         cf_meta=meta)
    outputs = out_all[:n_out]
    fstates = out_all[n_out:]
    return (outputs[0] if single_out else outputs,
            fstates[0] if single_state else fstates)


def while_loop(cond, func, loop_vars, max_iterations=None,
               name="while_loop"):
    """Run ``func`` while ``cond`` holds, at most ``max_iterations``
    times (reference symbol/contrib.py:157). Step outputs are stacked
    into a (max_iterations, ...) array, zero-padded past the actual
    iteration count; returns (outputs, final_loop_vars). Lowers to
    ``jax.lax.scan`` with a live-flag (the XLA-friendly bounded loop)."""
    import jax
    import jax.numpy as jnp

    if max_iterations is None:
        raise MXNetError("while_loop requires max_iterations")
    lvars, single_var = _as_list(loop_vars)

    var_vars = [Variable("%s_var%d" % (name, i)) for i in range(len(lvars))]
    cond_sym = cond(*var_vars)
    step_out, new_vars = func(*var_vars)
    out_syms, single_out = _as_list(step_out) if step_out is not None \
        else ([], True)
    nvar_syms, _ = _as_list(new_vars)
    if len(nvar_syms) != len(lvars):
        raise MXNetError("while_loop func must return as many loop_vars")

    sub = Symbol([e for s in ([cond_sym] + out_syms + nvar_syms)
                  for e in s._entries])
    var_names = [v.name for v in var_vars]
    params = _free_vars(sub, set(var_names))
    n_out, n_var = len(out_syms), len(nvar_syms)

    meta = {"subgraphs": [sub], "var_names": var_names, "params": params,
            "n_out": n_out, "n_var": n_var,
            "max_iterations": int(max_iterations)}
    fn = _while_lowering(sub, meta)
    out_all = _make_node("_while_loop", fn, n_out + n_var,
                         lvars + list(map(Variable, params)), name,
                         cf_meta=meta)
    outputs = out_all[:n_out]
    fvars = out_all[n_out:]
    return (outputs[0] if single_out and outputs else outputs,
            fvars[0] if single_var else fvars)


def cond(pred, then_func, else_func, name="cond"):
    """Branch on a scalar symbol (reference symbol/contrib.py cond).
    ``then_func``/``else_func`` are nullary callables returning symbols
    of identical shapes. Lowers to ``jax.lax.cond``."""
    import jax
    import jax.numpy as jnp

    then_out = then_func()
    else_out = else_func()
    t_syms, single = _as_list(then_out)
    e_syms, _ = _as_list(else_out)
    if len(t_syms) != len(e_syms):
        raise MXNetError("cond branches must return the same number of "
                         "outputs")
    n_out = len(t_syms)

    t_sub = Symbol([e for s in t_syms for e in s._entries])
    e_sub = Symbol([e for s in e_syms for e in s._entries])
    t_params = _free_vars(t_sub, set())
    e_params = _free_vars(e_sub, set())
    all_params = list(dict.fromkeys(t_params + e_params))

    meta = {"subgraphs": [t_sub, e_sub], "t_params": t_params,
            "e_params": e_params, "all_params": all_params,
            "n_out": n_out}
    fn = _cond_lowering(t_sub, e_sub, meta)
    out_all = _make_node("_cond", fn, n_out,
                         [pred] + list(map(Variable, all_params)), name,
                         cf_meta=meta)
    return out_all[0] if single else out_all


# ----------------------------------------------------------------------
# expose every _contrib_* registry op under its stripped name
# (reference python/mxnet/symbol/contrib.py is code-generated the same
# way from the _contrib_ prefix)
# ----------------------------------------------------------------------
def _install_contrib_ops():
    from ..ops import registry as _reg
    from . import _make_sym_func
    g = globals()
    for _name in _reg.list_ops():
        if not _name.startswith("_contrib_"):
            continue
        short = _name[len("_contrib_"):]
        if short in g:  # hand-written wrappers (foreach/while_loop/cond) win
            continue
        g[short] = _make_sym_func(_reg.get_op(_name), short)


_install_contrib_ops()
