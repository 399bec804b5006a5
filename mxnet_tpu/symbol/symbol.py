"""Symbol: the declarative graph IR.

Reference parity: python/mxnet/symbol/symbol.py over nnvm::Symbol. Here the
graph is a plain Python DAG whose nodes reference OpDefs; "compilation" is
tracing the DAG into one XLA computation (executor.py), replacing the
reference's nnvm pass pipeline (Gradient/PlaceDevice/PlanMemory — all
subsumed by jax.grad/sharding/XLA). JSON serialization keeps the reference's
``symbol.json`` node format for checkpoint interop (save_checkpoint writes
the same {"nodes": [...], "arg_nodes": ..., "heads": ...} structure).
"""
from __future__ import annotations

import json
import threading

import numpy as _np

from ..base import MXNetError, current_name_manager
from ..ops import registry as _reg

__all__ = ["Symbol", "Variable", "var", "Group", "load", "load_json",
           "AttrScope"]


class AttrScope:
    """with AttrScope(ctx_group='dev1'): — attach attrs to created nodes
    (reference: python/mxnet/attribute.py; used for model parallelism)."""
    _tls = threading.local()

    def __init__(self, **attrs):
        self._attrs = {k: str(v) for k, v in attrs.items()}

    @classmethod
    def current_attrs(cls):
        stack = getattr(cls._tls, "stack", None)
        merged = {}
        if stack:
            for scope in stack:
                merged.update(scope._attrs)
        return merged

    def __enter__(self):
        if not hasattr(AttrScope._tls, "stack"):
            AttrScope._tls.stack = []
        AttrScope._tls.stack.append(self)
        return self

    def __exit__(self, *a):
        AttrScope._tls.stack.pop()


class _Node:
    __slots__ = ("op", "name", "attrs", "str_attrs", "inputs", "cf_meta",
                 "given_attrs")
    _uid = [0]

    def __init__(self, op, name, attrs, inputs, str_attrs=None,
                 cf_meta=None, given_attrs=None):
        self.op = op            # OpDef or None for variables
        self.name = name
        self.attrs = attrs      # typed op attrs
        self.str_attrs = dict(str_attrs or {})  # user attrs (ctx_group, __shape__…)
        # attr names the CALLER passed (normalize_attrs fills defaults
        # into `attrs`, losing explicitness); None = unknown, fall back
        # to the value-differs-from-default heuristic
        self.given_attrs = (frozenset(given_attrs)
                            if given_attrs is not None else None)
        self.inputs = inputs    # list[(Node, out_idx)]
        # control-flow metadata: {"kind", "subgraphs": [Symbol, ...],
        # **json-able fields} — lets foreach/while_loop/cond nodes
        # serialize (tojson emits the reference's nested "subgraphs"
        # field; load_json rebuilds the lax.scan/cond lowering)
        self.cf_meta = cf_meta

    @property
    def is_var(self):
        return self.op is None

    def out_count(self):
        return 1 if self.is_var else self.op.out_count(self.attrs)

    def visible_out_count(self):
        return 1 if self.is_var else self.op.visible_out_count(self.attrs)

    def output_name(self, idx):
        if self.is_var:
            return self.name
        n = self.visible_out_count()
        if n == 1:
            return self.name + "_output"
        # match reference multi-output naming: name + suffix per output
        return "%s_output%d" % (self.name, idx)

    def input_names(self):
        """The declared name of each of this node's inputs.  An op's
        inputs that its attrs make absent (``unused_inputs``: a bias
        under ``no_bias``, the router that ``RoutedExperts`` was not
        asked for) are skipped when the node is composed; where exactly
        the present ones were given, those are the names, wherever the
        absent ones stand in the declaration.  (A caller may still pass
        an input its attrs call unused: then, as before, the inputs are
        the declaration's first.)"""
        op = self.op
        if op.variadic:
            return [str(i) for i in range(len(self.inputs))]
        names = op.input_names
        if op.unused_inputs is not None:
            absent = op.unused_inputs(self.attrs)
            present = [n for n in names if n not in absent]
            if len(present) == len(self.inputs):
                return present
        return names[:len(self.inputs)]

    def apply(self, ins):
        """The op on this node's input values.  Positional where the
        inputs are the declaration's first; by name where an absent
        input stands before a present one."""
        op = self.op
        if op.variadic or op.unused_inputs is None:
            return op.fn(*ins, **self.attrs)
        names = self.input_names()
        if names == op.input_names[:len(names)]:
            return op.fn(*ins, **self.attrs)
        return op.fn(**dict(zip(names, ins)), **self.attrs)

    def explicit_attrs(self):
        """The op attrs the caller actually passed, as {name: value} —
        exact when tracked at creation, else the params whose value
        differs from the registry default (a value explicitly set TO its
        default is indistinguishable then)."""
        if self.is_var:
            return {}
        if self.given_attrs is not None:
            return {k: v for k, v in self.attrs.items()
                    if k in self.given_attrs}
        defaults = self.op.attr_defaults
        return {k: v for k, v in self.attrs.items()
                if k not in defaults or defaults[k] != v}


class Symbol:
    def __init__(self, entries):
        self._entries = list(entries)  # list[(Node, out_idx)]

    # ------------------------------------------------------------------
    # graph traversal
    # ------------------------------------------------------------------
    def _topo(self):
        """Post-order DFS (matches reference nnvm ordering for
        list_arguments)."""
        seen = {}
        order = []

        def visit(node):
            if id(node) in seen:
                return
            seen[id(node)] = node
            for inp, _ in node.inputs:
                visit(inp)
            order.append(node)

        for node, _ in self._entries:
            visit(node)
        return order

    def _aux_names_set(self):
        aux = set()
        for node in self._topo():
            if node.is_var or not node.op.mutate_inputs:
                continue
            mut = {nm for nm, _ in node.op.mutate_inputs}
            for (inp, _), nm in zip(node.inputs, node.input_names()):
                if nm in mut and inp.is_var:
                    aux.add(inp.name)
        return aux

    def list_arguments(self):
        aux = self._aux_names_set()
        out, seen = [], set()
        for node in self._topo():
            if node.is_var and node.name not in aux and node.name not in seen:
                seen.add(node.name)
                out.append(node.name)
        return out

    def list_auxiliary_states(self):
        aux = self._aux_names_set()
        out, seen = [], set()
        for node in self._topo():
            if node.is_var and node.name in aux and node.name not in seen:
                seen.add(node.name)
                out.append(node.name)
        return out

    def list_outputs(self):
        return [node.output_name(idx) for node, idx in self._entries]

    def list_inputs(self):
        return self.list_arguments() + self.list_auxiliary_states()

    @property
    def name(self):
        if len(self._entries) == 1:
            return self._entries[0][0].name
        return None

    # ------------------------------------------------------------------
    # composition / indexing
    # ------------------------------------------------------------------
    def __getitem__(self, index):
        if isinstance(index, str):
            names = self.list_outputs()
            if index not in names:
                raise MXNetError("output '%s' not found; outputs=%s" % (index, names))
            index = names.index(index)
        if isinstance(index, slice):
            return Symbol(self._entries[index])
        return Symbol([self._entries[index]])

    def __len__(self):
        return len(self._entries)

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    def get_internals(self):
        entries = []
        for node in self._topo():
            for i in range(node.visible_out_count()):
                entries.append((node, i))
        return Symbol(entries)

    def get_children(self):
        node = self._entries[0][0]
        if not node.inputs:
            return None
        return Symbol([(n, i) for n, i in node.inputs])

    # ------------------------------------------------------------------
    # attrs
    # ------------------------------------------------------------------
    def attr(self, key):
        """User attribute lookup with the reference's dunder fallback:
        ``attr('lr_mult')`` finds a value stored as ``__lr_mult__`` and
        vice versa (conformance: the reference's test_attr reads both
        spellings of the same attribute)."""
        node = self._entries[0][0]
        if key in node.str_attrs:
            return node.str_attrs[key]
        if not (key.startswith("__") and key.endswith("__")):
            return node.str_attrs.get("__%s__" % key)
        stripped = key[2:-2]
        if stripped:
            return node.str_attrs.get(stripped)
        return None

    def list_attr(self, recursive=False):
        """Shallow user-attr dict of the head node (reference
        symbol.py list_attr; recursive aggregation moved to
        ``attr_dict`` in the reference too)."""
        if recursive:
            raise DeprecationWarning(
                "Symbol.list_attr with recursive=True has been deprecated; "
                "use attr_dict instead")
        return dict(self._entries[0][0].str_attrs)

    def attr_dict(self):
        """{node name: attrs} over the whole graph. Matches the
        reference's aggregation: user attrs verbatim, plus — for op
        nodes — the *explicitly given* op params as MXNet-style strings
        (the reference's nnvm attrs.dict holds only what the caller
        passed; filled-in defaults stay out)."""
        out = {}
        for node in self._topo():
            if node.str_attrs or not node.is_var:
                d = dict(node.str_attrs)
                d.update({k: _attr_to_str(v)
                          for k, v in node.explicit_attrs().items()})
                if d:
                    out[node.name] = d
        return out

    def _set_attr(self, **kwargs):
        self._entries[0][0].str_attrs.update(
            _expand_user_attrs({k: str(v) for k, v in kwargs.items()}))

    # ------------------------------------------------------------------
    # shape/type inference
    # ------------------------------------------------------------------
    def infer_shape(self, *args, **kwargs):
        arg_shapes, out_shapes, aux_shapes = self.infer_shape_partial(*args, **kwargs)
        if arg_shapes is not None and any(s is None for s in arg_shapes):
            missing = [n for n, s in zip(self.list_arguments(), arg_shapes) if s is None]
            raise MXNetError("infer_shape: cannot determine shapes of %s" % missing)
        return arg_shapes, out_shapes, aux_shapes

    def infer_shape_partial(self, *args, **kwargs):
        known = {}
        arg_names = self.list_arguments()
        if args:
            for n, s in zip(arg_names, args):
                if s is not None:
                    known[n] = tuple(s)
        for k, v in kwargs.items():
            if v is not None:
                known[k] = tuple(v)
        shapes, _ = self._infer(known, {})
        if shapes is None:
            return None, None, None
        arg_shapes = [shapes.get(n) for n in arg_names]
        aux_shapes = [shapes.get(n) for n in self.list_auxiliary_states()]
        out_shapes = [shapes.get(("out", id(node), idx))
                      for node, idx in self._entries]
        return arg_shapes, out_shapes, aux_shapes

    def infer_shape_type(self, shape_kwargs, type_kwargs=None):
        """Joint shape+dtype inference — needed because dtype propagation
        (bf16 data ⇒ bf16 weights) rides the same eval_shape pass. Returns
        (arg_shapes, arg_types, aux_shapes, aux_types)."""
        known_shapes = {k: tuple(v) for k, v in shape_kwargs.items()
                        if v is not None}
        known_dtypes = {k: _np.dtype(v) for k, v in (type_kwargs or {}).items()}
        shapes, dtypes = self._infer(known_shapes, known_dtypes)
        args = self.list_arguments()
        auxs = self.list_auxiliary_states()
        f32 = _np.dtype("float32")
        return ([shapes.get(n) for n in args],
                [dtypes.get(n, f32) for n in args],
                [shapes.get(n) for n in auxs],
                [dtypes.get(n, f32) for n in auxs])

    def infer_type(self, *args, **kwargs):
        known = {}
        arg_names = self.list_arguments()
        if args:
            for n, t in zip(arg_names, args):
                if t is not None:
                    known[n] = _np.dtype(t)
        for k, v in kwargs.items():
            known[k] = _np.dtype(v)
        _, dtypes = self._infer({}, known)
        if dtypes is None:
            return None, None, None
        arg_types = [dtypes.get(n, _np.dtype("float32")) for n in arg_names]
        aux_types = [dtypes.get(n, _np.dtype("float32"))
                     for n in self.list_auxiliary_states()]
        out_types = [dtypes.get(("out", id(node), idx), _np.dtype("float32"))
                     for node, idx in self._entries]
        return arg_types, out_types, aux_types

    def _infer(self, known_shapes, known_dtypes):
        """Forward propagation of shapes+dtypes through the DAG using
        jax.eval_shape per node, with backward param rules filling in
        variable shapes (ops/shape_rules.py)."""
        import jax

        shapes = dict(known_shapes)
        dtypes = dict(known_dtypes)
        env = {}  # (id(node), out_idx) -> jax.ShapeDtypeStruct | None
        # vars whose dtype wasn't given: provisionally fp32, upgraded to the
        # dtype of a sibling input on first use (the reference's same-type
        # FInferType default, e.g. bf16 data ⇒ bf16 conv weights)
        pending_dtype_vars = {}

        for node in self._topo():
            if node.is_var:
                shp = shapes.get(node.name)
                if shp is None and "__shape__" in node.str_attrs:
                    shp = _reg._parse_attr_string(node.str_attrs["__shape__"], None)
                    shapes[node.name] = tuple(shp)
                dt = dtypes.get(node.name)
                if dt is None and "__dtype__" in node.str_attrs:
                    dt = _np.dtype(node.str_attrs["__dtype__"])
                if dt is None:
                    pending_dtype_vars[id(node)] = node
                env[(id(node), 0)] = (
                    jax.ShapeDtypeStruct(tuple(shp), dt or _np.dtype("float32"))
                    if shp is not None else None)
                continue
            # same-dtype rule: resolve pending param-var dtypes from the
            # first input whose dtype is definitively known. Integer inputs
            # (Embedding/take indices, labels) never anchor — the reference's
            # FInferType same-type rule is a float-dtype rule; Embedding
            # weights take their dtype from the op's dtype attr, not the
            # index input.
            anchor = None
            for inp, oi in node.inputs:
                if not (inp.is_var and id(inp) in pending_dtype_vars):
                    sds = env.get((id(inp), oi))
                    if sds is not None and jax.numpy.issubdtype(
                            sds.dtype, _np.floating):  # bf16-aware check
                        anchor = sds.dtype
                        break
            if anchor is not None:
                for inp, oi in node.inputs:
                    if inp.is_var and id(inp) in pending_dtype_vars:
                        sds = env.get((id(inp), 0))
                        if sds is not None:
                            env[(id(inp), 0)] = jax.ShapeDtypeStruct(
                                sds.shape, anchor)
                        dtypes[inp.name] = _np.dtype(anchor)
                        del pending_dtype_vars[id(inp)]

            in_names = node.input_names()
            known_in = {}
            for (inp, oi), nm in zip(node.inputs, in_names):
                sds = env.get((id(inp), oi))
                known_in[nm] = tuple(sds.shape) if sds is not None else None
            # fill parameter-var shapes via backward rule
            if node.op.param_shapes is not None and any(
                    v is None for v in known_in.values()):
                inferred = node.op.param_shapes(known_in, node.attrs)
                for (inp, oi), nm in zip(node.inputs, in_names):
                    if known_in[nm] is None and nm in inferred and inp.is_var:
                        shp = tuple(inferred[nm])
                        prev = shapes.get(inp.name)
                        if prev is not None and tuple(prev) != shp:
                            raise MXNetError(
                                "shape mismatch for %s: %s vs %s"
                                % (inp.name, prev, shp))
                        shapes[inp.name] = shp
                        dt = dtypes.get(inp.name, _np.dtype("float32"))
                        env[(id(inp), oi)] = jax.ShapeDtypeStruct(shp, dt)
                        known_in[nm] = shp
            ins = [env.get((id(inp), oi)) for inp, oi in node.inputs]
            if any(x is None for x in ins):
                for i in range(node.out_count()):
                    env[(id(node), i)] = None
                continue
            with _reg._OpCtxScope(True, None):
                try:
                    out = jax.eval_shape(lambda *xs: node.apply(xs), *ins)
                except Exception as e:  # surface the node for debuggability
                    raise MXNetError("shape inference failed at node %s(%s): %s"
                                     % (node.op.name, node.name, e)) from e
            outs = list(out) if isinstance(out, (tuple, list)) else [out]
            for i, sds in enumerate(outs):
                env[(id(node), i)] = sds

        for node, idx in self._entries:
            sds = env.get((id(node), idx))
            if sds is not None:
                shapes[("out", id(node), idx)] = tuple(sds.shape)
                dtypes[("out", id(node), idx)] = _np.dtype(sds.dtype)
        # record dtypes for vars
        for node in self._topo():
            if node.is_var:
                sds = env.get((id(node), 0))
                if sds is not None:
                    dtypes.setdefault(node.name, _np.dtype(sds.dtype))
        return shapes, dtypes

    # ------------------------------------------------------------------
    # serialization — reference symbol.json format
    # ------------------------------------------------------------------
    def tojson(self):
        nodes = self._topo()
        nid = {id(n): i for i, n in enumerate(nodes)}
        jnodes = []
        arg_nodes = []
        for i, n in enumerate(nodes):
            if n.is_var:
                arg_nodes.append(i)
            # explicit params only — the reference's symbol.json carries
            # what the caller passed, never parser-filled defaults (and
            # load_json can then recover the explicit set exactly)
            attrs = {k: _attr_to_str(v)
                     for k, v in n.explicit_attrs().items()}
            attrs.update(n.str_attrs)
            jn = {"op": "null" if n.is_var else n.op.name,
                  "name": n.name,
                  "inputs": [[nid[id(inp)], oi, 0] for inp, oi in n.inputs]}
            if n.cf_meta is not None:
                # control-flow node: nested graphs ride the reference's
                # "subgraphs" field; the rebuild recipe rides one JSON
                # attr (merged with user attrs like ctx_group)
                meta = dict(n.cf_meta)
                subs = meta.pop("subgraphs")
                jn["subgraphs"] = [json.loads(s.tojson()) for s in subs]
                attrs = dict(n.str_attrs)
                attrs["cf_meta"] = json.dumps(meta)
            if attrs:
                jn["attrs"] = attrs
            jnodes.append(jn)
        heads = [[nid[id(n)], oi, 0] for n, oi in self._entries]
        return json.dumps({"nodes": jnodes, "arg_nodes": arg_nodes,
                           "heads": heads,
                           "attrs": {"mxnet_version": ["str", "tpu-native-0.1"]}},
                          indent=2)

    def save(self, fname):
        with open(fname, "w") as f:
            f.write(self.tojson())

    # ------------------------------------------------------------------
    # binding/eval — implemented in executor.py
    # ------------------------------------------------------------------
    def simple_bind(self, ctx=None, grad_req="write", type_dict=None,
                    stype_dict=None, group2ctx=None, shared_arg_names=None,
                    shared_exec=None, shared_buffer=None, **kwargs):
        from ..executor import Executor
        return Executor._simple_bind(self, ctx, grad_req, type_dict,
                                     group2ctx, shared_exec, shared_buffer,
                                     kwargs)

    def bind(self, ctx, args, args_grad=None, grad_req="write",
             aux_states=None, group2ctx=None, shared_exec=None):
        from ..executor import Executor
        return Executor._bind(self, ctx, args, args_grad, grad_req,
                              aux_states, group2ctx, shared_exec)

    def eval(self, ctx=None, **kwargs):
        ex = self.bind(ctx, kwargs)
        return ex.forward()

    def __call__(self, *args, **kwargs):
        # composition: replace variable nodes with given symbols
        return self._compose(*args, **kwargs)

    def _compose(self, *args, **kwargs):
        if args and kwargs:
            raise MXNetError("compose accepts positional or keyword, not both")
        arg_names = self.list_arguments()
        mapping = dict(zip(arg_names, args)) if args else dict(kwargs)
        memo = {}

        def rebuild(node):
            if id(node) in memo:
                return memo[id(node)]
            if node.is_var and node.name in mapping:
                new = mapping[node.name]._entries[0][0]
            elif node.is_var:
                new = node
            else:
                new = _Node(node.op, node.name, dict(node.attrs),
                            [(rebuild(i), oi) for i, oi in node.inputs],
                            node.str_attrs, given_attrs=node.given_attrs)
            memo[id(node)] = new
            return new

        return Symbol([(rebuild(n), oi) for n, oi in self._entries])

    # ------------------------------------------------------------------
    # operators — mirror NDArray's surface
    # ------------------------------------------------------------------
    def _binop(self, other, op, scalar_op, reverse=False):
        from . import _invoke_op, _invoke_scalar
        if isinstance(other, Symbol):
            a, b = (other, self) if reverse else (self, other)
            return _invoke_op(op, [a, b])
        from ..base import numeric_types
        if isinstance(other, numeric_types):
            return _invoke_scalar(scalar_op, self, float(other), reverse)
        return NotImplemented

    def __add__(self, o): return self._binop(o, "broadcast_add", "_plus_scalar")
    def __radd__(self, o): return self._binop(o, "broadcast_add", "_plus_scalar", True)
    def __sub__(self, o): return self._binop(o, "broadcast_sub", "_minus_scalar")
    def __rsub__(self, o): return self._binop(o, "broadcast_sub", "_minus_scalar", True)
    def __mul__(self, o): return self._binop(o, "broadcast_mul", "_mul_scalar")
    def __rmul__(self, o): return self._binop(o, "broadcast_mul", "_mul_scalar", True)
    def __truediv__(self, o): return self._binop(o, "broadcast_div", "_div_scalar")
    def __rtruediv__(self, o): return self._binop(o, "broadcast_div", "_div_scalar", True)
    def __pow__(self, o): return self._binop(o, "broadcast_power", "_power_scalar")
    def __neg__(self): return self._binop(-1.0, None, "_mul_scalar")
    def __eq__(self, o): return self._binop(o, "broadcast_equal", "_equal_scalar")
    def __ne__(self, o): return self._binop(o, "broadcast_not_equal", "_not_equal_scalar")
    def __gt__(self, o): return self._binop(o, "broadcast_greater", "_greater_scalar")
    def __ge__(self, o): return self._binop(o, "broadcast_greater_equal", "_greater_equal_scalar")
    def __lt__(self, o): return self._binop(o, "broadcast_lesser", "_lesser_scalar")
    def __le__(self, o): return self._binop(o, "broadcast_lesser_equal", "_lesser_equal_scalar")

    def __hash__(self):
        return id(self)

    # pickling rides the JSON wire format (the reference pickles through
    # tojson/load_json the same way, symbol.py __getstate__): _Node/OpDef
    # object graphs never enter the pickle, so compiled-cache handles and
    # op closures can't leak in
    def __getstate__(self):
        return {"handle": self.tojson()}

    def __setstate__(self, state):
        self._entries = load_json(state["handle"])._entries

    def __repr__(self):
        outs = self.list_outputs()
        return "<Symbol %s>" % (self.name or ("group [%s]" % ", ".join(outs[:4])))

    # common method surface delegating to ops
    def _unary(self, op, **attrs):
        from . import _invoke_op
        return _invoke_op(op, [self], attrs)

    def reshape(self, shape, **kw): return self._unary("Reshape", shape=tuple(shape))
    def astype(self, dtype): return self._unary("Cast", dtype=str(_np.dtype(dtype)))
    def transpose(self, axes=()): return self._unary("transpose", axes=tuple(axes))
    def flatten(self): return self._unary("Flatten")
    def sum(self, axis=None, keepdims=False):
        return self._unary("sum", axis=axis, keepdims=keepdims)
    def mean(self, axis=None, keepdims=False):
        return self._unary("mean", axis=axis, keepdims=keepdims)
    def max(self, axis=None, keepdims=False):
        return self._unary("max", axis=axis, keepdims=keepdims)
    def slice_axis(self, axis, begin, end):
        return self._unary("slice_axis", axis=axis, begin=begin, end=end)
    def expand_dims(self, axis): return self._unary("expand_dims", axis=axis)
    def squeeze(self, axis=None): return self._unary("squeeze", axis=axis)
    def softmax(self, axis=-1): return self._unary("softmax", axis=axis)
    def exp(self): return self._unary("exp")
    def log(self): return self._unary("log")
    def sqrt(self): return self._unary("sqrt")
    def square(self): return self._unary("square")
    def abs(self): return self._unary("abs")
    def sigmoid(self): return self._unary("sigmoid")
    def tanh(self): return self._unary("tanh")
    def relu(self): return self._unary("relu")


def _attr_to_str(v):
    if isinstance(v, bool):
        return str(v)
    if isinstance(v, (tuple, list)):
        return "(" + ", ".join(str(x) for x in v) + ")"
    if v is None:
        return "None"
    return str(v)


# the user attrs the framework itself consumes in dunder form
# (optimizer lr/wd multipliers, the mirroring hint) — a plain-spelled
# one is mirrored to its dunder twin at store time, like the reference
_MIRRORED_USER_ATTRS = ("lr_mult", "wd_mult", "force_mirroring")


def _expand_user_attrs(attrs):
    """Mirror recognized plain keys to their dunder twins so both
    spellings list (conformance: test_attr reads attr('lr_mult') and
    attr('__lr_mult__') after setting either one)."""
    out = dict(attrs)
    for key in _MIRRORED_USER_ATTRS:
        if key in out and ("__%s__" % key) not in out:
            out["__%s__" % key] = str(out[key])
    return out


def Variable(name, attr=None, shape=None, lr_mult=None, wd_mult=None,
             dtype=None, init=None, stype=None, **kwargs):
    if not isinstance(name, str):
        raise TypeError("Variable name must be a string")
    str_attrs = AttrScope.current_attrs()
    if attr:
        str_attrs.update({k: str(v) for k, v in attr.items()})
    str_attrs = _expand_user_attrs(str_attrs)
    if shape is not None:
        str_attrs["__shape__"] = str(tuple(shape))
    if dtype is not None:
        str_attrs["__dtype__"] = str(_np.dtype(dtype))
    if lr_mult is not None:
        str_attrs["__lr_mult__"] = str(lr_mult)
    if wd_mult is not None:
        str_attrs["__wd_mult__"] = str(wd_mult)
    if init is not None:
        str_attrs["__init__"] = init if isinstance(init, str) else init.dumps()
    for k, v in kwargs.items():
        if k.startswith("__") and k.endswith("__"):
            # free-form dunder kwargs attach as user attrs (reference
            # symbol.py var(): "Additional attributes must start and end
            # with double underscores")
            str_attrs[k] = str(v)
        else:
            raise ValueError(
                "Variable attribute name=%s is not supported. Additional "
                "attributes must start and end with double underscores, "
                "e.g. __yourattr__" % k)
    node = _Node(None, name, {}, [], str_attrs)
    return Symbol([(node, 0)])


var = Variable


def Group(symbols):
    entries = []
    for s in symbols:
        entries.extend(s._entries)
    return Symbol(entries)


def load(fname):
    with open(fname) as f:
        return load_json(f.read())


def load_json(json_str):
    """Parse a graph JSON, including every legacy layout the reference
    upgrades in src/nnvm/legacy_json_util.cc:43 (UpgradeJSON_*): op
    params under "param" (pre-0.9), user attrs under "attr" (0.9-1.1),
    and the merged "attrs" dict (1.2+) whose values are MXNet-style
    strings like "(3, 3)" / "True" (coerced per-op by
    OpDef.normalize_attrs)."""
    return _load_graph_dict(json.loads(json_str))


def _load_graph_dict(data):
    jnodes = data["nodes"]
    nodes = []
    for jn in jnodes:
        attrs = dict(jn.get("param", {}) or {})
        attrs.update(jn.get("attr", {}) or {})
        attrs.update(jn.get("attrs", {}) or {})
        inputs = [(nodes[i], oi) for i, oi, *_ in jn["inputs"]]
        if jn["op"] == "null":
            nodes.append(_Node(None, jn["name"], {}, [], attrs))
        elif jn.get("subgraphs"):
            # control-flow node: rebuild the lax lowering from the
            # nested graphs + the cf_meta recipe (contrib._rebuild_cf);
            # user attrs (ctx_group, ...) pass through
            from . import contrib as _cf
            subs = [_load_graph_dict(g) for g in jn["subgraphs"]]
            meta = json.loads(attrs["cf_meta"])
            meta["subgraphs"] = subs
            opdef, n_out = _cf._rebuild_cf(jn["op"], meta)
            user = {k: v for k, v in attrs.items() if k != "cf_meta"}
            nodes.append(_Node(opdef, jn["name"], {}, inputs,
                               str_attrs=user, cf_meta=meta))
        else:
            opdef = _reg.get_op(jn["op"])
            given = [k for k in attrs if k in opdef.attr_names]
            typed = opdef.normalize_attrs({k: attrs[k] for k in given})
            user = {k: v for k, v in attrs.items() if k not in opdef.attr_names}
            nodes.append(_Node(opdef, jn["name"], typed, inputs, user,
                               given_attrs=given))
    heads = data["heads"]
    return Symbol([(nodes[h[0]], h[1]) for h in heads])
