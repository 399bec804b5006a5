"""A loss head's output, deferred (docs/TRAINING.md, "What a fused
step returns").

A training graph ends in a normalising loss head: ``SoftmaxOutput``
over the logits, usually behind a ``Cast`` to float32 and a ``Reshape``.
Its value, the probabilities, has tokens x vocabulary elements, and the
backward does not need it written: the head's gradient is rebuilt from
the logits inside the fusions that consume it.  A fit program that
RETURNS the probabilities makes the compiler write that array (for a
language model the largest of the step) for a reader that seldom comes.

So the fused fit program (module/fused_fit.py) returns the head's
**stem** instead: the value the head's cheap chain starts from, as its
producer wrote it (the bfloat16 logits).  The chain back from the head
runs through single-consumer view and elementwise nodes only.  This
module holds the three pieces:

* :func:`plans` reads the graph: which outputs are such heads, and
  their chains;
* :class:`DeferredHead` is what a device metric gets in the head's
  place inside the program: the full value for a metric that asks for
  it, the probabilities AT THE LABELS computed from the stem for the
  metrics that read nothing else (``ce``, ``nll_loss``, ``perplexity``);
* :class:`DeferredOutputs` is what ``Executor._outputs`` holds after
  such a step: on the first read of ``Executor.outputs`` a small cached
  program runs the chain's own operators and the head over the stem,
  once, and the result stays until the next step.
"""
from __future__ import annotations

import collections

import jax
import jax.numpy as jnp

from . import telemetry as _telemetry
from .executor import _count_dispatch, _note_retrace, _timed_dispatch
from .ndarray import NDArray
from .ops import registry as _reg

__all__ = ["plans", "tail_program", "DeferredHead", "DeferredOutputs",
           "DEFERRED", "MATERIALIZED"]

DEFERRED = _telemetry.REGISTRY.counter(
    "fit_outputs_deferred",
    "fused fit steps whose program returned a loss head's stem in place "
    "of the head's value", unit="steps", vital=True)
MATERIALIZED = _telemetry.REGISTRY.counter(
    "fit_outputs_materialized",
    "on-demand tail programs run: deferred loss-head outputs built "
    "because Executor.outputs was read", unit="programs", vital=True)

# what may be deferred, and what its chain may run through: operators
# that cost nothing beside the array they read
_HEADS = frozenset(("SoftmaxOutput",))
_VIEWS = frozenset(("Reshape", "Flatten"))
_ELEMENTWISE = frozenset(("Cast",))
_CHAIN = _VIEWS | _ELEMENTWISE

# index: the head's position among the graph's outputs; stem: the
# (node, output index) the chain starts from; chain: the nodes from
# the stem to the head's data input, in graph order; label: the name
# of the variable the head takes its labels from
HeadPlan = collections.namedtuple("HeadPlan",
                                  "index stem chain head label")


def plans(symbol):
    """The outputs of ``symbol`` that are loss heads behind a cheap
    chain, as :class:`HeadPlan` s; cached on the symbol."""
    found = getattr(symbol, "_loss_head_plans", None)
    if found is not None:
        return found
    entries = list(symbol._entries)
    readers = collections.Counter()
    for node in symbol._topo():
        for inp, oi in node.inputs:
            readers[(id(inp), oi)] += 1
    for node, oi in entries:
        readers[(id(node), oi)] += 1
    found = []
    for index, (head, oi) in enumerate(entries):
        if head.is_var or head.op.name not in _HEADS or oi != 0 \
                or readers[(id(head), 0)] != 1 or len(head.inputs) != 2 \
                or not head.inputs[1][0].is_var:
            continue
        chain = []
        node, oi = head.inputs[0]
        while not node.is_var and len(node.inputs) == 1 \
                and node.op.name in _CHAIN \
                and readers[(id(node), oi)] == 1:
            chain.append(node)
            node, oi = node.inputs[0]
        found.append(HeadPlan(index, (node, oi), tuple(reversed(chain)),
                              head, head.inputs[1][0].name))
    symbol._loss_head_plans = found = tuple(found)
    return found


def _run(node, *ins):
    with jax.named_scope(node.op.name), jax.named_scope(node.name):
        return node.op.fn(*ins, **node.attrs)


def _chain(plan, x, kinds):
    for node in plan.chain:
        if node.op.name in kinds:
            x = _run(node, x)
    return x


class DeferredHead:
    """A deferred head inside the fit program, as a device metric sees
    it.  ``value`` is the head's full value as the graph traced it (the
    compiler builds it only if something reads it); :meth:`at_labels`
    reads the stem."""

    def __init__(self, plan, stem, value):
        self._plan = plan
        self._stem = stem
        self.value = value

    def at_labels(self, label):
        """``value.reshape(-1, classes)[arange, label]`` for flat int32
        ``label``, without the value: ``exp(x_label - max) / sum`` with
        the row statistics taken over the stem the way
        ``jax.nn.softmax`` takes them (so the compiler shares them with
        the backward's).  ``x_label`` is a third sum over the same rows,
        of the one entry the label names: it reads the stem in whatever
        layout its producer chose, where a gather has the compiler copy
        the whole stem into the layout a gather wants.  The labels are
        wrapped and clamped as the indexing expression does it."""
        plan = self._plan
        if plan.head.attrs.get("multi_output"):
            value = self.value.reshape(-1, self.value.shape[-1])
            return value[jnp.arange(label.shape[0]), label]
        with jax.named_scope(plan.head.op.name), \
                jax.named_scope(plan.head.name):
            # a view and an elementwise operator commute: the chain's
            # views first, then its casts inside the sums that read them
            x = _chain(plan, self._stem, _VIEWS)
            x = _chain(plan, x.reshape(-1, x.shape[-1]), _ELEMENTWISE)
            classes = x.shape[-1]
            x_max = jnp.max(x, axis=-1, keepdims=True)
            total = jnp.sum(jnp.exp(x - jax.lax.stop_gradient(x_max)),
                            axis=-1, keepdims=True)
            label = jnp.clip(jnp.where(label < 0, label + classes, label),
                             0, classes - 1)
            named = jnp.arange(classes, dtype=label.dtype) == label[:, None]
            at = jnp.sum(jnp.where(named, x, 0), axis=-1, keepdims=True)
            return (jnp.exp(at - x_max) / total)[:, 0]


def tail_program(head_plans):
    """The jitted program ``(stems, labels) -> the heads' values``: the
    chain's own operators, then the head, in graph order."""
    def tail(stems, labels):
        _note_retrace()
        with _reg._OpCtxScope(True, jax.random.key(0)):
            return [_run(plan.head, _chain(plan, x, _CHAIN), label)
                    for plan, x, label in zip(head_plans, stems, labels)]

    return jax.jit(tail)


class DeferredOutputs:
    """What ``Executor._outputs`` holds after a fused step that deferred
    its heads: call it for the list of outputs.  ``Executor.outputs``
    does, once, and keeps the list."""

    def __init__(self, ctx, outs, stems, head_plans, labels, tail):
        self._ctx = ctx
        self._outs = outs          # the program's outputs, None at a head
        self._stems = stems
        self._plans = head_plans
        self._labels = labels
        self._tail = tail

    def __call__(self):
        outs = list(self._outs)
        _count_dispatch()
        heads = _timed_dispatch(self._tail, self._stems, self._labels)
        MATERIALIZED.inc()
        for plan, value in zip(self._plans, heads):
            outs[plan.index] = value
        return [NDArray(o, self._ctx) for o in outs]
