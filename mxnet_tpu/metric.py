"""Evaluation metrics (reference parity: python/mxnet/metric.py, ~20 metrics).

TPU-native addition (docs/TRAINING.md): metrics can accumulate ON DEVICE.
A metric that implements :meth:`EvalMetric.device_fn` hands the fused fit
step (module/fused_fit.py) a pure jnp function ``(labels, preds) ->
(batch_sum, batch_num)``; the step folds it into the one compiled training
program and keeps ``sum_metric``/``num_inst`` as device scalars. The host
reads them back only when :meth:`get` is called (Speedometer frequency /
epoch boundaries), so the per-batch fit loop never blocks on ``asnumpy``.
``fit_host_syncs`` (profiler counter) witnesses every blocking readback
the metric layer performs.
"""
from __future__ import annotations

import math
from time import perf_counter_ns as _now_ns

import numpy as _np

from .base import MXNetError
from . import profiler as _profiler
from .telemetry import tracing as _tracing

__all__ = ["EvalMetric", "CompositeEvalMetric", "Accuracy", "TopKAccuracy",
           "F1", "MCC", "Perplexity", "MAE", "MSE", "RMSE", "CrossEntropy",
           "NegativeLogLikelihood", "PearsonCorrelation", "Loss", "Torch",
           "Caffe", "CustomMetric", "np", "create", "check_label_shapes"]

_METRIC_REGISTRY = {}


def register(klass=None, *names):
    if klass is None or isinstance(klass, str):
        extra = ([klass] if isinstance(klass, str) else []) + list(names)

        def deco(k):
            _METRIC_REGISTRY[k.__name__.lower()] = k
            for n in extra:
                _METRIC_REGISTRY[n] = k
            return k
        return deco
    _METRIC_REGISTRY[klass.__name__.lower()] = klass
    return klass


def create(metric, *args, **kwargs):
    if callable(metric) and not isinstance(metric, type):
        return CustomMetric(metric, *args, **kwargs)
    if isinstance(metric, EvalMetric):
        return metric
    if isinstance(metric, list):
        composite = CompositeEvalMetric()
        for child in metric:
            composite.add(create(child, *args, **kwargs))
        return composite
    key = str(metric).lower()
    if key not in _METRIC_REGISTRY:
        raise MXNetError("unknown metric '%s'" % metric)
    return _METRIC_REGISTRY[key](*args, **kwargs)


def check_label_shapes(labels, preds, wrap=False, shape=False):
    if not shape:
        label_shape, pred_shape = len(labels), len(preds)
    else:
        label_shape, pred_shape = labels.shape, preds.shape
    if label_shape != pred_shape:
        raise ValueError("Shape of labels %s does not match shape of "
                         "predictions %s" % (label_shape, pred_shape))
    if wrap:
        if not isinstance(labels, (list, tuple)):
            labels = [labels]
        if not isinstance(preds, (list, tuple)):
            preds = [preds]
    return labels, preds


# the fit loop's host-sync witness (tests/test_fused_fit.py,
# tests/test_sentinel.py): incremented on every blocking device->host
# readback the metric layer performs — per-batch update() conversions on
# the eager path, get()-time accumulator folds on the device path.
# Registry-backed (telemetry series ``fit_host_syncs``): this name is a
# live alias over mx.telemetry — see docs/OBSERVABILITY.md.
_fit_domain = _profiler.Domain("fit")
HOST_SYNCS = _fit_domain.new_counter("fit_host_syncs", vital=True)


def consume_device_batch(metric):
    """True — and clears the marker — when the fused fit step already
    folded the current batch into ``metric``'s device accumulator;
    callers must then skip the host update for this batch. The ONE
    implementation of the consume-and-clear protocol (used by both
    update_dict and executor_group.update_metric)."""
    if getattr(metric, "_device_consumed", False):
        metric._device_consumed = False
        return True
    return False


def _asnp(x):
    """The one device-aware conversion helper: labels/preds/losses of any
    flavor (NDArray, jax array, numpy, list) to numpy, counting a host
    sync whenever the value was device-resident."""
    if isinstance(x, _np.ndarray):
        return x
    if hasattr(x, "asnumpy"):
        HOST_SYNCS.increment()
        return x.asnumpy()
    if hasattr(x, "devices"):        # bare jax.Array
        HOST_SYNCS.increment()
    return _np.asarray(x)


def _probs_at_labels(pred, label):
    """``pred.reshape(-1, classes)[arange, label]`` inside a device
    metric, for flat int32 ``label``.  Where the fused fit program
    deferred the loss head (loss_head.DeferredHead), the head computes
    these few probabilities from its stem: the array of all of them is
    then never built."""
    at_labels = getattr(pred, "at_labels", None)
    if at_labels is not None:
        return at_labels(label)
    import jax.numpy as jnp
    pred = pred.reshape(-1, pred.shape[-1])
    return pred[jnp.arange(label.shape[0]), label]


class EvalMetric:
    # device-resident accumulator (fed by the fused fit step); None means
    # "host accumulation only". _device_consumed marks a batch the fused
    # step already folded on device, so the fit loop's update_metric call
    # must not convert the same preds again.
    _dev_sum = None
    _dev_num = None
    _device_consumed = False
    # True where device_fn reads a prediction through _probs_at_labels
    # only: the fit program then hands it a deferred loss head as it is,
    # and every other metric the head's full value
    device_reads_at_labels = False

    def __init__(self, name, output_names=None, label_names=None, **kwargs):
        self.name = str(name)
        self.output_names = output_names
        self.label_names = label_names
        self._kwargs = kwargs
        self.reset()

    def reset(self):
        self.num_inst = 0
        self.sum_metric = 0.0
        self._dev_sum = None
        self._dev_num = None
        self._device_consumed = False

    def update(self, labels, preds):
        raise NotImplementedError

    def update_dict(self, label, pred):
        if consume_device_batch(self):
            # the fused fit step already folded this batch into the
            # device accumulator — don't convert the preds a second time
            return
        if self.output_names is not None:
            pred = [pred[name] for name in self.output_names if name in pred]
        else:
            pred = list(pred.values())
        if self.label_names is not None:
            label = [label[name] for name in self.label_names if name in label]
        else:
            label = list(label.values())
        self.update(label, pred)

    # -- device-side accumulation (module/fused_fit.py) -----------------
    def device_fn(self):
        """A pure jnp function ``(labels, preds) -> (batch_sum,
        batch_num)`` mirroring :meth:`update`, or None when this metric
        must accumulate on the host. The fused fit step folds it into
        the one compiled training program."""
        return None

    def device_sig(self):
        """Hashable config distinguishing compiled metric variants (part
        of the fused-step program cache key)."""
        return None

    def _totals(self):
        """(sum, num) with the device accumulator folded in — a blocking
        readback ONLY when device scalars are pending (get()-time, i.e.
        Speedometer frequency / epoch boundaries)."""
        if self._dev_sum is None:
            return self.sum_metric, self.num_inst
        HOST_SYNCS.increment()
        # on the profiler's clock (docs/OBSERVABILITY.md): the wait for
        # the step (the device is busy) apart from the two scalar
        # transfers (the device waits).  The same three boundaries go
        # into the open fit step's record (tracing.steps()): the wait of
        # its first readback, the end of its last
        rec = _tracing.open_step()
        with _tracing.span("metric.readback"):
            if rec is not None and rec.wait0 is None:
                rec.wait0 = _now_ns()
            with _tracing.span("metric.wait"):
                self._dev_sum.block_until_ready()
            if rec is not None and rec.wait1 is None:
                rec.wait1 = _now_ns()
            with _tracing.span("metric.transfer"):
                totals = (self.sum_metric + float(self._dev_sum),
                          self.num_inst + float(self._dev_num))
            if rec is not None:
                rec.transfer1 = _now_ns()
            return totals

    def get(self):
        total, num = self._totals()
        if num == 0:
            return (self.name, float("nan"))
        return (self.name, total / num)

    def get_name_value(self):
        name, value = self.get()
        if not isinstance(name, list):
            name = [name]
        if not isinstance(value, list):
            value = [value]
        return list(zip(name, value))

    def get_config(self):
        return {"metric": self.__class__.__name__, "name": self.name,
                **self._kwargs}

    def __str__(self):
        return "EvalMetric: %s" % dict(self.get_name_value())


@register(None, "composite")
class CompositeEvalMetric(EvalMetric):
    def __init__(self, metrics=None, name="composite", output_names=None,
                 label_names=None):
        super().__init__(name, output_names, label_names)
        self.metrics = [create(m) for m in (metrics or [])]

    def add(self, metric):
        self.metrics.append(create(metric))

    def get_metric(self, index):
        return self.metrics[index]

    def update(self, labels, preds):
        for metric in self.metrics:
            metric.update(labels, preds)

    def update_dict(self, labels, preds):
        for metric in self.metrics:
            metric.update_dict(labels, preds)

    def reset(self):
        for metric in getattr(self, "metrics", []):
            metric.reset()

    def get(self):
        names, values = [], []
        for metric in self.metrics:
            name, value = metric.get()
            names.append(name) if not isinstance(name, list) else names.extend(name)
            values.append(value) if not isinstance(value, list) else values.extend(value)
        return names, values


@register(None, "acc")
class Accuracy(EvalMetric):
    def __init__(self, axis=1, name="accuracy", output_names=None,
                 label_names=None):
        super().__init__(name, output_names, label_names, axis=axis)
        self.axis = axis

    def update(self, labels, preds):
        labels, preds = check_label_shapes(labels, preds, True)
        for label, pred in zip(labels, preds):
            label = _asnp(label).astype("int32")
            pred = _asnp(pred)
            if pred.ndim > label.ndim:
                pred = pred.argmax(axis=self.axis)
            label = label.reshape(-1)
            pred = pred.astype("int32").reshape(-1)
            self.sum_metric += (label == pred).sum()
            self.num_inst += label.size

    def device_fn(self):
        import jax.numpy as jnp
        axis = self.axis

        def fn(labels, preds):
            s = jnp.float32(0.0)
            n = 0
            for label, pred in zip(labels, preds):
                label = label.astype(jnp.int32)
                if pred.ndim > label.ndim:
                    pred = jnp.argmax(pred, axis=axis)
                label = label.reshape(-1)
                pred = pred.astype(jnp.int32).reshape(-1)
                s = s + (label == pred).sum().astype(jnp.float32)
                n += label.size
            return s, jnp.float32(n)
        return fn

    def device_sig(self):
        return ("accuracy", self.axis)


@register(None, "topkaccuracy", "top_k_accuracy")
class TopKAccuracy(EvalMetric):
    def __init__(self, top_k=1, name="top_k_accuracy", output_names=None,
                 label_names=None):
        super().__init__("%s_%d" % (name, top_k), output_names, label_names,
                         top_k=top_k)
        self.top_k = top_k

    def update(self, labels, preds):
        labels, preds = check_label_shapes(labels, preds, True)
        for label, pred in zip(labels, preds):
            label = _asnp(label).astype("int32")
            pred = _asnp(pred)
            topk = _np.argsort(pred, axis=-1)[:, -self.top_k:]
            for j in range(self.top_k):
                self.sum_metric += (topk[:, j].flatten() == label.flatten()).sum()
            self.num_inst += len(label.flatten())

    def device_fn(self):
        import jax.numpy as jnp
        top_k = self.top_k

        def fn(labels, preds):
            s = jnp.float32(0.0)
            n = 0
            for label, pred in zip(labels, preds):
                label = label.astype(jnp.int32).reshape(-1)
                topk = jnp.argsort(pred, axis=-1)[:, -top_k:]
                s = s + (topk == label[:, None]).sum().astype(jnp.float32)
                n += label.size
            return s, jnp.float32(n)
        return fn

    def device_sig(self):
        return ("top_k_accuracy", self.top_k)


def _binary_counts(label, pred, check_binary=False, metric_name=""):
    """(tp, fp, fn, tn) for one (label, pred) pair — the shared
    sufficient statistics of F1/MCC (ref metric.py
    _BinaryClassificationMetrics.update_binary_stats)."""
    label = _asnp(label).flatten().astype("int32")
    pred = _asnp(pred)
    if pred.ndim > 1 and pred.shape[-1] > 1:
        pred = pred.argmax(axis=-1)
    pred = pred.flatten().astype("int32")
    if check_binary and _np.unique(label).size > 2:
        raise ValueError("%s currently only supports binary "
                         "classification." % metric_name)
    return (((pred == 1) & (label == 1)).sum(),
            ((pred == 1) & (label == 0)).sum(),
            ((pred == 0) & (label == 1)).sum(),
            ((pred == 0) & (label == 0)).sum())


@register
class F1(EvalMetric):
    def __init__(self, name="f1", output_names=None, label_names=None,
                 average="macro"):
        super().__init__(name, output_names, label_names)
        self.average = average
        self._tp = self._fp = self._fn = 0.0

    def reset(self):
        super().reset()
        self._tp = self._fp = self._fn = 0.0

    def update(self, labels, preds):
        labels, preds = check_label_shapes(labels, preds, True)
        for label, pred in zip(labels, preds):
            tp, fp, fn, _ = _binary_counts(label, pred)
            self._tp += tp
            self._fp += fp
            self._fn += fn
            precision = self._tp / max(self._tp + self._fp, 1e-12)
            recall = self._tp / max(self._tp + self._fn, 1e-12)
            f1 = 2 * precision * recall / max(precision + recall, 1e-12)
            self.sum_metric = f1
            self.num_inst = 1


@register
class MCC(EvalMetric):
    """Matthews correlation coefficient for binary classification
    (ref metric.py MCC over _BinaryClassificationMetrics: tp/fp/tn/fn
    accumulated across batches; 'micro' averages over all samples,
    'macro' re-reports per batch)."""

    def __init__(self, name="mcc", output_names=None, label_names=None,
                 average="macro"):
        super().__init__(name, output_names, label_names)
        self.average = average
        self._tp = self._fp = self._tn = self._fn = 0.0

    def reset(self):
        super().reset()
        self._tp = self._fp = self._tn = self._fn = 0.0

    def _mcc(self):
        terms = ((self._tp + self._fp) * (self._tp + self._fn)
                 * (self._tn + self._fp) * (self._tn + self._fn))
        denom = terms ** 0.5 if terms > 0 else 1.0
        return (self._tp * self._tn - self._fp * self._fn) / denom

    def update(self, labels, preds):
        labels, preds = check_label_shapes(labels, preds, True)
        for label, pred in zip(labels, preds):
            tp, fp, fn, tn = _binary_counts(label, pred,
                                            check_binary=True,
                                            metric_name="MCC")
            self._tp += tp
            self._fp += fp
            self._fn += fn
            self._tn += tn
            if self.average == "macro":
                # mean of per-batch MCCs (reference macro resets counts)
                self.sum_metric += self._mcc()
                self.num_inst += 1
                self._tp = self._fp = self._tn = self._fn = 0.0
            else:
                # micro: one MCC over all samples seen so far
                self.sum_metric = self._mcc()
                self.num_inst = 1


@register
class Perplexity(EvalMetric):
    device_reads_at_labels = True

    def __init__(self, ignore_label=None, axis=-1, name="perplexity",
                 output_names=None, label_names=None):
        super().__init__(name, output_names, label_names,
                         ignore_label=ignore_label)
        self.ignore_label = ignore_label
        self.axis = axis

    def update(self, labels, preds):
        loss = 0.0
        num = 0
        for label, pred in zip(labels, preds):
            label = _asnp(label).astype("int32").flatten()
            pred = _asnp(pred)
            pred = pred.reshape(-1, pred.shape[-1])
            probs = pred[_np.arange(label.size), label]
            if self.ignore_label is not None:
                ignore = (label == self.ignore_label)
                probs = _np.where(ignore, 1.0, probs)
                num -= ignore.sum()
            loss -= _np.sum(_np.log(_np.maximum(1e-10, probs)))
            num += label.size
        self.sum_metric += loss
        self.num_inst += num

    def device_fn(self):
        import jax.numpy as jnp
        ignore_label = self.ignore_label

        def fn(labels, preds):
            loss = jnp.float32(0.0)
            num = jnp.float32(0.0)
            for label, pred in zip(labels, preds):
                label = label.reshape(-1).astype(jnp.int32)
                probs = _probs_at_labels(pred, label)
                num = num + jnp.float32(label.shape[0])
                if ignore_label is not None:
                    ignore = (label == ignore_label)
                    probs = jnp.where(ignore, 1.0, probs)
                    num = num - ignore.sum().astype(jnp.float32)
                loss = loss - jnp.log(jnp.maximum(1e-10, probs)).sum()
            return loss, num
        return fn

    def device_sig(self):
        return ("perplexity", self.ignore_label)

    def get(self):
        total, num = self._totals()
        if num == 0:
            return (self.name, float("nan"))
        return (self.name, math.exp(total / num))


@register
class MAE(EvalMetric):
    def __init__(self, name="mae", output_names=None, label_names=None):
        super().__init__(name, output_names, label_names)

    def update(self, labels, preds):
        labels, preds = check_label_shapes(labels, preds, True)
        for label, pred in zip(labels, preds):
            label, pred = _asnp(label), _asnp(pred)
            if label.ndim == 1:
                label = label.reshape(label.shape[0], 1)
            if pred.ndim == 1:
                pred = pred.reshape(pred.shape[0], 1)
            self.sum_metric += _np.abs(label - pred).mean()
            self.num_inst += 1

    def device_fn(self):
        import jax.numpy as jnp

        def fn(labels, preds):
            s = jnp.float32(0.0)
            n = 0
            for label, pred in zip(labels, preds):
                if label.ndim == 1:
                    label = label.reshape(label.shape[0], 1)
                if pred.ndim == 1:
                    pred = pred.reshape(pred.shape[0], 1)
                s = s + jnp.abs(label - pred).mean().astype(jnp.float32)
                n += 1
            return s, jnp.float32(n)
        return fn



@register
class MSE(EvalMetric):
    def __init__(self, name="mse", output_names=None, label_names=None):
        super().__init__(name, output_names, label_names)

    def update(self, labels, preds):
        labels, preds = check_label_shapes(labels, preds, True)
        for label, pred in zip(labels, preds):
            label, pred = _asnp(label), _asnp(pred)
            if label.ndim == 1:
                label = label.reshape(label.shape[0], 1)
            if pred.ndim == 1:
                pred = pred.reshape(pred.shape[0], 1)
            self.sum_metric += ((label - pred) ** 2.0).mean()
            self.num_inst += 1

    def device_fn(self):
        import jax.numpy as jnp

        def fn(labels, preds):
            s = jnp.float32(0.0)
            n = 0
            for label, pred in zip(labels, preds):
                if label.ndim == 1:
                    label = label.reshape(label.shape[0], 1)
                if pred.ndim == 1:
                    pred = pred.reshape(pred.shape[0], 1)
                s = s + ((label - pred) ** 2.0).mean().astype(jnp.float32)
                n += 1
            return s, jnp.float32(n)
        return fn



@register
class RMSE(MSE):
    def __init__(self, name="rmse", output_names=None, label_names=None):
        EvalMetric.__init__(self, name, output_names, label_names)


    def get(self):
        total, num = self._totals()
        if num == 0:
            return (self.name, float("nan"))
        return (self.name, math.sqrt(total / num))


@register(None, "crossentropy", "ce")
class CrossEntropy(EvalMetric):
    device_reads_at_labels = True

    def __init__(self, eps=1e-12, name="cross-entropy", output_names=None,
                 label_names=None):
        super().__init__(name, output_names, label_names, eps=eps)
        self.eps = eps

    def update(self, labels, preds):
        labels, preds = check_label_shapes(labels, preds, True)
        for label, pred in zip(labels, preds):
            label = _asnp(label).ravel().astype("int32")
            pred = _asnp(pred)
            assert label.shape[0] == pred.shape[0]
            prob = pred[_np.arange(label.shape[0]), label]
            self.sum_metric += (-_np.log(prob + self.eps)).sum()
            self.num_inst += label.shape[0]

    def device_fn(self):
        import jax.numpy as jnp
        eps = self.eps

        def fn(labels, preds):
            s = jnp.float32(0.0)
            n = 0
            for label, pred in zip(labels, preds):
                label = label.reshape(-1).astype(jnp.int32)
                prob = _probs_at_labels(pred, label)
                s = s + (-jnp.log(prob + eps)).sum().astype(jnp.float32)
                n += label.shape[0]
            return s, jnp.float32(n)
        return fn

    def device_sig(self):
        return ("cross-entropy", self.eps)


@register(None, "nll_loss", "negativeloglikelihood")
class NegativeLogLikelihood(CrossEntropy):
    def __init__(self, eps=1e-12, name="nll-loss", output_names=None,
                 label_names=None):
        CrossEntropy.__init__(self, eps, name, output_names, label_names)


@register(None, "pearsonr", "pearsoncorrelation")
class PearsonCorrelation(EvalMetric):
    def __init__(self, name="pearsonr", output_names=None, label_names=None):
        super().__init__(name, output_names, label_names)

    def update(self, labels, preds):
        labels, preds = check_label_shapes(labels, preds, True)
        for label, pred in zip(labels, preds):
            label, pred = _asnp(label).ravel(), _asnp(pred).ravel()
            self.sum_metric += _np.corrcoef(pred, label)[0, 1]
            self.num_inst += 1


@register
class Loss(EvalMetric):
    def __init__(self, name="loss", output_names=None, label_names=None):
        super().__init__(name, output_names, label_names)

    def update(self, _, preds):
        if not isinstance(preds, (list, tuple)):
            preds = [preds]
        for pred in preds:
            loss = _asnp(pred)
            self.sum_metric += loss.sum()
            self.num_inst += loss.size

    def device_fn(self):
        import jax.numpy as jnp

        def fn(_labels, preds):
            s = jnp.float32(0.0)
            n = 0
            for pred in preds:
                s = s + pred.sum().astype(jnp.float32)
                n += pred.size
            return s, jnp.float32(n)
        return fn



@register
class Torch(Loss):
    def __init__(self, name="torch", output_names=None, label_names=None):
        Loss.__init__(self, name, output_names, label_names)


@register
class Caffe(Loss):
    def __init__(self, name="caffe", output_names=None, label_names=None):
        Loss.__init__(self, name, output_names, label_names)


@register
class CustomMetric(EvalMetric):
    def __init__(self, feval, name=None, allow_extra_outputs=False,
                 output_names=None, label_names=None):
        if name is None:
            name = feval.__name__ if hasattr(feval, "__name__") else "custom"
        super().__init__("custom(%s)" % name, output_names, label_names)
        self._feval = feval
        self._allow_extra_outputs = allow_extra_outputs

    def update(self, labels, preds):
        if not self._allow_extra_outputs:
            labels, preds = check_label_shapes(labels, preds, True)
        for label, pred in zip(labels, preds):
            reval = self._feval(_asnp(label), _asnp(pred))
            if isinstance(reval, tuple):
                sum_metric, num_inst = reval
                self.sum_metric += sum_metric
                self.num_inst += num_inst
            else:
                self.sum_metric += reval
                self.num_inst += 1


def np(numpy_feval, name=None, allow_extra_outputs=False):
    """Wrap a numpy feval into a metric (reference metric.np)."""
    def feval(label, pred):
        return numpy_feval(label, pred)
    feval.__name__ = name or getattr(numpy_feval, "__name__", "custom")
    return CustomMetric(feval, name, allow_extra_outputs)
