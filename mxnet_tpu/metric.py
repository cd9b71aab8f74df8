"""Evaluation metrics (reference ``python/mxnet/metric.py:22-424``).

Two update paths per metric:

- ``update(labels, preds)`` — the reference's numpy path: fetches
  predictions to host (``.asnumpy()``) every call.  Always available;
  custom metrics only have this form.
- ``device_update(label, pred)`` — a *pure jnp* functional form
  returning ``(sum_delta, inst_delta)`` device scalars.  Metrics that
  define it can accumulate **on device**: the fit loop folds the delta
  computation into the compiled train step (``module.Module``) or
  dispatches it asynchronously (:meth:`EvalMetric.update_device`), and
  the host sees a value only when :meth:`EvalMetric.get` drains the
  accumulators — the per-batch device→host round-trip of the numpy path
  disappears from the steady-state training loop.  Every drain bumps the
  ``metric.host_syncs`` counter so tests can assert sync-freedom.
"""
from __future__ import annotations

import math

import numpy
import numpy as np  # noqa: shadowed by the np() factory below in function scope

from . import instrument
from .ndarray import NDArray


def check_label_shapes(labels, preds, shape=0):
    if shape == 0:
        label_shape, pred_shape = len(labels), len(preds)
    else:
        label_shape, pred_shape = labels.shape, preds.shape
    if label_shape != pred_shape:
        raise ValueError('Shape of labels {} does not match shape of '
                         'predictions {}'.format(label_shape, pred_shape))


class EvalMetric(object):
    """Base metric (metric.py:22)."""

    # subclasses with an on-device functional form override this with a
    # method ``device_update(self, label, pred) -> (sum_delta,
    # inst_delta)`` in pure jnp (traceable inside jax.jit)
    device_update = None

    def __init__(self, name, num=None):
        self.name = name
        self.num = num
        self.reset()

    def update(self, label, pred):
        raise NotImplementedError()

    def reset(self):
        if self.num is None:
            self.num_inst = 0
            self.sum_metric = 0.0
        else:
            self.num_inst = [0] * self.num
            self.sum_metric = [0.0] * self.num
        # lazy on-device accumulators (jnp scalars); discarded, not
        # synced — reset must never force a device round-trip
        self._dev_sum = None
        self._dev_inst = None

    # -- on-device accumulation --------------------------------------------
    def device_capable(self):
        """Whether this metric can accumulate on device (a functional
        ``device_update`` exists and the single-accumulator form is in
        use — the legacy ``num``-sliced form stays on the numpy path)."""
        return callable(self.device_update) and self.num is None

    def device_state(self):
        """Current ``(sum, inst)`` device scalars, creating zeros on
        first use.  The fused train step threads this state through the
        compiled program; :meth:`set_device_state` stores the result."""
        if self._dev_sum is None:
            import jax.numpy as jnp
            self._dev_sum = jnp.float32(0.0)
            self._dev_inst = jnp.int32(0)
        return (self._dev_sum, self._dev_inst)

    def set_device_state(self, state):
        self._dev_sum, self._dev_inst = state

    def device_delta_fn(self):
        """A pure function ``(label, pred) -> deltas`` whose result has
        the same pytree structure as :meth:`device_state` — what the
        fused train step folds into the compiled program."""
        assert self.device_capable()
        return self.device_update

    def device_fold_key(self):
        """Hashable identity of the folded computation.  Two metric
        OBJECTS with equal keys produce identical compiled programs, so
        the fused step is reused across fit() calls (each of which may
        construct a fresh metric from a string) instead of recompiling.
        Subclasses whose ``device_update`` math depends on parameters
        must include them (see TopKAccuracy/CrossEntropy/Perplexity)."""
        return (type(self).__module__, type(self).__qualname__)

    def update_device(self, labels, preds):
        """Async metric update: compute the delta with
        :meth:`device_update` and fold it into the device accumulators.
        No host synchronization — everything stays dispatched."""
        assert self.device_capable()
        s, n = self.device_state()
        for label, pred in zip(labels, preds):
            lv = label.handle if isinstance(label, NDArray) else label
            pv = pred.handle if isinstance(pred, NDArray) else pred
            ds, dn = self.device_update(lv, pv)
            s = s + ds
            n = n + dn
        self.set_device_state((s, n))

    def _take_device_state(self):
        """Detach pending device accumulators WITHOUT syncing: a list of
        ``(owner, sum, inst)`` (composites flatten their children so one
        drain batches every accumulator into a single host sync)."""
        if self._dev_sum is None:
            return []
        s, n = self._dev_sum, self._dev_inst
        self._dev_sum = self._dev_inst = None
        return [(self, s, n)]

    def _apply_drained(self, s, n):
        self.sum_metric += float(numpy.asarray(s))
        self.num_inst += int(numpy.asarray(n))

    def _drain_device(self):
        """Fold the device accumulators into the host sums.  This is THE
        host sync point of the device-metric path (Speedometer log
        ticks, epoch end) — counted so tests can assert there are no
        others.  ONE sync and ONE count per drain point, however many
        accumulators (composite children) are pending.

        The active health monitor's sentinel scalars (health.py) ride
        the SAME batched sync: a steady-state fit with sentinels on pays
        zero extra host syncs (``health.host_syncs`` stays 0 — it counts
        only drains health had to force on its own, i.e. when no metric
        state was pending at this point)."""
        from . import health as _health
        pending = self._take_device_state()
        extra = _health._piggyback_take()
        if not pending and not extra:
            return
        from . import iowatch as _iowatch
        from . import perfwatch as _perfwatch
        from .engine import sync
        # counters the step computed on the device ride the same sync
        counted = instrument.take_device_sources()
        # one completion barrier for the whole batch of states.  The
        # goodput ledger charges it to metric_drain — exactly one
        # ledger event per counted host sync, so the exclusive-bucket
        # invariant is checkable against the sync-budget counters
        with _perfwatch.phase('metric_drain'), \
                _iowatch.account('metric_drain'):
            sync([x for _, s, n in pending for x in (s, n)] + list(extra) +
                 [x for arrays, _ in counted for x in arrays])
        if pending:
            instrument.inc('metric.host_syncs')
        elif extra:
            instrument.inc('health.host_syncs')
        for metric, s, n in pending:
            metric._apply_drained(s, n)
        for _, apply in counted:
            apply()
        # applied last: the divergence action may raise, and the metric
        # sums above must land first so the raise site sees them
        _health._piggyback_apply(extra)

    def get(self):
        self._drain_device()
        if self.num is None:
            if self.num_inst == 0:
                return (self.name, float('nan'))
            return (self.name, self.sum_metric / self.num_inst)
        names = ['%s_%d' % (self.name, i) for i in range(self.num)]
        values = [x / y if y != 0 else float('nan')
                  for x, y in zip(self.sum_metric, self.num_inst)]
        return (names, values)

    def get_name_value(self):
        name, value = self.get()
        if not isinstance(name, list):
            name = [name]
        if not isinstance(value, list):
            value = [value]
        return list(zip(name, value))

    def __str__(self):
        return 'EvalMetric: {}'.format(dict(self.get_name_value()))


class CompositeEvalMetric(EvalMetric):
    """Manage multiple metrics (metric.py:81)."""

    def __init__(self, **kwargs):
        super().__init__('composite')
        try:
            self.metrics = kwargs['metrics']
        except KeyError:
            self.metrics = []

    def add(self, metric):
        self.metrics.append(metric)

    def get_metric(self, index):
        # Deviation: the reference *returns* the ValueError instead of
        # raising it (python/mxnet/metric.py:96-101) — a bug; we raise.
        # Negative indices keep list semantics (metrics[-1] = last),
        # exactly as the reference's self.metrics[index] did.
        try:
            return self.metrics[index]
        except IndexError:
            raise ValueError('Metric index {} is out of range for {} '
                             'metrics'.format(index, len(self.metrics)))

    def update(self, labels, preds):
        for metric in self.metrics:
            metric.update(labels, preds)

    def reset(self):
        try:
            for metric in self.metrics:
                metric.reset()
        except AttributeError:
            pass

    def get(self):
        # drain every child in ONE batched host sync before the
        # per-child get() calls (which would otherwise sync one by one)
        self._drain_device()
        names = []
        results = []
        for metric in self.metrics:
            result = metric.get()
            names.append(result[0])
            results.append(result[1])
        return (names, results)

    # -- on-device accumulation: delegate to the children ------------------
    def device_capable(self):
        return bool(self.metrics) and \
            all(m.device_capable() for m in self.metrics)

    def device_state(self):
        return tuple(m.device_state() for m in self.metrics)

    def set_device_state(self, state):
        for metric, st in zip(self.metrics, state):
            metric.set_device_state(st)

    def device_delta_fn(self):
        assert self.device_capable()
        fns = [m.device_delta_fn() for m in self.metrics]
        return lambda label, pred: tuple(fn(label, pred) for fn in fns)

    def device_fold_key(self):
        return (type(self).__module__, type(self).__qualname__,
                tuple(m.device_fold_key() for m in self.metrics))

    def update_device(self, labels, preds):
        for metric in self.metrics:
            metric.update_device(labels, preds)

    def _take_device_state(self):
        return [p for m in self.metrics for p in m._take_device_state()]


class Accuracy(EvalMetric):
    """Classification accuracy (metric.py:128)."""

    def __init__(self):
        super().__init__('accuracy')

    def update(self, labels, preds):
        check_label_shapes(labels, preds)
        for label, pred_label in zip(labels, preds):
            pred = pred_label.asnumpy()
            if pred.shape != label.shape:
                pred_np = numpy.argmax(pred, axis=1)
            else:
                pred_np = pred
            label_np = label.asnumpy().astype('int32')
            pred_np = pred_np.astype('int32')
            check_label_shapes(label_np, pred_np)
            self.sum_metric += int((pred_np.flat == label_np.flat).sum())
            self.num_inst += len(pred_np.flat)

    def device_update(self, label, pred):
        import jax.numpy as jnp
        if pred.shape != label.shape:
            pred = jnp.argmax(pred, axis=1)
        hits = (pred.astype(jnp.int32).ravel() ==
                label.astype(jnp.int32).ravel())
        return (hits.sum().astype(jnp.float32),
                jnp.int32(hits.size))


class TopKAccuracy(EvalMetric):
    """Top-k accuracy (metric.py:160)."""

    def __init__(self, **kwargs):
        super().__init__('top_k_accuracy')
        try:
            self.top_k = kwargs['top_k']
        except KeyError:
            self.top_k = 1
        assert self.top_k > 1, 'Please use Accuracy if top_k is no more than 1'
        self.name += '_%d' % self.top_k

    def update(self, labels, preds):
        check_label_shapes(labels, preds)
        for label, pred_label in zip(labels, preds):
            scores = pred_label.asnumpy().astype('float32')
            truth = label.asnumpy().astype('int32').ravel()
            if scores.ndim == 1:
                # single score column == a (N, 1) prediction matrix
                scores = scores[:, None]
            if scores.ndim != 2:
                raise ValueError('TopKAccuracy expects 1-D or 2-D '
                                 'predictions, got %d-D' % scores.ndim)
            k = min(self.top_k, scores.shape[1])
            # stable argsort keeps the reference's tie-break at the k
            # boundary (among equal scores the higher class index wins),
            # membership tested vectorized instead of per-column
            topk = numpy.argsort(scores, axis=1, kind='stable')[:, -k:]
            self.sum_metric += int(
                (topk == truth[:, None]).any(axis=1).sum())
            self.num_inst += scores.shape[0]

    def device_update(self, label, pred):
        import jax.numpy as jnp
        scores = pred.astype(jnp.float32)
        truth = label.astype(jnp.int32).ravel()
        if scores.ndim == 1:
            scores = scores[:, None]
        k = min(self.top_k, scores.shape[1])
        # stable argsort matches the numpy path's tie-break exactly
        topk = jnp.argsort(scores, axis=1, stable=True)[:, -k:]
        hits = (topk == truth[:, None]).any(axis=1)
        return (hits.sum().astype(jnp.float32),
                jnp.int32(scores.shape[0]))

    def device_fold_key(self):
        return super().device_fold_key() + (self.top_k,)


class F1(EvalMetric):
    """Binary-classification F1 (metric.py:198)."""

    def __init__(self):
        super().__init__('f1')

    def update(self, labels, preds):
        check_label_shapes(labels, preds)
        for label, pred in zip(labels, preds):
            scores = pred.asnumpy()
            truth = label.asnumpy().astype('int32')
            check_label_shapes(truth, scores)
            if numpy.unique(truth).size > 2:
                raise ValueError('F1 currently only supports binary '
                                 'classification.')
            truth = truth.ravel()
            decided = numpy.argmax(scores, axis=1)
            tp = int(numpy.sum((decided == 1) & (truth == 1)))
            fp = int(numpy.sum((decided == 1) & (truth == 0)))
            fn = int(numpy.sum((decided == 0) & (truth == 1)))
            precision = tp / (tp + fp) if tp + fp else 0.0
            recall = tp / (tp + fn) if tp + fn else 0.0
            f1_score = (2 * precision * recall / (precision + recall)
                        if precision + recall else 0.0)
            self.sum_metric += f1_score
            self.num_inst += 1


class Perplexity(EvalMetric):
    """Perplexity over softmax outputs (metric.py:237)."""

    def __init__(self, ignore_label, axis=-1):
        super().__init__('Perplexity')
        self.ignore_label = ignore_label
        self.axis = axis

    def update(self, labels, preds):
        assert len(labels) == len(preds)
        loss = 0.
        num = 0
        for label, pred in zip(labels, preds):
            assert label.size == pred.size / pred.shape[-1], \
                'shape mismatch: %s vs. %s' % (label.shape, pred.shape)
            label = label.as_in_context(pred.context).reshape((label.size,))
            label_np = label.asnumpy().astype('int32')
            pred_np = pred.asnumpy().reshape(-1, pred.shape[-1])
            probs = pred_np[numpy.arange(label_np.shape[0]), label_np]
            if self.ignore_label is not None:
                ignore = (label_np == self.ignore_label)
                probs = numpy.where(ignore, 1.0, probs)
                num -= int(ignore.sum())
            loss -= numpy.sum(numpy.log(numpy.maximum(1e-10, probs)))
            num += pred_np.shape[0]
        self.sum_metric += loss
        self.num_inst += num

    def device_update(self, label, pred):
        import jax.numpy as jnp
        label = label.reshape((-1,)).astype(jnp.int32)
        pred2 = pred.reshape(-1, pred.shape[-1]).astype(jnp.float32)
        probs = jnp.take_along_axis(pred2, label[:, None], axis=1)[:, 0]
        num = jnp.int32(pred2.shape[0])
        if self.ignore_label is not None:
            ignore = (label == self.ignore_label)
            probs = jnp.where(ignore, 1.0, probs)
            num = num - ignore.sum().astype(jnp.int32)
        loss = -jnp.sum(jnp.log(jnp.maximum(1e-10, probs)))
        return (loss.astype(jnp.float32), num)

    def device_fold_key(self):
        return super().device_fold_key() + (self.ignore_label, self.axis)

    def get(self):
        self._drain_device()
        if self.num_inst == 0:
            return (self.name, float('nan'))
        return (self.name, math.exp(self.sum_metric / self.num_inst))


def _align_regression(label, pred):
    """Column-ize 1-D labels/preds so elementwise differences never
    broadcast a (N,) against an (N,1) into an (N,N) matrix.  Shape-only,
    so it works on numpy and jnp arrays alike."""
    if len(label.shape) == 1:
        label = label.reshape(label.shape[0], 1)
    if len(pred.shape) == 1:
        pred = pred.reshape(pred.shape[0], 1)
    return label, pred


class MAE(EvalMetric):
    """Mean absolute error (metric.py:310)."""

    def __init__(self):
        super().__init__('mae')

    def update(self, labels, preds):
        check_label_shapes(labels, preds)
        for label, pred in zip(labels, preds):
            label, pred = _align_regression(label.asnumpy(),
                                            pred.asnumpy())
            self.sum_metric += numpy.abs(label - pred).mean()
            self.num_inst += 1

    def device_update(self, label, pred):
        import jax.numpy as jnp
        label, pred = _align_regression(label, pred)
        return (jnp.abs(label - pred).mean().astype(jnp.float32),
                jnp.int32(1))


class MSE(EvalMetric):
    """Mean squared error (metric.py:330)."""

    def __init__(self):
        super().__init__('mse')

    def update(self, labels, preds):
        check_label_shapes(labels, preds)
        for label, pred in zip(labels, preds):
            label, pred = _align_regression(label.asnumpy(),
                                            pred.asnumpy())
            self.sum_metric += ((label - pred) ** 2.0).mean()
            self.num_inst += 1

    def device_update(self, label, pred):
        import jax.numpy as jnp
        label, pred = _align_regression(label, pred)
        return (((label - pred) ** 2.0).mean().astype(jnp.float32),
                jnp.int32(1))


class RMSE(EvalMetric):
    """Root mean squared error (metric.py:350)."""

    def __init__(self):
        super().__init__('rmse')

    def update(self, labels, preds):
        check_label_shapes(labels, preds)
        for label, pred in zip(labels, preds):
            label, pred = _align_regression(label.asnumpy(),
                                            pred.asnumpy())
            self.sum_metric += numpy.sqrt(((label - pred) ** 2.0).mean())
            self.num_inst += 1

    def device_update(self, label, pred):
        import jax.numpy as jnp
        label, pred = _align_regression(label, pred)
        rmse = jnp.sqrt(((label - pred) ** 2.0).mean())
        return (rmse.astype(jnp.float32), jnp.int32(1))


class CrossEntropy(EvalMetric):
    """Cross-entropy of softmax outputs (metric.py:370)."""

    def __init__(self, eps=1e-8):
        super().__init__('cross-entropy')
        self.eps = eps

    def update(self, labels, preds):
        check_label_shapes(labels, preds)
        for label, pred in zip(labels, preds):
            label = label.asnumpy()
            pred = pred.asnumpy()
            label = label.ravel()
            assert label.shape[0] == pred.shape[0]
            prob = pred[numpy.arange(label.shape[0]), numpy.int64(label)]
            self.sum_metric += (-numpy.log(prob + self.eps)).sum()
            self.num_inst += label.shape[0]

    def device_update(self, label, pred):
        import jax.numpy as jnp
        label = label.ravel().astype(jnp.int32)
        prob = jnp.take_along_axis(pred, label[:, None], axis=1)[:, 0]
        loss = (-jnp.log(prob.astype(jnp.float32) + self.eps)).sum()
        return (loss, jnp.int32(label.shape[0]))

    def device_fold_key(self):
        return super().device_fold_key() + (self.eps,)


class Torch(EvalMetric):
    """Dummy metric for torch criterions (metric.py:395)."""

    def __init__(self, name='torch'):
        super().__init__(name)

    def update(self, _, preds):
        for pred in preds:
            self.sum_metric += pred.asnumpy().mean()
        self.num_inst += 1


class Caffe(Torch):
    def __init__(self):
        super().__init__('caffe')


class CustomMetric(EvalMetric):
    """Metric from a python function (metric.py:407)."""

    def __init__(self, feval, name=None, allow_extra_outputs=False):
        if name is None:
            name = feval.__name__
            if name.find('<') != -1:
                name = 'custom(%s)' % name
        super().__init__(name)
        self._feval = feval
        self._allow_extra_outputs = allow_extra_outputs

    def update(self, labels, preds):
        if not self._allow_extra_outputs:
            check_label_shapes(labels, preds)
        for pred, label in zip(preds, labels):
            label = label.asnumpy()
            pred = pred.asnumpy()
            reval = self._feval(label, pred)
            if isinstance(reval, tuple):
                (sum_metric, num_inst) = reval
                self.sum_metric += sum_metric
                self.num_inst += num_inst
            else:
                self.sum_metric += reval
                self.num_inst += 1


def np(numpy_feval, name=None, allow_extra_outputs=False):
    """Wrap a numpy eval function into a CustomMetric (metric.py:447)."""
    def feval(label, pred):
        return numpy_feval(label, pred)
    feval.__name__ = numpy_feval.__name__
    return CustomMetric(feval, name, allow_extra_outputs)


def create(metric, **kwargs):
    """Create by name or callable (metric.py:462).

    Examples
    --------
    >>> import numpy as np
    >>> from mxnet_tpu import nd
    >>> m = create('acc')
    >>> m.update([nd.array(np.array([1.0, 0.0]))],
    ...          [nd.array(np.array([[0.3, 0.7], [0.6, 0.4]]))])
    >>> m.get()
    ('accuracy', 1.0)
    >>> m.reset(); m.get()[1] != m.get()[1]   # NaN when empty
    True
    """
    if callable(metric):
        return CustomMetric(metric)
    if isinstance(metric, EvalMetric):
        return metric
    if isinstance(metric, list):
        composite_metric = CompositeEvalMetric()
        for child_metric in metric:
            composite_metric.add(create(child_metric, **kwargs))
        return composite_metric
    metrics = {
        'acc': Accuracy, 'accuracy': Accuracy, 'ce': CrossEntropy,
        'f1': F1, 'mae': MAE, 'mse': MSE, 'rmse': RMSE,
        'top_k_accuracy': TopKAccuracy, 'perplexity': Perplexity,
    }
    try:
        return metrics[metric.lower()](**kwargs)
    except Exception:
        raise ValueError('Metric must be either callable or in {}'.format(
            sorted(metrics)))
