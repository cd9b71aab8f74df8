#!/usr/bin/env python
"""Benchmark harness — the analogue of the reference's
``example/image-classification/benchmark_score.py`` (synthetic inference)
and ``train_imagenet.py --benchmark 1`` (synthetic training).

Prints ONE JSON line, measured by this run on the device it names:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...,
   "device": {"platform": "tpu", "kind": ..., "count": N}}

One process holds the chip.  The run exits non-zero, printing no result,
when JAX's first device is not a TPU, and exits non-zero after the
result when any leg raised or timed out.  Nothing is replayed from
``bench_state.json``.

Primary metric: ResNet-50 synthetic training images/sec on one chip, bf16
compute.  ``vs_baseline`` is the ratio to the BASELINE.json north star —
H100-class training throughput (~3000 imgs/sec/chip); ``vs_p100`` keeps
the ratio to the fastest number published in the reference repo itself
(181.5 imgs/sec on P100, docs/how_to/perf.md:132-139).

The JSON also reports ``mfu`` (model FLOPs utilization: XLA-counted step
FLOPs vs the chip's peak) and ``roofline_mandatory`` (the analytic
MANDATORY per-step HBM traffic — see :func:`analytic_min_bytes` — times
steps/sec over the chip's peak bandwidth; <= 1 by construction, and
1 - frac is the removable-traffic headroom).  XLA cost-analysis bytes
are kept as ``bytes_cost_analysis`` for reference only: they bill
VMEM-resident producer-consumer traffic as HBM and exceeded 100% of
peak in r03.

Extra metrics (inference sweep, Module.fit leg, the sync-free pipeline
fit leg with device metrics — ``module_fit_pipeline_ips``, persisted
with its ``pct_of_raw_step`` gap to the raw fused step; ``--full`` adds
the other BASELINE.json configs: Inception-v3/VGG inference, LSTM
bucketing, LeNet, SSD forward) go to stderr so the driver's one-line
contract holds.
"""
import argparse
import contextlib
import json
import os
import sys
import time
import traceback

import numpy as np

# Every successful leg measurement is written here as the run progresses
# (tools/check_perf.py and tools/bench_report.py read it).
STATE_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          'bench_state.json')


BASELINE_RESNET50_TRAIN_P100 = 181.5   # docs/how_to/perf.md:132-139
BASELINE_RESNET50_INFER_P100 = 713.17  # docs/how_to/perf.md:91-98
NORTH_STAR_TRAIN = 3000.0              # H100-class imgs/sec/chip (BASELINE.json)

# Peak FLOP/s + HBM bandwidth per device kind live in
# mxnet_tpu.perfwatch.PEAKS (shared with the runtime's live perf.mfu
# gauge); see device_peaks() below — resolved only after backend init.


def log(*args):
    print(*args, file=sys.stderr, flush=True)


@contextlib.contextmanager
def _fuse_env(fuse):
    """Scoped MXTPU_FUSE_BN_CONV: set (True/False) or just guard
    (None — restore whatever the caller had on exit).  One shared
    implementation for the train-variant and folded-inference legs so
    no leg can leak its setting into later legs."""
    saved = os.environ.get('MXTPU_FUSE_BN_CONV')
    if fuse is not None:
        os.environ['MXTPU_FUSE_BN_CONV'] = '1' if fuse else '0'
    try:
        yield
    finally:
        if saved is None:
            os.environ.pop('MXTPU_FUSE_BN_CONV', None)
        else:
            os.environ['MXTPU_FUSE_BN_CONV'] = saved


def load_state():
    try:
        with open(STATE_PATH) as f:
            return json.load(f)
    except Exception:
        return {}


def record_leg(name, value, **extra):
    """Persist a leg's result.  Commits via resilience.atomic_replace
    (tmp + fsync + rename + dir fsync): a kill -9 or power cut at any
    instant leaves the previous state file intact, never a torn one."""
    from mxnet_tpu import resilience
    state = load_state()
    # small-magnitude legs (goodput_fraction lives in [0, 1], kernel
    # speedups near 1) would be destroyed by 1-decimal rounding; keep 4
    # places for them
    digits = 4 if abs(float(value)) < 10 else 1
    entry = {'value': round(float(value), digits),
             'ts': time.strftime('%Y-%m-%dT%H:%M:%S')}
    entry.update(extra)
    state[name] = entry
    with resilience.atomic_replace(STATE_PATH) as tmp:
        with open(tmp, 'w') as f:
            json.dump(state, f, indent=1, sort_keys=True)
    return entry['value']


def sync(x):
    """Force completion of ``x``'s computation chain (engine.sync walks
    pytrees, so lists/tuples pass through)."""
    from mxnet_tpu.engine import sync as _sync
    return _sync(x)


def device_peaks():
    """(peak flops/sec, peak HBM bytes/sec) of the attached device —
    the shared perfwatch table/override, so bench MFU and the runtime's
    live ``perf.mfu`` gauge can never disagree on the denominator."""
    import jax
    from mxnet_tpu import perfwatch
    jax.devices()                    # force backend init under the leg
    return perfwatch.peaks()


def analytic_min_bytes(model='resnet-50', batch_size=128,
                       image_shape=(3, 224, 224),
                       stem='space_to_depth'):
    """Lower bound on per-step HBM traffic for the fused train step —
    the roofline denominator.  XLA cost-analysis 'bytes accessed' bills
    VMEM-resident producer-consumer traffic as HBM bytes and exceeded
    100% of peak in r03 (a roofline you can exceed measures nothing);
    this model counts only the MANDATORY traffic:

      - parameters: f32 read + write, momentum f32 read + write
      - the batch input: one bf16 read
      - each materializing op output (conv / FC / fused bn-conv /
        pooling): written once and read at least once, in both the
        value (forward) and gradient (backward) form — 4 passes of
        2 bytes.  Extra reads the real program does (dY consumed by
        both dW and dX kernels, activations re-read for dW) are
        fusable in principle and excluded from the floor.

    Elementwise/BN chains are assumed fully fused (that is what the
    fusion work removes).  Every real program moves AT LEAST this, so
    ``min_bytes * steps_per_sec / peak_bw <= 1`` by construction, and
    1 - frac is exactly the removable-traffic headroom.
    """
    from mxnet_tpu import models
    kw = {'stem': stem} if model == 'resnet-50' else {}
    sym = models.get_symbol(model, num_classes=1000, **kw)
    dshape = (batch_size,) + tuple(image_shape)
    arg_shapes, _, _ = sym.infer_shape(data=dshape)
    param_elems = sum(
        int(np.prod(s)) for name, s in zip(sym.list_arguments(),
                                           arg_shapes)
        if name not in ('data', 'softmax_label'))
    ints = sym.get_internals()
    out_names = ints.list_outputs()
    _, out_shapes, _ = ints.infer_shape(data=dshape)
    act_elems = 0
    mat_ops = ('Convolution', 'FullyConnected', 'Pooling',
               '_bn_relu_conv')
    node_ops = {}
    for n in sym.topo_nodes():
        if not n.is_variable:
            node_ops[n.name] = n.op
    for name, shape in zip(out_names, out_shapes):
        base = name[:-len('_output')] if name.endswith('_output') \
            else name
        if node_ops.get(base) in mat_ops and shape is not None:
            act_elems += int(np.prod(shape))
    return (16.0 * param_elems            # f32 param+mom, read+write
            + 2.0 * int(np.prod(dshape))  # bf16 input read
            + 8.0 * act_elems)            # bf16 value+grad, write+read


def _resnet50_setup(batch_size, stem='space_to_depth'):
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import models
    sym = models.get_symbol('resnet-50', num_classes=1000, stem=stem)
    dshape = (batch_size, 3, 224, 224)
    arg_shapes, _, aux_shapes = sym.infer_shape(data=dshape)
    rng = np.random.RandomState(0)
    params = {}
    for name, shape in zip(sym.list_arguments(), arg_shapes):
        if name in ('data', 'softmax_label'):
            continue
        params[name] = jnp.asarray(
            rng.normal(0, 0.01, size=shape).astype(np.float32))
    aux = {name: (jnp.ones(s, jnp.float32) if 'var' in name
                  else jnp.zeros(s, jnp.float32))
           for name, s in zip(sym.list_auxiliary_states(), aux_shapes)}
    data = jnp.asarray(rng.rand(*dshape).astype(np.float32),
                       dtype=jnp.bfloat16)
    label = jnp.asarray(rng.randint(0, 1000, batch_size).astype(np.float32))
    return sym, params, aux, {'data': data, 'softmax_label': label}


def bench_resnet50_train(batch_size=256, iters=20, warmup=5):
    """Returns (imgs/sec, step_flops, step_bytes) — flops/bytes from the
    compiled program's own cost analysis, so MFU is honest."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.parallel.train_step import (make_train_step,
                                               make_sgd_momentum,
                                               sgd_momentum_init)
    sym, params, aux, batch = _resnet50_setup(batch_size)
    opt_update = make_sgd_momentum(lr=0.05, momentum=0.9, wd=1e-4,
                                   rescale_grad=1.0 / batch_size)
    opt_state = sgd_momentum_init(params)
    step = make_train_step(sym, opt_update, ('data', 'softmax_label'),
                           compute_dtype=jnp.bfloat16)
    key = jax.random.PRNGKey(0)

    log('compiling resnet-50 train step (bs=%d)...' % batch_size)
    t0 = time.time()
    step_flops = step_bytes = 0.0
    try:
        # AOT-compile once and reuse the executable for the run itself
        # (calling the jit wrapper afterwards would compile a second time)
        compiled = step.lower(params, aux, opt_state, batch, key).compile()
        # flops/bytes through the SAME extraction the runtime perf
        # plane uses (perfwatch leg 1), so bench MFU cannot drift from
        # the live perf.mfu gauge's cost model; the executable's
        # cost/memory row also lands in the xla.* gauges for the
        # BENCH_metrics.json memory waterfall
        from mxnet_tpu import perfwatch
        cost = perfwatch.extract_cost(compiled)
        step_flops = cost['flops']
        step_bytes = cost['bytes_accessed']
        perfwatch.register_executable('bench_train_step',
                                      'resnet50_bs%d' % batch_size,
                                      compiled)
        step = compiled
    except Exception:
        log('cost analysis unavailable (jit path will compile):\n' +
            traceback.format_exc())
    outs, params, aux, opt_state = step(params, aux, opt_state, batch, key)
    sync(outs)
    log('compile+first step: %.1fs' % (time.time() - t0))

    for _ in range(warmup):
        outs, params, aux, opt_state = step(params, aux, opt_state, batch,
                                            key)
    sync(outs)
    t0 = time.time()
    for _ in range(iters):
        outs, params, aux, opt_state = step(params, aux, opt_state, batch,
                                            key)
    sync(outs)
    dt = time.time() - t0
    return batch_size * iters / dt, step_flops, step_bytes


class _RepeatBatchIter:
    """Synthetic DataIter replaying ONE random batch (no host-RAM blowup,
    no per-epoch data generation — the --benchmark data contract)."""

    def __init__(self, batch_size, image_shape, num_classes, batches,
                 data_name='data', label_name='softmax_label'):
        import mxnet_tpu as mx
        rng = np.random.RandomState(0)
        self._data = mx.nd.array(
            rng.rand(batch_size, *image_shape).astype(np.float32))
        self._label = mx.nd.array(
            rng.randint(0, num_classes, batch_size).astype(np.float32))
        self.batch_size = batch_size
        self.batches = batches
        self.provide_data = [(data_name,
                              (batch_size,) + tuple(image_shape))]
        self.provide_label = [(label_name, (batch_size,))]
        self._i = 0

    def reset(self):
        self._i = 0

    def __iter__(self):
        return self

    def __next__(self):
        return self.next()

    def next(self):
        import mxnet_tpu as mx
        if self._i >= self.batches:
            raise StopIteration
        self._i += 1
        return mx.io.DataBatch([self._data], [self._label], pad=0)


def _throughput_metric():
    """Metric that never fetches predictions: metric VALUES are
    irrelevant to the throughput bench."""
    import mxnet_tpu as mx

    class _ThroughputMetric(mx.metric.EvalMetric):
        def __init__(self):
            super(_ThroughputMetric, self).__init__('throughput')

        def update(self, labels, preds):
            self.num_inst += 1

    return _ThroughputMetric()


def bench_module_fit(batch_size=256, batches=12, warmup_batches=4,
                     model='resnet-50', num_classes=1000,
                     image_shape=(3, 224, 224)):
    """The user path: Module.fit with the fused step (imgs/sec measured
    over the steady-state tail of a synthetic epoch)."""
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu import models

    kw = {'stem': 'space_to_depth'} if model == 'resnet-50' else {}
    sym = models.get_symbol(model, num_classes=num_classes, **kw)
    it = _RepeatBatchIter(batch_size, image_shape, num_classes,
                          batches + warmup_batches)
    mod = mx.module.Module(sym, context=mx.current_context(),
                           compute_dtype=jnp.bfloat16)
    times = []

    def batch_cb(param):
        sync(mod._exec_group.execs[0].outputs)
        times.append(time.time())

    mod.fit(it, num_epoch=1, optimizer='sgd',
            optimizer_params={'learning_rate': 0.05, 'momentum': 0.9,
                              'wd': 1e-4},
            initializer=mx.init.Uniform(0.01),
            batch_end_callback=batch_cb,
            eval_metric=_throughput_metric())
    if mod._fused is None:
        raise RuntimeError('Module.fit did not take the fused path')
    tail = times[warmup_batches:]
    return batch_size * (len(tail) - 1) / (tail[-1] - tail[0])


def bench_module_fit_pipeline(batch_size=256, batches=12,
                              warmup_batches=4, model='resnet-50',
                              num_classes=1000,
                              image_shape=(3, 224, 224), async_depth=2):
    """The sync-free fit loop (docs/performance.md): Module.fit with a
    REAL eval metric accumulated on device, the double-buffered device
    feed and the bounded async step window.  Comparing this leg against
    the raw fused-step number (resnet50_train*) tracks the remaining
    loop overhead — pre-pipeline, per-batch metric .asnumpy() calls made
    the gap the largest host-sync cost in the fit path."""
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu import models
    knobs = {'MXTPU_ASYNC_DEPTH': str(async_depth),
             'MXTPU_DEVICE_METRICS': '1', 'MXTPU_DEVICE_FEED': '1'}
    saved = {k: os.environ.get(k) for k in knobs}
    os.environ.update(knobs)
    try:
        kw = {'stem': 'space_to_depth'} if model == 'resnet-50' else {}
        sym = models.get_symbol(model, num_classes=num_classes, **kw)
        it = _RepeatBatchIter(batch_size, image_shape, num_classes,
                              batches + warmup_batches)
        mod = mx.module.Module(sym, context=mx.current_context(),
                               compute_dtype=jnp.bfloat16)
        times = []
        t_done = []
        last = batches + warmup_batches - 1

        def batch_cb(param):
            # NO per-batch device sync (that is the point of the leg);
            # dispatch timestamps only — except the LAST batch, which
            # drains the in-flight tail IN the loop so t_end excludes
            # the epoch teardown (param sync, metric drain, logging)
            times.append(time.monotonic())
            if param.nbatch == last and not t_done:
                sync(mod._exec_group.execs[0].outputs)
                t_done.append(time.monotonic())

        mod.fit(it, num_epoch=1, optimizer='sgd',
                optimizer_params={'learning_rate': 0.05, 'momentum': 0.9,
                                  'wd': 1e-4},
                initializer=mx.init.Uniform(0.01),
                batch_end_callback=batch_cb,
                eval_metric='acc')
        if mod._fused is None:
            raise RuntimeError('pipeline leg did not take the fused path')
        if mod._fused_metric_ref is None:
            raise RuntimeError('pipeline leg did not fold the metric '
                               'into the fused step')
        if len(times) <= warmup_batches or not t_done:
            raise RuntimeError('too few batches for a steady-state tail')
        tail = len(times) - warmup_batches
        return batch_size * tail / (t_done[0] - times[warmup_batches - 1])
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def bench_health_overhead(batch_size=256, batches=16, warmup_batches=4,
                          d_in=256, hidden=512, classes=64):
    """On-device health sentinels on vs off around an otherwise
    identical fused fit (docs/observability.md): the probe — global
    non-finite flag, grad norm, update ratio — is folded into the
    compiled step and drained only at existing metric drain points, so
    this leg measures its pure device-compute cost as a percent of the
    steady-state step time.  Returns the overhead percent."""
    import numpy as np_
    import mxnet_tpu as mx

    def build():
        net = mx.sym.Variable('data')
        net = mx.sym.FullyConnected(net, num_hidden=hidden, name='hfc1')
        net = mx.sym.Activation(net, act_type='relu', name='hact1')
        net = mx.sym.FullyConnected(net, num_hidden=classes, name='hfc2')
        return mx.sym.SoftmaxOutput(net, name='softmax')

    rng = np_.random.RandomState(0)
    n = batch_size * (batches + warmup_batches)
    X = rng.randn(n, d_in).astype(np_.float32)
    Y = (rng.rand(n) * classes).astype(np_.float32)

    def steady_step_secs(sentinels):
        knobs = {'MXTPU_HEALTH_SENTINELS': '1' if sentinels else '0',
                 'MXTPU_HEALTH_ACTION': 'warn',
                 'MXTPU_DEVICE_METRICS': '1'}
        saved = {k: os.environ.get(k) for k in knobs}
        os.environ.update(knobs)
        try:
            it = mx.io.NDArrayIter(X, Y, batch_size=batch_size)
            mod = mx.mod.Module(build(), context=mx.current_context())
            times = []
            t_done = []
            last = batches + warmup_batches - 1

            def cb(param):
                times.append(time.monotonic())
                if param.nbatch == last and not t_done:
                    sync(mod._exec_group.execs[0].outputs)
                    t_done.append(time.monotonic())

            mod.fit(it, num_epoch=1, optimizer='sgd',
                    optimizer_params={'learning_rate': 0.05,
                                      'momentum': 0.9},
                    initializer=mx.init.Uniform(0.05),
                    eval_metric='acc', batch_end_callback=cb)
            if sentinels and mod._fused_health_key is None:
                raise RuntimeError('health leg did not fold the '
                                   'sentinels into the fused step')
            tail = len(times) - warmup_batches
            if tail <= 0 or not t_done:
                raise RuntimeError('too few batches for a steady tail')
            return (t_done[0] - times[warmup_batches - 1]) / tail
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v

    off = steady_step_secs(False)
    on = steady_step_secs(True)
    pct = 100.0 * (on / max(off, 1e-9) - 1.0)
    log('health sentinels: %.4fs/step on vs %.4fs/step off '
        '(%.1f%% overhead)' % (on, off, pct))
    return pct


def bench_serving(duration_s=3.0, slo_p99_ms=100.0, max_concurrency=64):
    """Serving-plane capacity (docs/serving.md): requests/sec at a p99
    SLO through the ModelServer's dynamic batcher, measured by the
    tools/serve_bench.py closed-loop SLO sweep against a synthetic MLP
    checkpoint.  Returns (qps, best_summary)."""
    import shutil as _shutil
    import tempfile
    import mxnet_tpu as mx
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), 'tools'))
    import serve_bench
    from mxnet_tpu.serving import ModelServer

    tmp = tempfile.mkdtemp(prefix='mxtpu_bench_serve_')
    try:
        prefix, shapes = serve_bench.build_synthetic_checkpoint(tmp)
        ctx = mx.current_context()
        server = ModelServer(dev_type=ctx.device_type,
                             dev_id=ctx.device_id)
        server.load_model('bench', prefix=prefix, epoch=1,
                          input_shapes=shapes)
        try:
            rng = np.random.RandomState(0)
            sample = {'data': rng.rand(1, shapes['data'][1])
                      .astype(np.float32)}
            server.predict('bench', **sample)   # compile off the path
            best, sweep = serve_bench.find_qps_at_slo(
                server, 'bench', lambda: sample,
                slo_p99_ms=slo_p99_ms, duration_s=duration_s,
                max_concurrency=max_concurrency, log=log)
            if best is None:
                raise RuntimeError(
                    'no concurrency level met the %.0fms p99 SLO: %s'
                    % (slo_p99_ms,
                       ['%d@p99=%.1fms' % (s['concurrency'], s['p99_ms'])
                        for s in sweep]))
            best['slo_p99_ms'] = slo_p99_ms   # the SLO actually enforced
            return best['qps'], best
        finally:
            server.close(drain=False)
    finally:
        _shutil.rmtree(tmp, ignore_errors=True)


def bench_multichip_fit(timeout_s=600):
    """dp×tp sharded Module.fit throughput over 8 VIRTUAL CPU devices
    (docs/parallel.md): runs ``tools/check_multichip.py --bench`` in a
    subprocess — the child pins ``XLA_FLAGS=--xla_force_host_platform_
    device_count=8`` + ``JAX_PLATFORMS=cpu`` before jax initializes, so
    the leg is hermetic no matter what backend this process holds.
    Returns (ips, extras)."""
    import subprocess
    tool = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        'tools', 'check_multichip.py')
    env = dict(os.environ)
    env.pop('MXTPU_MESH', None)
    env.pop('MXTPU_PARTITION', None)
    out = subprocess.run([sys.executable, tool, '--bench'], env=env,
                         capture_output=True, text=True,
                         timeout=timeout_s)
    if out.returncode != 0:
        raise RuntimeError('multichip bench child failed (rc %d): %s'
                           % (out.returncode, out.stderr[-400:]))
    res = json.loads(out.stdout.strip().splitlines()[-1])
    extras = {'mesh': res['mesh'], 'partition': res['partition'],
              'virtual_devices': res['virtual_devices']}
    # comm attribution (MXTPU_COMMWATCH rides in the bench child): the
    # leg records WHAT the sharded step moved over the interconnect
    # next to how fast it went — check_perf gates comm_fraction
    # direction-aware (lower is better)
    for k in ('comm_bytes_per_step', 'comm_fraction'):
        if isinstance(res.get(k), (int, float)):
            extras[k] = res[k]
    return float(res['ips']), extras


def _bench_tool_json(tool_name, timeout_s):
    """Run ``tools/<tool_name> --bench`` in a subprocess (the child
    pins its own CPU backend before jax init, so it never asks for the
    chip this process holds) and parse the one-JSON-line contract off
    its stdout."""
    import subprocess
    tool = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        'tools', tool_name)
    out = subprocess.run([sys.executable, tool, '--bench'],
                         env=dict(os.environ), capture_output=True,
                         text=True, timeout=timeout_s)
    if out.returncode != 0:
        raise RuntimeError('%s bench child failed (rc %d): %s'
                           % (tool_name, out.returncode,
                              out.stderr[-400:]))
    return json.loads(out.stdout.strip().splitlines()[-1])


def bench_goodput(timeout_s=420):
    """Goodput fraction of a hermetic CPU fit through the full
    iterator chain (``tools/check_io.py --bench``: synthetic RecordIO
    -> PrefetchingIter -> DeviceFeedIter under MXTPU_IOWATCH) — the
    trajectory gate for "the product path silently became input-bound"
    (tools/check_perf.py compares it higher-is-better)."""
    res = _bench_tool_json('check_io.py', timeout_s)
    return float(res['goodput_fraction']), \
        {'wall_secs': res.get('wall_secs')}


def bench_recovery(timeout_s=420):
    """Elastic repair latency: ``tools/check_elastic.py --bench`` kills
    a worker mid-epoch in a hermetic 2-worker dist_async fit (CPU
    backend, subprocesses) and measures injected kill -> first
    post-repair productive step through the dp-shrink path
    (docs/resilience.md).  check_perf gates it LOWER-is-better: a
    refactor that silently fattens the detect->repair loop moves this
    leg."""
    res = _bench_tool_json('check_elastic.py', timeout_s)
    return float(res['recovery_time_secs']), {}


def bench_fleet(timeout_s=600):
    """Serving-fleet qps: ``tools/check_fleet.py --bench`` runs the
    2-replica closed-loop sweep (real model, disjoint virtual devices,
    hermetic CPU child) and reports the qps at the p99 SLO with the
    1->2 replica scaling factor beside it — the trajectory datapoint
    for "the serving fleet silently stopped scaling" (check_perf gates
    the qps with a generous LEG_TOL: virtual devices contend for host
    cores).  The same run's chaos leg reports the supervisor's worst
    quarantine->replacement repair (``replica_recovery_secs``,
    recorded as its own lower-is-better leg)."""
    res = _bench_tool_json('check_fleet.py', timeout_s)
    extras = {}
    for k in ('qps_1r', 'scaling', 'scaling_sim', 'slo_ms',
              'replica_recovery_secs'):
        if isinstance(res.get(k), (int, float)):
            extras[k] = res[k]
    return float(res['qps_2r']), extras


def bench_fused_step(timeout_s=420):
    """Step-compiler throughput: ``tools/check_fusion.py --bench``
    times the fused fit step of the conv+BN+FC reference model under
    ``MXTPU_FUSE=aggressive`` on the hermetic CPU backend and reports
    the registered executable's cost_analysis next to it — so the pass
    pipeline's win has a check_perf-gated trajectory datapoint (and a
    flops/bytes attribution) even before the next TPU window prices it
    on real hardware."""
    res = _bench_tool_json('check_fusion.py', timeout_s)
    extras = {}
    for k in ('flops_per_batch', 'bytes_per_batch', 'bytes_drop_frac'):
        if isinstance(res.get(k), (int, float)):
            extras[k] = res[k]
    return float(res['ips']), extras


def _synth_recfile(num_images=512, side=256, seed=7):
    """Write (once, cached) a synthetic JPEG RecordIO file so the
    native decode pipeline can be measured without a dataset."""
    import tempfile
    path = os.path.join(tempfile.gettempdir(),
                        'mxtpu_bench_%d_%d.rec' % (num_images, side))
    if os.path.exists(path):
        return path
    from mxnet_tpu import recordio
    rng = np.random.RandomState(seed)
    tmp = path + '.tmp.%d' % os.getpid()
    rec = recordio.MXRecordIO(tmp, 'w')
    for i in range(num_images):
        # structured patterns JPEG-compress realistically (pure noise
        # inflates decode cost; flat color deflates it)
        yy, xx = np.mgrid[0:side, 0:side]
        img = np.stack([
            (127 + 120 * np.sin(xx / (3.0 + i % 7) + i)),
            (127 + 120 * np.cos(yy / (2.0 + i % 5))),
            rng.randint(0, 255, (side, side)),
        ], axis=2).astype(np.uint8)
        header = recordio.IRHeader(0, float(i % 1000), i, 0)
        rec.write(recordio.pack_img(header, img, quality=85))
    rec.close()
    os.replace(tmp, path)     # atomic: no torn file on interruption
    return path


def bench_io_pipeline(batch_size=128, num_images=512, epochs=4):
    """Native input pipeline standalone: RecordIO + threaded JPEG
    decode + augment to (3,224,224) — decoded imgs/sec on the host
    (reference ``src/io/iter_image_recordio.cc:150-370``).  This is the
    feed-rate ceiling for Module.fit with real data."""
    from mxnet_tpu.io_record import ImageRecordIter
    path = _synth_recfile(num_images)
    it = ImageRecordIter(path_imgrec=path, data_shape=(3, 224, 224),
                         batch_size=batch_size, shuffle=True,
                         rand_crop=True, rand_mirror=True)
    # warm one epoch (thread spin-up), then measure
    n = 0
    for _ in it:
        pass
    t0 = time.time()
    for _ in range(epochs):
        it.reset()
        for batch in it:
            n += batch.data[0].shape[0]
    dt = time.time() - t0
    try:
        it.close()
    except Exception:
        pass
    return n / dt


def bench_module_fit_native(batch_size=128, num_images=None):
    """The full product path: native RecordIO+JPEG pipeline feeding
    Module.fit.  On a many-core host this tracks module_fit_ips; on a
    starved host it is input-bound at io_pipeline_ips (compare the two
    legs to see which regime the measurement ran in)."""
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu import models
    from mxnet_tpu.io_record import ImageRecordIter
    if num_images is None:
        num_images = max(512, 4 * batch_size)   # >= 4 steps/epoch
    path = _synth_recfile(num_images)
    it = ImageRecordIter(path_imgrec=path, data_shape=(3, 224, 224),
                         batch_size=batch_size, shuffle=True,
                         rand_crop=True, rand_mirror=True)
    sym = models.get_symbol('resnet-50', num_classes=1000,
                            stem='space_to_depth')
    mod = mx.module.Module(sym, context=mx.current_context(),
                           compute_dtype=jnp.bfloat16)
    times = []

    def batch_cb(param):
        sync(mod._exec_group.execs[0].outputs)
        times.append(time.time())

    mod.fit(it, num_epoch=3, optimizer='sgd',
            optimizer_params={'learning_rate': 0.05, 'momentum': 0.9,
                              'wd': 1e-4},
            initializer=mx.init.Uniform(0.01),
            batch_end_callback=batch_cb,
            eval_metric=_throughput_metric())
    try:
        it.close()
    except Exception:
        pass
    tail = times[max(2, len(times) // 3):]
    if len(tail) < 2:
        raise RuntimeError('too few steady-state batches (%d callbacks '
                           'total) — raise num_images or lower '
                           'batch_size' % len(times))
    return batch_size * (len(tail) - 1) / (tail[-1] - tail[0])


def bench_inference(model_name, batch_size=32, iters=30, warmup=5,
                    image_shape=(3, 224, 224)):
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import models
    from mxnet_tpu.parallel.train_step import make_eval_step
    sym = models.get_symbol(model_name, num_classes=1000)
    dshape = (batch_size,) + tuple(image_shape)
    arg_shapes, _, aux_shapes = sym.infer_shape(data=dshape)
    rng = np.random.RandomState(0)
    params = {}
    for name, shape in zip(sym.list_arguments(), arg_shapes):
        if name in ('data', 'softmax_label'):
            continue
        params[name] = jnp.asarray(
            rng.normal(0, 0.01, size=shape).astype(np.float32))
    aux = {name: (jnp.ones(s, jnp.float32) if 'var' in name
                  else jnp.zeros(s, jnp.float32))
           for name, s in zip(sym.list_auxiliary_states(), aux_shapes)}
    step = make_eval_step(sym, compute_dtype=jnp.bfloat16)
    batch = {'data': jnp.asarray(rng.rand(*dshape).astype(np.float32)),
             'softmax_label': jnp.zeros(batch_size, jnp.float32)}
    key = jax.random.PRNGKey(0)
    outs = step(params, aux, batch, key)
    sync(outs)
    for _ in range(warmup):
        outs = step(params, aux, batch, key)
    sync(outs)
    t0 = time.time()
    for _ in range(iters):
        outs = step(params, aux, batch, key)
    sync(outs)
    return batch_size * iters / (time.time() - t0)


def bench_lstm_bucketing(batch_size=32, seq_len=35, iters=20):
    """LSTM PTB-style language model leg (BASELINE.json config 4)."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import models
    from mxnet_tpu.parallel.train_step import (make_train_step,
                                               make_sgd_momentum,
                                               sgd_momentum_init)
    sym = models.get_symbol('lstm_lm', num_layers=2, num_hidden=200,
                            num_embed=200, vocab_size=10000,
                            seq_len=seq_len)
    dshape = (batch_size, seq_len)
    # the label reaches SoftmaxOutput through a Reshape, so its shape
    # cannot be back-inferred from data alone
    arg_shapes, _, aux_shapes = sym.infer_shape(data=dshape,
                                                softmax_label=dshape)
    rng = np.random.RandomState(0)
    params = {}
    for name, shape in zip(sym.list_arguments(), arg_shapes):
        if name in ('data', 'softmax_label'):
            continue
        params[name] = jnp.asarray(
            rng.normal(0, 0.05, size=shape).astype(np.float32))
    aux = {}
    opt_update = make_sgd_momentum(lr=0.1, momentum=0.9, wd=0.0,
                                   rescale_grad=1.0 / batch_size)
    opt_state = sgd_momentum_init(params)
    step = make_train_step(sym, opt_update, ('data', 'softmax_label'))
    batch = {'data': jnp.asarray(
                 rng.randint(0, 10000, dshape).astype(np.float32)),
             'softmax_label': jnp.asarray(
                 rng.randint(0, 10000, dshape).astype(np.float32))}
    key = jax.random.PRNGKey(0)
    outs, params, aux, opt_state = step(params, aux, opt_state, batch, key)
    sync(outs)
    t0 = time.time()
    for _ in range(iters):
        outs, params, aux, opt_state = step(params, aux, opt_state, batch,
                                            key)
    sync(outs)
    wps = batch_size * seq_len * iters / (time.time() - t0)
    return wps


def bench_transformer_lm(batch_size=16, seq_len=512, iters=15):
    """Decoder-only transformer LM train step (fused flash-attention
    blocks) — tokens/sec; the modern-architecture counterpart of the
    LSTM leg."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import models
    from mxnet_tpu.parallel.train_step import (make_train_step,
                                               make_sgd_momentum,
                                               sgd_momentum_init)
    V = 32000
    sym = models.get_symbol('transformer_lm', vocab_size=V,
                            num_embed=512, num_heads=8, num_layers=6,
                            seq_len=seq_len)
    arg_shapes, _, _ = sym.infer_shape(
        data=(batch_size, seq_len), softmax_label=(batch_size, seq_len))
    rng = np.random.RandomState(0)
    params = {n: jnp.asarray(
                  rng.normal(0, 0.02, s).astype(np.float32))
              for n, s in zip(sym.list_arguments(), arg_shapes)
              if n not in ('data', 'softmax_label')}
    opt = make_sgd_momentum(lr=0.01, momentum=0.9, wd=0.0,
                            rescale_grad=1.0 / (batch_size * seq_len))
    step = make_train_step(sym, opt, ('data', 'softmax_label'),
                           compute_dtype=jnp.bfloat16)
    toks = rng.randint(0, V, (batch_size, seq_len)).astype(np.float32)
    batch = {'data': jnp.asarray(toks),
             'softmax_label': jnp.asarray((toks + 1) % V)}
    key = jax.random.PRNGKey(0)
    state = sgd_momentum_init(params)
    outs, params, aux, state = step(params, {}, state, batch, key)
    sync(outs)
    t0 = time.time()
    for _ in range(iters):
        outs, params, aux, state = step(params, aux, state, batch, key)
    sync(outs)
    return batch_size * seq_len * iters / (time.time() - t0)


def bench_lenet(batch_size=128, iters=30):
    """LeNet MNIST training leg (BASELINE.json config 1)."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import models
    from mxnet_tpu.parallel.train_step import (make_train_step,
                                               make_sgd_momentum,
                                               sgd_momentum_init)
    sym = models.get_symbol('lenet', num_classes=10)
    dshape = (batch_size, 1, 28, 28)
    arg_shapes, _, aux_shapes = sym.infer_shape(data=dshape)
    rng = np.random.RandomState(0)
    params = {name: jnp.asarray(
                  rng.normal(0, 0.05, size=shape).astype(np.float32))
              for name, shape in zip(sym.list_arguments(), arg_shapes)
              if name not in ('data', 'softmax_label')}
    opt_update = make_sgd_momentum(lr=0.1, momentum=0.9, wd=0.0,
                                   rescale_grad=1.0 / batch_size)
    step = make_train_step(sym, opt_update, ('data', 'softmax_label'))
    batch = {'data': jnp.asarray(rng.rand(*dshape).astype(np.float32)),
             'softmax_label': jnp.asarray(
                 rng.randint(0, 10, batch_size).astype(np.float32))}
    key = jax.random.PRNGKey(0)
    opt_state = sgd_momentum_init(params)
    outs, params, aux, opt_state = step(params, {}, opt_state, batch, key)
    sync(outs)
    t0 = time.time()
    for _ in range(iters):
        outs, params, aux, opt_state = step(params, {}, opt_state, batch,
                                            key)
    sync(outs)
    return batch_size * iters / (time.time() - t0)


def bench_ssd_forward(batch_size=8, iters=10):
    """SSD VGG16-reduced detection forward (BASELINE.json config 5)."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import models
    from mxnet_tpu.parallel.train_step import make_eval_step
    sym = models.get_symbol('ssd-vgg16', num_classes=20)
    dshape = (batch_size, 3, 300, 300)
    arg_shapes, _, aux_shapes = sym.infer_shape(data=dshape)
    rng = np.random.RandomState(0)
    params = {name: jnp.asarray(
                  rng.normal(0, 0.02, size=shape).astype(np.float32))
              for name, shape in zip(sym.list_arguments(), arg_shapes)
              if name != 'data'}
    aux = {name: (jnp.ones(s, jnp.float32) if 'var' in name
                  else jnp.zeros(s, jnp.float32))
           for name, s in zip(sym.list_auxiliary_states(), aux_shapes)}
    step = make_eval_step(sym, compute_dtype=jnp.bfloat16)
    batch = {'data': jnp.asarray(rng.rand(*dshape).astype(np.float32))}
    key = jax.random.PRNGKey(0)
    outs = step(params, aux, batch, key)
    sync(outs)
    t0 = time.time()
    for _ in range(iters):
        outs = step(params, aux, batch, key)
    sync(outs)
    return batch_size * iters / (time.time() - t0)


def bench_pallas_kernels(iters=30):
    """On-chip parity + timing for the fusion kernels at ResNet shape
    classes: fused BN-apply matmul (1x1 path) and fused conv3x3 vs the
    plain-XLA reference expression.  Returns the geometric-mean
    speedup; logs per-shape numbers and max abs error (bf16 inputs, so
    tolerance ~3e-2 vs the f32-accumulated reference)."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import pallas_fused, pallas_conv
    rng = np.random.RandomState(0)
    speedups = []

    def timed(fn, *args):
        out = fn(*args)
        sync(out)
        t0 = time.time()
        for _ in range(iters):
            out = fn(*args)
        sync(out)
        return out, (time.time() - t0) / iters

    # 1x1 path: (N*H*W, C) x (C, F) per ResNet stage
    for (m, c, f) in ((128 * 56 * 56, 64, 64), (128 * 28 * 28, 128, 512),
                      (128 * 7 * 7, 512, 2048)):
        x = jnp.asarray(rng.randn(m, c).astype(np.float32) * 0.5,
                        jnp.bfloat16)
        w = jnp.asarray(rng.randn(c, f).astype(np.float32) * 0.2,
                        jnp.bfloat16)
        s = jnp.asarray(rng.rand(c).astype(np.float32) + 0.5,
                        jnp.bfloat16)
        b = jnp.asarray(rng.randn(c).astype(np.float32) * 0.2,
                        jnp.bfloat16)
        fused = jax.jit(lambda *a: pallas_fused.fused_scale_bias_dot(
            *a, relu=True))
        ref = jax.jit(lambda *a: pallas_fused._reference(*a, relu=True))
        got, t_fused = timed(fused, x, w, s, b)
        want, t_ref = timed(ref, x, w, s, b)
        err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                    - want.astype(jnp.float32))))
        scale = float(jnp.max(jnp.abs(want.astype(jnp.float32)))) + 1e-6
        log('pallas 1x1 m=%d c=%d f=%d: %.3fms vs xla %.3fms '
            '(%.2fx), rel err %.2e'
            % (m, c, f, t_fused * 1e3, t_ref * 1e3, t_ref / t_fused,
               err / scale))
        if err / scale > 0.05:
            raise RuntimeError('1x1 kernel parity FAILED: rel err %.3e'
                               % (err / scale))
        speedups.append(t_ref / t_fused)

    # 3x3 path per ResNet stage (NHWC), incl. the reshape-factored
    # stride-2 taps
    for (n, h, c, f, stride) in ((32, 56, 64, 64, 1),
                                 (32, 28, 128, 128, 1),
                                 (32, 28, 128, 128, 2)):
        x = jnp.asarray(rng.randn(n, h, h, c).astype(np.float32) * 0.5,
                        jnp.bfloat16)
        w = jnp.asarray(
            rng.randn(3, 3, c, f).astype(np.float32) * 0.1, jnp.bfloat16)
        s = jnp.asarray(rng.rand(c).astype(np.float32) + 0.5,
                        jnp.bfloat16)
        b = jnp.asarray(rng.randn(c).astype(np.float32) * 0.2,
                        jnp.bfloat16)
        fused = jax.jit(lambda *a: pallas_conv.fused_scale_bias_conv3x3(
            *a, stride=stride, relu=True))
        ref = jax.jit(lambda *a: pallas_conv._reference(
            *a, stride, True))
        got, t_fused = timed(fused, x, w, s, b)
        want, t_ref = timed(ref, x, w, s, b)
        err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                    - want.astype(jnp.float32))))
        scale = float(jnp.max(jnp.abs(want.astype(jnp.float32)))) + 1e-6
        log('pallas 3x3 n=%d h=%d c=%d f=%d s=%d: %.3fms vs xla '
            '%.3fms (%.2fx), rel err %.2e'
            % (n, h, c, f, stride, t_fused * 1e3, t_ref * 1e3,
               t_ref / t_fused, err / scale))
        if err / scale > 0.05:
            raise RuntimeError('3x3 kernel parity FAILED: rel err %.3e'
                               % (err / scale))
        speedups.append(t_ref / t_fused)
    return float(np.exp(np.mean(np.log(speedups))))


class _LegTimeout(Exception):
    pass


def run_leg(failed, results, name, fn, fmt='%s: %.1f', timeout_s=900):
    """Run one leg under a wall-clock cap.  A leg that raises or times
    out is logged, its name appended to ``failed``, and the run goes on
    to the next leg; main() then exits non-zero."""
    import signal

    def _alarm(signum, frame):
        raise _LegTimeout('%s exceeded %ds' % (name, timeout_s))

    old = signal.signal(signal.SIGALRM, _alarm)
    signal.alarm(timeout_s)
    try:
        t0 = time.time()
        val = fn()
        results[name] = val
        # per-phase wall time into the metrics registry so the
        # BENCH_metrics.json snapshot explains where the run's time went
        from mxnet_tpu import instrument
        instrument.observe('bench.leg.%s' % name, time.time() - t0)
        log(fmt % (name, val))
    except _LegTimeout as e:
        failed.append(name)
        log('%s leg TIMED OUT: %s' % (name, e))
    except Exception:
        failed.append(name)
        log('%s leg FAILED:\n%s' % (name, traceback.format_exc()))
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


def _primary_json(entry, device):
    """Build the one-line contract dict from a just-measured train
    entry (value + config + mfu/roofline when known) and the device it
    ran on."""
    out = {
        'metric': 'resnet50_train_imgs_per_sec_per_chip',
        'value': entry['value'],
        'unit': 'images/sec',
        'vs_baseline': round(entry['value'] / NORTH_STAR_TRAIN, 2),
        'vs_p100': round(entry['value'] / BASELINE_RESNET50_TRAIN_P100,
                         2),
    }
    for k in ('mfu', 'roofline_mandatory', 'batch_size', 'stem',
              'fuse_bn_conv'):
        if k in entry:
            out[k] = entry[k]
    out['device'] = device
    return out


def _best_train_entry(state):
    """Best train entry across the plain/fused variants."""
    cands = [state[k] for k in ('resnet50_train', 'resnet50_train_fused')
             if k in state]
    return max(cands, key=lambda e: e['value']) if cands else None


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--full', action='store_true',
                    help='also run the non-primary BASELINE.json configs')
    ap.add_argument('--batch-size', type=int, default=128)
    ap.add_argument('--skip-fused-compare', action='store_true',
                    help='measure only the current MXTPU_FUSE_BN_CONV '
                         'setting, not both variants')
    args = ap.parse_args()
    failed = []       # legs that raised or timed out

    # One process holds the chip.  No chip, no run: there is no stored
    # number to fall back on and no CPU leg under a speed metric's name.
    import jax
    dev = jax.devices()[0]
    device = {'platform': dev.platform, 'kind': dev.device_kind,
              'count': len(jax.devices())}
    log('benchmark device: platform=%(platform)s device_kind=%(kind)s '
        'count=%(count)d' % device)
    if dev.platform != 'tpu':
        sys.exit('bench.py needs a TPU, but JAX reports platform=%r '
                 '(device_kind=%r)' % (dev.platform, dev.device_kind))
    from mxnet_tpu import compile_cache, config, instrument
    peak_flops, peak_bw = device_peaks()   # an unknown device kind raises
    log('compile cache: %s'
        % compile_cache.ensure_persistent_cache(checkout_default=True))
    # metrics on for the whole run: the BENCH_metrics.json snapshot
    # records WHY throughput moved (retraces, samples/sec, transfer
    # bytes, per-leg wall time), not just that it did
    instrument.set_metrics(True)

    # The five hermetic CPU-child legs (ROADMAP Speed item 1 takes them
    # out of the perf record): each child pins its own CPU backend
    # before jax init, so none asks for the chip this process holds.
    multichip_fresh = {}

    def _multichip_leg():
        v, extra = bench_multichip_fit()
        record_leg('multichip_fit_ips', v, **extra)
        return v

    run_leg(failed, multichip_fresh, 'multichip_fit_ips', _multichip_leg,
            '%s: %.1f imgs/sec (dp x tp sharded fit, 8 virtual '
            'devices)')

    # goodput leg: the input-pipeline & goodput plane's trajectory
    # datapoint (full iterator chain on the CPU backend)
    def _goodput_leg():
        v, extra = bench_goodput()
        record_leg('goodput_fraction', v, **extra)
        return v

    run_leg(failed, multichip_fresh, 'goodput_fraction', _goodput_leg,
            '%s: %.3f (hermetic CPU fit, full iterator chain)')

    # elastic repair leg: the detect->repair latency
    def _recovery_leg():
        v, extra = bench_recovery()
        record_leg('recovery_time_secs', v, **extra)
        return v

    run_leg(failed, multichip_fresh, 'recovery_time_secs', _recovery_leg,
            '%s: %.2f s (injected kill -> first post-repair step)')

    # step-compiler leg: the fusion pipeline's before/after datapoint
    # (check_fusion reference model, MXTPU_FUSE=aggressive, CPU backend)
    def _fused_step_leg():
        v, extra = bench_fused_step()
        record_leg('fused_step_ips', v, **extra)
        return v

    run_leg(failed, multichip_fresh, 'fused_step_ips', _fused_step_leg,
            '%s: %.1f imgs/sec (step-compiler reference model, '
            'MXTPU_FUSE=aggressive)')

    # serving-fleet leg: the 2-replica closed-loop qps at the p99 SLO
    # (and the scaling factor)
    def _fleet_leg():
        v, extra = bench_fleet()
        # the chaos leg's repair latency rides the same child run but
        # is its own trajectory datapoint (lower-is-better: a fattened
        # detect->quarantine->replace loop must trip check_perf even
        # while qps holds)
        rec = extra.pop('replica_recovery_secs', None)
        record_leg('serve_fleet_qps', v, **extra)
        if isinstance(rec, (int, float)):
            record_leg('replica_recovery_secs', rec)
            log('replica_recovery_secs: %.3f s (chaos leg: injected '
                'kill/wedge -> warmed replacement attached)' % rec)
        return v

    run_leg(failed, multichip_fresh, 'serve_fleet_qps', _fleet_leg,
            '%s: %.1f req/sec (2-replica fleet at the p99 SLO, '
            'virtual devices)')

    default_fuse = bool(config.get('MXTPU_FUSE_BN_CONV'))
    results = {}

    stem = 'space_to_depth'
    fresh = {}   # legs measured by THIS process (no cache involved)
    fresh.update(multichip_fresh)

    try:
        min_bytes = analytic_min_bytes(batch_size=args.batch_size,
                                       stem=stem)
    except Exception:
        log('analytic byte model failed:\n' + traceback.format_exc())
        min_bytes = None

    def train_entry(fuse):
        os.environ['MXTPU_FUSE_BN_CONV'] = '1' if fuse else '0'
        ips, step_flops, step_bytes = bench_resnet50_train(
            batch_size=args.batch_size)
        sps = ips / args.batch_size
        extra = {'batch_size': args.batch_size, 'stem': stem,
                 'fuse_bn_conv': fuse,
                 'metric_mode': 'raw_fused_step'}
        from mxnet_tpu import perfwatch
        if step_flops:
            extra['mfu'] = round(
                perfwatch.mfu(step_flops, sps, peak=peak_flops), 4)
            # cost-analysis bytes kept for reference only — they bill
            # VMEM-resident traffic as HBM and can exceed peak
            extra['bytes_cost_analysis'] = step_bytes
        if min_bytes:
            # mandatory-traffic roofline: <= 1 by construction,
            # 1 - frac = removable-traffic headroom (new key name —
            # r02/r03 'roofline_frac' had cost-analysis semantics and
            # must not replay under the new interpretation)
            extra['roofline_mandatory'] = round(
                perfwatch.roofline_mandatory(min_bytes, sps,
                                             peak_bw=peak_bw), 4)
        name = 'resnet50_train_fused' if fuse else 'resnet50_train'
        record_leg(name, ips, **extra)
        log('resnet-50 train (fuse_bn_conv=%s): %.1f imgs/sec '
            '(north star %.0f, %.2fx)%s%s'
            % (fuse, ips, NORTH_STAR_TRAIN, ips / NORTH_STAR_TRAIN,
               ('; mfu %.1f%%' % (100 * extra['mfu']))
               if step_flops else '',
               ('; mandatory-traffic roofline %.1f%%'
                % (100 * extra['roofline_mandatory']))
               if min_bytes else ''))
        entry = {'value': round(ips, 1)}
        entry.update(extra)
        fresh[name] = entry
        return entry

    with _fuse_env(None):   # restore whatever the caller had
        run_leg(failed, results, 'train_default',
                lambda: train_entry(default_fuse),
                fmt='%s measured: %s', timeout_s=720)
        if not args.skip_fused_compare:
            run_leg(failed, results, 'train_other',
                    lambda: train_entry(not default_fuse),
                    fmt='%s measured: %s', timeout_s=720)

    # PRIMARY CONTRACT: one JSON line on stdout, from a measurement of
    # THIS run or not at all.  Extra legs only write stderr afterwards.
    entry = _best_train_entry(fresh)
    if entry is None:
        sys.exit('bench.py: no train leg completed (failed: %s)'
                 % ', '.join(failed))
    print(json.dumps(_primary_json(entry, device)), flush=True)
    train_ips = entry['value']

    extras = {}

    def leg(name, fn, fmt='%s: %.1f imgs/sec', **extra_kw):
        """Run a non-primary leg; persist + mark fresh on success.
        extra_kw overrides the recorded defaults (the folded inference
        legs record their own fuse_bn_conv)."""
        def wrapped():
            v = fn()
            record_leg(name, v,
                       **{'fuse_bn_conv': default_fuse, **extra_kw})
            fresh[name] = v
            return v
        run_leg(failed, extras, name, wrapped, fmt)

    def _under_fuse(fuse, fn, **kw):
        with _fuse_env(fuse):
            return fn(**kw)

    # plain leg pinned unfused so the folded leg below is a real
    # comparison even when the caller exported the knob
    leg('resnet50_infer_bs32_ips',
        lambda: _under_fuse(False, bench_inference, model_name='resnet-50'),
        batch_size=32, fuse_bn_conv=False)
    # eval-time conv->bn folding + pre-act fusion: measured explicitly
    # because the knob defaults off
    leg('resnet50_infer_folded_ips',
        lambda: _under_fuse(True, bench_inference,
                            model_name='resnet-50'),
        batch_size=32, fuse_bn_conv=True)
    # decode throughput scales with host cores (preprocess_threads);
    # record the core count so the figure is interpretable
    leg('io_pipeline_ips', bench_io_pipeline,
        '%s: %.1f decoded imgs/sec (host feed-rate ceiling)',
        host_cpus=os.cpu_count())
    # the product path measures under the variant that WON the train
    # comparison, so "within N%" compares like to like
    best_fuse = bool(entry.get('fuse_bn_conv', default_fuse))
    if best_fuse != default_fuse:
        log('module_fit legs use fuse_bn_conv=%s (the winning train '
            'variant)' % best_fuse)

    leg('module_fit_ips',
        lambda: _under_fuse(best_fuse, bench_module_fit,
                            batch_size=args.batch_size),
        '%s: %.1f imgs/sec (user path)',
        batch_size=args.batch_size, stem=stem, fuse_bn_conv=best_fuse)
    if extras.get('module_fit_ips'):
        log('Module.fit achieves %.0f%% of the raw fused step'
            % (100 * extras['module_fit_ips'] / train_ips))

    # pipeline leg: the fit loop WITH metrics enabled through the
    # sync-free pipeline — persisted with its gap to the raw fused step
    # so BENCH_*.json tracks loop overhead round over round.  Recorded
    # directly (not via leg()) because pct_of_raw_step is computed from
    # the runtime value — one record_leg call, one write path.
    def _pipeline_fit():
        v = _under_fuse(best_fuse, bench_module_fit_pipeline,
                        batch_size=args.batch_size)
        extra = {'batch_size': args.batch_size, 'stem': stem,
                 'fuse_bn_conv': best_fuse,
                 'metric_mode': 'device_metrics', 'async_depth': 2}
        extra['pct_of_raw_step'] = round(100.0 * v / train_ips, 1)
        log('pipeline fit loop achieves %.0f%% of the raw fused step '
            '(metrics on)' % extra['pct_of_raw_step'])
        record_leg('module_fit_pipeline_ips', v, **extra)
        fresh['module_fit_pipeline_ips'] = v
        return v

    run_leg(failed, extras, 'module_fit_pipeline_ips', _pipeline_fit,
            '%s: %.1f imgs/sec (sync-free fit loop, metrics on)')

    # health-plane leg: what the on-device sentinels cost per fused
    # step (docs/observability.md — the number that justifies leaving
    # MXTPU_HEALTH_SENTINELS on for long runs)
    def _health_leg():
        pct = bench_health_overhead()
        record_leg('health_overhead_pct', pct, action='warn',
                   device_metrics=True)
        fresh['health_overhead_pct'] = pct
        return pct

    run_leg(failed, extras, 'health_overhead_pct', _health_leg,
            '%s: %.1f%% (fused step, sentinels on vs off)')

    # serving-plane leg: requests/sec at a p99 SLO through the dynamic
    # batcher (docs/serving.md) — the capacity number the ModelServer
    # is provisioned on.  The serving.* histograms ride into
    # BENCH_metrics.json with the end-of-round snapshot.
    def _serving_leg():
        qps, best = bench_serving()
        record_leg('serve_qps_at_p99_slo', qps,
                   p99_ms=round(best['p99_ms'], 2),
                   p50_ms=round(best['p50_ms'], 2),
                   slo_p99_ms=best['slo_p99_ms'],
                   concurrency=best['concurrency'])
        fresh['serve_qps_at_p99_slo'] = qps
        return qps

    run_leg(failed, extras, 'serve_qps_at_p99_slo', _serving_leg,
            '%s: %.1f req/s (dynamic batcher, p99 within SLO)')
    if args.full:
        def _train_nhwc():
            saved = os.environ.get('MXTPU_CONV_LAYOUT')
            os.environ['MXTPU_CONV_LAYOUT'] = 'NHWC'
            try:
                with _fuse_env(False):
                    ips, _, _ = bench_resnet50_train(
                        batch_size=args.batch_size)
                return ips
            finally:
                if saved is None:
                    os.environ.pop('MXTPU_CONV_LAYOUT', None)
                else:
                    os.environ['MXTPU_CONV_LAYOUT'] = saved

        # layout experiment: channels-last convs, unfused (the knob
        # README marks 'exposed for experimentation' — this is its
        # chip number)
        leg('resnet50_train_nhwc_ips', _train_nhwc,
            batch_size=args.batch_size, conv_layout='NHWC',
            fuse_bn_conv=False)
        # batch-size sweep point: r02's best was bs256 pre-fusion
        if args.batch_size != 256:
            leg('resnet50_train_bs256_ips',
                lambda: _under_fuse(best_fuse, lambda:
                    bench_resnet50_train(batch_size=256)[0]),
                batch_size=256, fuse_bn_conv=best_fuse)
        leg('module_fit_native_ips',
            lambda: _under_fuse(best_fuse, bench_module_fit_native,
                                batch_size=args.batch_size),
            '%s: %.1f imgs/sec (native pipeline -> Module.fit)',
            batch_size=args.batch_size, host_cpus=os.cpu_count(),
            fuse_bn_conv=best_fuse)
        leg('resnet152_infer_ips',
            lambda: _under_fuse(False, bench_inference,
                                model_name='resnet-152'),
            batch_size=32, fuse_bn_conv=False)
        leg('inception_v3_infer_ips',
            lambda: _under_fuse(False, bench_inference,
                                model_name='inception-v3',
                                image_shape=(3, 299, 299)),
            batch_size=32, fuse_bn_conv=False)
        leg('inception_v3_infer_folded_ips',
            lambda: _under_fuse(True, bench_inference,
                                model_name='inception-v3',
                                image_shape=(3, 299, 299)),
            batch_size=32, fuse_bn_conv=True)
        leg('vgg16_infer_ips', lambda: bench_inference('vgg16'),
            batch_size=32)
        leg('pallas_kernel_speedup_geomean', bench_pallas_kernels,
            '%s: %.2fx (fused kernel vs plain-XLA expression)')
        leg('lstm_lm_train_wps', bench_lstm_bucketing,
            '%s: %.1f words/sec')
        leg('transformer_lm_train_tps', bench_transformer_lm,
            '%s: %.1f tokens/sec (bf16 flash-attention)')
        leg('lenet_train_ips', bench_lenet)
        leg('ssd_fwd_ips', bench_ssd_forward)

    metrics_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), 'BENCH_metrics.json')
    instrument.dump_metrics(metrics_path)
    log('metrics snapshot: %s' % metrics_path)
    log('persisted state: %s' % json.dumps(load_state(), sort_keys=True))
    if failed:
        sys.exit('bench.py: %d leg(s) failed or timed out: %s'
                 % (len(failed), ', '.join(failed)))


if __name__ == '__main__':
    main()
