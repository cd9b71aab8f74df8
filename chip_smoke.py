#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on the
chip.

One process, no children.  Drives the main path once through the entry
points a user calls — ``Module.fit`` and ``ModelServer`` — at the full
width and depth of ResNet-50 (batch 128, bf16 compute, random weights
from a seed), then compiles every Pallas kernel at the shapes that model
makes, times the completion barrier, and records a profiler trace.  With
four or more devices it repeats the fit sharded and serves four replicas.

Exits non-zero at the first failed check, naming the phase, and then
prints no result.  It fails at once unless JAX's first device is a TPU
whose kind is in ``perfwatch.PEAKS``: there is no CPU fallback, so here
in the sandbox (``JAX_PLATFORMS=cpu``) it must fail.  On success the last
line of stdout is one JSON object::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

The compile cache goes where ``compile_cache.resolve_cache_dir`` says
(``JAX_COMPILATION_CACHE_DIR``, else ``<checkout>/.jax_cache``), so a
second run in the same checkout reports cache hits and a shorter time.
"""
import json
import os
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

BATCH = 128
IMAGE = (3, 224, 224)
CLASSES = 1000
FIT_STEPS = 8
FIT_ARGS = dict(
    num_epoch=1, optimizer='sgd', eval_metric='acc',
    optimizer_params={'learning_rate': 0.05, 'momentum': 0.9, 'wd': 1e-4})
TOL = {'bfloat16': 3e-2, 'float32': 2e-3}
ATTENTION_SHAPES = ((16, 8, 512, 64), (1, 8, 2048, 128))   # b, h, t, d
# the gated delta rule's segment in kimi_linear_fit_8k: sequences, tokens of
# a segment, heads, channels a head, tokens a chunk
KDA_SEGMENT_SHAPE = (2, 1024, 32, 128, 64)


class SmokeFailure(Exception):
    pass


def check(cond, message):
    if not cond:
        raise SmokeFailure(message)


def run_phase(name, fn, *args):
    """Run one phase; a failure names the phase and ends the run."""
    print('[%s] start' % name, flush=True)
    t0 = time.time()
    try:
        out = fn(*args)
    except BaseException:
        print('chip_smoke: phase %r FAILED after %.1fs'
              % (name, time.time() - t0), file=sys.stderr, flush=True)
        raise
    print('[%s] ok in %.1fs' % (name, time.time() - t0), flush=True)
    return out


def on_tpu(array):
    return all(d.platform == 'tpu' for d in array.devices())


def memory_stats(device):
    return device.memory_stats()


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

class RepeatBatchIter(object):
    """Synthetic DataIter replaying one seeded host batch."""

    def __init__(self, batches):
        import mxnet_tpu as mx
        rng = np.random.RandomState(0)
        self.data = rng.rand(BATCH, *IMAGE).astype(np.float32)
        self.label = rng.randint(0, CLASSES, BATCH).astype(np.float32)
        self._batch = mx.io.DataBatch([self.data], [self.label], pad=0)
        self.batch_size = BATCH
        self.batches = batches
        self.provide_data = [('data', (BATCH,) + IMAGE)]
        self.provide_label = [('softmax_label', (BATCH,))]
        self._i = 0

    def reset(self):
        self._i = 0

    def __iter__(self):
        return self

    def __next__(self):
        if self._i >= self.batches:
            raise StopIteration
        self._i += 1
        return self._batch

    next = __next__


def resnet50():
    from mxnet_tpu import models
    return models.get_symbol('resnet-50', num_classes=CLASSES,
                             image_shape=IMAGE, stem='space_to_depth')


def fit_resnet50(context=None, **fit_kw):
    """``Module(...).fit`` for FIT_STEPS steps through the default fit
    pipeline (device feed, device metrics, step window, SGD with
    momentum).  Returns (module, loss at the first and the last step)."""
    import mxnet_tpu as mx
    mx.random.seed(0)            # the initializer draws from this stream
    it = RepeatBatchIter(FIT_STEPS)
    mod = mx.mod.Module(resnet50(), context=context,
                        compute_dtype=jnp.bfloat16)
    losses = {}

    def batch_end(param):
        if param.nbatch in (0, FIT_STEPS - 1):
            out = mod.get_outputs()[0]
            check(on_tpu(out.handle), 'step output is not on a TPU: %s'
                  % out.handle.devices())
            prob = out.asnumpy().astype(np.float64)
            picked = prob[np.arange(BATCH), it.label.astype(int)]
            losses[param.nbatch] = float(-np.log(picked + 1e-12).mean())

    mod.fit(it, initializer=mx.init.Xavier(rnd_type='gaussian',
                                           factor_type='in', magnitude=2),
            batch_end_callback=batch_end, **FIT_ARGS, **fit_kw)
    check(mod._fused is not None, 'Module.fit did not take the fused step')
    first, last = losses[0], losses[FIT_STEPS - 1]
    check(np.isfinite(first) and np.isfinite(last),
          'loss is not finite: first %r last %r' % (first, last))
    check(first != last, 'loss did not move in %d steps: %r'
          % (FIT_STEPS, first))
    exec_ = mod._exec_group.execs[0]
    for name, arr in list(exec_.arg_dict.items()) + \
            list(exec_.aux_dict.items()):
        check(on_tpu(arr.handle), '%s is not on a TPU: %s'
              % (name, arr.handle.devices()))
    print('  loss %.4f -> %.4f over %d steps' % (first, last, FIT_STEPS),
          flush=True)
    return mod, (first, last)


def phase_train(workdir):
    import mxnet_tpu as mx
    mod, losses = fit_resnet50()
    stats = memory_stats(jax.devices()[0])
    check(stats['peak_bytes_in_use'] > 0,
          'memory_stats reports no peak bytes in use: %s' % stats)
    print('  memory_stats: ' + ', '.join(
        '%s %.2f GiB' % (k, stats[k] / 2.0 ** 30)
        for k in ('bytes_in_use', 'peak_bytes_in_use', 'bytes_limit')
        if k in stats), flush=True)
    # the reads after donation: the fused step donated every parameter
    # buffer it was given, so these must see the live ones
    arg_params, aux_params = mod.get_params()
    for name, arr in list(arg_params.items()) + list(aux_params.items()):
        check(np.isfinite(arr.asnumpy()).all(),
              'parameter %s is not finite after fit' % name)
    prefix = os.path.join(workdir, 'resnet50')
    mx.model.save_checkpoint(prefix, 1, mod.symbol, arg_params, aux_params)
    return mod, prefix, losses


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def replica_flushes():
    from mxnet_tpu import instrument
    counters = instrument.metrics_snapshot()['counters']
    return {k: v for k, v in counters.items()
            if k.startswith('serving.flushes|')}


def phase_serve(prefix):
    import mxnet_tpu as mx
    from mxnet_tpu import instrument
    from mxnet_tpu.predictor import Predictor
    from mxnet_tpu.serving import ModelServer
    rows = np.random.RandomState(1).rand(8, *IMAGE).astype(np.float32)
    with open(prefix + '-symbol.json') as f:
        direct = Predictor(f.read(), mx.nd.load(prefix + '-0001.params'),
                           {'data': (8,) + IMAGE})
    want = direct.forward(data=rows)[0]
    check(want.shape == (8, CLASSES) and np.isfinite(want).all(),
          'direct Predictor output is wrong: shape %s' % (want.shape,))
    before = instrument.metrics_snapshot()['counters'].get(
        'serving.flushes', 0)
    server = ModelServer()
    try:
        served = server.load_model('resnet50', prefix=prefix, epoch=1,
                                   input_shapes={'data': (8,) + IMAGE})
        for arr in served._executor.arg_dict.values():
            check(on_tpu(arr.handle), 'a served parameter is not on a '
                  'TPU: %s' % arr.handle.devices())
        for n in (1, 3, 8):          # pow2 buckets 1, 4 and 8
            got = server.predict('resnet50', data=rows[:n])[0]
            check(got.shape == (n, CLASSES),
                  'served %d rows, got shape %s' % (n, got.shape))
            err = float(np.abs(got - want[:n]).max())
            check(err <= TOL['bfloat16'], 'served %d rows differ from '
                  'Predictor.forward by %.3g' % (n, err))
            print('  %d row(s): max abs diff %.2e' % (n, err), flush=True)
        moved = instrument.metrics_snapshot()['counters'].get(
            'serving.flushes', 0) - before
        check(moved >= 3, 'serving.flushes moved by %d, expected >= 3'
              % moved)
    finally:
        server.close()


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def conv_shapes():
    """Every distinct convolution of ResNet-50 at BATCH as
    (kernel, height, in channels, filters, stride), read off the symbol
    so the list cannot drift from the model."""
    sym = resnet50()
    internals = sym.get_internals()
    _, out_shapes, _ = internals.infer_shape(data=(BATCH,) + IMAGE)
    shape_of = dict(zip(internals.list_outputs(), out_shapes))
    found = set()
    for node in sym.topo_nodes():
        if node.op != 'Convolution':
            continue
        src, idx = node.inputs[0]
        _, c, h, _ = shape_of[src.output_names()[idx]]
        found.add((tuple(node.attrs['kernel'])[0], h, c,
                   int(node.attrs['num_filter']),
                   tuple(node.attrs.get('stride', (1, 1)))[0]))
    return sorted(found)


@jax.jit
def max_error(got, want):
    """Largest absolute difference, relative to the reference's largest
    magnitude where that exceeds 1; reduced on the device."""
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return jnp.max(jnp.abs(got - want)) / \
        jnp.maximum(1.0, jnp.max(jnp.abs(want)))


def compile_and_compare(name, kernel_fn, ref_fn, args, expect_kernel, tol):
    """Compile ``kernel_fn`` for the chip, require the Pallas custom call
    in its HLO exactly when the dispatcher's shape guard admits the
    kernel, run it, and compare with the jitted reference."""
    compiled = jax.jit(kernel_fn).lower(*args).compile()
    has_call = 'tpu_custom_call' in compiled.as_text()
    check(has_call == expect_kernel,
          '%s: tpu_custom_call %s in the compiled HLO, but the shape '
          'guard says %s' % (name, 'is' if has_call else 'is not',
                             'kernel' if expect_kernel else 'reference'))
    got = jax.tree_util.tree_leaves(compiled(*args))
    want = jax.tree_util.tree_leaves(jax.jit(ref_fn)(*args))
    # a NaN or an infinity in either output makes the error non-finite
    worst = max(float(max_error(g, w)) for g, w in zip(got, want))
    check(np.isfinite(worst) and worst <= tol,
          '%s: differs from its reference by %.3g (tol %g)'
          % (name, worst, tol))
    print('  %-58s %s  err %.1e' % (
        name, 'kernel   ' if has_call else 'reference', worst), flush=True)


def phase_kernels():
    from mxnet_tpu.ops import pallas_attention, pallas_conv, pallas_fused
    rng = np.random.RandomState(2)

    def rand(shape, dtype, scale=1.0):
        return jnp.asarray(rng.randn(*shape).astype(np.float32) * scale,
                           dtype)

    def affine(c):
        return (jnp.asarray(rng.rand(c).astype(np.float32) + 0.5),
                jnp.asarray(rng.randn(c).astype(np.float32) * 0.2))

    convs = conv_shapes()
    for dtype in ('bfloat16', 'float32'):
        tol = TOL[dtype]
        for k, h, c, f, stride in convs:
            if k != 3:
                continue
            x = rand((BATCH, h, h, c), dtype, 0.5)
            w = rand((3, 3, c, f), dtype, (9 * c) ** -0.5)
            s, b = affine(c)
            compile_and_compare(
                'conv3x3 %s h=%d c=%d f=%d stride=%d' % (dtype, h, c, f,
                                                        stride),
                lambda *a, st=stride:
                    pallas_conv.fused_scale_bias_conv3x3(*a, stride=st),
                lambda *a, st=stride: pallas_conv._reference(*a, st, True),
                (x, w, s, b),
                pallas_conv.kernel_blocks(x.shape, f, stride) is not None,
                tol)
        # the 1x1 convolutions as matmuls over (N*H*W, C), plus the FC
        dots = sorted({(BATCH * (h // stride) ** 2, c, f)
                       for k, h, c, f, stride in convs if k == 1}
                      | {(BATCH, 2048, CLASSES)})
        for m, c, f in dots:
            x = rand((m, c), dtype, 0.5)
            w = rand((c, f), dtype, c ** -0.5)
            s, b = affine(c)
            bias = rand((f,), dtype, 0.2)
            admits = pallas_fused.dot_blocks(m, c, f) is not None
            compile_and_compare(
                'scale_bias_dot %s m=%d c=%d f=%d' % (dtype, m, c, f),
                lambda *a: pallas_fused.fused_scale_bias_dot(*a, relu=True),
                lambda *a: pallas_fused._reference(*a, relu=True),
                (x, w, s, b), admits, tol)
            compile_and_compare(
                'dot_epilogue %s m=%d c=%d f=%d' % (dtype, m, c, f),
                lambda *a: pallas_fused.fused_dot_epilogue(*a, relu=True),
                lambda *a: pallas_fused._dot_epi_reference(*a, True, None),
                (x, w, bias), admits, tol)
        for m, c in sorted({(m, c) for m, c, _ in dots}):
            x = rand((m, c), dtype, 0.5)
            compile_and_compare(
                'bn_relu %s m=%d c=%d' % (dtype, m, c),
                pallas_fused.fused_bn_relu,
                pallas_fused._bn_relu_reference,
                (x,) + affine(c),
                pallas_fused.bn_relu_blocks(m, c) is not None, tol)

    for b, heads, t, d in ATTENTION_SHAPES:
        q, k, v = (rand((b, heads, t, d), 'bfloat16', 0.5)
                   for _ in range(3))

        def ref(q, k, v):
            bh = (b * heads, t, d)
            out, _ = pallas_attention._ref_attention(
                q.reshape(bh), k.reshape(bh), v.reshape(bh),
                d ** -0.5, True)
            return out.reshape(q.shape)

        def flash(q, k, v):
            return pallas_attention.flash_attention(q, k, v, causal=True)

        def grads(fn):
            return jax.grad(lambda *a: fn(*a).astype(jnp.float32).sum(),
                            argnums=(0, 1, 2))

        shape = 'b=%d h=%d t=%d d=%d' % (b, heads, t, d)
        compile_and_compare('flash_attention fwd ' + shape, flash, ref,
                            (q, k, v), True, TOL['bfloat16'])
        compile_and_compare('flash_attention bwd ' + shape, grads(flash),
                            grads(ref), (q, k, v), True, TOL['bfloat16'])

    # the gated delta rule of KimiDeltaAttention: the two Pallas kernels of
    # a segment against the jnp form, value and all six cotangents
    from mxnet_tpu import config
    from mxnet_tpu.ops import lm
    n, rows, heads, d, chunk = KDA_SEGMENT_SHAPE
    q = rand((n, rows, heads, d), 'bfloat16', d ** -0.5)
    k = rand((n, rows, heads, d), 'float32')
    k = (k / jnp.linalg.norm(k, axis=-1, keepdims=True)).astype(jnp.bfloat16)
    v = rand((n, rows, heads, d), 'bfloat16')
    g = -jnp.abs(rand((n, rows, heads, d), 'float32', 0.5)) ** 3
    beta = jax.nn.sigmoid(rand((n, rows, heads), 'float32'))
    state = rand((n, heads, d, d), 'float32', 0.1)
    cotangent = (rand((n, heads, d, d), 'float32', 0.1),
                 rand((n, rows, heads, d), 'bfloat16'))

    def rule(kernels):
        def segment(*a):
            # read when the segment is traced: the jnp form is the
            # reference
            if not kernels:
                os.environ['MXTPU_DISABLE_PALLAS'] = '1'
            try:
                after, out, _ = lm._rule_segment(
                    rows, rows // chunk, chunk, a[5], a[:5], 0)
            finally:
                os.environ.pop('MXTPU_DISABLE_PALLAS', None)
            return after, out
        return segment

    def backward(fn):
        return lambda *a: jax.vjp(fn, *a)[1](cotangent)
    shape = 'n=%d rows=%d h=%d d=%d chunk=%d' % KDA_SEGMENT_SHAPE
    check(config.pallas_mode() == 'kernel' and lm._rule_in_kernel(
        rows, d, d, chunk, jnp.bfloat16),
          'the rule of %s does not take its kernels' % shape)
    compile_and_compare('kda rule fwd ' + shape, rule(True), rule(False),
                        (q, k, v, g, beta, state), True, TOL['bfloat16'])
    compile_and_compare('kda rule bwd ' + shape, backward(rule(True)),
                        backward(rule(False)), (q, k, v, g, beta, state),
                        True, TOL['bfloat16'])


# ---------------------------------------------------------------------------
# barrier and trace
# ---------------------------------------------------------------------------

def phase_barrier():
    """Time one ~1 s matmul chain three ways.  ``engine.sync`` is
    ``block_until_ready``; if that wait returned before the device had
    finished it would come out shorter than a device-to-host fetch of
    the result, which cannot."""
    from mxnet_tpu import engine
    n, links = 8192, 160

    @jax.jit
    def chain(x, w):
        return jax.lax.fori_loop(
            0, links, lambda _, y: jnp.dot(y, w).astype(y.dtype), x)

    key = jax.random.PRNGKey(3)
    x = jax.random.normal(key, (n, n), jnp.bfloat16)
    w = jax.random.normal(key, (n, n), jnp.bfloat16) * n ** -0.5
    engine.sync(chain(x, w))                      # compile + warm

    def timed(wait):
        t0 = time.perf_counter()
        y = chain(x, w)
        t_dispatch = time.perf_counter() - t0
        wait(y)
        total = time.perf_counter() - t0
        engine.sync(y)
        return t_dispatch, total

    dispatch, block = timed(engine.sync)
    _, fetch = timed(lambda y: np.asarray(y.ravel()[:1]))
    print('  matmul chain (%d x %dx%d bf16): dispatch only %.4fs, '
          'block_until_ready %.4fs, one-element fetch %.4fs'
          % (links, n, n, dispatch, block, fetch), flush=True)
    check(block >= 0.9 * fetch,
          'block_until_ready (%.4fs) returned more than 10%% before a '
          'device-to-host fetch of the same result (%.4fs): it is not a '
          'barrier here' % (block, fetch))
    return {'dispatch_s': dispatch, 'block_until_ready_s': block,
            'fetch_s': fetch}


def phase_trace(mod, workdir):
    """Two more fit steps of the trained module under the profiler."""
    import glob
    import mxnet_tpu as mx
    mx.profiler.profiler_set_config(
        filename=os.path.join(workdir, 'profile.json'))
    mx.profiler.profiler_set_state('run')   # jax.profiler.start_trace
    try:
        mod.fit(RepeatBatchIter(2), **FIT_ARGS)
    finally:
        mx.profiler.profiler_set_state('stop')
    traces = glob.glob(os.path.join(workdir, 'profile_jax_trace',
                                    '**', '*.xplane.pb'), recursive=True)
    check(traces, 'the profiler wrote no .xplane.pb under %s' % workdir)
    print('  %s (%.1f MiB)' % (os.path.basename(traces[0]),
                               os.path.getsize(traces[0]) / 2.0 ** 20),
          flush=True)


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def check_spread(mod, one_chip_first_loss, first_loss, what):
    devices = jax.devices()[:4]
    out = mod.get_outputs()[0].handle
    check(len({s.device for s in out.addressable_shards}) == 4 and
          out.addressable_shards[0].data.shape[0] == BATCH // 4,
          '%s: the batch is not split over four devices: %s'
          % (what, out.sharding))
    leaves = jax.tree_util.tree_leaves(mod._fused_opt_state)
    check(leaves and all(len(leaf.sharding.device_set) == 4
                         for leaf in leaves),
          '%s: optimizer state does not span four devices' % what)
    for d in devices:
        check(memory_stats(d)['bytes_in_use'] > 0,
              '%s: %s reports no memory in use' % (what, d))
    rel = abs(first_loss - one_chip_first_loss) / one_chip_first_loss
    check(rel <= 1e-2, '%s: first-step loss %.5f vs one chip %.5f '
          '(rel %.3g)' % (what, first_loss, one_chip_first_loss, rel))


def phase_four_chips(prefix, one_chip_first_loss):
    import mxnet_tpu as mx
    from mxnet_tpu import commwatch
    from mxnet_tpu.serving import ModelServer
    # commwatch makes the fit capture its compiled step, whose HLO and
    # collective counts are read below (fit re-reads the knob)
    os.environ['MXTPU_COMMWATCH'] = '1'
    try:
        mod, (first, _) = fit_resnet50(mesh='dp=4')
        check_spread(mod, one_chip_first_loss, first, "fit(mesh='dp=4')")
        hlo = '\n'.join(c.as_text() for c in mod._fused_aot.values())
        check('all-reduce' in hlo,
              'the dp=4 compiled step contains no all-reduce')
        counted = sum(row['collectives'].get('all-reduce', {}).get(
            'count', 0) for row in commwatch.programs())
        check(counted > 0, 'commwatch counted no all-reduce in the dp=4 '
              'step: %s' % commwatch.programs())
        print('  dp=4: %d all-reduce(s) counted by commwatch' % counted,
              flush=True)
        del mod
    finally:
        del os.environ['MXTPU_COMMWATCH']
    mod, (first, _) = fit_resnet50(
        context=[mx.tpu(i) for i in range(4)])
    check_spread(mod, one_chip_first_loss, first, 'Module(context=[4 tpu])')
    del mod

    rows = np.random.RandomState(1).rand(8, *IMAGE).astype(np.float32)
    # one row a flush, so that a burst spreads over the replicas instead
    # of coalescing into one batch on whichever replica is free first
    server = ModelServer(max_batch=1)
    try:
        server.load_model('resnet50x4', prefix=prefix, epoch=1, replicas=4,
                          input_shapes={'data': (8,) + IMAGE})
        placed = set()
        for rep in server._entry('resnet50x4').replicas:
            params = rep.predictor._executor.arg_dict.values()
            devs = {d for arr in params for d in arr.handle.devices()}
            check(len(devs) == 1 and all(on_tpu(a.handle) for a in params),
                  'replica %s sits on %s' % (rep.rid, devs))
            placed |= devs
        check(len(placed) == 4, 'four replicas sit on %s' % placed)
        deadline = time.time() + 300
        answered = {}
        while len(answered) < 4:
            check(time.time() < deadline,
                  'only replicas %s answered in 300s' % sorted(answered))
            futures = [server.submit('resnet50x4', data=rows[:1])
                       for _ in range(32)]
            for fut in futures:
                check(fut.result(timeout=300)[0].shape == (1, CLASSES),
                      'a replica returned the wrong shape')
            answered = {k: v for k, v in replica_flushes().items()
                        if 'model=resnet50x4' in k and v > 0}
        print('  four replicas answered: %s' % sorted(answered.items()),
              flush=True)
    finally:
        server.close()


# ---------------------------------------------------------------------------

def main():
    t_start = time.time()
    dev = jax.devices()[0]
    device = {'platform': dev.platform, 'kind': dev.device_kind,
              'count': len(jax.devices())}
    print('platform=%(platform)s device_kind=%(kind)s count=%(count)d'
          % device, flush=True)
    if dev.platform != 'tpu':
        sys.exit('chip_smoke: needs a TPU, but JAX reports platform=%r '
                 '(device_kind=%r); there is no CPU fallback'
                 % (dev.platform, dev.device_kind))
    from mxnet_tpu import compile_cache, instrument, perfwatch
    if not any(dev.device_kind.startswith(k) for k in perfwatch.PEAKS):
        sys.exit('chip_smoke: device_kind %r is not in perfwatch.PEAKS'
                 % dev.device_kind)
    cache = compile_cache.ensure_persistent_cache(checkout_default=True)
    instrument.set_metrics(True)
    print('compile cache: %s' % cache, flush=True)

    with tempfile.TemporaryDirectory(prefix='chip_smoke_') as workdir:
        mod, prefix, losses = run_phase('train', phase_train, workdir)
        run_phase('serve', phase_serve, prefix)
        run_phase('kernels', phase_kernels)
        run_phase('barrier', phase_barrier)
        run_phase('trace', phase_trace, mod, workdir)
        del mod
        if device['count'] >= 4:
            run_phase('four chips', phase_four_chips, prefix, losses[0])
        else:
            print('[four chips] not run: %d device(s)' % device['count'],
                  flush=True)

    counters = instrument.metrics_snapshot()['counters']
    print('compile cache hits %d, misses %d; total %.1fs'
          % (counters.get('compile.cache_hits', 0),
             counters.get('compile.cache_misses', 0),
             time.time() - t_start), flush=True)
    print(json.dumps({'ok': True, 'device': device}), flush=True)


if __name__ == '__main__':
    main()
