"""What every driver needs: the device check, the compile counter, the
memory reading, the traced slice and the arithmetic of the data-defined
per-layer metrics."""
import glob
import math
import os
import shutil
import time

from . import flops, manifest, peaks, trace_reduce

SLICE_SPAN = 'bench.slice'
TRACE_DIR = os.path.join(manifest.ROOT, '.benchmark_out', 'trace')


class BenchmarkError(Exception):
    """The run cannot give a number that means anything."""


def log(message):
    print('[bench] ' + message, flush=True)


def check_devices(chips, rehearsal):
    """The devices JAX reports, as the output's ``device`` object wants
    them.  No TPU of a known kind, or fewer chips than the cell asks
    for, ends the run: there is no fall-back to the CPU."""
    import jax
    devices = jax.devices()
    first = devices[0]
    info = {'platform': first.platform, 'kind': first.device_kind,
            'count': len(devices)}
    log('platform=%(platform)s device_kind=%(kind)s count=%(count)d' % info)
    if rehearsal:
        if first.platform == 'tpu':
            raise BenchmarkError('--rehearse-cpu is for a process without '
                                 'a TPU; this one has %r' % first.device_kind)
    else:
        if first.platform != 'tpu':
            raise BenchmarkError(
                'needs a TPU, but JAX reports platform=%r device_kind=%r; '
                'there is no CPU fall-back' % (first.platform,
                                               first.device_kind))
        peaks.peaks_for(first.device_kind)        # unknown kind: raises
    if len(devices) < chips:
        raise BenchmarkError('the cell needs %d chip(s), JAX reports %d'
                             % (chips, len(devices)))
    return info


def sizes(ctx):
    """The configuration as it is run: the file's sizes, or under the
    rehearsal switch its ``rehearsal`` sizes laid over them."""
    config = dict(ctx.config)
    if ctx.rehearsal:
        if 'rehearsal' not in config:
            raise BenchmarkError('configuration %r has no rehearsal sizes'
                                 % config['name'])
        config.update(config['rehearsal'])
    return config


def build_symbol(config):
    from mxnet_tpu import models
    builder = config['builder']
    return models.get_symbol(builder['network'], **builder['kwargs'])


def check_pinned(symbol, config, rehearsal):
    """Ends the run unless the symbol the program built is the model the
    configuration's file pins (``flops.pinned``).  Only a rehearsal, whose
    sizes are others, runs a configuration that pins nothing."""
    want = config.get('pinned')
    if want is None:
        if rehearsal:
            return
        raise BenchmarkError('configuration %r pins no model'
                             % config['name'])
    built = flops.pinned(symbol, config['image_shape'])
    for key, value in built.items():
        if value != want[key]:
            raise BenchmarkError(
                'configuration %r pins %s, and the program builds another '
                'model: %s' % (config['name'], key, _first_difference(
                    want[key], value)))


def _first_difference(want, built):
    if not isinstance(want, list):
        return 'pinned %r, built %r' % (want, built)
    for index, (a, b) in enumerate(zip(want, built)):
        if a != b:
            return 'entry %d pinned %r, built %r' % (index, a, b)
    return '%d entries pinned, %d built' % (len(want), len(built))


class CompileCounter(object):
    """Counts, through ``jax.monitoring``, every program JAX asks the
    compiler or its persistent cache for, so that a run can print how
    many were needed inside its measured window (none may be)."""

    REQUEST = '/jax/compilation_cache/compile_requests_use_cache'
    HIT = '/jax/compilation_cache/cache_hits'
    COMPILE = '/jax/core/compile/backend_compile_duration'

    def __init__(self):
        import jax.monitoring
        self.requests = self.hits = self.compiles = 0
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_event(self, event, **kwargs):
        if event == self.REQUEST:
            self.requests += 1
        elif event == self.HIT:
            self.hits += 1

    def _on_duration(self, event, duration, **kwargs):
        if event == self.COMPILE:
            self.compiles += 1

    def programs(self):
        """Programs built or fetched so far.  A program the persistent
        cache cannot hold makes no request, only a compilation, so the
        count is the larger of the two."""
        return max(self.requests, self.compiles)


def memory_peak_bytes(devices):
    """The peak on the fullest chip.  On the v5e the runtime books a
    compiled program's temporaries as *reserved*, apart from the buffers
    *in use* (after a ResNet-50 fit at batch 256: peak in use 0.97 GiB,
    peak reserved 7.69 GiB, against ``memory_analysis()`` temporaries of
    7.73 GiB; my chip run, PR 23), so the peak is the sum of the two
    high-water marks."""
    peak = 0
    for device in devices:
        stats = device.memory_stats() or {}
        peak = max(peak, int(stats.get('peak_bytes_in_use', 0)) +
                   int(stats.get('peak_bytes_reserved', 0)))
    return peak


class SliceTrace(object):
    """One profiled slice: ``start()`` and ``stop()`` bracket it, with a
    host span named ``bench.slice`` over exactly the slice, and
    ``reduced()`` gives ``trace_reduce.reduce_profile`` of it."""

    def __init__(self, cell_name, chips):
        self.dir = os.path.join(TRACE_DIR, cell_name)
        self.chips = chips
        self._span = None
        self.t0 = self.t1 = None

    def start(self):
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0      # host spans: ours and JAX's
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self._span = jax.profiler.TraceAnnotation(SLICE_SPAN)
        self._span.__enter__()
        self.t0 = time.perf_counter()

    def stop(self):
        import jax
        self.t1 = time.perf_counter()
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def reduced(self):
        paths = glob.glob(os.path.join(self.dir, '**', '*.xplane.pb'),
                          recursive=True)
        if not paths:
            raise BenchmarkError('the profiler wrote no .xplane.pb under %s'
                                 % self.dir)
        log('trace %s (%.1f MiB)' % (paths[0],
                                     os.path.getsize(paths[0]) / 2.0 ** 20))
        return trace_reduce.reduce_profile(
            trace_reduce.load(paths[0]), SLICE_SPAN, chips=self.chips)


def holds(entry):
    """Whether one number compared keeps its limit.  A driver gives each as
    ``{'value': v, <kind>: limit}``, the kind ``most`` (at most), ``least``
    (at least) or ``under`` (less than); ``correct`` is all of them, and
    the result line carries them under ``compared``."""
    value = entry['value']
    if not math.isfinite(value):
        return False
    if 'most' in entry:
        return value <= entry['most']
    if 'least' in entry:
        return value >= entry['least']
    return value < entry['under']


def span(name):
    import jax
    return jax.profiler.TraceAnnotation(name)


# -- per-layer metrics defined as data -------------------------------------

def _term(term, slice_):
    """One operand of a data-defined metric: ``histogram_sum:<name>``,
    ``histogram_count:<name>``, ``counter:<name>`` (each the difference
    of the two snapshots at the slice's ends), ``slice:<field>`` or a
    number.  None when the program never wrote the series."""
    if isinstance(term, (int, float)):
        return float(term)
    kind, _, name = term.partition(':')
    if kind == 'slice':
        return slice_.get(name)
    before, after = slice_['snap0'], slice_['snap1']
    if kind == 'counter':
        if name not in after.get('counters', {}):
            return None
        return float(after['counters'][name] -
                     before.get('counters', {}).get(name, 0))
    if kind in ('histogram_sum', 'histogram_count'):
        field = kind[len('histogram_'):]
        hist = after.get('histograms', {}).get(name)
        if hist is None:
            return None
        old = before.get('histograms', {}).get(name, {})
        return float(hist[field] - old.get(field, 0))
    raise manifest.ManifestError('unknown term %r in a layer metric' % term)


def evaluate(spec, slice_):
    """The value of one per-layer metric file over one slice, or None
    when there is nothing to read (the metric is then left out).

    ``spec['read']`` is either ``{"reader": <module under
    benchmark/readers>}`` or the arithmetic as data:
    ``scale * sum(numerator) / sum(denominator)``."""
    read = spec['read']
    if 'reader' in read:
        return manifest.load_module('readers', read['reader']).read(slice_)
    top = [_term(t, slice_) for t in read['numerator']]
    bottom = [_term(t, slice_) for t in read['denominator']]
    if any(v is None for v in top + bottom) or sum(bottom) <= 0:
        return None
    return float(read.get('scale', 1.0)) * sum(top) / sum(bottom)
