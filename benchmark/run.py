#!/usr/bin/env python3
"""One cell of the benchmark, once, in this process.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its metrics are found by name: the cell
in ``BENCHMARK.json`` and ``benchmark/workloads/<cell>.json`` (which names
its driver, a module under ``benchmark/drivers``), the configuration's
file through the manifest, each per-layer metric in
``benchmark/layer_metrics/<metric>.json``.  The last line of standard
output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device``, traced ``breakdown``, and last ``compared``: every
number that decided ``correct`` beside its limit, which are also the last
lines of standard error.

``--trace 0`` leaves every observability plane of the program off, as a
user runs it, and reports the cell's end-to-end metrics.  ``--trace 1``
turns on the program's metrics registry and performance plane, profiles
a short steady slice and reports the cell's per-layer metrics.

There is no fall-back to the CPU: without a TPU of a known kind and the
chips the cell asks for the run exits non-zero and prints no result.
``--rehearse-cpu`` is the harness's own switch for its tests: the
configuration's ``rehearsal`` sizes on whatever JAX finds, every step of
the run, and a result line that holds no metric but what the program
counted.
"""
import time
T_START = time.perf_counter()

import argparse     # noqa: E402
import json         # noqa: E402
import os           # noqa: E402
import sys          # noqa: E402
import types        # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def parse(argv):
    parser = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    parser.add_argument('--workload', required=True)
    parser.add_argument('--seed', type=int, required=True)
    parser.add_argument('--seconds', type=float, required=True)
    parser.add_argument('--trace', type=int, choices=(0, 1), default=0)
    parser.add_argument('--rehearse-cpu', action='store_true')
    return parser.parse_args(argv)


def main(argv=None):
    args = parse(argv)
    if args.trace:
        # before the program is imported: it reads these once
        os.environ['MXTPU_METRICS'] = '1'
        os.environ['MXTPU_PERFWATCH'] = '1'
    from benchmark import harness, manifest
    from benchmark.harness import log

    spec = manifest.load_manifest()
    entry = manifest.cell_entry(spec, args.workload)
    ctx = types.SimpleNamespace()       # what a driver is given
    ctx.cell_name = entry['name']
    ctx.cell = manifest.load_cell(entry['name'])
    ctx.config = manifest.load_config(spec, entry['config'])
    ctx.chips = int(entry['chips'])
    ctx.seed, ctx.seconds, ctx.trace = args.seed, args.seconds, args.trace
    ctx.rehearsal = args.rehearse_cpu
    if ctx.rehearsal:
        # the rehearsal's peak stands for no chip: the performance plane
        # refuses to guess one for an unknown device
        os.environ.setdefault('MXTPU_PEAK_FLOPS', '1e12')

    ctx.compiles = harness.CompileCounter()
    ctx.device = harness.check_devices(ctx.chips, ctx.rehearsal)
    from mxnet_tpu import compile_cache
    log('compile cache: %s' % compile_cache.ensure_persistent_cache(
        checkout_default=True))

    driver = manifest.load_module('drivers', ctx.cell['driver'])
    result = driver.run(ctx)

    measured = dict(result['end_to_end'])
    measured['setup_s'] = result['t0'] - T_START
    group = 'per_layer' if args.trace else 'end_to_end'
    metrics = {}
    for metric in manifest.metrics_of(spec, group, entry['name']):
        name = metric['name']
        if args.trace:
            value = harness.evaluate(manifest.load_layer_metric(name),
                                     result['slice'])
        else:
            value = measured.get(name)
        if value is not None:
            metrics[name] = {'value': value, 'unit': metric['unit']}
    device = dict(ctx.device)
    device['memory_peak_bytes'] = harness.memory_peak_bytes(
        result['devices'])
    line = {'correct': result['correct'], 'attempted': result['attempted'],
            'failed': result['failed'], 'metrics': metrics,
            'device': device}
    if args.trace and result['slice']['trace'] is None:
        if not ctx.rehearsal:
            raise harness.BenchmarkError(
                'the traced slice holds no operation on a TPU')
    elif args.trace:
        trace = result['slice']['trace']
        device['busy_s'] = trace['busy_s']
        device['window_s'] = trace['window_s']
        line['breakdown'] = {'device_ops': trace['device_ops'],
                             'idle_gaps': trace['idle_gaps']}
    log('programs compiled or fetched in all: %d (%d from the persistent '
        'cache); set-up %.1f s' % (ctx.compiles.programs(),
                                   ctx.compiles.hits, measured['setup_s']))
    if ctx.rehearsal:
        # a CPU run gives no time, rate or share under a metric's name:
        # only what the program counted stays
        counted = {m['name'] for m in spec[group]
                   if m['source'] == 'program_counter'}
        line['metrics'] = {k: v for k, v in metrics.items() if k in counted}
        line['device'] = dict(ctx.device)
        line.pop('breakdown', None)
        line['rehearsal'] = True
    # every number compared beside its limit: the line's last key and the
    # last lines of standard error
    line['compared'] = {name: {k: float(v) for k, v in entry.items()}
                        for name, entry in result['compared'].items()}
    for name, entry in line['compared'].items():
        kind = next(k for k in entry if k != 'value')
        print('compared %s: %r (%s %r)%s' % (
            name, entry['value'], kind, entry[kind],
            '' if harness.holds(entry) else '  NOT HELD'),
            file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)


if __name__ == '__main__':
    main()
