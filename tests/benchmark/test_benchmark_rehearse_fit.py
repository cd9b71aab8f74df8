"""The harness is driven by data: a throw-away configuration, cell and
per-layer metric, added as files and manifest entries in a temporary
copy, run without a change to any file that was there.  And the command
refuses a process with no TPU unless it is given the harness's own
rehearsal switch, whose result line holds no device metric."""
import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BIG_SEED = 2 ** 31 + 4242


def run_cell(root, cell, *extra, env=None):
    environ = dict(os.environ, JAX_PLATFORMS='cpu',
                   JAX_COMPILATION_CACHE_DIR=os.path.join(root, '.jax_cache'))
    environ.update(env or {})
    done = subprocess.run(
        [sys.executable, 'benchmark/run.py', '--workload', cell, '--seed',
         str(BIG_SEED), '--seconds', '1'] + list(extra),
        cwd=root, env=environ, capture_output=True, text=True, timeout=600)
    return done


def last_json(stdout):
    lines = [l for l in stdout.splitlines() if l.strip()]
    return json.loads(lines[-1])


def digests(root):
    out = {}
    for folder, _, files in os.walk(os.path.join(root, 'benchmark')):
        if '__pycache__' in folder:
            continue
        for name in files:
            path = os.path.join(folder, name)
            with open(path, 'rb') as f:
                out[os.path.relpath(path, root)] = \
                    hashlib.sha256(f.read()).hexdigest()
    return out


def make_copy(root):
    """The benchmark's files and the manifest in ``root``, with the
    program linked beside them."""
    shutil.copytree(os.path.join(ROOT, 'benchmark'),
                    os.path.join(root, 'benchmark'),
                    ignore=shutil.ignore_patterns('__pycache__'))
    shutil.copy(os.path.join(ROOT, 'BENCHMARK.json'), root)
    os.symlink(os.path.join(ROOT, 'mxnet_tpu'),
               os.path.join(root, 'mxnet_tpu'))
    return root


def add_entries(root, **groups):
    """Append entries to the copy's manifest; a new cell is also listed
    by the end-to-end metric its driver reports."""
    path = os.path.join(root, 'BENCHMARK.json')
    with open(path) as f:
        spec = json.load(f)
    for group, entries in groups.items():
        spec[group].extend(entries)
    for metric in spec['end_to_end']:
        if metric['name'] == 'fit_samples_per_s':
            metric['workloads'] += [w['name'] for w in
                                    groups.get('workloads', [])]
    with open(path, 'w') as f:
        json.dump(spec, f)


@pytest.fixture(scope='module')
def copy(tmp_path_factory):
    return make_copy(str(tmp_path_factory.mktemp('checkout')))


def test_added_files_and_entries_run_without_editing_a_file(copy):
    before = digests(copy)

    def write(rel, body):
        with open(os.path.join(copy, rel), 'w') as f:
            json.dump(body, f)

    write('benchmark/configs/throwaway.json', {
        'name': 'throwaway', 'source': 'https://example.org/throwaway',
        'builder': {'network': 'resnet', 'kwargs': {
            'num_layers': 8, 'num_classes': 10, 'image_shape': [3, 16, 16]}},
        'num_classes': 10, 'image_shape': [3, 16, 16],
        'compute_dtype': 'float32',
        'optimizer': {'name': 'sgd', 'learning_rate': 0.05, 'momentum': 0.9},
        'fit': {'kvstore': 'device', 'eval_metric': ['acc'],
                'speedometer_every': 0},
        'per_chip_batch': 4, 'reference': 'benchmark/reference.py',
        'reduced': [], 'rehearsal': {}})
    write('benchmark/workloads/throwaway_fit.json', {
        'name': 'throwaway_fit', 'driver': 'fit', 'config': 'throwaway',
        'traffic': 'tiny', 'mesh': None,
        'warmup_steps': 3, 'trace_steps': 5})
    write('benchmark/layer_metrics/throwaway_batches.json', {
        'name': 'throwaway_batches', 'unit': 'batches/step',
        'better': 'lower', 'source': 'program_counter', 'layer': 'fit loop',
        'moves': 'fit_samples_per_s', 'drivers': ['fit'],
        'read': {'numerator': ['counter:fit.batches'],
                 'denominator': ['slice:steps'], 'scale': 1.0}})
    add_entries(copy, configs=[{
        'name': 'throwaway', 'source': 'https://example.org/throwaway',
        'file': 'benchmark/configs/throwaway.json', 'reduced': [],
        'why': 'a test'}], workloads=[{
            'name': 'throwaway_fit', 'config': 'throwaway',
            'traffic': 'tiny', 'chips': 1, 'why': 'a test'}],
        per_layer=[{
            'name': 'throwaway_batches', 'unit': 'batches/step',
            'better': 'lower', 'source': 'program_counter',
            'layer': 'fit loop', 'moves': 'fit_samples_per_s',
            'workloads': ['throwaway_fit']}])

    done = run_cell(copy, 'throwaway_fit', '--trace', '1', '--rehearse-cpu')
    assert done.returncode == 0, done.stderr[-3000:]
    line = last_json(done.stdout)
    assert line['correct'] is True and line['rehearsal'] is True
    assert line['attempted'] == 5 and line['failed'] == 0
    # one batch counted a step, read as the difference of two snapshots
    assert line['metrics'] == {'throwaway_batches': {
        'value': 1.0, 'unit': 'batches/step'}}
    assert line['device'] == {'platform': 'cpu', 'kind': 'cpu',
                              'count': line['device']['count']}
    assert 'breakdown' not in line
    assert 'inside the window: 0' in done.stdout
    after = digests(copy)
    assert {k: after[k] for k in before} == before
    assert sorted(set(after) - set(before)) == [
        'benchmark/configs/throwaway.json',
        'benchmark/layer_metrics/throwaway_batches.json',
        'benchmark/workloads/throwaway_fit.json']


def test_no_tpu_and_no_switch_exits_nonzero_and_prints_no_result(copy):
    done = run_cell(copy, 'resnet50_fit_hostfeed', '--trace', '0')
    assert done.returncode != 0
    assert 'there is no CPU fall-back' in done.stderr
    for text in done.stdout.splitlines():
        assert not text.startswith('{')


def test_untraced_rehearsal_prints_no_metric(copy):
    done = run_cell(copy, 'resnet50_fit_hostfeed', '--trace', '0',
                    '--rehearse-cpu')
    assert done.returncode == 0, done.stderr[-3000:]
    line = last_json(done.stdout)
    assert line['metrics'] == {} and line['correct'] is True
    assert line['attempted'] >= 1 and 'memory_peak_bytes' not in \
        line['device']


def test_the_four_chip_cell_rehearses_on_four_virtual_devices(copy):
    done = run_cell(
        copy, 'resnet50_fit_dp4', '--trace', '0', '--rehearse-cpu',
        env={'XLA_FLAGS': '--xla_force_host_platform_device_count=4'})
    assert done.returncode == 0, done.stderr[-3000:]
    line = last_json(done.stdout)
    assert line['correct'] is True and line['device']['count'] == 4
    assert 'batch 32 x' in done.stdout       # 8 a chip on four chips
    # and with fewer chips than the cell asks for there is no result
    done = run_cell(copy, 'resnet50_fit_dp4', '--trace', '0',
                    '--rehearse-cpu',
                    env={'XLA_FLAGS':
                         '--xla_force_host_platform_device_count=2'})
    assert done.returncode != 0
    assert 'needs 4 chip(s), JAX reports 2' in done.stderr
