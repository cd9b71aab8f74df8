"""Finding the benchmark's data files by the names in BENCHMARK.json.

Everything that belongs to one configuration, one cell or one per-layer
metric is a file of its own; this module only maps names to paths and
checks that what a file says agrees with the manifest.  A later PR adds a
model, a cell or a metric by adding files and manifest entries: nothing
here lists them.
"""
import importlib
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

NAME_RE = re.compile(r'^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$')
UNIT_RE = re.compile(r'^[A-Za-z0-9_/%.\-]{1,16}$')
SOURCES = ('device_trace', 'program_span', 'program_counter', 'host_clock')


class ManifestError(Exception):
    pass


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_manifest(root=ROOT):
    return load_json(os.path.join(root, 'BENCHMARK.json'))


def _named(entries, name, what):
    for entry in entries:
        if entry['name'] == name:
            return entry
    raise ManifestError('%s %r is not in BENCHMARK.json (has: %s)'
                        % (what, name, sorted(e['name'] for e in entries)))


def cell_entry(manifest, name):
    return _named(manifest['workloads'], name, 'workload')


def config_entry(manifest, name):
    return _named(manifest['configs'], name, 'config')


def load_config(manifest, name, root=ROOT):
    return load_json(os.path.join(root, config_entry(manifest, name)['file']))


def load_cell(name, root=ROOT):
    """The cell's own file: its driver and its traffic parameters."""
    return load_json(os.path.join(root, 'benchmark', 'workloads',
                                  name + '.json'))


def load_layer_metric(name, root=ROOT):
    return load_json(os.path.join(root, 'benchmark', 'layer_metrics',
                                  name + '.json'))


def metrics_of(manifest, group, cell):
    """The manifest's metrics of ``group`` ('end_to_end' or 'per_layer')
    that ``cell`` reports: those with no ``workloads`` key, and those
    that list it."""
    return [m for m in manifest[group]
            if 'workloads' not in m or cell in m['workloads']]


def load_module(kind, name):
    """``benchmark/<kind>/<name>.py`` as a module (drivers, readers)."""
    if not NAME_RE.match(name):
        raise ManifestError('bad %s name %r' % (kind, name))
    return importlib.import_module('benchmark.%s.%s' % (kind, name))
