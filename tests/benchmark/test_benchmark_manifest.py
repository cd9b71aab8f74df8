"""BENCHMARK.json against the contract, and every name in it against the
files the harness finds by that name."""
import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import manifest  # noqa: E402

SPEC = manifest.load_manifest()
CELLS = [w['name'] for w in SPEC['workloads']]
LAYER = [m['name'] for m in SPEC['per_layer']]
ALL_METRICS = SPEC['end_to_end'] + SPEC['per_layer']
WIDTH_WORDS = ('hidden', 'intermediate', 'latent', 'state', 'proj', 'head',
               'expansion', 'experts_per_tok', 'filter', 'width')


def test_manifest_has_exactly_the_contracts_keys():
    assert sorted(SPEC) == sorted(['command', 'paths', 'run_seconds',
                                   'configs', 'workloads', 'end_to_end',
                                   'per_layer'])
    assert len(json.dumps(SPEC)) < 64 * 1024
    assert isinstance(SPEC['run_seconds'], int)
    assert 1 <= SPEC['run_seconds'] <= 51
    assert 1 <= len(SPEC['paths']) <= 16
    assert len(SPEC['command']) <= 32
    for word in SPEC['command']:
        assert not word.startswith('/') and '..' not in word
        if '/' in word:
            assert any(word.startswith(p + '/') for p in SPEC['paths'])


@pytest.mark.parametrize('entry', ALL_METRICS + SPEC['workloads'] +
                         SPEC['configs'], ids=lambda e: e['name'])
def test_names_units_and_lines_obey_the_character_rules(entry):
    assert manifest.NAME_RE.match(entry['name'])
    for key in ('config', 'traffic', 'moves'):
        if key in entry:
            assert manifest.NAME_RE.match(entry[key])
    if 'unit' in entry:
        assert manifest.UNIT_RE.match(entry['unit'])
        assert entry['better'] in ('lower', 'higher')
        assert entry['source'] in manifest.SOURCES
    for key in ('why', 'layer', 'source'):
        if key in entry:
            assert 1 <= len(entry[key]) <= 200
            assert '\n' not in entry[key] and '\t' not in entry[key]
    for key in entry.get('reduced', []):
        assert manifest.NAME_RE.match(key)
        assert not any(w in key for w in WIDTH_WORDS) and \
            not key.endswith(('_dim', '_rank'))


def test_entries_have_just_the_contracts_keys():
    for e in SPEC['end_to_end']:
        assert set(e) - {'workloads'} == {'name', 'unit', 'better', 'bound',
                                          'source'}
        assert e['source'] in ('host_clock', 'device_trace')
        assert 0.01 <= e['bound'] <= 0.1
    for e in SPEC['per_layer']:
        assert set(e) - {'workloads'} == {'name', 'unit', 'better', 'source',
                                          'layer', 'moves'}
    for e in SPEC['workloads']:
        assert set(e) == {'name', 'config', 'traffic', 'chips', 'why'}
        assert e['chips'] in (1, 4)
    for e in SPEC['configs']:
        assert set(e) == {'name', 'source', 'file', 'reduced', 'why'}
    names = [e['name'] for e in ALL_METRICS]
    assert len(names) == len(set(names))
    assert len(CELLS) == len(set(CELLS))
    pairs = [(w['config'], w['traffic']) for w in SPEC['workloads']]
    assert len(pairs) == len(set(pairs))
    assert 'setup_s' in [e['name'] for e in SPEC['end_to_end']]
    four = sum(1 for w in SPEC['workloads'] if w['chips'] == 4)
    assert four <= max(1, len(CELLS) // 4)


def test_files_under_paths_are_named_from_name_characters():
    for path in SPEC['paths']:
        for folder, _, files in os.walk(os.path.join(ROOT, path)):
            if '__pycache__' in folder:
                continue
            for name in files:
                rel = os.path.relpath(os.path.join(folder, name), ROOT)
                assert re.match(r'^[A-Za-z0-9_.\-/]+$', rel), rel


@pytest.mark.parametrize('config', SPEC['configs'], ids=lambda c: c['name'])
def test_configuration_file_is_found_and_agrees(config):
    assert any(config['file'].startswith(p + '/') for p in SPEC['paths'])
    body = manifest.load_config(SPEC, config['name'])
    assert body['name'] == config['name']
    assert body['source'] == config['source']
    assert body['reduced'] == config['reduced']
    assert any(w['config'] == config['name'] for w in SPEC['workloads'])
    reference = os.path.join(ROOT, body['reference'])
    assert os.path.isfile(reference)


@pytest.mark.parametrize('cell', CELLS)
def test_cell_file_driver_and_metrics_are_found_by_name(cell):
    entry = manifest.cell_entry(SPEC, cell)
    body = manifest.load_cell(cell)
    assert (body['name'], body['config'], body['traffic']) == \
        (entry['name'], entry['config'], entry['traffic'])
    manifest.config_entry(SPEC, entry['config'])
    driver = manifest.load_module('drivers', body['driver'])
    assert callable(driver.run)
    end_to_end = [m['name'] for m in
                  manifest.metrics_of(SPEC, 'end_to_end', cell)]
    per_layer = manifest.metrics_of(SPEC, 'per_layer', cell)
    assert 'setup_s' in end_to_end and len(end_to_end) >= 2
    assert per_layer
    for metric in per_layer:
        # what a per-layer metric should move is reported wherever it is
        assert metric['moves'] in end_to_end, (metric['name'], cell)
        assert body['driver'] in \
            manifest.load_layer_metric(metric['name'])['drivers']


@pytest.mark.parametrize('name', LAYER)
def test_layer_metric_file_agrees_with_the_manifest(name):
    entry = [m for m in SPEC['per_layer'] if m['name'] == name][0]
    body = manifest.load_layer_metric(name)
    for key in ('name', 'unit', 'better', 'source', 'layer', 'moves'):
        assert body[key] == entry[key], key
    # which cells report it is the manifest's to say, so that a later PR
    # lists a new cell there and edits no file
    assert 'workloads' not in body
    for cell in entry.get('workloads', []):
        assert cell in CELLS
    read = body['read']
    if 'reader' in read:
        assert callable(manifest.load_module('readers', read['reader']).read)
    else:
        assert read['numerator'] and read['denominator']
