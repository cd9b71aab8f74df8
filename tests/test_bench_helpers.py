"""Bench harness helpers (bench.py): the mandatory-traffic byte model,
the persisted-state logic, and the synthetic RecordIO source — these
guard the quality of every measured number, so they get tests too."""
import importlib.util
import json
import os

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def bench(tmp_path, monkeypatch):
    spec = importlib.util.spec_from_file_location(
        'bench_under_test', os.path.join(ROOT, 'bench.py'))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    monkeypatch.setattr(mod, 'STATE_PATH',
                        str(tmp_path / 'bench_state.json'))
    return mod


def test_analytic_min_bytes_is_a_sane_floor(bench):
    b128 = bench.analytic_min_bytes(batch_size=128)
    b256 = bench.analytic_min_bytes(batch_size=256)
    # activations dominate and scale with batch; params do not
    assert 1.5 < b256 / b128 < 2.0
    # the bs128 floor must sit in the physically plausible band:
    # more than params alone (~0.4GB), less than the cost-analysis
    # figure that exceeded peak (~38GB/step at r03 throughput)
    assert 5e9 < b128 < 2e10
    # classic stem counts the 7x7 conv output too
    classic = bench.analytic_min_bytes(batch_size=128, stem='classic')
    assert classic > 0 and abs(classic - b128) / b128 < 0.25


def test_record_leg_keeps_latest_and_survives_reload(bench):
    bench.record_leg('resnet50_train', 2000.0, fuse_bn_conv=False)
    bench.record_leg('resnet50_train', 1500.0, fuse_bn_conv=False)
    # the newest measurement wins, even when lower: the state file is
    # what tools/check_perf.py gates, and a regression must show there
    assert bench.load_state()['resnet50_train']['value'] == 1500.0
    bench.record_leg('resnet50_train_fused', 2400.0, fuse_bn_conv=True)
    best = bench._best_train_entry(bench.load_state())
    assert best['value'] == 2400.0 and best['fuse_bn_conv'] is True
    device = {'platform': 'tpu', 'kind': 'TPU v5 lite', 'count': 1}
    out = bench._primary_json(best, device)
    assert out['value'] == 2400.0 and out['device'] == device
    assert 'from_cache' not in out
    # the state file is valid JSON on disk (atomic write path)
    with open(bench.STATE_PATH) as f:
        assert set(json.load(f)) == {'resnet50_train',
                                     'resnet50_train_fused'}


def test_no_chip_exits_nonzero_without_a_result():
    """bench.py holds no stored number to fall back on: pinned to the
    CPU it names the platform, exits non-zero and prints no result."""
    import subprocess
    import sys
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, 'bench.py')],
        env=dict(os.environ, JAX_PLATFORMS='cpu'),
        capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "platform='cpu'" in proc.stderr
    assert proc.stdout.strip() == ''
    assert 'from_cache' not in proc.stdout + proc.stderr


def test_record_leg_commits_atomically(bench, tmp_path):
    """record_leg persists through resilience.atomic_replace: the state
    file on disk is always complete JSON and survives a same-tick
    second write."""
    bench.record_leg('serve_qps_at_p99_slo', 100.0, p99_ms=5.0)
    bench.record_leg('serve_qps_at_p99_slo', 250.0, p99_ms=9.0)
    with open(bench.STATE_PATH) as f:
        state = json.load(f)
    assert state['serve_qps_at_p99_slo']['value'] == 250.0
    assert state['serve_qps_at_p99_slo']['p99_ms'] == 9.0
    # no orphaned tmp files left next to the committed state
    leftovers = [p for p in os.listdir(os.path.dirname(bench.STATE_PATH))
                 if '.tmp' in p]
    assert leftovers == []


def test_synth_recfile_round_trips(bench, tmp_path, monkeypatch):
    monkeypatch.setattr('tempfile.gettempdir', lambda: str(tmp_path))
    path = bench._synth_recfile(num_images=8, side=64)
    assert os.path.exists(path)
    from mxnet_tpu import recordio
    rec = recordio.MXRecordIO(path, 'r')
    n = 0
    while True:
        item = rec.read()
        if item is None:
            break
        header, img = recordio.unpack_img(item)
        assert img.shape == (64, 64, 3)
        assert int(header.id) == n
        n += 1
    rec.close()
    assert n == 8
    # caching: second call returns the same file without rewriting
    mtime = os.path.getmtime(path)
    assert bench._synth_recfile(num_images=8, side=64) == path
    assert os.path.getmtime(path) == mtime
