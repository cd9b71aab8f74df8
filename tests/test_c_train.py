"""The binding-bearing C ABI, proven from C: compile and run
``tests/c/train_lenet.c`` — a pure-C driver that trains LeNet end to end
through libmxtpu_predict.so (Executor bind/forward/backward, KVStore
push/pull with a C-side SGD updater invoked through the ctypes
trampoline, DataIter, RecordIO, NDArray save/load) with no Python in
the driver.  The reference proved the same surface through its language
bindings (R/Scala/Perl all sit on c_api.cc); here the C program IS the
binding."""
import os
import subprocess

import numpy as np
import pytest

from mxnet_tpu import models

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SO_DIR = os.path.join(ROOT, 'mxnet_tpu')
SO = os.path.join(SO_DIR, 'libmxtpu_predict.so')
DRIVER_SRC = os.path.join(ROOT, 'tests', 'c', 'train_lenet.c')


def build(tmp_path):
    # always run make: its dependency tracking rebuilds a stale .so
    subprocess.check_call(['make', '-s', 'predict'],
                          cwd=os.path.join(ROOT, 'src'))
    exe = str(tmp_path / 'train_lenet')
    subprocess.check_call(
        ['gcc', '-O1', '-Wall', '-Werror', DRIVER_SRC, '-o', exe,
         '-I', os.path.join(ROOT, 'include'),
         '-L', SO_DIR, '-lmxtpu_predict', '-lm',
         '-Wl,-rpath,' + SO_DIR])
    return exe


def test_c_abi_trains_lenet(tmp_path):
    exe = build(tmp_path)

    sym = models.get_symbol('lenet', num_classes=10)
    json_path = str(tmp_path / 'lenet.json')
    with open(json_path, 'w') as f:
        f.write(sym.tojson())

    rng = np.random.RandomState(0)
    data_csv = str(tmp_path / 'data.csv')
    label_csv = str(tmp_path / 'label.csv')
    np.savetxt(data_csv, rng.rand(64, 784).astype(np.float32) * 0.5,
               delimiter=',', fmt='%.4f')
    np.savetxt(label_csv, rng.randint(0, 10, 64), fmt='%d')

    env = dict(os.environ)
    env['MXTPU_HOME'] = ROOT
    env['MXTPU_FORCE_CPU'] = '1'
    # the embedded interpreter must see the repo, not a stale install
    env.pop('PYTHONPATH', None)
    res = subprocess.run(
        [exe, json_path, data_csv, label_csv, str(tmp_path)],
        capture_output=True, text=True, timeout=600, env=env)
    assert res.returncode == 0, \
        'driver failed\nstdout:\n%s\nstderr:\n%s' % (res.stdout,
                                                     res.stderr)
    assert 'C ABI end-to-end training: PASS' in res.stdout
    assert 'recordio: 3-record round-trip OK' in res.stdout
    assert 'dataiter: CSVIter 2 batches' in res.stdout
