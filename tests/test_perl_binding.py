"""The Perl binding (perl-package/AI-MXNetTPU): a real XS module over
the C ABI — the role of the reference's perl-package (AI::MXNet, which
sat on the same c_api.cc surface).  Builds with the system perl's
ExtUtils and trains an MLP end-to-end from Perl."""
import os
import shutil
import subprocess

import pytest

from mxnet_tpu import sym

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(ROOT, 'perl-package', 'AI-MXNetTPU')
SO = os.path.join(ROOT, 'mxnet_tpu', 'libmxtpu_predict.so')

perl = shutil.which('perl')
pytestmark = pytest.mark.skipif(perl is None,
                                reason='no perl in this image')


def build():
    # always run make: its dependency tracking rebuilds a stale .so
    subprocess.check_call(['make', '-s', 'predict'],
                          cwd=os.path.join(ROOT, 'src'))
    if not os.path.exists(os.path.join(PKG, 'Makefile')):
        subprocess.check_call([perl, 'Makefile.PL'], cwd=PKG,
                              stdout=subprocess.DEVNULL)
    # make is incremental: XS/pm edits always rebuild
    subprocess.check_call(['make'], cwd=PKG,
                          stdout=subprocess.DEVNULL)


def test_perl_trains_mlp(tmp_path):
    build()
    d = sym.Variable('data')
    fc1 = sym.FullyConnected(d, num_hidden=16, name='fc1')
    a = sym.Activation(fc1, act_type='relu')
    fc2 = sym.FullyConnected(a, num_hidden=4, name='fc2')
    net = sym.SoftmaxOutput(fc2, name='softmax')
    json_path = str(tmp_path / 'mlp4.json')
    with open(json_path, 'w') as f:
        f.write(net.tojson())

    env = dict(os.environ)
    env['MXTPU_HOME'] = ROOT
    env['MXTPU_FORCE_CPU'] = '1'
    env.pop('PYTHONPATH', None)
    res = subprocess.run(
        [perl, os.path.join(PKG, 't', 'train_mlp.pl'), json_path],
        capture_output=True, text=True, timeout=600, env=env)
    assert res.returncode == 0, \
        'perl driver failed\nstdout:\n%s\nstderr:\n%s' % (res.stdout,
                                                          res.stderr)
    assert 'PERL BINDING: PASS' in res.stdout
