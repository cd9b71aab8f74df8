"""Env-var config registry tests (reference docs/how_to/env_var.md,
dmlc::GetEnv call sites)."""
import os
import re
import subprocess
import sys

from mxnet_tpu import config


def test_defaults_and_parsing(monkeypatch):
    assert config.get('MXNET_ENGINE_TYPE') == 'ThreadedEnginePerDevice'
    monkeypatch.setenv('MXNET_CPU_WORKER_NTHREADS', '3')
    assert config.get('MXNET_CPU_WORKER_NTHREADS') == 3
    monkeypatch.setenv('MXNET_PROFILER_AUTOSTART', 'true')
    assert config.get('MXNET_PROFILER_AUTOSTART') is True
    monkeypatch.setenv('MXNET_PROFILER_AUTOSTART', '0')
    assert config.get('MXNET_PROFILER_AUTOSTART') is False


def test_catalog_lists_reference_knobs():
    knobs = config.list_knobs()
    for expected in ('MXNET_ENGINE_TYPE', 'MXNET_CPU_WORKER_NTHREADS',
                     'MXNET_GPU_MEM_POOL_RESERVE',
                     'MXNET_KVSTORE_BIGARRAY_BOUND',
                     'MXNET_CUDNN_AUTOTUNE_DEFAULT',
                     'MXNET_PROFILER_AUTOSTART'):
        assert expected in knobs
    text = config.describe()
    assert 'no-op on TPU' in text


def test_naive_engine_env(tmp_path):
    """MXNET_ENGINE_TYPE=NaiveEngine at import => jit disabled, native
    engine synchronous (env_var.md:8, engine.cc:13-39)."""
    script = (
        "import os\n"
        "os.environ['XLA_FLAGS'] = "
        "os.environ.get('XLA_FLAGS','')"
        " + ' --xla_force_host_platform_device_count=2'\n"
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "import mxnet_tpu as mx\n"
        "assert jax.config.jax_disable_jit\n"
        "from mxnet_tpu.engine import native_engine\n"
        "out = []\n"
        "eng = native_engine()\n"
        "v = eng.new_var()\n"
        "eng.push(lambda: out.append(1), mutable_vars=[v])\n"
        "assert out == [1]\n"
        "print('naive-ok')\n")
    env = dict(os.environ, MXNET_ENGINE_TYPE='NaiveEngine')
    env.pop('JAX_PLATFORMS', None)
    proc = subprocess.run([sys.executable, '-c', script],
                          capture_output=True, text=True, timeout=120,
                          env=env)
    assert 'naive-ok' in proc.stdout, proc.stderr[-1500:]


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _sources(*dirs, suffixes=('.py', '.md')):
    for d in dirs:
        for dirpath, _, files in os.walk(os.path.join(REPO, d)):
            for f in files:
                if f.endswith(suffixes):
                    path = os.path.join(dirpath, f)
                    with open(path, encoding='utf-8') as fh:
                        yield os.path.relpath(path, REPO), fh.read()


def test_env_vars_doc_is_the_registry():
    """docs/env_vars.md holds config.describe() verbatim: a knob that
    is added or removed shows in the document or fails here."""
    with open(os.path.join(REPO, 'docs', 'env_vars.md')) as f:
        assert config.describe() in f.read(), \
            'regenerate docs/env_vars.md from config.describe()'


def test_every_knob_is_read_and_the_removed_names_stay_gone():
    """Every registered MXTPU_* name is read by a config.get under
    mxnet_tpu/ or tools/ (a knob nothing reads is a registration to
    delete), and what was removed with the old benchmark stack is named
    nowhere.  The names are assembled here so that this file does not
    hold them either."""
    code = '\n'.join(text for _, text in
                     _sources('mxnet_tpu', 'tools', suffixes=('.py',)))
    unread = [n for n in config.list_knobs() if n.startswith('MXTPU_')
              and not re.search(r'''get\(\s*['"]%s['"]''' % n, code)]
    assert unread == []
    gone = ['MXTPU_FUSE_' + 'BN_CONV', 'MXTPU_CONV_' + 'LAYOUT',
            'bench' + '.py', 'check_' + 'perf', 'bench_' + 'report']
    pattern = re.compile('|'.join(
        r'(?<![A-Za-z0-9_])%s' % re.escape(g) for g in gone))
    named = sorted({(path, m.group(0)) for path, text in
                    _sources('mxnet_tpu', 'tools', 'tests', 'examples',
                             'docs')
                    for m in pattern.finditer(text)})
    assert named == []
