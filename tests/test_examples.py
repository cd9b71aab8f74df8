"""End-to-end example apps stay green (reference example/ dir breadth:
train_imagenet --benchmark, RecordIO real mode, SSD training)."""
import os
import subprocess
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PREAMBLE = """
import os
os.environ['JAX_PLATFORMS'] = 'cpu'   # also inherited by subprocesses
import jax
jax.config.update('jax_platforms', 'cpu')
import sys, runpy
sys.argv = {argv!r}
runpy.run_path({script!r}, run_name='__main__')
"""


def run_example(script, argv, timeout=240):
    code = PREAMBLE.format(argv=[os.path.basename(script)] + argv,
                           script=os.path.join(ROOT, script))
    proc = subprocess.run([sys.executable, '-c', code], cwd=ROOT,
                          capture_output=True, text=True, timeout=timeout)
    assert proc.returncode == 0, (proc.stdout[-1500:], proc.stderr[-1500:])
    return proc


def test_train_imagenet_benchmark_mode():
    proc = run_example('examples/train_imagenet.py',
                       ['--benchmark', '1', '--network', 'lenet',
                        '--batch-size', '8', '--image-shape', '3,28,28',
                        '--num-classes', '10', '--benchmark-batches', '10',
                        '--disp-batches', '4'])
    assert 'imgs/sec' in proc.stdout


def test_train_imagenet_recordio_mode(tmp_path):
    from mxnet_tpu import recordio
    rng = np.random.RandomState(0)
    frec = str(tmp_path / 'train.rec')
    w = recordio.MXRecordIO(frec, 'w')
    for i in range(32):
        img = (rng.rand(36, 36, 3) * 255).astype(np.uint8)
        w.write(recordio.pack_img(
            recordio.IRHeader(0, float(i % 4), i, 0), img))
    del w
    prefix = str(tmp_path / 'ckpt')
    run_example('examples/train_imagenet.py',
                ['--data-train', frec, '--network', 'lenet',
                 '--batch-size', '8', '--num-classes', '4',
                 '--image-shape', '3,32,32', '--num-epochs', '1',
                 '--num-examples', '32', '--model-prefix', prefix,
                 '--max-random-rotate-angle', '10', '--random-l', '15'])
    assert os.path.exists(prefix + '-0001.params')
    assert os.path.exists(prefix + '-symbol.json')


def test_train_ssd_synthetic():
    run_example('examples/train_ssd.py',
                ['--batch-size', '4', '--data-shape', '96',
                 '--num-classes', '4', '--max-objects', '3',
                 '--num-epochs', '1', '--num-batches', '3',
                 '--disp-batches', '2'])


def test_adversary_fgsm():
    """FGSM demo: exercises inputs_need_grad end-to-end; the attack must
    actually reduce accuracy."""
    proc = run_example('examples/adversary_fgsm.py',
                       ['--num-epochs', '10', '--batch-size', '64'])
    line = [l for l in proc.stdout.splitlines() if 'adversarial' in l][-1]
    clean = float(line.split('clean=')[1].split()[0])
    adv = float(line.split('adversarial=')[1].split()[0])
    assert clean > 0.9 and adv < clean - 0.3, line


def test_dcgan_runs():
    """DCGAN loop: Deconvolution training + discriminator input-grad
    chaining stay functional."""
    proc = run_example('examples/train_dcgan.py',
                       ['--iters', '12', '--batch-size', '8'])
    assert 'final real_acc=' in proc.stdout


def _final_value(proc, tag):
    line = [l for l in proc.stdout.splitlines() if tag in l][-1]
    return float(line.split('=')[-1].split()[0])


def test_matrix_factorization():
    proc = run_example('examples/matrix_factorization.py', [])
    assert _final_value(proc, 'final validation rmse') < 0.45


def test_multi_task():
    proc = run_example('examples/multi_task.py', ['--num-epochs', '4'])
    line = [l for l in proc.stdout.splitlines() if 'final' in l][-1]
    accs = [float(p.split('=')[1]) for p in line.split()[1:]]
    assert len(accs) == 2 and min(accs) > 0.9, line


def test_svm_mnist():
    for extra in ([], ['--l1']):
        proc = run_example('examples/svm_mnist.py',
                           ['--num-epochs', '4'] + extra)
        assert _final_value(proc, 'final validation accuracy') > 0.9


def test_bi_lstm_sort():
    proc = run_example('examples/bi_lstm_sort.py',
                       ['--num-epochs', '8', '--num-samples', '3000'],
                       timeout=420)
    assert _final_value(proc, 'sort accuracy') > 0.7


def test_cnn_text_classification():
    proc = run_example('examples/cnn_text_classification.py',
                       ['--num-epochs', '3', '--num-samples', '2000'])
    assert _final_value(proc, 'final validation accuracy') > 0.9


def test_nce_loss():
    proc = run_example('examples/nce_loss.py', ['--num-epochs', '5'])
    assert _final_value(proc, 'final nce accuracy') > 0.9


def test_autoencoder():
    proc = run_example('examples/autoencoder.py',
                       ['--pretrain-epochs', '2', '--finetune-epochs',
                        '4'])
    assert _final_value(proc, 'final reconstruction mse') < 0.05


def test_stochastic_depth():
    proc = run_example('examples/stochastic_depth.py',
                       ['--num-epochs', '8'], timeout=420)
    assert _final_value(proc, 'final validation accuracy') > 0.7


def test_memcost_mirror_tradeoff():
    proc = run_example('examples/memcost.py',
                       ['--batch-size', '4', '--image-size', '64',
                        '--policies', 'off,nothing'],
                       timeout=560)
    lines = [l.split() for l in proc.stdout.splitlines()
             if l.startswith(('off', 'dots', 'nothing'))]
    ratios = {l[0]: float(l[2].rstrip('x')) for l in lines}
    assert ratios['off'] == 1.0 and ratios['nothing'] > 1.2, ratios


def test_bayesian_sgld():
    proc = run_example('examples/bayesian_sgld.py',
                       ['--num-epochs', '40', '--burn-in-epochs', '15'])
    line = [l for l in proc.stdout.splitlines()
            if 'posterior w' in l][-1]
    w_mean = float(line.split('mean=')[1].split()[0])
    assert abs(w_mean - 2.0) < 0.3, line


def test_fcn_xs():
    proc = run_example('examples/fcn_xs.py',
                       ['--num-epochs', '4', '--num-samples', '256'])
    assert _final_value(proc, 'final pixel accuracy') > 0.8


def test_neural_style():
    proc = run_example('examples/neural_style.py', [])
    assert 'decreased=True' in proc.stdout


def test_module_usage_tour():
    proc = run_example('examples/module_usage.py', [])
    line = [l for l in proc.stdout.splitlines() if 'explicit-loop' in l][-1]
    vals = [float(p.split('=')[1]) for p in line.split() if '=' in p]
    assert min(vals) > 0.9, line


def test_speech_ctc():
    proc = run_example('examples/speech_ctc.py',
                       ['--num-epochs', '8', '--num-samples', '512'],
                       timeout=420)
    assert _final_value(proc, 'final token error rate') < 0.2


def test_profiler_demo(tmp_path):
    out = str(tmp_path / 'trace.json')
    proc = run_example('examples/profiler_demo.py', ['--output', out])
    assert 'complete events' in proc.stdout
    import json
    events = json.load(open(out))
    events = events['traceEvents'] if isinstance(events, dict) else events
    assert any(e.get('ph') == 'X' for e in events)


def test_numpy_ops_example():
    proc = run_example('examples/numpy_ops.py', ['--num-epochs', '3'])
    line = [l for l in proc.stdout.splitlines() if 'acc=' in l][-1]
    vals = [float(p.split('=')[1]) for p in line.split() if '=' in p]
    assert min(vals) > 0.9, line


def test_dec_clustering():
    proc = run_example('examples/dec_clustering.py', [], timeout=420)
    line = [l for l in proc.stdout.splitlines() if 'dec acc=' in l][-1]
    km = float(line.split('kmeans acc=')[1].split()[0])
    dec = float(line.split('dec acc=')[1].split()[0])
    assert dec > 0.85 and dec >= km - 0.02, line


def test_rnn_time_major():
    proc = run_example('examples/rnn_time_major.py', ['--iters', '4'])
    assert 'outputs match=True' in proc.stdout


def test_torch_module_demo():
    proc = run_example('examples/torch_module_demo.py',
                       ['--num-epochs', '3'])
    if 'demo skipped' in proc.stdout:
        return
    assert _final_value(proc, 'final accuracy') > 0.9


def test_rcnn_roi_classifier():
    proc = run_example('examples/rcnn_roi_classifier.py', [],
                       timeout=420)
    assert _final_value(proc, 'final roi accuracy') > 0.9


def test_kaggle_starter_pipeline(tmp_path):
    """kaggle_image_classification: pack -> train -> submission CSV,
    fully synthetic (the reference's kaggle-ndsb1 starter role)."""
    proc = run_example('examples/kaggle_image_classification.py',
                       ['--synthetic', '--classes', '3', '--epochs',
                        '4', '--batch-size', '8', '--shape', '32'],
                       timeout=420)
    assert 'wrote' in proc.stdout and 'submission' in proc.stdout


def test_dqn_cartpole_short():
    """dqn_cartpole: a few episodes end-to-end through the Module API
    (the reinforcement-learning example family role)."""
    code = PREAMBLE.format(
        argv=['dqn_cartpole.py', '--episodes', '2'],
        script=os.path.join(ROOT, 'examples', 'dqn_cartpole.py'))
    proc = subprocess.run([sys.executable, '-c', code], cwd=ROOT,
                          capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, proc.stderr[-1000:]


def test_pipeline_parallel_mlp_example():
    """pipeline_parallel_mlp: the group2ctx pipeline successor of the
    model-parallel-lstm example, on the virtual mesh."""
    code = PREAMBLE.format(
        argv=['pipeline_parallel_mlp.py', '--stages', '4',
              '--epochs', '6'],
        script=os.path.join(ROOT, 'examples',
                            'pipeline_parallel_mlp.py'))
    env = dict(os.environ)
    env['XLA_FLAGS'] = env.get('XLA_FLAGS', '') + \
        ' --xla_force_host_platform_device_count=8'
    proc = subprocess.run([sys.executable, '-c', code], cwd=ROOT,
                          capture_output=True, text=True, timeout=420,
                          env=env)
    assert proc.returncode == 0, proc.stderr[-1200:]
    assert 'final train accuracy' in proc.stdout
