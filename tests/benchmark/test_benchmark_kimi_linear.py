"""The cell ``kimi_linear_fit_8k``'s own files: the manifest's new entries
against them, the configuration against the catalog's row and the counts
ISSUE 34 worked out, ``flops_kimi_linear``'s counts against hand arithmetic,
each new reader on a synthetic slice, the driver's own pieces, and a
rehearsal of the cell to its result line."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import flops_kimi_linear, harness, manifest  # noqa: E402

SPEC = manifest.load_manifest()
CELL = 'kimi_linear_fit_8k'
CONFIG = 'kimi_linear_48b_a3b'
METRICS = ['kimi_step_mfu_pct', 'kimi_step_device_ms', 'kimi_kda_share_pct',
           'kimi_kda_scan_roofline_pct', 'kimi_mla_roofline_pct',
           'kimi_moe_share_pct', 'kimi_experts_roofline_pct',
           'kimi_held_share_pct']
# the catalog's row for Kimi-Linear-48B-A3B-Instruct (the guide's
# architectures.jsonl), every key of its ``config``
PUBLISHED = {
    'first_k_dense_replace': 1, 'head_dim': 72, 'hidden_act': 'silu',
    'hidden_size': 2304, 'intermediate_size': 9216, 'kv_lora_rank': 512,
    'linear_attn_config': {
        'full_attn_layers': [4, 8, 12, 16, 20, 24, 27], 'head_dim': 128,
        'kda_layers': [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19,
                       21, 22, 23, 25, 26],
        'num_heads': 32, 'short_conv_kernel_size': 4},
    'mla_use_nope': True, 'model_max_length': 1048576,
    'model_type': 'kimi_linear', 'moe_intermediate_size': 1024,
    'moe_layer_freq': 1, 'moe_renormalize': True,
    'moe_router_activation_func': 'sigmoid', 'num_attention_heads': 32,
    'num_expert_group': 1, 'num_experts': 256, 'num_experts_per_token': 8,
    'num_hidden_layers': 27, 'num_key_value_heads': 32,
    'num_nextn_predict_layers': 0, 'num_shared_experts': 1,
    'q_lora_rank': None, 'qk_nope_head_dim': 128, 'qk_rope_head_dim': 64,
    'rms_norm_eps': 1e-05, 'rope_scaling': None, 'rope_theta': 10000,
    'routed_scaling_factor': 2.446, 'tie_word_embeddings': False,
    'topk_group': 1, 'use_grouped_topk': True, 'v_head_dim': 128,
    'vocab_size': 163840}
INPUTS = {'data': (2, 8192), 'softmax_label': (2, 8192)}


@pytest.fixture(scope='module')
def config():
    return manifest.load_config(SPEC, CONFIG)


@pytest.fixture(scope='module')
def symbol(config):
    return harness.build_symbol(config)


def test_the_cell_and_its_metrics_are_in_the_manifest():
    entry = manifest.cell_entry(SPEC, CELL)
    assert (entry['config'], entry['traffic'], entry['chips']) == \
        (CONFIG, 'packed8k_hostfeed', 1)
    assert SPEC['workloads'][-1] == entry       # new entries go last
    assert SPEC['configs'][-1]['name'] == CONFIG
    body = manifest.load_cell(CELL)
    assert body['driver'] == 'fit_kimi_linear' and body['ring'] == 8
    assert (body['warmup_steps'], body['trace_steps']) == (4, 20)
    assert body['zipf_exponent'] == 1.0 and body['mesh'] is None
    assert body['balance_passes'] >= len(body['balance_step']) == 3
    assert body['held_share_band'] == [0.92, 1.08]
    assert body['balance_why'] and body['held_share_band_why']
    reported = [m['name'] for m in manifest.metrics_of(SPEC, 'per_layer',
                                                       CELL)]
    assert reported == METRICS
    assert [m['name'] for m in SPEC['per_layer'][-len(METRICS):]] == METRICS
    assert [m['name'] for m in manifest.metrics_of(SPEC, 'end_to_end',
                                                   CELL)] == \
        ['fit_samples_per_s', 'setup_s']
    # the one entry that existed and changed: the cell's name, appended
    rate = [m for m in SPEC['end_to_end']
            if m['name'] == 'fit_samples_per_s'][0]
    assert rate['workloads'][-2:] == ['lfm2_moe_fit_8k', CELL]
    # what the cut distorts, in the cell's own words
    assert '32' in entry['why'] and '1/16' in entry['why']


@pytest.mark.parametrize('name', METRICS)
def test_layer_metric_reads_nothing_from_a_slice_without_its_source(name):
    body = manifest.load_layer_metric(name)
    assert body['drivers'] == ['fit_kimi_linear']
    assert body['moves'] == 'fit_samples_per_s'
    entry = [m for m in SPEC['per_layer'] if m['name'] == name][0]
    assert entry['workloads'] == [CELL]
    # a program without the scope, counter or text: nothing, and no raise
    assert harness.evaluate(body, {
        'trace': None, 'steps': 20.0, 'chips': 1.0, 'snap0': {}, 'snap1': {},
        'device_kind': 'TPU v5 lite'}) is None


def test_configuration_keeps_every_published_number_but_the_reduced(config):
    assert config['reduced'] == ['num_layers', 'experts_held', 'vocab_size']
    for key, value in PUBLISHED.items():
        if key not in config['reduced']:
            assert config[key] == value, key
    assert config['published'] == dict(
        config['published'], num_hidden_layers=27, num_experts=256,
        vocab_size=163840)
    assert '32 chips share each layer' in config['deployment']
    assert config['source'] == ('https://huggingface.co/moonshotai/'
                                'Kimi-Linear-48B-A3B-Instruct/blob/main/'
                                'config.json')
    kwargs = config['builder']['kwargs']
    # what is run: every width as published, the cut as the file states it
    for key in ('hidden_size', 'intermediate_size', 'moe_intermediate_size',
                'num_experts', 'num_experts_per_token', 'num_shared_experts',
                'num_attention_heads', 'kv_lora_rank', 'q_lora_rank',
                'qk_nope_head_dim', 'qk_rope_head_dim', 'v_head_dim',
                'rms_norm_eps', 'routed_scaling_factor',
                'first_k_dense_replace', 'linear_attn_config',
                'mla_use_nope', 'moe_renormalize', 'num_expert_group',
                'topk_group', 'moe_router_activation_func',
                'tie_word_embeddings'):
        assert kwargs[key] == PUBLISHED[key], key
    assert kwargs['num_hidden_layers'] == config['num_layers'] == 5
    assert config['layers_run'] == ['kda', 'kda', 'kda', 'mla', 'kda']
    linear = PUBLISHED['linear_attn_config']
    assert config['layers_run'] == [
        'kda' if i in linear['kda_layers'] else 'mla' for i in range(1, 6)]
    assert kwargs['experts_held'] == config['experts_held'] == [0, 8]
    assert kwargs['vocab_size'] == config['vocab_size'] == 163840 // 8
    assert kwargs['kda_gate_rank'] == linear['head_dim']
    assert (config['seq_len'], config['per_chip_batch'],
            config['compute_dtype']) == (8192, 2, 'bfloat16')
    assert config['optimizer'] == {
        'name': 'adam', 'learning_rate': 5e-6, 'beta1': 0.9, 'beta2': 0.95,
        'epsilon': 1e-8, 'wd': 0.1}
    assert len(config['assumed']) >= 6
    for key in ('pinned', 'memory', 'rehearsal', 'sample', 'described_as'):
        assert config[key], key


def test_the_built_model_is_the_one_the_file_pins(config, symbol):
    assert flops_kimi_linear.pinned(symbol, INPUTS) == {
        k: config['pinned'][k] for k in ('forward_macs_per_token',
                                         'parameters', 'weights')}


def millions(value):
    return round(value / 1e6, 1)


def test_the_pins_are_the_published_counts_issue_34_worked_out(config):
    """From the config's widths, by hand: a Kimi Delta Attention layer
    outside its feed-forward, a latent attention layer, the shared expert,
    the router, one routed expert, the dense MLP and the two tables."""
    hidden, channels = 2304, 32 * 128
    kda = (3 * hidden * channels + 3 * channels * 4 +
           2 * (hidden * 128 + 128 * channels) + 32 + channels +
           hidden * 32 + 128 + channels * hidden)
    mla = (hidden * 32 * 192 + hidden * 576 + 512 + 512 * 32 * 256 +
           channels * hidden)
    expert = 3 * hidden * 1024
    dense = 3 * hidden * 9216
    norms = 2 * hidden
    assert [millions(v) for v in (kda, mla, expert, dense)] == \
        [39.5, 29.1, 7.1, 63.7]
    outside = 3 * (kda + norms + expert + hidden * 256) + \
        (mla + norms + expert + hidden * 256)
    tables = 2 * 20480 * hidden
    total = (kda + norms + dense) + outside + 4 * 8 * expert + tables + hidden
    assert [millions(v) for v in (kda + norms + dense, outside,
                                  4 * 8 * expert, tables)] == \
        [103.2, 178.3, 226.5, 94.4]
    assert config['pinned']['parameters'] == total == 602433408
    assert len(config['pinned']['weights']) == 109
    shapes = dict((n, s) for n, s in config['pinned']['weights'])
    assert shapes['embed_weight'] == shapes['lm_head_weight'] == [20480, 2304]
    assert shapes['l1_experts_w1_weight'] == [8, 2304, 1024]
    assert shapes['l3_kv_b_weight'] == [8192, 512]
    assert shapes['l0_kda_A_log'] == [32]


def test_flops_kimi_linear_counts_what_hand_arithmetic_gives(symbol):
    dense, per_assignment, rows = \
        flops_kimi_linear.forward_macs_per_token(symbol, INPUTS)
    by = dict((name, macs) for name, _, macs in rows)
    hidden, channels = 2304, 4096
    assert by['l3_att'] == 32 * 4096 * (192 + 128)      # half the square
    assert by['l0_kda/conv'] == 3 * channels * 4
    # per head: two triangles of pairs and the solve over half a chunk of
    # 64, the outputs inside the chunk, three products with the state
    assert by['l0_kda/scan'] == 32 * (32 * (3 * 128 + 2 * 128) +
                                      3 * 128 * 128) == 2228224
    assert flops_kimi_linear.kda_scan_macs(32, 128, 128, 64) == 2228224
    assert by['l1_moe/router'] == 256 * hidden
    assert per_assignment == 3 * hidden * 1024
    assert by['l1_moe/experts'] == per_assignment * 8 * 8 // 256
    assert by['l1_shared_w1'] == 1024 * hidden
    assert by['lm_head'] == 20480 * hidden
    layer0 = sum(m for n, m in by.items() if n.startswith('l0_'))
    assert millions(layer0) == 105.4
    assert millions(sum(by.values())) == 386.6
    # a step of 16384 tokens at uniform routing: 3.75e13 FLOPs, 190 ms at
    # the chip's peak
    step = flops_kimi_linear.train_step_flops(dense, per_assignment, 16384,
                                              16384 * 8 * 8 / 256.0)
    assert round(step / 1e13, 2) == 3.75
    shapes = flops_kimi_linear.kernel_shapes(symbol, INPUTS)
    assert shapes['attention'] == [(32, 32, 8192, 192, 128)]
    assert shapes['kda'] == [(32, 8192, 128, 128, 64)] * 4
    assert (shapes['experts_held_total'], shapes['expert_width_in'],
            shapes['expert_width']) == (32, 2304, 1024)
    # one layer's rule, a step: bytes bind (5.7 ns a token and head against
    # 2.1 ns of products at the peak)
    flops = flops_kimi_linear.kda_scan_flops(2, 32, 8192, 128, 128, 64)
    moved = flops_kimi_linear.kda_scan_bytes(2, 32, 8192, 128, 128)
    assert flops == 6 * 16384 * 2228224
    assert moved == 3 * 16384 * 32 * (4 * 128 * 2 + 4 * 128 + 4)
    assert moved / 819e9 > flops / 197e12
    assert flops_kimi_linear.attention_flops(2, 32, 8192, 192, 128) == \
        6 * 2 * 32 * (8192 * 8192 // 2) * 320


def synthetic_slice():
    """A slice as the driver makes it, by hand: 20 steps, 0.5 s of device
    time a step, Kimi Delta Attention 150 ms of it (100 under ``scan``),
    latent attention 25 ms, the experts 30 ms (12 in the products)."""
    steps = 20.0
    shapes = {'attention': [(32, 32, 8192, 192, 128)],
              'kda': [(32, 8192, 128, 128, 64)] * 4,
              'experts_held_total': 32, 'expert_width_in': 2304,
              'expert_width': 1024}
    return {
        'steps': steps, 'chips': 1.0, 'device_kind': 'TPU v5 lite',
        'window_s': 10.2,
        'trace': {'busy_s': 0.5 * steps, 'window_s': 10.2, 'chips': 1},
        'scopes': {
            'busy_s': 0.5 * steps,
            'by_operator': {'KimiDeltaAttention': 0.150 * steps,
                            'FlashAttention': 0.025 * steps,
                            'SparseExperts': 0.030 * steps},
            'by_inner': {'KimiDeltaAttention/scan': 0.100 * steps,
                         'SparseExperts/experts': 0.012 * steps},
            'by_part': {}, 'recomputed_by_inner': {}},
        'step_flops': 3.8e13,
        'lm': dict(shapes, sequences=2, assignments_held_per_step=4096.0),
        'snap0': {'counters': {'moe.assignments': 1000,
                               'moe.assignments_held': 100}},
        'snap1': {'counters': {'moe.assignments': 1000 + 20 * 4 * 131072,
                               'moe.assignments_held': 100 + 20 * 4 * 4096}}}


def test_each_reader_reads_the_synthetic_slice_as_hand_arithmetic_does():
    slice_ = synthetic_slice()
    read = {name: harness.evaluate(manifest.load_layer_metric(name), slice_)
            for name in METRICS}
    assert read['kimi_step_device_ms'] == pytest.approx(500.0)
    assert read['kimi_step_mfu_pct'] == pytest.approx(
        100 * 3.8e13 / (0.5 * 197e12))
    assert read['kimi_kda_share_pct'] == pytest.approx(30.0)
    assert read['kimi_moe_share_pct'] == pytest.approx(6.0)
    assert read['kimi_held_share_pct'] == pytest.approx(3.125)
    # four layers' rule: bytes over the bandwidth, over 100 ms
    moved = 4 * flops_kimi_linear.kda_scan_bytes(2, 32, 8192, 128, 128)
    assert read['kimi_kda_scan_roofline_pct'] == pytest.approx(
        100 * moved / 819e9 / 0.100)
    assert read['kimi_mla_roofline_pct'] == pytest.approx(
        100 * flops_kimi_linear.attention_flops(2, 32, 8192, 192, 128) /
        197e12 / 0.025)
    from benchmark import flops_lm
    # 128 rows an expert: reading the 32 experts' matrices binds, not the
    # products (1.66 ms against 0.88 ms a step)
    assert flops_lm.experts_bytes(4096.0, 32, 2304, 1024) / 819e9 > \
        flops_lm.experts_flops(4096.0, 2304, 1024) / 197e12
    assert read['kimi_experts_roofline_pct'] == pytest.approx(
        100 * flops_lm.experts_bytes(4096.0, 32, 2304, 1024) / 819e9 / 0.012)
    for name, value in read.items():
        assert value > 0, name
        if name.endswith('_pct'):
            assert value < 100, name


def test_a_loops_own_event_goes_and_a_nested_scope_gets_its_own_entry():
    """``refine_scopes`` on a hand-made trace: a ``while`` whose event spans
    its body's three events under ``KimiDeltaAttention/.../scan``, one of
    them under the ``gates`` scope the operator opens inside the loop."""
    from benchmark import trace_reduce, trace_scopes
    from benchmark.drivers import fit_kimi_linear as driver

    class Event(object):
        def __init__(self, name, start, duration):
            self.name, self.start_ns, self.duration_ns = name, start, duration

    class Line(object):
        def __init__(self, name, events):
            self.name, self.events = name, events

    class Plane(object):
        def __init__(self, name, lines):
            self.name, self.lines = name, lines

    class Profile(object):
        def __init__(self, planes):
            self.planes = planes

    scope = 'jit(step)/forward_backward/KimiDeltaAttention/l0_kda/scan/'

    def named(what):
        return 'metadata={op_name="' + scope + what + '"}'
    text = '\n'.join([
        'HloModule step', '', 'ENTRY %main (p: f32[8]) -> f32[8] {',
        '  %p = f32[8]{0} parameter(0)',
        '  %while.1 = f32[8]{0} while(%p), condition=%c, body=%b, ' +
        named('while'),
        '  %fusion.1 = f32[8]{0} fusion(%p), kind=kLoop, calls=%f1, ' +
        named('while/body/closed_call/gates/mul'),
        '  %fusion.2 = f32[8]{0} fusion(%fusion.1), kind=kLoop, calls=%f2, ' +
        named('while/body/closed_call/while/body/dot_general'),
        '  %fusion.3 = f32[8]{0} fusion(%fusion.2), kind=kLoop, calls=%f3, ' +
        named('while/body/transpose(jvp(out_gate))/mul'),
        '  ROOT %copy.1 = f32[8]{0} copy(%while.1)', '}'])
    ops = Line(trace_reduce.OPS_LINE, [
        Event('%while.1 = f32[8]{0} while(%p), condition=%c, body=%b',
              1000, 7000),
        Event('%fusion.1 = f32[8]{0} fusion(%p), kind=kLoop', 1500, 1000),
        Event('%fusion.2 = f32[8]{0} fusion(%fusion.1), kind=kLoop', 3000,
              2000),
        Event('%fusion.3 = f32[8]{0} fusion(%fusion.2), kind=kLoop', 5500,
              500)])
    host = Plane(trace_reduce.HOST_PLANE, [Line('thread', [
        Event(harness.SLICE_SPAN, 0, 10000)])])
    profile = Profile([Plane(trace_reduce.DEVICE_PLANE + '0', [ops]), host])
    pairs = [('KimiDeltaAttention', 'l0_kda')]
    scopes = trace_scopes.reduce_scopes(profile, text, pairs,
                                        harness.SLICE_SPAN, chips=1)
    assert scopes['by_inner'] == {
        'KimiDeltaAttention/scan': pytest.approx(10.5e-6)}
    taken = driver.refine_scopes(scopes, profile, text, pairs)
    assert taken == pytest.approx(7e-6)
    assert scopes['by_inner'] == {
        'KimiDeltaAttention/scan': pytest.approx(2e-6),
        'KimiDeltaAttention/gates': pytest.approx(1e-6),
        'KimiDeltaAttention/out_gate': pytest.approx(0.5e-6)}
    assert scopes['by_operator']['KimiDeltaAttention'] == \
        pytest.approx(3.5e-6)
    assert scopes['busy_s'] == pytest.approx(3.5e-6)
    assert scopes['joined_s'] == scopes['scoped_s'] == pytest.approx(3.5e-6)


def test_the_drivers_weights_follow_the_seed_and_the_published_draws(config):
    from benchmark.drivers import fit_kimi_linear as driver
    small = harness.build_symbol(config['rehearsal'])
    shapes = {'data': (2, 64), 'softmax_label': (2, 64)}
    args, aux = driver.make_weights(small, shapes, 2 ** 31 + 5)
    again, _ = driver.make_weights(small, shapes, 2 ** 31 + 5)
    other, _ = driver.make_weights(small, shapes, 2 ** 31 + 6)
    for name, value in args.items():
        value = np.asarray(value)
        assert (value == np.asarray(again[name])).all(), name
        if name.endswith('_gamma'):
            assert (value == 1).all()
        elif name.endswith('_A_log'):
            assert (0 <= value).all() and (value <= np.log(16)).all()
        elif name.endswith('_dt_bias'):
            step = np.log1p(np.exp(value))          # softplus
            assert (0.0009 < step).all() and (step < 0.11).all()
        else:
            assert name.endswith('_weight'), name
            assert not (value == np.asarray(other[name])).all()
            assert abs(value.std() * np.sqrt(value.shape[1]) - 1) < 0.2
    assert all(not np.asarray(v).any() for v in aux.values())
    assert set(driver.reference_config(config)) >= {
        'layer_types', 'kda_num_heads', 'experts_held', 'kv_lora_rank'}
    assert driver.reference_config(config)['layer_types'] == \
        config['layers_run']


def test_limits_hold_a_reading_at_the_limit_and_refuse_one_past_it():
    from benchmark.drivers import fit_kimi_linear as driver
    at = {name: limit for name, (limit, _) in driver.LIMITS.items()}
    assert driver.broken(at) == []
    for name, (limit, kind) in driver.LIMITS.items():
        past = limit * (1.01 if kind == 'most' else 0.99)
        assert driver.broken(dict(at, **{name: past})) == [name]
        assert driver.broken(dict(at, **{name: float('nan')})) == [name]
    assert driver.LIMITS['gradient_error_worst'][0] < 1.0
    assert driver.LIMITS['update_error_worst'][0] < 1.0


def test_the_cell_runs_to_its_result_line_at_its_rehearsal_sizes(tmp_path):
    from benchmark.drivers import fit_kimi_linear as driver
    environ = dict(os.environ, JAX_PLATFORMS='cpu',
                   JAX_COMPILATION_CACHE_DIR=str(tmp_path / 'cache'))
    done = subprocess.run(
        [sys.executable, 'benchmark/run.py', '--workload', CELL,
         '--seed', str(2 ** 31 + 4242), '--seconds', '1', '--trace', '1',
         '--rehearse-cpu'],
        cwd=ROOT, env=environ, capture_output=True, text=True, timeout=1200)
    assert done.returncode == 0, done.stdout[-3000:] + done.stderr[-3000:]
    out = [l for l in done.stdout.splitlines() if l.strip()]
    line = json.loads(out[-1])
    assert '0 tokens dropped' in done.stdout
    assert 'inside the window: 0' in done.stdout
    assert ('the selection bias bit for bit what set-up made in 4 of 4 '
            'layers, 109 of 109 trained arrays moved\n') in done.stdout
    assert done.stdout.count('the bias balanced: held experts') == 4
    assert 'Kimi Delta Attention in all:' in done.stdout
    compared = line['compared']
    assert list(line)[-1] == 'compared'
    last = done.stderr.strip().splitlines()[-len(compared):]
    assert [l.split()[1].rstrip(':') for l in last] == list(compared)
    assert set(driver.LIMITS) < set(compared)
    for key in ('tokens_dropped', 'bias_moved', 'arrays_unmoved'):
        assert compared[key] == {'value': 0.0, 'most': 0.0}
    assert compared['loss_last_over_first']['value'] < 1.0
    assert compared['update_error_worst']['value'] < 0.01
    assert line['rehearsal'] is True and line['failed'] == 0
    assert line['attempted'] == 20
    # a CPU run gives no device number: only what the program counted
    assert list(line['metrics']) == ['kimi_held_share_pct']
    held = line['metrics']['kimi_held_share_pct']
    assert held['unit'] == '%' and 23.0 <= held['value'] <= 27.0


def test_without_a_chip_and_without_the_switch_the_cell_refuses():
    done = subprocess.run(
        [sys.executable, 'benchmark/run.py', '--workload', CELL, '--seed',
         '7', '--seconds', '1'], cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS='cpu'), capture_output=True,
        text=True, timeout=300)
    assert done.returncode != 0
    assert 'no CPU fall-back' in done.stderr + done.stdout
