"""The cell ``nemotron3_super_fit_8k``'s own files: the manifest's new entries
against them, the configuration against the catalog's row and the counts
ISSUE 36 worked out, ``flops_nemotron_h``'s counts against hand arithmetic,
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

from benchmark import flops_nemotron_h, harness, manifest  # noqa: E402

SPEC = manifest.load_manifest()
CELL = 'nemotron3_super_fit_8k'
CONFIG = 'nemotron3_super_120b_a12b'
METRICS = ['nemo_step_mfu_pct', 'nemo_step_device_ms',
           'nemo_mamba_share_pct', 'nemo_ssd_scan_roofline_pct',
           'nemo_moe_share_pct', 'nemo_experts_roofline_pct',
           'nemo_held_share_pct']
REDUCED = ['num_layers', 'experts_held', 'mixers_held', 'vocab_size',
           'num_nextn_predict_layers']
# the catalog's row for NVIDIA-Nemotron-3-Super-120B-A12B-BF16 (the guide's
# architectures.jsonl), every key of its ``config``
PUBLISHED = {
    'attention_bias': False, 'chunk_size': 128, 'conv_kernel': 4,
    'expand': 2, 'head_dim': 128, 'hidden_size': 4096,
    'hybrid_override_pattern': (
        'MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*'
        'EMEMEMEMEM*EMEMEMEM*EMEMEMEME'),
    'intermediate_size': 2688, 'layer_norm_epsilon': 1e-05,
    'mamba_head_dim': 64, 'mamba_hidden_act': 'silu', 'mamba_num_heads': 128,
    'mamba_proj_bias': False, 'max_position_embeddings': 262144,
    'mlp_bias': False, 'mlp_hidden_act': 'relu2', 'model_type': 'nemotron_h',
    'moe_intermediate_size': 2688, 'moe_latent_size': 1024,
    'moe_shared_expert_intermediate_size': 5376,
    'moe_shared_expert_overlap': False, 'mtp_hybrid_override_pattern': '*E',
    'n_group': 1, 'n_groups': 8, 'n_routed_experts': 512,
    'n_shared_experts': 1, 'norm_eps': 1e-05, 'norm_topk_prob': True,
    'num_attention_heads': 32, 'num_experts_per_tok': 22,
    'num_hidden_layers': 88, 'num_key_value_heads': 2,
    'num_logits_to_keep': 1, 'num_nextn_predict_layers': 1,
    'partial_rotary_factor': 1, 'rescale_prenorm_residual': True,
    'residual_in_fp32': False, 'rope_theta': 10000,
    'routed_scaling_factor': 5, 'sliding_window': None,
    'ssm_state_size': 128, 'tie_word_embeddings': False,
    'time_step_floor': 0.0001, 'time_step_max': 0.1, 'time_step_min': 0.001,
    'topk_group': 1, 'use_bias': False, 'use_conv_bias': True,
    'use_mamba_kernels': True, 'vocab_size': 131072}
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
    # new entries went after what was there; a later PR's go after these,
    # so nothing here says "last"
    cells = [w['name'] for w in SPEC['workloads']]
    configs = [c['name'] for c in SPEC['configs']]
    assert cells.index(CELL) == cells.index('kimi_linear_fit_8k') + 1
    assert configs.index(CONFIG) == configs.index('kimi_linear_48b_a3b') + 1
    mine = [c for c in SPEC['configs'] if c['name'] == CONFIG][0]
    assert mine['reduced'] == REDUCED
    assert len(SPEC['workloads']) >= 6
    assert [w['name'] for w in SPEC['workloads'] if w['chips'] == 4] == \
        ['resnet50_fit_dp4']
    body = manifest.load_cell(CELL)
    assert body['driver'] == 'fit_nemotron_h' and body['ring'] == 8
    assert (body['warmup_steps'], body['trace_steps']) == (4, 20)
    assert body['zipf_exponent'] == 1.0 and body['mesh'] is None
    assert body['balance_passes'] >= len(body['balance_step']) == 3
    assert body['held_share_band'] == [0.92, 1.08]
    assert body['balance_why'] and body['held_share_band_why']
    reported = [m['name'] for m in manifest.metrics_of(SPEC, 'per_layer',
                                                       CELL)]
    assert reported == METRICS
    layers = [m['name'] for m in SPEC['per_layer']]
    first = layers.index(METRICS[0])
    assert layers[first:first + len(METRICS)] == METRICS
    assert first == layers.index('kimi_held_share_pct') + 1
    assert [m['name'] for m in manifest.metrics_of(SPEC, 'end_to_end',
                                                   CELL)] == \
        ['fit_samples_per_s', 'setup_s']
    # the one entry that existed and changed: the cell's name, appended
    rate = [m for m in SPEC['end_to_end']
            if m['name'] == 'fit_samples_per_s'][0]
    at = rate['workloads'].index(CELL)
    assert rate['workloads'][at - 1] == 'kimi_linear_fit_8k'
    # what the cut distorts, in the cell's own words
    assert '1/64' in entry['why'] and '8x' in entry['why']
    for text in (entry['why'], mine['why']):
        assert len(text) <= 200 and '\n' not in text and '\t' not in text


@pytest.mark.parametrize('name', METRICS)
def test_layer_metric_reads_nothing_from_a_slice_without_its_source(name):
    body = manifest.load_layer_metric(name)
    assert body['drivers'] == ['fit_nemotron_h']
    assert body['moves'] == 'fit_samples_per_s'
    entry = [m for m in SPEC['per_layer'] if m['name'] == name][0]
    assert entry['workloads'] == [CELL]
    assert {k: body[k] for k in entry if k != 'workloads'} == \
        {k: entry[k] for k in entry if k != 'workloads'}
    # a program without the scope, counter or text (the parent commit's):
    # nothing, and no raise
    assert harness.evaluate(body, {
        'trace': None, 'steps': 20.0, 'chips': 1.0, 'snap0': {}, 'snap1': {},
        'device_kind': 'TPU v5 lite'}) is None
    if name == 'nemo_step_device_ms':
        return          # reads the trace alone, whatever the program is
    assert harness.evaluate(body, {
        'trace': {'busy_s': 1.0}, 'steps': 20.0, 'chips': 1.0,
        'snap0': {'counters': {}}, 'snap1': {'counters': {}},
        'device_kind': 'TPU v5 lite', 'lm': {'ssm': []},
        'scopes': {'busy_s': 1.0, 'by_operator': {}, 'by_inner': {},
                   'by_node': {}, 'by_part': {}}}) is None


def test_configuration_keeps_every_published_number_but_the_reduced(config):
    assert config['reduced'] == REDUCED
    for key, value in PUBLISHED.items():
        if key not in config['reduced']:
            assert config[key] == value, key
    assert config['published'] == dict(
        config['published'], num_hidden_layers=88, n_routed_experts=512,
        mamba_num_heads=128, num_attention_heads=32, num_key_value_heads=2,
        n_groups=8, vocab_size=131072, num_nextn_predict_layers=1)
    assert '64 chips share each layer' in config['deployment']
    assert config['source'] == (
        'https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-'
        'BF16/blob/main/config.json')
    kwargs = config['builder']['kwargs']
    # what is run: every width as published, the cut as the file states it
    for key in ('hidden_size', 'mamba_num_heads', 'mamba_head_dim',
                'ssm_state_size', 'n_groups', 'conv_kernel', 'chunk_size',
                'num_attention_heads', 'num_key_value_heads', 'head_dim',
                'n_routed_experts', 'num_experts_per_tok',
                'moe_intermediate_size', 'moe_latent_size',
                'moe_shared_expert_intermediate_size', 'n_shared_experts',
                'routed_scaling_factor', 'norm_topk_prob', 'mlp_hidden_act',
                'layer_norm_epsilon', 'use_conv_bias', 'n_group',
                'topk_group', 'tie_word_embeddings'):
        assert kwargs[key] == PUBLISHED[key], key
    assert kwargs['num_hidden_layers'] == config['num_layers'] == 11
    # one whole period of the published pattern, 5 : 5 : 1
    assert kwargs['hybrid_override_pattern'] == config['layers_run'] == \
        PUBLISHED['hybrid_override_pattern'][27:38] == 'MEMEMEMEM*E'
    assert kwargs['experts_held'] == config['experts_held'] == [0, 8]
    assert kwargs['mixers_held'] == config['mixers_held'] == [0, 8]
    assert kwargs['vocab_size'] == config['vocab_size'] == 131072 // 8
    assert kwargs['num_nextn_predict_layers'] == \
        config['num_nextn_predict_layers'] == 0
    assert (config['seq_len'], config['per_chip_batch'],
            config['compute_dtype']) == (8192, 2, 'bfloat16')
    assert config['optimizer'] == {
        'name': 'adam', 'learning_rate': 5e-6, 'beta1': 0.9, 'beta2': 0.95,
        'epsilon': 1e-8, 'wd': 0.1}
    assert len(config['assumed']) >= 9
    for key in ('pinned', 'memory', 'rehearsal', 'sample', 'described_as'):
        assert config[key], key
    memory = config['memory']
    assert memory['argument_bytes'] + memory['output_bytes'] + \
        memory['temp_bytes'] - memory['alias_bytes'] < 15e9


def test_the_built_model_is_the_one_the_file_pins(config, symbol):
    assert flops_nemotron_h.pinned(symbol, INPUTS) == {
        k: config['pinned'][k] for k in ('forward_macs_per_token',
                                         'parameters', 'weights')}


def millions(value):
    return round(value / 1e6, 2)


def test_the_pins_are_the_published_counts_issue_36_worked_out(config):
    """From the config's widths, by hand, at the share held: a Mamba-2
    block, an attention block, an expert block and the two tables."""
    hidden = 4096
    heads, size, groups, state = 128 // 8, 64, 8 // 8, 128
    d, mixed = heads * size, heads * size + 2 * groups * state
    mamba = hidden * (d + mixed + heads) + d * hidden + mixed * 4 + mixed + \
        3 * heads + d + hidden
    attention = hidden * (4 + 1 + 1) * 128 + 4 * 128 * hidden + hidden
    router, latent = 512 * hidden, 2 * hidden * 1024
    shared, expert = 2 * hidden * 5376, 2 * 1024 * 2688
    experts = router + latent + shared + 8 * expert + hidden
    assert [millions(v) for v in (mamba, attention, experts)] == \
        [13.71, 5.25, 98.57]
    assert [millions(v) for v in (router, latent, shared, 8 * expert)] == \
        [2.1, 8.39, 44.04, 44.04]
    tables = 2 * 16384 * hidden
    total = 5 * mamba + attention + 5 * experts + tables + hidden
    assert config['pinned']['parameters'] == total == 700862960
    assert millions(total) == 700.86
    shapes = dict((n, s) for n, s in config['pinned']['weights'])
    assert len(shapes) == 5 * 9 + 5 + 5 * 8 + 3
    assert shapes['embed_weight'] == shapes['lm_head_weight'] == [16384, 4096]
    assert shapes['l0_in_weight'] == [2320, 4096]
    assert shapes['l0_out_weight'] == [4096, 1024]
    assert shapes['l0_ssm_conv_weight'] == [1280, 4]
    assert shapes['l0_ssm_A_log'] == shapes['l0_ssm_D'] == [16]
    assert shapes['l1_experts_w1_weight'] == [8, 1024, 2688]
    assert shapes['l1_experts_w2_weight'] == [8, 2688, 1024]
    assert shapes['l1_router_weight'] == [512, 4096]
    assert shapes['l1_shared_w1_weight'] == [5376, 4096]
    assert shapes['l9_q_weight'] == [512, 4096]
    assert shapes['l9_k_weight'] == shapes['l9_v_weight'] == [128, 4096]


def test_flops_nemotron_h_counts_what_hand_arithmetic_gives(symbol):
    dense, per_assignment, rows = \
        flops_nemotron_h.forward_macs_per_token(symbol, INPUTS)
    by = dict((name, macs) for name, _, macs in rows)
    hidden = 4096
    assert by['l9_att'] == 4 * 4096 * (128 + 128)       # half the square
    assert by['l0_ssm/conv'] == 1280 * 4
    # a Mamba-2 head's work counted once, at 16 heads and one group: the
    # group's triangle of C . B over half a chunk of 128 on a state of 128;
    # per head the triangle applied to 64 values and two products of 64 x
    # 128 with the carried state
    assert by['l0_ssm/scan'] == 64 * 128 + 16 * (64 * 64 + 2 * 64 * 128) \
        == 335872
    assert flops_nemotron_h.ssd_scan_macs(16, 1, 64, 128, 128) == 335872
    # the whole model's 128 heads in 8 groups would be eight times that
    assert flops_nemotron_h.ssd_scan_macs(128, 8, 64, 128, 128) == 8 * 335872
    assert by['l1_moe/router'] == 512 * hidden
    assert per_assignment == 2 * 1024 * 2688
    assert by['l1_moe/experts'] == per_assignment * 22 * 8 // 512
    assert by['l1_down'] == by['l1_up'] == 1024 * hidden
    assert by['l1_shared_w1'] == by['l1_shared_w2'] == 5376 * hidden
    assert by['l0_in'] == 2320 * hidden and by['l0_out'] == 1024 * hidden
    assert by['lm_head'] == 16384 * hidden
    block = {kind: sum(m for n, m in by.items()
                       if n.startswith('l%d_' % index))
             for kind, index in (('M', 0), ('E', 1), ('*', 9))}
    assert [millions(block[k]) for k in 'ME*'] == [14.04, 56.42, 9.44]
    assert millions(sum(by.values())) == 428.83
    # a step of 16384 tokens at uniform routing: 4.22e13 FLOPs, 214 ms at
    # the chip's peak
    step = flops_nemotron_h.train_step_flops(dense, per_assignment, 16384,
                                             5 * 16384 * 22 * 8 / 512.0)
    assert round(step / 1e13, 2) == 4.22
    shapes = flops_nemotron_h.kernel_shapes(symbol, INPUTS)
    assert shapes['ssm'] == [(16, 1, 8192, 64, 128, 128)] * 5
    assert (shapes['experts_held_total'], shapes['expert_width_in'],
            shapes['expert_width'], shapes['expert_matrices']) == \
        (40, 1024, 2688, 2)
    assert shapes['latent_projections'] == [
        'l%d_%s' % (i, what) for i in (1, 3, 5, 7, 10)
        for what in ('down', 'up')]
    # one layer's recurrence, a step: bytes bind
    flops = flops_nemotron_h.ssd_scan_flops(2, 16, 1, 8192, 64, 128, 128)
    moved = flops_nemotron_h.ssd_scan_bytes(2, 16, 1, 8192, 64, 128)
    assert flops == 6 * 16384 * 335872
    assert moved == 3 * 16384 * ((2 * 1024 + 2 * 128) * 2 + 4 * 16)
    assert moved / 819e9 > flops / 197e12
    # the ungated products: two an assignment
    assert flops_nemotron_h.experts_flops(5632.0, 1024, 2688) == \
        6 * 5632 * 2 * 1024 * 2688
    assert flops_nemotron_h.experts_bytes(5632.0, 8, 1024, 2688) == \
        3 * 8 * 2 * 1024 * 2688 * 2 + 3 * 5632 * 2 * (1024 + 2688) * 2
    assert flops_nemotron_h.experts_flops(10.0, 8, 16, matrices=3) == \
        6 * 10 * 3 * 8 * 16


def synthetic_slice():
    """A slice as the driver makes it, by hand: 20 steps, 0.6 s of device
    time a step, Mamba-2 90 ms of it (40 under ``scan``), the experts 120 ms
    (6 in the products) and the latent's projections 3 ms each."""
    steps = 20.0
    shapes = {'ssm': [(16, 1, 8192, 64, 128, 128)] * 5,
              'experts_held_total': 40, 'expert_width_in': 1024,
              'expert_width': 2688, 'expert_matrices': 2,
              'latent_projections': ['l1_down', 'l1_up']}
    return {
        'steps': steps, 'chips': 1.0, 'device_kind': 'TPU v5 lite',
        'window_s': 12.2,
        'trace': {'busy_s': 0.6 * steps, 'window_s': 12.2, 'chips': 1},
        'scopes': {
            'busy_s': 0.6 * steps,
            'by_operator': {'Mamba2Mixer': 0.090 * steps,
                            'SparseExperts': 0.120 * steps,
                            'FullyConnected': 0.3 * steps},
            'by_inner': {'Mamba2Mixer/scan': 0.040 * steps,
                         'SparseExperts/experts': 0.006 * steps},
            'by_node': {'FullyConnected/l1_down': 0.003 * steps,
                        'FullyConnected/l1_up': 0.003 * steps,
                        'FullyConnected/l1_shared_w1': 0.05 * steps},
            'by_part': {}, 'recomputed_by_inner': {}},
        'step_flops': 4.2e13,
        'lm': dict(shapes, sequences=2, assignments_held_per_step=28160.0),
        'snap0': {'counters': {'moe.assignments': 1000,
                               'moe.assignments_held': 100}},
        'snap1': {'counters': {'moe.assignments': 1000 + 20 * 5 * 360448,
                               'moe.assignments_held': 100 + 20 * 5 * 5632}}}


def test_each_reader_reads_the_synthetic_slice_as_hand_arithmetic_does():
    slice_ = synthetic_slice()
    read = {name: harness.evaluate(manifest.load_layer_metric(name), slice_)
            for name in METRICS}
    assert read['nemo_step_device_ms'] == pytest.approx(600.0)
    assert read['nemo_step_mfu_pct'] == pytest.approx(
        100 * 4.2e13 / (0.6 * 197e12))
    assert read['nemo_mamba_share_pct'] == pytest.approx(15.0)
    # SparseExperts and the latent's two projections; not the shared expert
    assert read['nemo_moe_share_pct'] == pytest.approx(100 * 0.126 / 0.6)
    assert read['nemo_held_share_pct'] == pytest.approx(1.5625)
    # five layers' recurrence: bytes over the bandwidth, over 40 ms
    moved = 5 * flops_nemotron_h.ssd_scan_bytes(2, 16, 1, 8192, 64, 128)
    assert read['nemo_ssd_scan_roofline_pct'] == pytest.approx(
        100 * moved / 819e9 / 0.040)
    # 704 rows an expert: the products bind, not the matrices' bytes
    flops = flops_nemotron_h.experts_flops(28160.0, 1024, 2688)
    moved = flops_nemotron_h.experts_bytes(28160.0, 40, 1024, 2688)
    assert flops / 197e12 > moved / 819e9
    assert read['nemo_experts_roofline_pct'] == pytest.approx(
        100 * flops / 197e12 / 0.006)
    for name, value in read.items():
        assert value > 0, name
        if name.endswith('_pct'):
            assert value < 100, name


def test_the_mixers_nested_scopes_get_their_own_entries():
    """``fit_kimi_linear.refine_scopes`` with this driver's entry in its
    table: a ``while`` whose event spans its body's three events under
    ``Mamba2Mixer/.../scan``, two of them under scopes the operator opens
    inside the loop."""
    from benchmark import trace_reduce, trace_scopes
    from benchmark.drivers import fit_kimi_linear, fit_nemotron_h as driver

    class Named(object):
        def __init__(self, name, **fields):
            self.name = name
            self.__dict__.update(fields)

    def event(name, start, duration):
        return Named(name, start_ns=start, duration_ns=duration)

    scope = 'jit(step)/forward_backward/Mamba2Mixer/l0_ssm/scan/'

    def named(what):
        return 'metadata={op_name="' + scope + what + '"}'
    text = '\n'.join([
        'HloModule step', '', 'ENTRY %main (p: f32[8]) -> f32[8] {',
        '  %p = f32[8]{0} parameter(0)',
        '  %while.1 = f32[8]{0} while(%p), condition=%c, body=%b, ' +
        named('while'),
        '  %fusion.1 = f32[8]{0} fusion(%p), kind=kLoop, calls=%f1, ' +
        named('while/body/closed_call/conv/mul'),
        '  %fusion.2 = f32[8]{0} fusion(%fusion.1), kind=kLoop, calls=%f2, ' +
        named('while/body/closed_call/dot_general'),
        '  %fusion.3 = f32[8]{0} fusion(%fusion.2), kind=kLoop, calls=%f3, ' +
        named('while/body/transpose(jvp(out_gate))/mul'),
        '  ROOT %copy.1 = f32[8]{0} copy(%while.1)', '}'])
    ops = Named(trace_reduce.OPS_LINE, events=[
        event('%while.1 = f32[8]{0} while(%p), condition=%c, body=%b',
              1000, 7000),
        event('%fusion.1 = f32[8]{0} fusion(%p), kind=kLoop', 1500, 1000),
        event('%fusion.2 = f32[8]{0} fusion(%fusion.1), kind=kLoop', 3000,
              2000),
        event('%fusion.3 = f32[8]{0} fusion(%fusion.2), kind=kLoop', 5500,
              500)])
    host = Named(trace_reduce.HOST_PLANE, lines=[Named('thread', events=[
        event(harness.SLICE_SPAN, 0, 10000)])])
    profile = Named('profile', planes=[
        Named(trace_reduce.DEVICE_PLANE + '0', lines=[ops]), host])
    pairs = [('Mamba2Mixer', 'l0_ssm')]
    assert fit_kimi_linear.NESTED[('Mamba2Mixer', 'scan')] == \
        ('conv', 'gates', 'out_gate')
    assert fit_kimi_linear.NESTED[('KimiDeltaAttention', 'scan')] == \
        ('conv', 'gates', 'out_gate')
    scopes = trace_scopes.reduce_scopes(profile, text, pairs,
                                        harness.SLICE_SPAN, chips=1)
    taken = driver.refine_scopes(scopes, profile, text, pairs)
    assert taken == pytest.approx(7e-6)
    assert scopes['by_inner'] == {
        'Mamba2Mixer/scan': pytest.approx(2e-6),
        'Mamba2Mixer/conv': pytest.approx(1e-6),
        'Mamba2Mixer/out_gate': pytest.approx(0.5e-6)}
    assert scopes['by_operator']['Mamba2Mixer'] == pytest.approx(3.5e-6)
    assert scopes['busy_s'] == pytest.approx(3.5e-6)


def test_the_drivers_weights_follow_the_seed_and_the_published_draws(config):
    from benchmark.drivers import fit_nemotron_h as driver
    small = harness.build_symbol(config['rehearsal'])
    shapes = {'data': (2, 64), 'softmax_label': (2, 64)}
    args, aux = driver.make_weights(small, shapes, 2 ** 31 + 5)
    again, _ = driver.make_weights(small, shapes, 2 ** 31 + 5)
    other, _ = driver.make_weights(small, shapes, 2 ** 31 + 6)
    for name, value in args.items():
        value = np.asarray(value)
        assert (value == np.asarray(again[name])).all(), name
        if name.endswith(('_gamma', '_ssm_D')):
            assert (value == 1).all()
        elif name.endswith('_A_log'):
            assert (0 <= value).all() and (value <= np.log(16)).all()
        elif name.endswith('_dt_bias'):
            step = np.log1p(np.exp(value))          # softplus
            assert (0.0009 < step).all() and (step < 0.11).all()
        elif name.endswith('_conv_bias'):
            assert not value.any()
        else:
            assert name.endswith('_weight'), name
            assert not (value == np.asarray(other[name])).all()
            assert abs(value.std() * np.sqrt(value.shape[1]) - 1) < 0.2
    assert all(not np.asarray(v).any() for v in aux.values())
    # a floor over the drawn step holds it
    floored, _ = driver.make_weights(small, shapes, 7, (0.001, 0.1, 0.05))
    step = np.log1p(np.exp(np.asarray(floored['l0_ssm_dt_bias'])))
    assert (step > 0.0499).all()
    # the reference is told the heads as held: an eighth of each
    ref_config = driver.reference_config(config)
    assert (ref_config['mamba_num_heads'], ref_config['n_groups'],
            ref_config['num_attention_heads'],
            ref_config['num_key_value_heads']) == (16, 1, 4, 1)
    assert ref_config['pattern'] == config['layers_run']
    assert ref_config['experts_held'] == (0, 8)
    assert ref_config['num_experts'] == ref_config['n_routed_experts'] == 512


def test_the_driver_refuses_a_model_that_does_not_meet_the_pins(config):
    from benchmark.drivers import fit_nemotron_h as driver
    small = harness.build_symbol(config['rehearsal'])
    shapes = {'data': (2, 64), 'softmax_label': (2, 64)}
    with pytest.raises(harness.BenchmarkError, match='pins'):
        driver.check_pinned(small, shapes, config, False)
    with pytest.raises(harness.BenchmarkError, match='pins no model'):
        driver.check_pinned(small, shapes, dict(config, pinned=None), False)
    driver.check_pinned(small, shapes, dict(config, pinned=None), True)


def test_limits_hold_a_reading_at_the_limit_and_refuse_one_past_it():
    from benchmark.drivers import fit_nemotron_h as driver
    at = {name: limit for name, (limit, _) in driver.LIMITS.items()}
    assert driver.broken(at) == []
    for name, (limit, kind) in driver.LIMITS.items():
        past = limit * (1.01 if kind == 'most' else 0.99)
        assert driver.broken(dict(at, **{name: past})) == [name]
        assert driver.broken(dict(at, **{name: float('nan')})) == [name]
    assert driver.LIMITS['gradient_error_worst'][0] < 1.0
    assert driver.LIMITS['update_error_worst'][0] < 1.0


def test_the_cell_runs_to_its_result_line_at_its_rehearsal_sizes(tmp_path):
    from benchmark.drivers import fit_nemotron_h as driver
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
    assert ('the selection bias bit for bit what set-up made in 5 of 5 '
            'layers, 93 of 93 trained arrays moved\n') in done.stdout
    assert done.stdout.count('the bias balanced: held experts') == 5
    assert 'Mamba-2 in all:' in done.stdout
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
    assert list(line['metrics']) == ['nemo_held_share_pct']
    held = line['metrics']['nemo_held_share_pct']
    assert held['unit'] == '%' and 11.0 <= held['value'] <= 14.0


def test_without_a_chip_and_without_the_switch_the_cell_refuses():
    done = subprocess.run(
        [sys.executable, 'benchmark/run.py', '--workload', CELL, '--seed',
         '7', '--seconds', '1'], cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS='cpu'), capture_output=True,
        text=True, timeout=300)
    assert done.returncode != 0
    assert 'no CPU fall-back' in done.stderr + done.stdout
