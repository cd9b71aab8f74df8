"""The language-model cell's own files: the manifest's new entries against
them, ``flops_lm``'s counts against ISSUE 28's table, ``trace_scopes`` on a
small trace recorded on the chip, and a rehearsal of the cell end to end."""
import gzip
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import flops_lm, harness, manifest, trace_scopes  # noqa: E402
from benchmark import trace_reduce  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
SPEC = manifest.load_manifest()
CELL = 'lfm2_moe_fit_8k'
CONFIG = 'lfm2_24b_a2b'
LM_METRICS = ['lm_step_mfu_pct', 'lm_step_device_ms', 'lm_moe_share_pct',
              'lm_experts_roofline_pct', 'lm_attention_roofline_pct',
              'lm_shortconv_ms', 'lm_optimizer_ms', 'lm_held_share_pct']
# the catalog's row for LFM2-24B-A2B (the guide's architectures.jsonl)
PUBLISHED = {
    'conv_L_cache': 3, 'conv_bias': False, 'hidden_size': 2048,
    'intermediate_size': 11776, 'max_position_embeddings': 128000,
    'model_type': 'lfm2_moe', 'moe_intermediate_size': 1536,
    'norm_eps': 1e-05, 'norm_topk_prob': True, 'num_attention_heads': 32,
    'num_dense_layers': 2, 'num_experts': 64, 'num_experts_per_tok': 4,
    'num_hidden_layers': 40, 'num_key_value_heads': 8,
    'rope_parameters': {'rope_theta': 1000000, 'rope_type': 'default'},
    'routed_scaling_factor': 1, 'use_expert_bias': True,
    'vocab_size': 65536}
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
    body = manifest.load_cell(CELL)
    assert body['driver'] == 'fit_lm' and body['ring'] == 8
    assert (body['warmup_steps'], body['trace_steps']) == (4, 20)
    # the balance's two keys and the guard's band, each with its reason
    assert body['balance_passes'] >= len(body['balance_step']) == 3
    assert body['held_share_band'][0] < 1 < body['held_share_band'][1]
    assert body['balance_why'] and body['held_share_band_why']
    reported = [m['name'] for m in manifest.metrics_of(SPEC, 'per_layer',
                                                       CELL)]
    assert reported == LM_METRICS
    assert [m['name'] for m in manifest.metrics_of(SPEC, 'end_to_end',
                                                   CELL)] == \
        ['fit_samples_per_s', 'setup_s']


@pytest.mark.parametrize('name', LM_METRICS)
def test_layer_metric_reads_nothing_from_a_slice_without_its_source(name):
    body = manifest.load_layer_metric(name)
    assert body['drivers'] == ['fit_lm'] and body['moves'] == \
        'fit_samples_per_s'
    # the parent has no scope, counter or text to read: nothing, no raise
    assert harness.evaluate(body, {
        'trace': None, 'steps': 20.0, 'chips': 1.0, 'snap0': {}, 'snap1': {},
        'device_kind': 'TPU v5 lite'}) is None


def test_configuration_keeps_every_published_number_but_the_reduced(config):
    reduced = set(config['reduced'])
    assert reduced == {'num_layers', 'num_dense_layers', 'experts_held',
                       'vocab_size'}
    for key, value in PUBLISHED.items():
        if key not in reduced:
            assert config[key] == value, key
    assert len(config['layer_types']) == 40
    assert config['layer_types'].count('full_attention') == 10
    assert config['published'] == dict(
        config['published'], num_hidden_layers=40, num_dense_layers=2,
        num_experts=64, vocab_size=65536)
    assert '8 chips share each layer' in config['deployment']
    assert config['source'].startswith('https://huggingface.co/LiquidAI/')
    kwargs = config['builder']['kwargs']
    # what is run: the widths as published, the cut as the file states it
    assert kwargs['layer_types'] == config['layers_run'] == \
        ['conv', 'full_attention', 'conv', 'conv', 'conv']
    assert len(kwargs['layer_types']) == config['num_layers'] == 5
    for ours, theirs in (('hidden_size', 'hidden_size'),
                         ('intermediate_size', 'intermediate_size'),
                         ('moe_intermediate_size', 'moe_intermediate_size'),
                         ('num_experts', 'num_experts'),
                         ('num_experts_per_tok', 'num_experts_per_tok'),
                         ('num_attention_heads', 'num_attention_heads'),
                         ('num_key_value_heads', 'num_key_value_heads'),
                         ('norm_eps', 'norm_eps'),
                         ('conv_L_cache', 'conv_L_cache')):
        assert kwargs[ours] == PUBLISHED[theirs], ours
    assert kwargs['rope_theta'] == PUBLISHED['rope_parameters']['rope_theta']
    assert kwargs['experts_held'] == config['experts_held'] == [0, 8]
    assert kwargs['vocab_size'] == config['vocab_size'] == 65536 // 8
    assert kwargs['num_dense_layers'] == config['num_dense_layers'] == 1
    for key in ('assumed', 'pinned', 'memory', 'rehearsal', 'sample'):
        assert config[key], key


def test_the_built_model_is_the_one_the_file_pins(config, symbol):
    assert flops_lm.pinned(symbol, INPUTS) == {
        k: config['pinned'][k] for k in ('forward_macs_per_token',
                                         'parameters', 'weights')}


def millions(value):
    return round(value / 1e6, 1)


def test_flops_lm_counts_what_issue_28_reckoned(symbol):
    dense, per_assignment, rows = flops_lm.forward_macs_per_token(symbol,
                                                                  INPUTS)
    by_node = {name: macs for name, _, macs in rows}

    def layer(prefix, experts=True):
        return sum(m for n, m in by_node.items() if n.startswith(prefix) and
                   (experts or not n.endswith('/experts')))

    # conv 16.8 + MLP 72.4: the table's 89.2 adds the rounded parts
    assert abs(layer('l0_') / 1e6 - 89.2) < 0.1
    assert millions(by_node['l0_conv_in'] + by_node['l0_conv_out']) == 16.8
    assert millions(by_node['l0_w1'] * 3) == 72.4
    assert millions(sum(by_node['l1_' + p] for p in 'qkvo')) == 10.5
    assert millions(by_node['l1_att']) == 16.8      # 32 x 4096 x 64 x 2
    assert millions(per_assignment) == 9.4          # 9.44M an expert
    assert per_assignment == 3 * 2048 * 1536
    assert by_node['l1_moe/router'] == 64 * 2048    # 0.13M
    assert by_node['l2_moe/experts'] == per_assignment * 4 * 8 // 64
    assert millions(by_node['lm_head']) == 16.8
    four = sum(layer('l%d_' % i) for i in (1, 2, 3, 4))
    assert 96.9 <= millions(four) <= 97.1
    total = sum(m for _, _, m in rows)
    assert round(total / 1e6) == 203
    assert dense == total - 4 * by_node['l2_moe/experts']
    # parameters: 89.2M, 363.4M and the table's 16.8M, less the norms
    pinned = flops_lm.pinned(symbol, INPUTS)
    assert round(pinned['parameters'] / 1e6) == 469
    # a step: 6 FLOPs a multiply-add; experts by the counted assignments
    uniform = 4 * 16384 * 4 * 8 // 64
    assert flops_lm.train_step_flops(dense, per_assignment, 16384,
                                     uniform) == 6 * 16384 * total
    assert flops_lm.experts_flops(uniform, 2048, 1536) == \
        6 * uniform * per_assignment
    assert flops_lm.attention_flops(2, 32, 8192, 64) == \
        6 * 16384 * by_node['l1_att']
    assert flops_lm.experts_bytes(uniform, 32, 2048, 1536) > 0
    assert flops_lm.attention_bytes(2, 32, 8, 8192, 64) > 0
    assert flops_lm.kernel_shapes(symbol, INPUTS) == {
        'attention': [(32, 8, 8192, 64)], 'experts_held_total': 32,
        'expert_width_in': 2048, 'expert_width': 1536}


# -- trace_scopes on a trace recorded on the v5e ----------------------------
# scratch/record_small_lm.py of PR 28: three steps of Module.fit on a
# three-layer lfm2_moe (hidden 256, 4 query heads over 2, 16 experts of
# which 4 held, 2 x 128 tokens), bf16, Adam, acc and ce, with the fused
# step's HLO text beside it.

@pytest.fixture(scope='module')
def recorded():
    with gzip.open(os.path.join(HERE, 'recorded_small_lm.hlo.txt.gz'),
                   'rt') as f:
        text = f.read()
    with open(os.path.join(HERE, 'recorded_small_lm.json')) as f:
        pairs = [tuple(p) for p in json.load(f)['pairs']]
    profile = trace_reduce.load(os.path.join(HERE,
                                             'recorded_small_lm.xplane.pb'))
    return text, pairs, profile


def test_instructions_find_their_operator_by_the_three_rules(recorded):
    text, pairs, _ = recorded
    scopes = trace_scopes.StepScopes(text, pairs)
    parse = scopes.parse
    assert parse('jit(step_m)/forward_backward/jvp(SparseExperts/l1_moe)/'
                 'router/top_k') == \
        ('forward_backward', 'SparseExperts', 'l1_moe', 'router')
    assert parse('jit(step_m)/forward_backward/transpose(jvp('
                 'forward_backward))/jvp()/checkpoint/rematted_computation/'
                 'FullyConnected/l0_w1/dot_general')[1:3] == \
        ('FullyConnected', 'l0_w1')
    assert parse('jit(step_m)/optimizer/mul') == \
        ('optimizer', None, None, None)
    assert parse('sort') == trace_scopes.NO_SCOPE
    # rule 3: XLA's own grouped product has lost its stack; its operands
    # say whose it is, its name says it is the experts' product
    ragged = [n for n in scopes.operands if n.startswith('ragged-dot-none')]
    assert ragged
    for name in ragged:
        scope = scopes.of(name)
        assert scope.operator == 'SparseExperts' and scope.inner == 'experts'
        assert scope.part == 'forward_backward'
    # the attention kernels keep theirs (rule 1)
    splash = [n for n in scopes.operands if n.startswith('splash_mqa')]
    assert splash
    assert {scopes.of(n).operator for n in splash} == {'FlashAttention'}
    # what has lost its stack cannot say which pass it is; a fusion under a
    # mirror stage's recomputation can
    assert not any(scopes.recomputed(n) for n in ragged + splash)
    again = [n for n in scopes.operands if scopes.recomputed(n)]
    assert again
    assert all('rematted_computation' in scopes.op_name.get(
        n, scopes.op_name.get(scopes.root.get(scopes.calls.get(n)), ''))
        for n in again)
    # an optimizer's instruction inherits nothing
    updates = [n for n in scopes.operands
               if scopes.parse(scopes.op_name.get(n)).part == 'optimizer']
    assert updates
    assert all(scopes.of(n).operator is None for n in updates)


def test_device_time_by_scope_adds_up_on_the_recorded_trace(recorded):
    text, pairs, profile = recorded
    reduced = trace_scopes.reduce_scopes(profile, text, pairs,
                                         harness.SLICE_SPAN, chips=1)
    plain = trace_reduce.reduce_profile(profile, harness.SLICE_SPAN, chips=1)
    # the same events as the accepted reduction sees; none overlap on a chip
    assert reduced['busy_s'] == pytest.approx(plain['busy_s'], rel=1e-6)
    assert reduced['joined_s'] > 0.99 * reduced['busy_s']
    assert reduced['scoped_s'] > 0.9 * reduced['busy_s']
    assert sum(reduced['by_operator'].values()) == \
        pytest.approx(reduced['scoped_s'], rel=1e-9)
    assert sum(reduced['by_node'].values()) == \
        pytest.approx(reduced['scoped_s'], rel=1e-9)
    for operator in ('SparseExperts', 'FlashAttention', 'GatedShortConv',
                     'FullyConnected', 'RMSNorm'):
        assert reduced['by_operator'][operator] > 0, operator
    for inner in ('router', 'dispatch', 'experts', 'combine'):
        assert reduced['by_inner']['SparseExperts/' + inner] > 0, inner
    assert sum(v for k, v in reduced['by_inner'].items()
               if k.startswith('SparseExperts/')) <= \
        reduced['by_operator']['SparseExperts'] * (1 + 1e-9)
    # the forward pass computed again, as far as instructions say so
    # themselves: the mirror stages' operators and nothing of attention,
    # which lies in no mirror stage; the grouped products are counted
    again, inner = (reduced['recomputed_by_operator'],
                    reduced['recomputed_by_inner'])
    assert set(again) <= set(reduced['by_operator'])
    assert again['SparseExperts'] > 0 and 'FlashAttention' not in again
    for key, seconds in inner.items():
        assert seconds <= reduced['by_inner'][key] * (1 + 1e-9), key
    # two expert layers: 3 forward, 3 computed again, 6 backward each
    assert reduced['kernel_instructions'] == {'SparseExperts/experts': 24}
    assert reduced['by_part']['optimizer'] > 0
    assert reduced['by_part']['forward_backward'] > \
        10 * reduced['by_part']['optimizer']
    # a program whose text is not at hand gives nothing, and does not raise
    assert trace_scopes.reduce_scopes(profile, None, pairs) is None
    assert trace_scopes.reduce_scopes(profile, 'no instruction here', pairs,
                                      harness.SLICE_SPAN)['joined_s'] == 0


@pytest.mark.parametrize('name', LM_METRICS)
def test_each_reader_reads_the_recorded_slice(recorded, name):
    text, pairs, profile = recorded
    slice_ = {
        'steps': 3.0, 'chips': 1.0, 'device_kind': 'TPU v5 lite',
        'trace': trace_reduce.reduce_profile(profile, harness.SLICE_SPAN,
                                             chips=1),
        'scopes': trace_scopes.reduce_scopes(profile, text, pairs,
                                             harness.SLICE_SPAN, chips=1),
        'step_flops': 1e9,
        'lm': {'sequences': 2, 'attention': [(4, 2, 128, 64)],
               'assignments_held_per_step': 256.0, 'experts_held_total': 8,
               'expert_width_in': 256, 'expert_width': 128},
        # what the program counted over those three steps: 2 expert layers
        # x 256 tokens x 4 a step, 128 of each layer's on held experts
        'snap0': {'counters': {'moe.assignments': 2048,
                               'moe.assignments_held': 250}},
        'snap1': {'counters': {'moe.assignments': 8192,
                               'moe.assignments_held': 1018}}}
    value = harness.evaluate(manifest.load_layer_metric(name), slice_)
    assert value is not None and value > 0
    if name == 'lm_held_share_pct':
        assert value == 12.5
    if name.endswith('_pct'):
        assert value < 100


def test_the_cell_is_the_one_issue_28_names(config):
    # ISSUE 28's Adam but for the rate: since PR 31 a constant 5e-6, what a
    # warm-up over 2000 updates to 3e-4 averages over the window's updates
    assert config['optimizer'] == {
        'name': 'adam', 'learning_rate': 5e-6, 'beta1': 0.9, 'beta2': 0.95,
        'epsilon': 1e-8, 'wd': 0.1}
    # a constant rate, Module's own rescale_grad, the bias as set-up left it
    assert 'lr_scheduler' not in config
    assert 'lr_scheduler' not in config['rehearsal']
    assert (config['seq_len'], config['per_chip_batch'],
            config['compute_dtype']) == (8192, 2, 'bfloat16')
    assert config['fit'] == {'kvstore': 'device',
                             'eval_metric': ['acc', 'ce'],
                             'speedometer_every': 20}
    body = manifest.load_cell(CELL)
    assert body['zipf_exponent'] == 1.0 and body['mesh'] is None


def test_the_ring_hands_its_batches_out_in_turn_and_closes_on_the_first():
    import mxnet_tpu as mx
    import numpy as np
    from benchmark.drivers import fit_lm
    host = fit_lm.make_batches(2 ** 31 + 9, 8, 2, 16, 512, 1.0)
    again = fit_lm.make_batches(2 ** 31 + 9, 8, 2, 16, 512, 1.0)
    for (data, label), (data2, _) in zip(host, again):
        assert (data == data2).all()                # the seed's batches
        assert (data[:, 1:] == label[:, :-1]).all()     # the next token
        assert data.dtype == np.float32 and data.max() < 512
    assert not (host[0][0] == host[1][0]).all()
    ring = [mx.io.DataBatch([d], [l], pad=0) for d, l in host]
    iterator = fit_lm.RingIter(ring, 3, traced=False)
    # the warm-up epoch: its limit, and no closing step
    assert [next(iterator) for _ in range(3)] == ring[:3]
    with pytest.raises(StopIteration):
        next(iterator)
    # the measured epoch goes on round the ring; past its limit one more
    # step, on the first batch, then the end
    iterator.reset()
    iterator.limit = 7
    assert [next(iterator) for _ in range(7)] == ring[3:] + ring[:2]
    assert next(iterator) is ring[0]
    with pytest.raises(StopIteration):
        next(iterator)
    assert iterator.handed == 8 and iterator.stopped_at is not None


def test_limits_hold_a_reading_at_the_limit_and_refuse_one_past_it():
    from benchmark.drivers import fit_lm
    at = {name: limit for name, (limit, _) in fit_lm.LIMITS.items()}
    assert fit_lm.broken(at) == []
    for name, (limit, kind) in fit_lm.LIMITS.items():
        past = limit * (1.01 if kind == 'most' else 0.99)
        assert fit_lm.broken(dict(at, **{name: past})) == [name]
        assert fit_lm.broken(dict(at, **{name: float('nan')})) == [name]
    # an unchanged array reads 1, and both limits on a change lie under it
    assert fit_lm.leaf_error([0.0, 0.0], [3.0, 4.0]) == 1.0
    assert fit_lm.LIMITS['gradient_error_worst'][0] < 1.0
    assert fit_lm.LIMITS['update_error_worst'][0] < 1.0


# -- the balance set-up makes, at the rehearsal's sizes ---------------------

@pytest.fixture(scope='module')
def small(config):
    """The configuration's rehearsal sizes, built: 16 experts of which 4 are
    held, 2 x 64 tokens a step."""
    import types
    from benchmark import reference_lfm2_moe
    from benchmark.drivers import fit_lm
    sizes = harness.sizes(types.SimpleNamespace(config=config,
                                                rehearsal=True))
    tokens = (sizes['per_chip_batch'], sizes['seq_len'])
    return types.SimpleNamespace(
        sizes=sizes, symbol=harness.build_symbol(sizes),
        shapes={'data': tokens, 'softmax_label': tokens},
        config=fit_lm.reference_config(sizes), cell=manifest.load_cell(CELL),
        reference=reference_lfm2_moe)


def drawn_bias(seed, names, experts):
    """What ``make_weights`` drew before PR 31: normal, 0.1."""
    import numpy as np
    rng = np.random.default_rng(seed)
    return {name: rng.normal(0.0, 0.1, experts).astype(np.float32)
            for name in sorted(names)}


def balance(small, seed, passes=None, bias=None):
    import numpy as np
    from benchmark.drivers import fit_lm
    batch, length = small.shapes['data']
    host = fit_lm.make_batches(seed, small.cell['ring'], batch, length,
                               small.sizes['vocab_size'],
                               small.cell['zipf_exponent'])
    args, aux = fit_lm.make_weights(small.symbol, small.shapes, seed)
    names = [k for k in aux if k.endswith('_expert_bias')]
    assert not any(np.asarray(aux[k]).any() for k in names)   # from zero
    if bias == 'drawn':
        bias = drawn_bias(seed, names, small.config['num_experts'])
    return fit_lm.balance_bias(
        small.reference, args, [data for data, _ in host], small.config,
        small.cell['balance_passes'] if passes is None else passes,
        small.cell['balance_step'], bias=bias)


def held_share(load, config):
    import numpy as np
    first, count = config['experts_held']
    return {layer: float(np.asarray(v)[:, first:first + count].sum() /
                         np.asarray(v).sum()) for layer, v in load.items()}


# three seeds whose first expert layer gives the held experts 23.6%, 9.6%
# and 34.6% of the ring's assignments under a drawn bias (even is 25%)
SEEDS_APART = (1, 8, 12)


def test_the_balanced_bias_brings_every_seed_inside_the_band(small):
    import numpy as np
    from benchmark.drivers import fit_lm
    band = small.cell['held_share_band']
    lot, even = [], 4.0 / 16
    for seed in SEEDS_APART:
        _, load = balance(small, seed, passes=0, bias='drawn')
        lot.append(held_share(load, small.config))
        bias, load = balance(small, seed)
        shares = fit_lm.check_balance(load, small.config, band)   # no raise
        assert shares == pytest.approx(held_share(load, small.config))
        assert sorted(shares) == [1, 2, 3, 4]
        for layer, share in shares.items():
            assert band[0] * even <= share <= band[1] * even, (seed, layer)
        # the bias moved, every expert's, and it is the seed's alone
        again, _ = balance(small, seed)
        assert sorted(bias) == ['l%d_moe_expert_bias' % i for i in (1, 2, 3,
                                                                    4)]
        for name, value in bias.items():
            assert np.asarray(value).shape == (16,) and \
                np.asarray(value).all()
            np.testing.assert_array_equal(np.asarray(value),
                                          np.asarray(again[name]))
    first = [shares[1] for shares in lot]
    assert max(first) >= 2 * min(first), first
    # the loads are the reference's own under that bias (its ``route``)
    host = fit_lm.make_batches(seed, 8, 2, 64, 512, 1.0)
    args, _ = fit_lm.make_weights(small.symbol, small.shapes, seed)
    _, held = small.reference.forward(dict(args, **bias), host[0][0],
                                      small.config)
    for layer in shares:
        np.testing.assert_array_equal(np.asarray(load[layer])[0, :4],
                                      np.asarray(held[layer]))


def test_a_bias_left_as_drawn_trips_the_check(small):
    from benchmark.drivers import fit_lm
    _, load = balance(small, SEEDS_APART[1], passes=0, bias='drawn')
    with pytest.raises(harness.BenchmarkError,
                       match=r'not balanced.*outside 23.00% to 27.00% in '
                       r'layer 1 \(9\.\d\d%\)'):
        fit_lm.check_balance(load, small.config,
                             small.cell['held_share_band'])


# -- the cell, rehearsed ----------------------------------------------------

def test_the_cell_runs_end_to_end_at_its_rehearsal_sizes(tmp_path):
    from benchmark.drivers import fit_lm
    environ = dict(os.environ, JAX_PLATFORMS='cpu',
                   JAX_COMPILATION_CACHE_DIR=str(tmp_path / 'cache'))
    lines = {}
    for trace in ('0', '1'):
        done = subprocess.run(
            [sys.executable, 'benchmark/run.py', '--workload', CELL,
             '--seed', str(2 ** 31 + 4242), '--seconds', '1', '--trace',
             trace, '--rehearse-cpu'],
            cwd=ROOT, env=environ, capture_output=True, text=True,
            timeout=900)
        assert done.returncode == 0, done.stdout[-3000:] + done.stderr[-3000:]
        out = [l for l in done.stdout.splitlines() if l.strip()]
        lines[trace] = json.loads(out[-1])
        assert '0 tokens dropped' in done.stdout
        assert 'inside the window: 0' in done.stdout
        # the bias is what set-up made, every trained array moved, and each
        # layer's balance was inside the band (it is logged only where the
        # check did not end the run)
        assert ('the selection bias bit for bit what set-up made in 4 of 4 '
                'layers, 49 of 49 trained arrays moved\n') in done.stdout
        assert done.stdout.count('the bias balanced: held experts') == 4
        # every number compared beside its limit: the last lines of standard
        # error and the line's last key
        compared = lines[trace]['compared']
        assert list(lines[trace])[-1] == 'compared'
        last = done.stderr.strip().splitlines()[-len(compared):]
        assert [l.split()[1].rstrip(':') for l in last] == list(compared)
        assert all(l.startswith('compared ') and 'NOT HELD' not in l
                   for l in last)
        assert compared['bias_moved'] == {'value': 0.0, 'most': 0.0}
        assert compared['arrays_unmoved'] == {'value': 0.0, 'most': 0.0}
        assert set(fit_lm.LIMITS) < set(compared)
    for line in lines.values():
        assert line['correct'] is True and line['rehearsal'] is True
        assert line['failed'] == 0 and line['attempted'] >= 1
    # a CPU run gives no device number: only what the program counted
    assert lines['0']['metrics'] == {}
    assert list(lines['1']['metrics']) == ['lm_held_share_pct']
    held = lines['1']['metrics']['lm_held_share_pct']
    assert held['unit'] == '%' and 23.0 <= held['value'] <= 27.0
    assert lines['1']['attempted'] == 20


def test_a_balance_that_did_not_happen_ends_the_run_with_no_result(tmp_path):
    # the cell's file with no pass of the rule: the bias stays at zero, the
    # drawn routers give the held experts their lot, and the check ends
    # the run before the reference's first step
    code = (
        'import sys; sys.path.insert(0, %r)\n'
        'from benchmark import manifest, run\n'
        'load = manifest.load_cell\n'
        'manifest.load_cell = lambda name: dict(load(name), '
        'balance_passes=0)\n'
        'run.main([\'--workload\', %r, \'--seed\', \'8\', \'--seconds\', '
        '\'1\', \'--rehearse-cpu\'])\n' % (ROOT, CELL))
    done = subprocess.run(
        [sys.executable, '-c', code], cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS='cpu',
                 JAX_COMPILATION_CACHE_DIR=str(tmp_path / 'cache')),
        capture_output=True, text=True, timeout=600)
    assert done.returncode != 0
    assert 'the selection bias is not balanced' in done.stderr
    assert 'the bias balanced: held experts receive' in done.stdout
    assert not any(l.startswith('{') for l in done.stdout.splitlines())
    assert 'compared ' not in done.stderr


def test_without_a_chip_and_without_the_switch_the_cell_refuses():
    done = subprocess.run(
        [sys.executable, 'benchmark/run.py', '--workload', CELL, '--seed',
         '7', '--seconds', '1'], cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS='cpu'), capture_output=True,
        text=True, timeout=300)
    assert done.returncode != 0
    assert 'no CPU fall-back' in done.stderr + done.stdout


# -- the rest of a run with the timed path broken underneath ----------------
# Each fault is planted in the program (or in what feeds it) by a line run
# before ``run.main``; the harness's look for a chip is skipped by the
# rehearsal switch, everything else is the run's own.  The run has to end
# with a result whose ``correct`` is false, and by the numbers named.
FAULTS = {
    # the optimizer's update gives back the array and the state it was given
    'a_step_that_returns_its_state_unchanged': ("""
from mxnet_tpu import optimizer
plain = optimizer.Adam.make_functional
def make_functional(self, *args, **kwargs):
    fo = plain(self, *args, **kwargs)
    fo._update_one = lambda name, w, g, s, lr_t: (w, s)
    return fo
optimizer.Adam.make_functional = make_functional
""", {'gradient_error_median', 'gradient_error_worst', 'arrays_unmoved',
      'loss_last_over_first'}),
    # the batch's second sequence never arrives: the first stands in its
    # place, so the sum is over half of the batch, counted twice
    'half_of_the_batch_left_out': ("""
import mxnet_tpu as mx
plain = mx.io.DataBatch
def batch(data, label, **kwargs):
    data, label = [d.copy() for d in data], [l.copy() for l in label]
    data[0][1], label[0][1] = data[0][0], label[0][0]
    return plain(data, label, **kwargs)
mx.io.DataBatch = batch
""", {'gradient_error_median'}),
}


@pytest.mark.parametrize('fault', sorted(FAULTS))
def test_a_run_with_the_timed_path_broken_reads_not_correct(fault, tmp_path):
    plant, names = FAULTS[fault]
    code = ('import sys; sys.path.insert(0, %r)\n%s\n'
            'from benchmark import run\n'
            'run.main([\'--workload\', %r, \'--seed\', \'2147491111\', '
            '\'--seconds\', \'1\', \'--rehearse-cpu\'])\n'
            % (ROOT, plant, CELL))
    done = subprocess.run(
        [sys.executable, '-c', code], cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS='cpu',
                 JAX_COMPILATION_CACHE_DIR=str(tmp_path / 'cache')),
        capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stdout[-3000:] + done.stderr[-3000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line['correct'] is False
    not_held = {name for name, entry in line['compared'].items()
                if not harness.holds(entry)}
    print(fault, {k: line['compared'][k]['value'] for k in not_held})
    assert names <= not_held, (names, not_held)
    assert done.stderr.count('NOT HELD') == len(not_held)
