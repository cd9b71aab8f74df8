"""The plain reference against the program, at small size on the CPU.

Tolerance: both sides compute in float32 here, the reference with
``highest`` matmul precision and two-pass variance, the program with its
own convolution layout and one-pass variance; over a dozen layers their
softmax outputs differ by a few 1e-6.  1e-4 absolute is fifty times that
and a tenth of what bf16 compute would give (about 1e-3), so computing in
a lower precision than stated, or leaving out an operator, fails.
"""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import reference, weights  # noqa: E402

ATOL = 1e-4


def three_block_resnet():
    from mxnet_tpu.models import resnet
    return resnet.resnet(units=[1, 1, 1], num_stages=3,
                         filter_list=[8, 16, 32, 64], num_classes=10,
                         image_shape=(3, 16, 16), bottle_neck=True), \
        (4, 3, 16, 16)


def one_inception_block():
    import mxnet_tpu as mx
    from mxnet_tpu.models import inception_v3
    data = mx.sym.Variable('data')
    block = inception_v3.Inception7C(data, 12, 8, 8, 12, 8, 8, 8, 8, 12,
                                     'avg', 12, 'mixed')
    block = inception_v3.Inception7D(block, 8, 12, 8, 8, 8, 8, 'max',
                                     'mixed_d')
    pool = mx.sym.Pooling(block, kernel=(8, 8), global_pool=True,
                          pool_type='avg', name='global_pool')
    fc = mx.sym.FullyConnected(mx.sym.Flatten(pool), num_hidden=7,
                               name='fc1')
    return mx.sym.SoftmaxOutput(fc, name='softmax'), (4, 10, 9, 9)


def program_forward(symbol, arg_params, aux_params, data, is_train):
    import mxnet_tpu as mx
    args = {k: mx.nd.array(np.asarray(v)) for k, v in arg_params.items()}
    args['data'] = mx.nd.array(data)
    args['softmax_label'] = mx.nd.zeros((data.shape[0],))
    aux = {k: mx.nd.array(np.asarray(v)) for k, v in aux_params.items()}
    executor = symbol.bind(mx.cpu(), args, grad_req='null', aux_states=aux)
    return executor.forward(is_train=is_train)[0].asnumpy()


@pytest.mark.parametrize('build', [three_block_resnet, one_inception_block],
                         ids=['resnet3', 'inception1'])
@pytest.mark.parametrize('is_train', [True, False],
                         ids=['training', 'inference'])
def test_reference_agrees_with_the_program(build, is_train):
    symbol, shape = build()
    arg_params, aux_params = weights.make(symbol, {'data': shape}, 7)
    rng = np.random.RandomState(3)
    data = rng.rand(*shape).astype(np.float32)
    # moving statistics that are not the identity, so that inference
    # mode is told apart from "BatchNorm left out"
    aux_params = {k: np.asarray(v) + 0.1 * rng.rand(*v.shape).astype('f')
                  for k, v in aux_params.items()}
    arrays = dict(arg_params, **aux_params)
    arrays['data'] = data
    want, stats = reference.forward_jit(symbol.tojson(), arrays, is_train)
    got = program_forward(symbol, arg_params, aux_params, data, is_train)
    assert got.shape == want.shape
    assert np.abs(got - np.asarray(want)).max() <= ATOL
    assert np.allclose(np.asarray(want).sum(axis=1), 1.0, atol=1e-5)
    assert len(stats) == sum(1 for n in symbol.tojson().split('"op": ')
                             if n.startswith('"BatchNorm"'))


def test_training_and_inference_modes_differ():
    symbol, shape = three_block_resnet()
    arg_params, aux_params = weights.make(symbol, {'data': shape}, 7)
    arrays = dict(arg_params, **aux_params)
    arrays['data'] = np.random.RandomState(3).rand(*shape).astype('f')
    train, _ = reference.forward_jit(symbol.tojson(), arrays, True)
    infer, _ = reference.forward_jit(symbol.tojson(), arrays, False)
    assert np.abs(np.asarray(train) - np.asarray(infer)).max() > 1e-3


def test_reference_refuses_an_operator_it_does_not_define():
    import mxnet_tpu as mx
    net = mx.sym.SoftmaxOutput(mx.sym.Dropout(mx.sym.Variable('data')),
                               name='softmax')
    with pytest.raises(NotImplementedError):
        reference.forward(net.tojson(), {'data': np.zeros((2, 3), 'f')},
                          False)


def softmax_rows(logits):
    e = np.exp(logits - logits.max(axis=1, keepdims=True))
    return e / e.sum(axis=1, keepdims=True)


def test_log_prob_error_and_row_agreement_by_hand():
    want = np.array([[0.5, 0.5], [0.25, 0.75]])
    assert reference.log_prob_error(want, want) == 0.0
    assert reference.row_agreement(want, want) == pytest.approx(1.0)
    got = want * np.exp([[0.1, -0.1], [0.1, -0.1]])
    assert reference.log_prob_error(got, want) == pytest.approx(
        0.1 / np.log(want).std(), rel=1e-9)
    # what every row shares is not a row's own answer
    assert reference.row_agreement(got, want) == pytest.approx(1.0)
    assert reference.row_agreement(want[::-1], want) == pytest.approx(-1.0)
    assert reference.row_agreement(want[:1].repeat(2, axis=0), want) == 0.0
    with pytest.raises(ValueError):
        reference.log_prob_error(want[:1], want)


@pytest.mark.parametrize('wrong', ['uniform', 'rows_swapped', 'one_row_on',
                                   'same_for_every_row', 'too_coarse'])
def test_correct_can_fail(wrong):
    """Outputs shaped like an untrained classifier's, as the fit cells
    compare them: 1000 classes, a spread of 0.6 in the log-probabilities
    that every row shares and 0.12 of each row's own (the cell's
    ResNet-50, measured on the CPU), largest probability under 0.03, so
    that a tolerance of 3e-2 absolute on a probability passes every one
    of these."""
    from benchmark.drivers import fit

    def verdict(got):
        return (reference.log_prob_error(got, want) <=
                fit.LOG_PROB_ERROR_MAX and
                reference.row_agreement(got, want) >= fit.ROW_AGREEMENT_MIN)

    rng = np.random.RandomState(5)
    shared = 0.6 * rng.randn(1, 1000)
    want = softmax_rows(shared + 0.12 * rng.randn(64, 1000))
    assert want.max() < 0.03
    # twice what bf16 compute does to Inception-v3 (0.13 in the logarithm)
    assert verdict(want * np.exp(0.26 * rng.randn(64, 1000)))
    got = {'uniform': np.full_like(want, 1e-3),
           'rows_swapped': want[::-1],
           'one_row_on': np.roll(want, 1, axis=0),
           'same_for_every_row': want[:1].repeat(64, axis=0),
           'too_coarse': want * np.exp(0.5 * rng.randn(64, 1000)),
           }[wrong]
    got = got / got.sum(axis=1, keepdims=True)
    assert np.abs(got - want).max() < 3e-2
    assert not verdict(got)


def test_cross_entropy_by_hand():
    prob = np.array([[0.5, 0.5], [0.25, 0.75]])
    assert reference.cross_entropy(prob, [0, 1]) == pytest.approx(
        -(np.log(0.5) + np.log(0.75)) / 2, abs=1e-9)


def test_weights_are_the_seeds_and_any_whole_number_is_a_seed():
    symbol, shape = three_block_resnet()
    big = 2 ** 31 + 12345
    a, aux = weights.make(symbol, {'data': shape}, big)
    b, _ = weights.make(symbol, {'data': shape}, big)
    c, _ = weights.make(symbol, {'data': shape}, big + 1)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert any(not np.array_equal(a[k], c[k]) for k in a)
    weight = np.asarray(a['stage1_unit1_conv2_weight'])
    fan_in = np.prod(weight.shape[1:])
    assert weight.dtype == np.float32
    assert abs(weight.std() - np.sqrt(2.0 / fan_in)) < 0.2 * weight.std()
    assert all(np.all(np.asarray(v) == (1.0 if k.endswith('var') else 0.0))
               for k, v in aux.items())
