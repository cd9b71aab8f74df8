#!/usr/bin/env python
"""Train ImageNet-class image classification
(reference ``example/image-classification/train_imagenet.py``).

Two modes, like the reference:
- real: ``--data-train /path/imagenet.rec`` drives the native
  ImageRecordIter (threaded C++ JPEG decode + full augmenter) into the
  mesh-sharded Module.fit path, with checkpoints via ``--model-prefix``.
- benchmark: ``--benchmark 1`` trains on synthetic data and reports
  imgs/sec (README.md:247-254: "--benchmark 1 ... run on a synthetic
  dataset, no data loading cost").

bf16 mixed precision via ``--dtype bfloat16`` (master weights stay f32).
"""
import argparse
import logging
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..'))
import mxnet_tpu as mx
from mxnet_tpu import models


class SyntheticImageIter(mx.io.DataIter):
    """Fixed random batch replayed ``num_batches`` times — the
    --benchmark data path (zero loading cost)."""

    def __init__(self, batch_size, data_shape, num_classes, num_batches):
        super().__init__()
        self.batch_size = batch_size
        self.data_shape = data_shape
        self.num_batches = num_batches
        rng = np.random.RandomState(0)
        self._data = mx.nd.array(
            rng.rand(batch_size, *data_shape).astype(np.float32))
        self._label = mx.nd.array(
            rng.randint(0, num_classes, batch_size).astype(np.float32))
        self._i = 0

    @property
    def provide_data(self):
        return [('data', (self.batch_size,) + tuple(self.data_shape))]

    @property
    def provide_label(self):
        return [('softmax_label', (self.batch_size,))]

    def reset(self):
        self._i = 0

    def next(self):
        if self._i >= self.num_batches:
            raise StopIteration
        self._i += 1
        return mx.io.DataBatch([self._data], [self._label], pad=0)


def add_data_args(parser):
    parser.add_argument('--data-train', default=None,
                        help='training RecordIO (.rec)')
    parser.add_argument('--data-val', default=None)
    parser.add_argument('--image-shape', default='3,224,224')
    parser.add_argument('--rgb-mean', default='123.68,116.779,103.939')
    parser.add_argument('--preprocess-threads', type=int, default=4)
    # augmenter knobs (reference image_aug_default.cc names)
    parser.add_argument('--max-random-scale', type=float, default=1.0)
    parser.add_argument('--min-random-scale', type=float, default=1.0)
    parser.add_argument('--max-random-rotate-angle', type=float, default=0)
    parser.add_argument('--max-random-shear-ratio', type=float, default=0)
    parser.add_argument('--max-random-aspect-ratio', type=float, default=0)
    parser.add_argument('--min-crop-size', type=int, default=0)
    parser.add_argument('--max-crop-size', type=int, default=0)
    parser.add_argument('--random-h', type=float, default=0)
    parser.add_argument('--random-s', type=float, default=0)
    parser.add_argument('--random-l', type=float, default=0)


def get_data(args, image_shape):
    mean = [float(v) for v in args.rgb_mean.split(',')]
    common = dict(data_shape=tuple(image_shape),
                  batch_size=args.batch_size,
                  mean_r=mean[0], mean_g=mean[1], mean_b=mean[2],
                  preprocess_threads=args.preprocess_threads)
    train = mx.io.ImageRecordIter(
        path_imgrec=args.data_train, shuffle=True,
        rand_crop=True, rand_mirror=True,
        max_random_scale=args.max_random_scale,
        min_random_scale=args.min_random_scale,
        max_rotate_angle=args.max_random_rotate_angle,
        max_shear_ratio=args.max_random_shear_ratio,
        max_aspect_ratio=args.max_random_aspect_ratio,
        min_crop_size=args.min_crop_size,
        max_crop_size=args.max_crop_size,
        random_h=args.random_h, random_s=args.random_s,
        random_l=args.random_l, **common)
    val = None
    if args.data_val:
        val = mx.io.ImageRecordIter(path_imgrec=args.data_val,
                                    shuffle=False, **common)
    return train, val


def main():
    parser = argparse.ArgumentParser(
        description='train an image classification model on ImageNet',
        formatter_class=argparse.ArgumentDefaultsHelpFormatter)
    parser.add_argument('--stem', default='classic',
                        choices=['classic', 'space_to_depth'],
                        help='ResNet stem variant: space_to_depth is the '
                             'MLPerf-style exact rewrite (TPU-faster; '
                             'models/resnet.py stem_weight_to_s2d maps '
                             'classic checkpoints)')
    parser.add_argument('--network', default='resnet-50',
                        help='any models.list_models() name')
    parser.add_argument('--num-classes', type=int, default=1000)
    parser.add_argument('--num-examples', type=int, default=1281167)
    parser.add_argument('--batch-size', type=int, default=256)
    parser.add_argument('--num-epochs', type=int, default=90)
    parser.add_argument('--lr', type=float, default=0.1)
    parser.add_argument('--lr-factor', type=float, default=0.1)
    parser.add_argument('--lr-step-epochs', default='30,60,80')
    parser.add_argument('--mom', type=float, default=0.9)
    parser.add_argument('--wd', type=float, default=1e-4)
    parser.add_argument('--kv-store', default='device')
    parser.add_argument('--model-prefix', default=None)
    parser.add_argument('--load-epoch', type=int, default=None)
    parser.add_argument('--auto-resume', type=int, default=0,
                        help='1: resume from the latest --model-prefix '
                             'checkpoint if one exists (crash recovery)')
    parser.add_argument('--dtype', default='float32',
                        choices=['float32', 'bfloat16'])
    parser.add_argument('--disp-batches', type=int, default=20)
    parser.add_argument('--benchmark', type=int, default=0,
                        help='1: train on synthetic data and report '
                             'imgs/sec (no IO cost)')
    parser.add_argument('--benchmark-batches', type=int, default=40)
    add_data_args(parser)
    args = parser.parse_args()
    logging.basicConfig(level=logging.INFO)
    # a ResNet-50 step takes about a minute to compile: keep it across
    # runs (JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache)
    logging.info('compile cache: %s',
                 mx.compile_cache.ensure_persistent_cache(
                     checkout_default=True))

    image_shape = tuple(int(v) for v in args.image_shape.split(','))
    kw = {'stem': args.stem,
          'image_shape': image_shape} \
        if args.network.startswith('resnet') else {}
    net = models.get_symbol(args.network, num_classes=args.num_classes,
                            **kw)

    if args.benchmark:
        train = SyntheticImageIter(args.batch_size, image_shape,
                                   args.num_classes,
                                   args.benchmark_batches)
        val = None
        epochs = 1
    else:
        assert args.data_train, '--data-train required (or --benchmark 1)'
        train, val = get_data(args, image_shape)
        epochs = args.num_epochs

    compute_dtype = None
    if args.dtype == 'bfloat16':
        import jax.numpy as jnp
        compute_dtype = jnp.bfloat16

    mod = mx.module.Module(net, context=mx.current_context(),
                           compute_dtype=compute_dtype)

    # lr schedule in steps of num_examples (reference fit.py _get_lr_scheduler)
    steps = [int(float(e) * args.num_examples / args.batch_size)
             for e in args.lr_step_epochs.split(',') if e]
    sched = mx.lr_scheduler.MultiFactorScheduler(steps, args.lr_factor) \
        if steps else None

    arg_params = aux_params = None
    begin_epoch = 0
    load_epoch = args.load_epoch
    if args.auto_resume and args.model_prefix and load_epoch is None:
        load_epoch = mx.model.find_latest_checkpoint(args.model_prefix)
        if load_epoch is not None:
            logging.info('auto-resuming from epoch %d', load_epoch)
    if args.model_prefix and load_epoch is not None:
        _, arg_params, aux_params = mx.model.load_checkpoint(
            args.model_prefix, load_epoch)
        begin_epoch = load_epoch

    times = []

    def bench_cb(param):
        from mxnet_tpu.engine import sync
        sync(mod._exec_group.execs[0].outputs)
        times.append(time.time())

    callbacks = [mx.callback.Speedometer(args.batch_size,
                                         args.disp_batches)]
    if args.benchmark:
        callbacks.append(bench_cb)
    epoch_cbs = []
    if args.model_prefix:
        epoch_cbs.append(mx.callback.do_checkpoint(args.model_prefix))

    mod.fit(train, eval_data=val,
            num_epoch=epochs, begin_epoch=begin_epoch,
            arg_params=arg_params, aux_params=aux_params,
            allow_missing=False,
            optimizer='sgd',
            optimizer_params={'learning_rate': args.lr,
                              'momentum': args.mom, 'wd': args.wd,
                              'lr_scheduler': sched,
                              'rescale_grad': 1.0 / args.batch_size},
            initializer=mx.init.Xavier(rnd_type='gaussian',
                                       factor_type='in', magnitude=2),
            kvstore=args.kv_store,
            batch_end_callback=callbacks,
            epoch_end_callback=epoch_cbs or None,
            eval_metric=['acc', 'ce'])

    if args.benchmark and len(times) > 8:
        warm = len(times) // 4
        tail = times[warm:]
        ips = args.batch_size * (len(tail) - 1) / (tail[-1] - tail[0])
        logging.info('benchmark: %.1f imgs/sec (batch %d, %s, %s)',
                     ips, args.batch_size, args.network, args.dtype)
        print('%.1f imgs/sec' % ips)


if __name__ == '__main__':
    main()
