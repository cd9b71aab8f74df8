"""mxnet_tpu — a TPU-native deep-learning framework.

A from-scratch re-design of the capabilities of MXNet v0.9.3
(reference: ap-hynninen/mxnet) on the JAX/XLA/Pallas stack:

- imperative ``nd.*`` arrays + symbolic ``sym.*`` graphs that mix freely
  (the reference's headline feature, README.md:11-14);
- ``Executor``/``Module``/``FeedForward`` training APIs with the same
  surface as ``python/mxnet``;
- data-parallel + model-parallel training via ``jax.sharding`` meshes and
  XLA collectives in place of kvstore device-comm / ps-lite;
- XLA compilation in place of the threaded dependency engine + memory
  planner; Pallas kernels in place of hand-written CUDA.
"""
from . import base
from .base import MXNetError, AttrScope
from . import context
from .context import Context, cpu, gpu, tpu, current_context
from . import ndarray
from . import ndarray as nd
from . import symbol
from . import symbol as sym
from . import executor
from .executor import Executor
from . import random
from . import initializer
from . import initializer as init
from . import optimizer
from . import optimizer as opt
from . import metric
from . import lr_scheduler
from . import io
from . import kvstore as kv
from . import kvstore
from . import callback
from . import monitor
from . import instrument
from . import compile_cache
from . import resilience
from . import health
from . import elastic
from . import detector
from . import chronicle
from . import perfwatch
from . import commwatch
from . import profiler
from . import engine
from . import module
from . import module as mod
from . import model
from .model import FeedForward
from . import visualization
from . import visualization as viz
from . import rnn
from . import operator
from . import recordio
from . import rtc
from . import predictor
from . import serving
from . import test_utils
from .executor_manager import DataParallelExecutorManager
from . import config
from . import image
from . import kvstore_server
from . import torch_bridge as torch
from . import caffe
# attribute/name module aliases (reference python/mxnet/{attribute,name}.py)
from . import base as attribute
from . import base as name

# install the persistent compilation cache + warmup manifest when
# JAX_COMPILATION_CACHE_DIR or MXTPU_COMPILE_CACHE names a directory
# (must precede the first XLA compile; two env reads otherwise —
# docs/performance.md warm start)
compile_cache.ensure_persistent_cache()

# install the crash flight recorder when MXTPU_FLIGHT_RECORDER is set
# (atexit/SIGTERM/SIGABRT/injected-kill postmortem dumps — a no-op
# single env read otherwise; docs/observability.md health plane)
health.install_flight_recorder()

# honor the reference's import-time env knobs (docs/how_to/env_var.md)
if config.get('MXNET_ENGINE_TYPE') != 'ThreadedEnginePerDevice':
    engine.set_engine_type(config.get('MXNET_ENGINE_TYPE'))
if config.get('MXNET_PROFILER_AUTOSTART'):
    import atexit as _atexit
    profiler.profiler_set_state('run')
    _atexit.register(lambda: (profiler.profiler_set_state('stop'),
                              profiler.dump_profile()))

__version__ = '0.1.0'
