"""Device context.

TPU-native re-imagining of MXNet's ``Context`` (reference:
``python/mxnet/context.py:1-118``, ``include/mxnet/base.h`` Context struct).
A ``Context`` names a logical device: ``cpu(i)`` or ``tpu(i)`` (``gpu`` is
kept as an alias for ``tpu`` so reference-era scripts keep working).  Unlike
the reference — where a Context selects a CUDA device and stream — here it
resolves to a ``jax.Device``, and device placement is delegated to XLA via
``jax.device_put`` / sharding annotations.
"""
from __future__ import annotations

import threading

import jax

from .base import MXNetError


def _pinned_to_cpu():
    """Whether the process asked for the CPU backend and nothing else."""
    return (jax.config.jax_platforms or '') == 'cpu'


class Context:
    """A logical device, e.g. ``Context('tpu', 0)``.

    Also usable as a ``with`` target to set the thread-local default
    context, mirroring ``python/mxnet/context.py:60-76``.
    """

    devtype2str = {1: 'cpu', 2: 'tpu', 3: 'cpu_pinned'}
    devstr2type = {'cpu': 1, 'tpu': 2, 'gpu': 2, 'cpu_pinned': 3}
    _default_ctx = threading.local()

    def __init__(self, device_type, device_id=0):
        if isinstance(device_type, Context):
            self.device_typeid = device_type.device_typeid
            self.device_id = device_type.device_id
        else:
            self.device_typeid = Context.devstr2type[device_type]
            self.device_id = device_id
        self._old_ctx = None

    @property
    def device_type(self):
        return Context.devtype2str[self.device_typeid]

    def __eq__(self, other):
        return (isinstance(other, Context) and
                self.device_typeid == other.device_typeid and
                self.device_id == other.device_id)

    def __hash__(self):
        return hash((self.device_typeid, self.device_id))

    def __str__(self):
        return '%s(%d)' % (self.device_type, self.device_id)

    __repr__ = __str__

    def __enter__(self):
        self._old_ctx = getattr(Context._default_ctx, 'value', None)
        Context._default_ctx.value = self
        return self

    def __exit__(self, ptype, value, trace):
        Context._default_ctx.value = self._old_ctx

    # -- JAX resolution ----------------------------------------------------
    @property
    def jax_device(self) -> jax.Device:
        """Resolve to a concrete ``jax.Device``.

        ``tpu(i)`` is device ``i`` of JAX's default backend.  That backend
        is the CPU only when the process was pinned to it
        (``jax_platforms=cpu``, as the tests do with a virtual
        multi-device host); an unpinned process in which JAX found no
        accelerator raises instead of training on the host.  ``cpu(i)``
        is a host device; its id wraps, as the reference treats every
        CPU ordinal as the same host.
        """
        # local_devices, not devices: under jax.distributed the global
        # list includes other processes' devices, which are not
        # addressable from here (a Context always names a local device,
        # like the reference's per-process CUDA ordinals)
        if self.device_type == 'tpu':
            devs = jax.local_devices()
            if devs[0].platform == 'cpu' and not _pinned_to_cpu():
                raise MXNetError(
                    '%s requested but JAX found no accelerator (default '
                    "backend is 'cpu' and jax_platforms is not pinned to "
                    "'cpu')" % self)
            if not 0 <= self.device_id < len(devs):
                raise MXNetError('%s is out of range: %d %s device(s)'
                                 % (self, len(devs), devs[0].platform))
            return devs[self.device_id]
        try:
            devs = jax.local_devices(backend='cpu')
        except RuntimeError:
            # jax_platforms names the accelerator only: no host backend
            devs = jax.local_devices()
        return devs[self.device_id % len(devs)]


def cpu(device_id=0):
    """Return a CPU context."""
    return Context('cpu', device_id)


def tpu(device_id=0):
    """Return a TPU context."""
    return Context('tpu', device_id)


def gpu(device_id=0):
    """Alias of :func:`tpu` for source compatibility with reference scripts."""
    return Context('tpu', device_id)


def num_devices():
    """Number of addressable accelerator devices."""
    return len(jax.devices())


def current_context() -> Context:
    """The thread-local default context.  With none set it follows
    JAX's default backend: ``tpu(0)`` where an accelerator answers,
    ``cpu(0)`` in a process pinned to the CPU (docs/deviations.md)."""
    ctx = getattr(Context._default_ctx, 'value', None)
    if ctx is not None:
        return ctx
    return Context('cpu' if jax.default_backend() == 'cpu' else 'tpu', 0)
