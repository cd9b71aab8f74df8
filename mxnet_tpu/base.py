"""Shared plumbing: errors, name scoping, attr scoping, dtype maps.

Replaces the reference's ctypes/base layer (``python/mxnet/base.py``,
``python/mxnet/name.py``, ``python/mxnet/attribute.py``).  There is no C ABI
to cross for graph construction here — the graph layer is in-process — so
this module only carries the pure-Python utilities those files provided.
"""
from __future__ import annotations

import threading

import numpy as np

__all__ = ['MXNetError', 'NameManager', 'Prefix', 'AttrScope', 'string_types']

string_types = (str,)


class MXNetError(Exception):
    """Error raised by the framework (reference ``base.py:MXNetError``)."""


class _ScopedSingleton:
    _tls = None  # subclass provides its own threading.local()

    @classmethod
    def current(cls):
        cur = getattr(cls._tls, 'value', None)
        if cur is None:
            cur = cls()
            cls._tls.value = cur
        return cur

    def __enter__(self):
        self._old = getattr(type(self)._tls, 'value', None)
        type(self)._tls.value = self
        return self

    def __exit__(self, ptype, value, trace):
        type(self)._tls.value = self._old


class NameManager(_ScopedSingleton):
    """Automatic symbol naming, mirroring ``python/mxnet/name.py:10-70``."""

    _tls = threading.local()

    def __init__(self):
        self._counter = {}

    def get(self, name, hint):
        if name:
            return name
        if hint not in self._counter:
            self._counter[hint] = 0
        name = '%s%d' % (hint, self._counter[hint])
        self._counter[hint] += 1
        return name


class Prefix(NameManager):
    """NameManager that prepends a prefix (``python/mxnet/name.py:73-88``)."""

    def __init__(self, prefix):
        super().__init__()
        self._prefix = prefix

    def get(self, name, hint):
        name = super().get(name, hint)
        return self._prefix + name


class AttrScope(_ScopedSingleton):
    """Scoped symbol attributes (``python/mxnet/attribute.py:9-60``).

    Used e.g. for model-parallel context groups::

        with AttrScope(ctx_group='dev1'):
            net = sym.FullyConnected(net, num_hidden=128)
    """

    _tls = threading.local()

    def __init__(self, **kwargs):
        self._attr = {str(k): str(v) for k, v in kwargs.items()}

    def __enter__(self):
        # nested scopes inherit the enclosing scope's attributes
        # (reference attribute.py:44-52 merges on entry)
        ret = super().__enter__()
        if self._old is not None:
            merged = dict(self._old._attr)
            merged.update(self._attr)
            self._attr = merged
        return ret

    def get(self, attr):
        merged = dict(self._attr)
        if attr:
            merged.update(attr)
        return merged


_DTYPE_ALIASES = {
    'float32': np.float32, 'float64': np.float64, 'float16': np.float16,
    'bfloat16': 'bfloat16', 'uint8': np.uint8, 'int8': np.int8,
    'int32': np.int32, 'int64': np.int64, 'bool': np.bool_,
}


def resolve_dtype(dtype):
    """Normalize a dtype spec (string/np dtype/jnp dtype) to a numpy-style dtype."""
    import jax.numpy as jnp
    if dtype is None:
        return np.float32
    if isinstance(dtype, str):
        if dtype == 'bfloat16':
            return jnp.bfloat16
        return np.dtype(dtype).type
    return dtype


def force_cpu_backend():
    """Pin JAX to the CPU backend — for host-side tools (im2rec,
    generators) that must not claim the accelerator.  Must run before
    the first device use; safe after `import jax`."""
    import jax
    jax.config.update('jax_platforms', 'cpu')
