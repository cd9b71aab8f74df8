"""NDArray — the imperative multi-device array.

TPU-native re-imagining of the reference NDArray
(``include/mxnet/ndarray.h:33-510``, ``src/ndarray/ndarray.cc``) and the
imperative op dispatch of ``MXImperativeInvoke``
(``src/c_api/c_api_ndarray.cc:19-``).

Design notes (what replaces what):

- The reference's dependency engine (``src/engine/threaded_engine*.cc``)
  serializes reads/writes on versioned variables so async CUDA work stays
  correct.  Here **XLA's async dispatch is the engine**: every jax.Array op
  is enqueued in-order per device and futures carry data dependencies, so
  write-after-read hazards cannot occur in the functional representation.
  ``wait_to_read`` maps to ``block_until_ready`` (engine ``WaitForVar``,
  ``include/mxnet/engine.h:141``); ``waitall`` to a barrier over live
  arrays (``WaitForAll``, ``engine.h:147``).
- In-place mutation (``+=``, ``x[:] = v``, ``kAddTo``) is a *handle-level*
  illusion: the handle swaps in a fresh functional value.  That preserves
  the reference's observable semantics (every reader sees a consistent
  version) with no aliasing machinery.
- Each op invocation jit-compiles once per (op, attrs, input-shapes) and is
  cached — the analogue of the engine reusing cached operators
  (``graph_executor.cc:537 InitCachedOps``), but done by XLA's jit cache.
"""
from __future__ import annotations

import struct
from typing import Any, Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from . import instrument
from . import perfwatch
from .base import MXNetError, resolve_dtype
from .context import Context, cpu, current_context
from .ops import registry as _reg
from .ops import get_op, list_ops

__all__ = ['NDArray', 'array', 'zeros', 'ones', 'full', 'empty', 'arange',
           'concatenate', 'load', 'save', 'validate', 'imperative_invoke',
           'waitall',
           'onehot_encode']

_live_arrays: Dict[int, Any] = {}


class _RandomState:
    """Process-global PRNG for imperative sampling ops.

    Functional replacement for the per-device ``mshadow::Random`` resource
    (``src/resource.cc:144``); ``mx.random.seed`` resets it.

    The key materializes LAZILY: building a PRNGKey initializes the JAX
    backend, and ``import mxnet_tpu`` must leave that to the first use,
    after the caller has had a chance to pin a platform.
    """

    def __init__(self, seed=0):
        self._seed = seed
        self._key = None

    @property
    def key(self):
        if self._key is None:
            self._key = jax.random.PRNGKey(self._seed)
        return self._key

    def next_key(self):
        self._key, sub = jax.random.split(self.key)
        return sub

    def seed(self, seed):
        self._key = jax.random.PRNGKey(seed)


RANDOM = _RandomState()


class NDArray:
    """Handle to an immutable on-device array with mutable-handle semantics."""

    __slots__ = ('_data', '_ctx', '_writable')
    # Make NumPy defer binary ops (np_scalar * NDArray) to our reflected ops.
    __array_priority__ = 100.0

    def __init__(self, data, ctx: Optional[Context] = None, writable=True):
        self._data = data
        self._ctx = ctx if ctx is not None else current_context()
        self._writable = writable

    # -- properties --------------------------------------------------------
    @property
    def shape(self):
        return tuple(self._data.shape)

    @property
    def dtype(self):
        return np.dtype(self._data.dtype) if self._data.dtype != jnp.bfloat16 \
            else jnp.bfloat16

    @property
    def size(self):
        return int(np.prod(self.shape)) if self.shape else 1

    @property
    def ndim(self):
        return self._data.ndim

    @property
    def context(self) -> Context:
        return self._ctx

    ctx = context

    @property
    def T(self):
        return NDArray(self._data.T, self._ctx)

    @property
    def handle(self):
        """The underlying jax.Array (the 'chunk' of ndarray.h:56)."""
        return self._data

    # -- engine sync points ------------------------------------------------
    def wait_to_read(self):
        from .engine import sync
        sync(self._data)
        return self

    wait_to_write = wait_to_read

    def asnumpy(self) -> np.ndarray:
        # a writable host copy, matching the reference's SyncCopyToCPU
        if instrument.metrics_enabled():
            instrument.inc('transfer.d2h_bytes',
                           self.size * np.dtype(self.dtype).itemsize)
        return np.array(self._data)

    def asscalar(self):
        if self.size != 1:
            raise ValueError('The current array is not a scalar')
        return self.asnumpy().reshape(())[()]

    def __array__(self, dtype=None):
        a = self.asnumpy()
        return a.astype(dtype) if dtype is not None else a

    # -- conversion / movement ---------------------------------------------
    def astype(self, dtype):
        dt = resolve_dtype(dtype)
        return NDArray(self._data.astype(dt), self._ctx)

    def copyto(self, other):
        """Copy to another NDArray (writes through the handle) or Context."""
        if isinstance(other, NDArray):
            if other is self:
                raise MXNetError('copy an array to itself, is it intended?')
            # preserve the destination's sharding (a write into a
            # mesh-replicated/sharded array stays so placed)
            try:
                target = other._data.sharding
            except AttributeError:
                target = other.context.jax_device
            other._set_data(jax.device_put(jnp.asarray(self._data),
                                           target))
            return other
        if isinstance(other, Context):
            return NDArray(jax.device_put(self._data, other.jax_device), other)
        raise TypeError('copyto does not support type ' + str(type(other)))

    def as_in_context(self, context: Context):
        if context == self._ctx:
            return self
        return self.copyto(context)

    def copy(self):
        return NDArray(jnp.array(self._data), self._ctx)

    # -- mutation through the handle ---------------------------------------
    def _set_data(self, new_data):
        if not self._writable:
            raise MXNetError('trying to write to a read-only NDArray')
        self._data = new_data

    def __setitem__(self, key, value):
        if isinstance(value, NDArray):
            value = value._data
        # NB: builtins.slice — the module-level name `slice` is the op
        # installed by _install_ops.
        import builtins
        if key == builtins.slice(None) or key is Ellipsis:
            if np.isscalar(value):
                self._set_data(jnp.full(self.shape, value, self._data.dtype))
            else:
                value = jnp.asarray(value, self._data.dtype)
                self._set_data(jnp.broadcast_to(value, self.shape))
            return
        self._set_data(self._data.at[key].set(value))

    def __getitem__(self, key):
        if isinstance(key, NDArray):
            key = key._data
        out = self._data[key]
        return NDArray(out, self._ctx)

    def slice(self, start, stop):
        return NDArray(self._data[start:stop], self._ctx)

    def reshape(self, shape):
        return NDArray(jnp.reshape(self._data, tuple(shape)), self._ctx)

    def broadcast_to(self, shape):
        return NDArray(jnp.broadcast_to(self._data, tuple(shape)), self._ctx)

    # -- arithmetic --------------------------------------------------------
    def _binary(self, other, fn):
        if isinstance(other, NDArray):
            other = other._data
        return NDArray(fn(self._data, other), self._ctx)

    def __add__(self, o): return self._binary(o, jnp.add)
    __radd__ = __add__
    def __sub__(self, o): return self._binary(o, jnp.subtract)
    def __rsub__(self, o): return self._binary(o, lambda a, b: b - a)
    def __mul__(self, o): return self._binary(o, jnp.multiply)
    __rmul__ = __mul__
    def __truediv__(self, o): return self._binary(o, jnp.divide)
    def __rtruediv__(self, o): return self._binary(o, lambda a, b: b / a)
    __div__ = __truediv__
    __rdiv__ = __rtruediv__
    def __mod__(self, o): return self._binary(o, jnp.mod)
    def __pow__(self, o): return self._binary(o, jnp.power)
    def __neg__(self): return NDArray(-self._data, self._ctx)
    def __abs__(self): return NDArray(jnp.abs(self._data), self._ctx)

    def __iadd__(self, o):
        self._set_data((self + o)._data)
        return self

    def __isub__(self, o):
        self._set_data((self - o)._data)
        return self

    def __imul__(self, o):
        self._set_data((self * o)._data)
        return self

    def __itruediv__(self, o):
        self._set_data((self / o)._data)
        return self

    def __eq__(self, o): return self._binary(o, lambda a, b: (a == b).astype(a.dtype)) if isinstance(o, (NDArray, np.ndarray, int, float)) else NotImplemented
    def __ne__(self, o): return self._binary(o, lambda a, b: (a != b).astype(a.dtype)) if isinstance(o, (NDArray, np.ndarray, int, float)) else NotImplemented
    def __gt__(self, o): return self._binary(o, lambda a, b: (a > b).astype(a.dtype))
    def __ge__(self, o): return self._binary(o, lambda a, b: (a >= b).astype(a.dtype))
    def __lt__(self, o): return self._binary(o, lambda a, b: (a < b).astype(a.dtype))
    def __le__(self, o): return self._binary(o, lambda a, b: (a <= b).astype(a.dtype))

    def __hash__(self):
        return id(self)

    def __len__(self):
        return self.shape[0]

    def __repr__(self):
        return '<NDArray %s @%s>' % ('x'.join(str(s) for s in self.shape),
                                     self._ctx)

    def __getstate__(self):
        return {'data': self.asnumpy(), 'ctx_type': self._ctx.device_type,
                'ctx_id': self._ctx.device_id}

    def __setstate__(self, state):
        ctx = Context(state['ctx_type'], state['ctx_id'])
        object.__setattr__(self, '_ctx', ctx)
        object.__setattr__(self, '_writable', True)
        object.__setattr__(self, '_data',
                           jax.device_put(state['data'], ctx.jax_device))


class ZerosWhenRead(NDArray):
    """An NDArray of zeros that reaches the device when it is first read.
    A training executor is bound with an array for every parameter's
    gradient; ``Module``'s fused step keeps its gradients inside the
    program and never reads or writes those arrays, which for a model of
    600M parameters are 2.4e9 B of zeros on the chip.  Written to
    (``_set_data``, what ``Executor.backward`` does) it is an ordinary
    NDArray from then on."""

    __slots__ = ('_made', '_make')

    def __init__(self, make, ctx=None):
        self._make, self._made = make, None
        NDArray.__init__(self, None, ctx)

    @property
    def _data(self):
        if self._made is None:
            self._made = self._make()
        return self._made

    @_data.setter
    def _data(self, value):
        self._made = value



def waitall():
    """Block until all queued device work completes (engine WaitForAll)."""
    jax.effects_barrier()
    # jax has no global queue handle; device streams are in-order, so
    # forcing a fresh no-op through engine.sync drains the default device.
    from .engine import sync
    sync(None)


# ---------------------------------------------------------------------------
# Creation
# ---------------------------------------------------------------------------

def _put(values, ctx: Optional[Context]):
    ctx = ctx if ctx is not None else current_context()
    # only genuine host arrays cross the boundary here; jnp inputs
    # (zeros/ones/op results) are device allocations, not transfers
    if instrument.metrics_enabled() and isinstance(values, np.ndarray):
        instrument.inc('transfer.h2d_bytes', int(values.nbytes))
    placed = jax.device_put(values, ctx.jax_device)
    if perfwatch.enabled():
        perfwatch.ledger_alloc('nd.array', placed)
    return NDArray(placed, ctx)


def array(source_array, ctx=None, dtype=None):
    """Default dtype is float32, like the reference (ndarray.py mx_real_t).

    Examples
    --------
    >>> a = array([[1, 2], [3, 4]])
    >>> a.shape
    (2, 2)
    >>> str(a.dtype)
    'float32'
    >>> (a * 2 + 1).asnumpy().tolist()
    [[3.0, 5.0], [7.0, 9.0]]
    >>> a[1].asnumpy().tolist()
    [3.0, 4.0]
    """
    if isinstance(source_array, NDArray):
        source_array = source_array.asnumpy()
    if dtype is None:
        src_dtype = getattr(source_array, 'dtype', None)
        dtype = src_dtype if src_dtype is not None and \
            np.dtype(src_dtype) != np.float64 else np.float32
    arr = np.asarray(source_array, dtype=resolve_dtype(dtype))
    return _put(arr, ctx)


def _shape_tuple(shape):
    return (shape,) if isinstance(shape, (int, np.integer)) else tuple(shape)


def zeros(shape, ctx=None, dtype=None):
    return _put(jnp.zeros(_shape_tuple(shape), resolve_dtype(dtype)), ctx)


def ones(shape, ctx=None, dtype=None):
    return _put(jnp.ones(_shape_tuple(shape), resolve_dtype(dtype)), ctx)


def full(shape, val, ctx=None, dtype=None):
    return _put(jnp.full(_shape_tuple(shape), val, resolve_dtype(dtype)), ctx)


def empty(shape, ctx=None, dtype=None):
    return zeros(shape, ctx, dtype)


def arange(start, stop=None, step=1.0, repeat=1, ctx=None, dtype=None):
    a = jnp.arange(start, stop, step, resolve_dtype(dtype))
    if repeat != 1:
        a = jnp.repeat(a, int(repeat))
    return _put(a, ctx)


def concatenate(arrays, axis=0, always_copy=True):
    if not always_copy and len(arrays) == 1:
        return arrays[0]
    return NDArray(jnp.concatenate([a._data for a in arrays], axis=axis),
                   arrays[0].context)


def onehot_encode(indices, out):
    """Legacy one-hot (ndarray.cc _onehot_encode)."""
    depth = out.shape[1]
    out._set_data(jax.nn.one_hot(indices._data.astype(jnp.int32), depth,
                                 dtype=out._data.dtype))
    return out


# ---------------------------------------------------------------------------
# Serialization — mirrors MXNDArraySave/Load (c_api.cc:211-263); format is
# a self-describing binary container (not the reference's byte layout).
# ---------------------------------------------------------------------------

_MAGIC = b'MXTPU001'


def save(fname, data):
    """Save a list or str->NDArray dict (reference ndarray.cc:593-680)."""
    if isinstance(data, NDArray):
        data = [data]
    if isinstance(data, dict):
        keys = list(data.keys())
        arrays = [data[k] for k in keys]
    else:
        keys = []
        arrays = list(data)
    from . import fs
    with fs.open_uri(fname, 'wb') as f:
        f.write(_MAGIC)
        f.write(struct.pack('<q', len(arrays)))
        f.write(struct.pack('<q', len(keys)))
        for k in keys:
            kb = k.encode()
            f.write(struct.pack('<q', len(kb)))
            f.write(kb)
        for a in arrays:
            npa = a.asnumpy() if isinstance(a, NDArray) else np.asarray(a)
            dt = npa.dtype.str.encode()
            f.write(struct.pack('<q', len(dt)))
            f.write(dt)
            f.write(struct.pack('<q', npa.ndim))
            for s in npa.shape:
                f.write(struct.pack('<q', s))
            buf = npa.tobytes()
            f.write(struct.pack('<q', len(buf)))
            f.write(buf)


def validate(fname):
    """Structural validity check of a saved NDArray container WITHOUT
    materializing the arrays: walks the headers, seeks over payloads and
    verifies every byte the headers promise is present (a truncated or
    torn file — e.g. a checkpoint interrupted by ``kill -9`` before
    atomic commits existed — fails).  Returns True/False, never raises.
    Remote URIs fall back to a full :func:`load` attempt."""
    from . import fs
    if fs.is_remote(fname):
        try:
            load(fname)
            return True
        except Exception:
            return False
    try:
        with fs.open_uri(fname, 'rb') as f:
            if f.read(len(_MAGIC)) != _MAGIC:
                return False
            n_arrays, = struct.unpack('<q', f.read(8))
            n_keys, = struct.unpack('<q', f.read(8))
            if not (0 <= n_arrays < 1 << 32 and 0 <= n_keys < 1 << 32):
                return False
            if n_keys and n_keys != n_arrays:
                return False
            for _ in range(n_keys):
                klen, = struct.unpack('<q', f.read(8))
                if not 0 <= klen < 1 << 20:
                    return False
                if len(f.read(klen)) != klen:
                    return False
            for _ in range(n_arrays):
                dtlen, = struct.unpack('<q', f.read(8))
                if not 0 < dtlen < 64:
                    return False
                dt = np.dtype(f.read(dtlen).decode())
                ndim, = struct.unpack('<q', f.read(8))
                if not 0 <= ndim < 64:
                    return False
                shape = tuple(struct.unpack('<q', f.read(8))[0]
                              for _ in range(ndim))
                blen, = struct.unpack('<q', f.read(8))
                expect = int(np.prod(shape, dtype=np.int64)) * dt.itemsize
                if blen != expect or blen < 0:
                    return False
                if blen:        # payload really present, not truncated
                    f.seek(blen - 1, 1)
                    if len(f.read(1)) != 1:
                        return False
            return True
    except Exception:
        return False


def load(fname):
    from . import fs
    with fs.open_uri(fname, 'rb') as f:
        magic = f.read(len(_MAGIC))
        if magic != _MAGIC:
            raise MXNetError('invalid NDArray file format: ' + fname)
        n_arrays, = struct.unpack('<q', f.read(8))
        n_keys, = struct.unpack('<q', f.read(8))
        keys = []
        for _ in range(n_keys):
            klen, = struct.unpack('<q', f.read(8))
            keys.append(f.read(klen).decode())
        arrays = []
        for _ in range(n_arrays):
            dtlen, = struct.unpack('<q', f.read(8))
            dt = np.dtype(f.read(dtlen).decode())
            ndim, = struct.unpack('<q', f.read(8))
            shape = tuple(struct.unpack('<q', f.read(8))[0]
                          for _ in range(ndim))
            blen, = struct.unpack('<q', f.read(8))
            arrays.append(array(np.frombuffer(f.read(blen),
                                              dtype=dt).reshape(shape)))
    if keys:
        return dict(zip(keys, arrays))
    return arrays


# ---------------------------------------------------------------------------
# Imperative op dispatch (MXImperativeInvoke analogue).  One jitted callable
# per (op, attrs, is_train) — XLA's jit cache keyed on input avals replaces
# per-shape engine op reuse.
#
# The cache is a size-capped LRU: scalar-attr churn (e.g. a clip bound
# computed per step, arange lengths) would otherwise grow it — and the
# XLA executables each entry pins — without limit over a long process.
# Evictions are counted as ``imperative.cache_evictions``; a high rate
# means some attr should be a dynamic_scalar instead (see below).
# ---------------------------------------------------------------------------

from collections import OrderedDict

_JIT_CACHE_CAP = 1024
_jit_cache: 'OrderedDict[Any, Any]' = OrderedDict()


def _freeze(v):
    if isinstance(v, (list, tuple)):
        return tuple(_freeze(x) for x in v)
    if isinstance(v, dict):
        return tuple(sorted((k, _freeze(x)) for k, x in v.items()))
    return v


def imperative_invoke(op_name: str, *args, out=None, name=None, **kwargs):
    op = get_op(op_name)
    # reference nd.* signatures take attrs positionally after the arrays
    # (e.g. nd.clip(x, a_min, a_max)): trailing non-NDArray positionals
    # map onto the op's declared attrs in registration order
    if args and not isinstance(args[-1], NDArray) and \
            'num_args' not in op.attr_defaults:
        n_arr = len(args)
        while n_arr and not isinstance(args[n_arr - 1], NDArray):
            n_arr -= 1
        extra = args[n_arr:]
        args = args[:n_arr]
        free_attrs = [k for k in op.arg_order if k not in kwargs]
        if len(extra) > len(free_attrs):
            raise MXNetError('too many positional args for op %s'
                             % op_name)
        kwargs.update(zip(free_attrs, extra))
    # split NDArray kwargs (named inputs) from attrs
    attrs = {}
    named_inputs = {}
    for k, v in kwargs.items():
        if isinstance(v, NDArray):
            named_inputs[k] = v
        elif k not in ('ctx',) or v is None:
            attrs[k] = v
        else:
            attrs[k] = str(v)
    cattrs = op.canon_attrs({k: v for k, v in attrs.items() if v is not None})
    if 'num_args' in op.attr_defaults and args:
        cattrs['num_args'] = len(args)
    in_names = op.input_names(cattrs) + op.aux_names(cattrs)
    inputs: List[NDArray] = list(args)
    if named_inputs:
        pos = {n: i for i, n in enumerate(in_names)}
        merged: List[Optional[NDArray]] = list(inputs) + \
            [None] * (len(in_names) - len(inputs))
        for k, v in named_inputs.items():
            if k not in pos:
                raise MXNetError('unknown input %r for op %s' % (k, op_name))
            merged[pos[k]] = v
        inputs = [m for m in merged if m is not None]
    # per-step float hyperparameters (op.dynamic_scalars, e.g. Adam's
    # bias-corrected lr) become TRACED jit arguments, not static attrs:
    # keying the compile cache on a value that changes every step would
    # compile a fresh XLA program per update (observed: thousands of
    # compiles, compiler OOM/segfault, in any unfused Adam/schedule loop)
    dyn_names = tuple(k for k in op.dynamic_scalars
                      if isinstance(cattrs.get(k), (int, float)))
    static_attrs = {k: v for k, v in cattrs.items()
                    if k not in dyn_names}
    dyn_vals = tuple(float(cattrs[k]) for k in dyn_names)
    key = (op.name, _freeze(static_attrs), dyn_names, len(inputs))
    fn = _jit_cache.get(key)
    if fn is None:
        # imperative-path cache efficiency, visible in the compile.*
        # namespace alongside imperative.cache_evictions: a high miss
        # rate means per-step attr churn is defeating the LRU
        instrument.inc('compile.imperative_cache_misses')

        def run(input_arrays, dvals, rng, _static=static_attrs,
                _dnames=dyn_names):
            attrs_full = dict(_static)
            attrs_full.update(zip(_dnames, dvals))
            outs, aux = op.apply(attrs_full, list(input_arrays), True,
                                 rng)
            return outs
        fn = jax.jit(run)
        _jit_cache[key] = fn
        while len(_jit_cache) > _JIT_CACHE_CAP:
            try:
                _jit_cache.popitem(last=False)
            except KeyError:        # concurrently emptied
                break
            instrument.inc('imperative.cache_evictions')
    else:
        instrument.inc('compile.imperative_cache_hits')
        # each OrderedDict op is GIL-atomic, but get→move_to_end is
        # not one op: a producer thread (PrefetchingIter/DeviceFeedIter
        # workers run imperative ops) may evict this key in between
        try:
            _jit_cache.move_to_end(key)
        except KeyError:
            _jit_cache[key] = fn
    rng = RANDOM.next_key() if op.takes_rng else RANDOM.key
    ctx = inputs[0].context if inputs else \
        (Context(cattrs['ctx']) if isinstance(cattrs.get('ctx'), Context)
         else current_context())
    raw = fn([a._data for a in inputs], dyn_vals, rng)
    outs = [NDArray(r, ctx) for r in raw]
    if out is not None:
        out_list = out if isinstance(out, (list, tuple)) else [out]
        for dst, src in zip(out_list, outs):
            dst._set_data(src._data)
        return out
    if len(outs) == 1:
        return outs[0]
    return outs


class _OpModule:
    """Namespace exposing every registered op as a function (mx.nd.*)."""

    def __getattr__(self, name):
        if name.startswith('__'):
            raise AttributeError(name)
        try:
            get_op(name)
        except KeyError:
            raise AttributeError('no operator %r' % name) from None

        def invoke(*args, **kwargs):
            args = [a if isinstance(a, NDArray) else a for a in args]
            return imperative_invoke(name, *args, **kwargs)

        invoke.__name__ = name
        setattr(self, name, invoke)
        return invoke


def _install_ops(namespace):
    """Expose registered ops as module-level functions, like the reference's
    auto-generated ``mxnet.ndarray`` module (``_init_ndarray_module``)."""
    for opname in list_ops():
        public = opname
        if public.startswith('_') and not public.startswith('_random'):
            continue
        if public in namespace:
            continue

        def make(op_name):
            def invoke(*args, **kwargs):
                return imperative_invoke(op_name, *args, **kwargs)
            invoke.__name__ = op_name
            invoke.__qualname__ = op_name
            invoke.__doc__ = get_op(op_name).doc
            return invoke

        namespace[public] = make(opname)


_install_ops(globals())


def _scalar_or_broadcast(lhs, rhs, broadcast_op, scalar_op,
                         rscalar_op=None):
    """Reference python-level binary helpers (ndarray.py maximum/
    minimum/power): dispatch on scalar-ness, broadcast otherwise."""
    if isinstance(lhs, NDArray) and isinstance(rhs, NDArray):
        return imperative_invoke(broadcast_op, lhs, rhs)
    if isinstance(lhs, NDArray):
        return imperative_invoke(scalar_op, lhs, scalar=float(rhs))
    if isinstance(rhs, NDArray):
        return imperative_invoke(rscalar_op or scalar_op, rhs,
                                 scalar=float(lhs))
    # both plain scalars: plain-number result (reference _ufunc_helper).
    # NB builtins: module-level `max`/`min`/`pow` are installed ops.
    import builtins
    fn = {'broadcast_maximum': builtins.max,
          'broadcast_minimum': builtins.min,
          'broadcast_power': builtins.pow}[broadcast_op]
    return fn(lhs, rhs)


def maximum(lhs, rhs):
    """Element-wise broadcasting maximum (reference ndarray.py:1315)."""
    return _scalar_or_broadcast(lhs, rhs, 'broadcast_maximum',
                                '_maximum_scalar')


def minimum(lhs, rhs):
    """Element-wise broadcasting minimum (reference ndarray.py:1358)."""
    return _scalar_or_broadcast(lhs, rhs, 'broadcast_minimum',
                                '_minimum_scalar')


def power(base, exp):
    """Element-wise broadcasting power (reference ndarray.py:1272)."""
    return _scalar_or_broadcast(base, exp, 'broadcast_power',
                                '_power_scalar', '_rpower_scalar')


def __getattr__(name):
    """Resolve ops registered after import (e.g. Custom, user ops)."""
    try:
        get_op(name)
    except KeyError:
        raise AttributeError('module %r has no attribute %r'
                             % (__name__, name)) from None

    def invoke(*args, **kwargs):
        return imperative_invoke(name, *args, **kwargs)

    invoke.__name__ = name
    globals()[name] = invoke
    return invoke
