"""Operator registry — the single source of truth for all ops.

TPU-native replacement for the reference's operator registration machinery
(``NNVM_REGISTER_OP`` / ``MXNET_REGISTER_OP_PROPERTY``; see
``src/operator/tensor/elemwise_unary_op.cc:20-78`` and
``include/mxnet/op_attr_types.h:31-59``).  Where the reference registers a
CPU and a CUDA ``FCompute`` per op, here each op registers ONE pure JAX
function — XLA compiles it for whatever backend the executor targets, so
the cpu/gpu instantiation split disappears.

Every op is an :class:`OpDef` with a canonical internal signature::

    apply(attrs, inputs, is_train, rng) -> (outputs, aux_updates)

- ``attrs``: dict of python-typed attributes (string forms are parsed once).
- ``inputs``: list of jax arrays — data inputs first, then parameters
  (weights), then auxiliary states (e.g. BatchNorm moving stats).
- ``outputs``: list of jax arrays, length ``num_outputs``.
- ``aux_updates``: dict aux-name -> new value (empty for stateless ops);
  gradients never flow through aux updates.

The imperative ``nd.*`` and symbolic ``sym.*`` namespaces are both
auto-generated from this registry, mirroring how the reference generates its
Python surface from the C op registry (``python/mxnet/ndarray.py``
``_init_ndarray_module`` / ``MXImperativeInvoke`` at
``src/c_api/c_api_ndarray.cc:19``).

Shape/type inference is done with ``jax.eval_shape`` over ``apply`` —
XLA's abstract evaluation replaces the reference's hand-written
``FInferShape``/``FInferType`` attributes.  Ops whose parameter shapes
depend on data shapes (FullyConnected, Convolution, ...) additionally
provide ``complete_shapes`` for the MXNet-style bidirectional inference
used by ``simple_bind`` (reference ``src/c_api/c_api_symbolic.cc:408``).
"""
from __future__ import annotations

import ast
import threading
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np
from jax.ad_checkpoint import checkpoint_name

__all__ = ['OpDef', 'register', 'register_simple', 'get_op', 'list_ops', 'alias',
           'keep', 'marks', 'KEEP']

# the one name under which an op marks a value that a mirror stage keeps for
# the backward pass instead of computing it again (executor._build_graph_fn)
KEEP = 'mxtpu.keep'
_marked = threading.local()


def keep(x):
    """``x``, marked for a mirror stage to keep: for a value cheap to keep
    and costly to compute again, such as a sort's order.  Outside a mirror
    stage the mark changes nothing."""
    _marked.count = marks() + 1
    return checkpoint_name(x, KEEP)


def marks():
    """How many values ``keep`` has marked on this thread, in all."""
    return getattr(_marked, 'count', 0)


_REGISTRY: Dict[str, 'OpDef'] = {}
_ALIASES: Dict[str, str] = {}


def parse_attr(value):
    """Parse a possibly-string attribute into a python value.

    Symbol JSON round-trips attrs as strings (the reference does the same
    through dmlc::Parameter); accept both forms everywhere.
    """
    if not isinstance(value, str):
        return value
    low = value.strip()
    if low in ('True', 'true'):
        return True
    if low in ('False', 'false'):
        return False
    if low == 'None':
        return None
    # NB: the literal string 'null' is a legal enum value in the
    # reference's params (e.g. SoftmaxOutput normalization='null') and
    # must NOT collapse to None, or JSON round-trips oscillate.
    try:
        return ast.literal_eval(low)
    except (ValueError, SyntaxError):
        return value


def parse_attrs(attrs: dict) -> dict:
    return {k: parse_attr(v) for k, v in attrs.items()}


class OpDef:
    """One registered operator."""

    def __init__(self, name, apply_fn, *,
                 input_names: Callable[[dict], List[str]],
                 num_outputs: Callable[[dict], int],
                 aux_names: Callable[[dict], List[str]] = lambda a: [],
                 complete_shapes: Optional[Callable] = None,
                 output_names: Optional[Callable[[dict], List[str]]] = None,
                 takes_rng: bool = False,
                 attr_defaults: Optional[dict] = None,
                 hint: Optional[str] = None,
                 input_var_attrs: Optional[Callable] = None,
                 arg_order: Optional[List[str]] = None,
                 aux_shape: Optional[Callable] = None,
                 dynamic_scalars: tuple = (),
                 keep_dtype: tuple = (),
                 aux_counters: Optional[Callable] = None,
                 doc: str = ''):
        self.name = name
        self.apply = apply_fn
        self.input_names = input_names
        self.num_outputs = num_outputs
        self.aux_names = aux_names
        self.complete_shapes = complete_shapes
        self.output_names = output_names or (
            lambda attrs: ['output'] if num_outputs(attrs) == 1
            else ['output%d' % i for i in range(num_outputs(attrs))])
        self.takes_rng = takes_rng
        # (attrs, input_name) -> dict of symbol attrs stamped on
        # auto-created input variables (the nnvm FSetInputVariableAttrs
        # analogue: how prelu's gamma advertises its 0.25 default init)
        self.input_var_attrs = input_var_attrs
        # (attrs, main_in_shapes) -> list of aux shapes, overriding the
        # infer fallback that assumes aux dims track input[0]'s channel
        # count (true for BatchNorm, wrong e.g. for the folded conv-bn
        # op whose aux sizes follow num_filter)
        self.aux_shape = aux_shape
        self.attr_defaults = attr_defaults or {}
        # positional-attr contract (reference nd.* signatures like
        # nd.clip(x, a_min, a_max)): trailing non-array positionals map
        # onto attrs in THIS order.  Defaults to attr_defaults
        # insertion order, which registrations declare to match the
        # reference signature — pass arg_order explicitly when the
        # two must differ.
        self.arg_order = list(arg_order) if arg_order is not None \
            else list(self.attr_defaults)
        self.hint = hint or name.lower().lstrip('_')
        # attr names whose FLOAT values the imperative layer passes as
        # traced jit arguments instead of static attrs — per-step
        # hyperparameters (Adam's bias-corrected lr, schedules) must
        # not recompile the update program every step (ndarray.py
        # imperative_invoke).  Only attrs used purely arithmetically in
        # apply() belong here (no Python control flow on the value).
        self.dynamic_scalars = tuple(dynamic_scalars)
        # input names whose variables a mixed-precision step must NOT cast
        # to the compute dtype (make_fit_step): Embedding's token ids
        # (bf16 rounds whole numbers above 256), SparseExperts' router
        # (its product is float32 by definition)
        self.keep_dtype = tuple(keep_dtype)
        # (now, before, attrs, in_shapes) -> None: writes instrument
        # counters from a node's auxiliary states (op-local name -> numpy
        # array) at this metric drain and at the one before (None at the
        # first), given the node's attributes and the shapes of its other
        # inputs; how an op's device-side counts reach the registry without
        # a sync of their own (instrument.add_device_source,
        # Module._aux_counter_source)
        self.aux_counters = aux_counters
        self.doc = doc

    def canon_attrs(self, attrs: dict) -> dict:
        out = dict(self.attr_defaults)
        out.update(parse_attrs(attrs))
        return out

    def __repr__(self):
        return 'OpDef(%s)' % self.name


def register(name, apply_fn, **kwargs):
    op = OpDef(name, apply_fn, **kwargs)
    if name in _REGISTRY:
        raise ValueError('duplicate op registration: %s' % name)
    _REGISTRY[name] = op
    return op


def register_simple(name, fn, *, ninputs=1, noutputs=1, input_names=None,
                    attr_defaults=None, takes_rng=False, hint=None,
                    arg_order=None, dynamic_scalars=(), doc=''):
    """Register a stateless op from a plain ``fn(*inputs, **attrs)``.

    This covers the reference's whole elemwise/broadcast/matrix tensor-op
    surface (``src/operator/tensor/``) with a one-line registration each.
    """
    if input_names is None:
        input_names = (['data'] if ninputs == 1 else
                       ['lhs', 'rhs'] if ninputs == 2 else
                       ['arg%d' % i for i in range(ninputs)])

    def apply_fn(attrs, inputs, is_train, rng):
        kw = dict(attrs)
        if takes_rng:
            kw['rng'] = rng
        out = fn(*inputs, **kw)
        outs = list(out) if isinstance(out, (tuple, list)) else [out]
        return outs, {}

    return register(
        name, apply_fn,
        input_names=lambda attrs, _n=tuple(input_names): list(_n),
        num_outputs=lambda attrs, _k=noutputs: _k,
        attr_defaults=attr_defaults, takes_rng=takes_rng, hint=hint,
        arg_order=arg_order, dynamic_scalars=dynamic_scalars, doc=doc)


def alias(new_name, existing):
    """Register ``new_name`` as an alias of an existing op."""
    _ALIASES[new_name] = existing


def get_op(name) -> OpDef:
    if name in _ALIASES:
        name = _ALIASES[name]
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError('operator %r is not registered '
                       '(have %d ops)' % (name, len(_REGISTRY))) from None


def list_ops() -> List[str]:
    return sorted(list(_REGISTRY) + list(_ALIASES))
