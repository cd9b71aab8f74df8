"""Operator registry and op families.

Importing this package registers every operator — the analogue of the
reference's static registration at library load
(``MXNET_REGISTER_OP_PROPERTY`` / ``NNVM_REGISTER_OP`` macro sites,
184 across ``src/operator``).
"""
from .registry import get_op, list_ops, register, register_simple, alias, OpDef
from . import tensor  # noqa: F401
from . import nn  # noqa: F401
from . import lm  # noqa: F401
from . import optim  # noqa: F401
from . import rnn_op  # noqa: F401
from . import vision  # noqa: F401
from . import multibox  # noqa: F401
from . import ctc  # noqa: F401
from . import pallas_fused  # noqa: F401

__all__ = ['get_op', 'list_ops', 'register', 'register_simple', 'alias',
           'OpDef']
