"""Model FLOPs of one training step of ``models/kimi_linear.py``'s language
model, counted from the symbol's shapes; what the configuration
``kimi_linear_48b_a3b`` pins; and the operations and bytes of its two
kernels, the chunked delta rule and latent attention, for their shares of
the roofline.  ``flops_lm.py``'s rules (2 FLOPs a multiply-add, matrix
products only, the backward pass twice the forward, nothing for
recomputation; ``FullyConnected`` out x in; ``SparseExperts`` the router and
three products an assignment on a held expert, pinned at uniform routing)
and beside them, per token of a sequence of T tokens:

- ``FlashAttention``, causal, keys of d_k and values of d_v a head: a token
  attends to T / 2 keys on average: heads x T / 2 x (d_k + d_v).
- ``KimiDeltaAttention``: the three convolutions' taps a channel, and the
  gated delta rule as its chunked form needs it at the chunk size C the
  node states, whatever implements it (``kda_scan_macs``): per head the
  chunk's two triangles of pairwise products (C / 2 x d_k each), the
  triangular solve applied to keys and values (C / 2 x (d_k + d_v)), the
  outputs from inside the chunk (C / 2 x d_v), and three products with the
  carried state (3 x d_k x d_v).  Token by token the rule needs the last
  term alone; the chunks' share is what buys matrix products.
"""
import math

from . import flops_lm
from .flops_lm import train_step_flops  # noqa: F401  (the driver's)


def kda_scan_macs(heads, d_k, d_v, chunk):
    """Multiply-adds of one token's gated delta rule in chunks of ``chunk``
    tokens, all heads, forward."""
    return heads * (chunk // 2 * (3 * d_k + 2 * d_v) + 3 * d_k * d_v)


def _kda(node, produced):
    """``(heads, length, d_k, d_v, chunk, channels, taps)`` of a
    ``KimiDeltaAttention`` node."""
    _, length, channels = produced(node['inputs'][0])
    heads = flops_lm._attr(node, 'num_heads')
    size = channels // heads
    chunk = min(int(node.get('attrs', {}).get('chunk_size', 64)), length)
    return heads, length, size, size, chunk, channels, \
        produced(node['inputs'][3])[1]


def forward_macs_per_token(symbol, input_shapes):
    """``(dense, expert, rows)`` as ``flops_lm.forward_macs_per_token``
    gives them, for a symbol that may hold ``KimiDeltaAttention`` and an
    attention whose values are narrower than its keys."""
    nodes, produced = flops_lm._graph(symbol, input_shapes)
    rows, per_assignment = [], 0
    for node in nodes:
        op = node['op']
        if op == 'FullyConnected':
            weight = produced(node['inputs'][1])
            rows.append((node['name'], op, weight[0] * weight[1]))
        elif op == 'FlashAttention':
            _, heads, length, d_k = produced(node['inputs'][0])
            d_v = produced(node['inputs'][2])[-1]
            rows.append((node['name'], op,
                         heads * (length // 2) * (d_k + d_v)))
        elif op == 'KimiDeltaAttention':
            heads, _, d_k, d_v, chunk, channels, taps = _kda(node, produced)
            rows.append((node['name'] + '/conv', op, 3 * channels * taps))
            rows.append((node['name'] + '/scan', op,
                         kda_scan_macs(heads, d_k, d_v, chunk)))
        elif op == 'SparseExperts':
            experts, width_in = produced(node['inputs'][1])
            held, _, width = produced(node['inputs'][2])
            per_assignment = 3 * width_in * width
            rows.append((node['name'] + '/router', op, experts * width_in))
            share = flops_lm._attr(node, 'experts_per_tok') * held / \
                float(experts)
            rows.append((node['name'] + '/experts', op,
                         int(round(share * per_assignment))))
        elif op in ('Convolution', 'Deconvolution', 'RNN', 'batch_dot',
                    'dot', 'GatedShortConv'):
            raise NotImplementedError(
                'benchmark/flops_kimi_linear.py does not count %s (node %s)'
                % (op, node['name']))
    dense = sum(r[2] for r in rows if not r[0].endswith('/experts'))
    return dense, per_assignment, rows


def pinned(symbol, input_shapes):
    """What ``"pinned"`` in the configuration's file holds the built model
    to: learnable numbers, forward multiply-adds of one token (experts at
    uniform routing), every learnable array's shape in the symbol's
    order."""
    _, _, rows = forward_macs_per_token(symbol, input_shapes)
    arg_shapes, _, _ = symbol.infer_shape(**input_shapes)
    weights = [[name, list(shape)] for name, shape in
               zip(symbol.list_arguments(), arg_shapes)
               if name not in input_shapes]
    return {'forward_macs_per_token': sum(r[2] for r in rows),
            'parameters': sum(math.prod(shape) for _, shape in weights),
            'weights': weights}


# -- the kernels: operations and bytes of one step --------------------------

def kernel_shapes(symbol, input_shapes):
    """What the counts below take, from the symbol: every ``FlashAttention``
    node as ``(heads, key-value heads, length, d_k, d_v)``, every
    ``KimiDeltaAttention`` node as ``(heads, length, d_k, d_v, chunk)``, and
    of the ``SparseExperts`` nodes what ``flops_lm.kernel_shapes`` gives."""
    nodes, produced = flops_lm._graph(symbol, input_shapes)
    out = {'attention': [], 'kda': [], 'experts_held_total': 0,
           'expert_width_in': 0, 'expert_width': 0}
    for node in nodes:
        if node['op'] == 'FlashAttention':
            _, heads, length, d_k = produced(node['inputs'][0])
            out['attention'].append(
                (heads, produced(node['inputs'][1])[1], length, d_k,
                 produced(node['inputs'][2])[-1]))
        elif node['op'] == 'KimiDeltaAttention':
            out['kda'].append(_kda(node, produced)[:5])
        elif node['op'] == 'SparseExperts':
            held, out['expert_width_in'], out['expert_width'] = \
                produced(node['inputs'][2])
            out['experts_held_total'] += held
    return out


def kda_scan_flops(sequences, heads, length, d_k, d_v, chunk):
    """The gated delta rule of one layer and step: ``kda_scan_macs`` a
    token forward, twice that backward."""
    return 3 * 2 * sequences * length * kda_scan_macs(heads, d_k, d_v, chunk)


def kda_scan_bytes(sequences, heads, length, d_k, d_v, itemsize=2):
    """The least the rule moves: queries, keys, values and outputs in the
    compute dtype, the log-decay a channel in float32 and beta a head,
    once forward; backward those again with the outputs' cotangent, and
    every input's gradient written."""
    token = heads * ((2 * d_k + 2 * d_v) * itemsize + 4 * d_k + 4)
    return 3 * sequences * length * token


def attention_flops(sequences, heads, length, d_k, d_v):
    """Causal attention with keys of ``d_k`` and values of ``d_v``:
    ``length^2 / 2 x (d_k + d_v)`` multiply-adds a sequence and head
    forward (two products over half the square), twice that backward."""
    return 3 * 2 * sequences * heads * (length * length // 2) * (d_k + d_v)


def attention_bytes(sequences, heads, kv_heads, length, d_k, d_v,
                    itemsize=2):
    """Queries, keys, values and outputs once forward, and with their
    gradients backward."""
    rows = sequences * length * (heads * (d_k + d_v) +
                                 kv_heads * (d_k + d_v))
    return 3 * rows * itemsize
