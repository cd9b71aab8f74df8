"""Model FLOPs of one training step of a language model built by
``mxnet_tpu/models``, counted from the symbol's shapes; what a language
model's configuration file pins; and the operations and bytes of each new
kernel, for its share of the roofline.

What the algorithm needs, not what the compiler emitted: 2 FLOPs per
multiply-add, matrix products only (norms, gates, the short convolution's
three taps and the loss are bandwidth), the backward pass as twice the
forward, nothing for recomputation.  Per token of a sequence of T tokens:

- ``FullyConnected``: out x in.
- ``FlashAttention``, causal: a token attends to T / 2 keys on average, two
  products of the head size each: heads x T / 2 x size x 2.
- ``SparseExperts``: the router's experts x in, and for every assignment
  that lands on a held expert three products of in x width.  Pinned at the
  share uniform routing gives (per_tok x held / experts assignments a
  token); a step's FLOPs take the assignments the program counted.
- ``GatedShortConv``: taps a channel.
"""
import json
import math


def _graph(symbol, input_shapes):
    """The symbol's JSON nodes, and the shape an input entry ``[node index,
    output, ...]`` of one of them carries."""
    internals = symbol.get_internals()
    _, out_shapes, _ = internals.infer_shape(**input_shapes)
    shape_of = dict(zip(internals.list_outputs(), out_shapes))
    nodes = json.loads(symbol.tojson())['nodes']

    def produced(entry):
        node = nodes[entry[0]]
        return shape_of[node['name'] if node['op'] == 'null'
                        else node['name'] + '_output']
    return nodes, produced


def _attr(node, key):
    value = node.get('attrs', {})[key]
    return json.loads(value.replace('(', '[').replace(')', ']')) \
        if isinstance(value, str) else value


def forward_macs_per_token(symbol, input_shapes):
    """``(dense, expert)``: multiply-adds of one token's forward pass
    outside the experts, and of one assignment to a held expert; with
    ``rows``, a list of ``(node, operator, multiply-adds a token)`` where
    the experts' row is at uniform routing."""
    nodes, produced = _graph(symbol, input_shapes)
    rows, per_assignment = [], 0
    for node in nodes:
        op = node['op']
        if op == 'FullyConnected':
            weight = produced(node['inputs'][1])
            rows.append((node['name'], op, weight[0] * weight[1]))
        elif op == 'FlashAttention':
            _, heads, length, size = produced(node['inputs'][0])
            rows.append((node['name'], op, heads * (length // 2) * size * 2))
        elif op == 'GatedShortConv':
            channels, taps = produced(node['inputs'][1])
            rows.append((node['name'], op, channels * taps))
        elif op == 'SparseExperts':
            experts, width_in = produced(node['inputs'][1])
            held, _, width = produced(node['inputs'][2])
            per_assignment = 3 * width_in * width
            rows.append((node['name'] + '/router', op, experts * width_in))
            share = _attr(node, 'experts_per_tok') * held / float(experts)
            rows.append((node['name'] + '/experts', op,
                         int(round(share * per_assignment))))
            continue
        elif op in ('Convolution', 'Deconvolution', 'RNN', 'batch_dot',
                    'dot'):
            raise NotImplementedError(
                'benchmark/flops_lm.py does not count %s (node %s)'
                % (op, node['name']))
    dense = sum(r[2] for r in rows if not r[0].endswith('/experts'))
    return dense, per_assignment, rows


def train_step_flops(dense_macs, expert_macs, tokens, assignments_held):
    """FLOPs one optimizer step needs: 2 a multiply-add, backward twice
    the forward; the experts by the assignments that landed on them."""
    return 3 * 2 * (dense_macs * tokens + expert_macs * assignments_held)


def pinned(symbol, input_shapes):
    """What ``"pinned"`` in a language model's configuration file holds
    the built model to: learnable numbers, forward multiply-adds of one
    token (experts at uniform routing), every learnable array's shape in
    the symbol's order."""
    _, _, rows = forward_macs_per_token(symbol, input_shapes)
    arg_shapes, _, _ = symbol.infer_shape(**input_shapes)
    weights = [[name, list(shape)] for name, shape in
               zip(symbol.list_arguments(), arg_shapes)
               if name not in input_shapes]
    return {'forward_macs_per_token': sum(r[2] for r in rows),
            'parameters': sum(math.prod(shape) for _, shape in weights),
            'weights': weights}


# -- the new kernels: operations and bytes of one step ---------------------

def kernel_shapes(symbol, input_shapes):
    """What the kernels' counts below take, from the symbol: every
    ``FlashAttention`` node as ``(heads, key-value heads, length, head
    size)``, and of the ``SparseExperts`` nodes the experts held in all and
    one expert's ``(width in, width)``."""
    nodes, produced = _graph(symbol, input_shapes)
    out = {'attention': [], 'experts_held_total': 0,
           'expert_width_in': 0, 'expert_width': 0}
    for node in nodes:
        if node['op'] == 'FlashAttention':
            _, heads, length, size = produced(node['inputs'][0])
            out['attention'].append((heads, produced(node['inputs'][1])[1],
                                     length, size))
        elif node['op'] == 'SparseExperts':
            held, out['expert_width_in'], out['expert_width'] = \
                produced(node['inputs'][2])
            out['experts_held_total'] += held
    return out


def experts_flops(assignments_held, width_in, width):
    """Three grouped products an assignment, forward; twice that
    backward (by the rows and by the weights)."""
    return 3 * 2 * assignments_held * 3 * width_in * width


def experts_bytes(assignments_held, experts_held, width_in, width,
                  itemsize=2):
    """The least the grouped products move: every held expert's three
    matrices once forward and twice backward (read, and their gradient
    written), the rows in and out of each product."""
    weights = 3 * experts_held * 3 * width_in * width * itemsize
    rows = 3 * assignments_held * (2 * width_in + 4 * width) * itemsize
    return weights + rows


def attention_flops(sequences, heads, length, size):
    """Causal attention: ``length^2 x size`` multiply-adds a sequence and
    head forward (two products over half the square), twice that
    backward."""
    return 3 * 2 * sequences * heads * length * length * size


def attention_bytes(sequences, heads, kv_heads, length, size, itemsize=2):
    """Queries, keys, values and outputs once forward, and with their
    gradients backward."""
    rows = sequences * length * size * (2 * heads + 2 * kv_heads)
    return 3 * rows * itemsize
