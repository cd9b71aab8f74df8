"""Model FLOPs of one training step of ``models/nemotron_h.py``'s language
model, counted from the symbol's shapes; what the configuration
``nemotron3_super_120b_a12b`` pins; and the operations and bytes of its
kernels, the Mamba-2 scan and the ungated grouped products, for their shares
of the roofline.  ``flops_lm.py``'s rules (2 FLOPs a multiply-add, matrix
products only, the backward pass twice the forward, nothing for
recomputation; ``FullyConnected`` out x in; ``FlashAttention``, causal, a
token attends to T / 2 keys on average) and beside them, per token:

- ``SparseExperts`` with experts of two matrices in a latent: the router's
  experts x hidden, and for every assignment on a held expert two products
  of latent x width; pinned at uniform routing.
- ``Mamba2Mixer``: the convolution's taps a channel, and the state-space
  recurrence as its chunked form needs it at the chunk size C the node
  states, whatever implements it (``ssd_scan_macs``): per group the
  chunk's triangle of C . B products (C / 2 x S), per head the triangle
  applied to the values (C / 2 x P), the chunk's outputs from the carried
  state (P x S) and its addition to the state (P x S).  Token by token the
  recurrence needs the last two alone; the triangles are what buys matrix
  products.  Heads and groups are the node's own, so a chip that holds 16
  of 128 heads and one of 8 groups counts those once.
"""
import math

from . import flops_lm
from .flops_lm import train_step_flops  # noqa: F401  (the driver's)


def ssd_scan_macs(heads, groups, head_dim, state, chunk):
    """Multiply-adds of one token's state-space recurrence in chunks of
    ``chunk`` tokens, all heads, forward."""
    return groups * (chunk // 2) * state + \
        heads * ((chunk // 2) * head_dim + 2 * head_dim * state)


def _ssm(node, produced):
    """``(heads, groups, length, head_dim, state, chunk, mixed channels,
    taps)`` of a ``Mamba2Mixer`` node."""
    _, length, _ = produced(node['inputs'][0])
    heads = flops_lm._attr(node, 'num_heads')
    attrs = node.get('attrs', {})
    chunk = min(int(attrs.get('chunk_size', 128)), length)
    mixed, taps = produced(node['inputs'][3])
    return (heads, int(attrs.get('num_groups', 1)), length,
            flops_lm._attr(node, 'head_dim'),
            flops_lm._attr(node, 'state_size'), chunk, mixed, taps)


def _experts(node, produced):
    """``(experts, hidden, held, latent, width, matrices an expert)`` of a
    ``SparseExperts`` node, whatever its form."""
    from mxnet_tpu.ops.registry import get_op
    op = get_op('SparseExperts')
    names = op.input_names(op.canon_attrs(node.get('attrs', {})))
    at = {name: produced(entry) for name, entry in zip(names, node['inputs'])}
    experts, hidden = at['router_weight']
    held, latent, width = at['w1_weight']
    return experts, hidden, held, latent, width, 3 if 'w3_weight' in at else 2


def forward_macs_per_token(symbol, input_shapes):
    """``(dense, expert, rows)`` as ``flops_lm.forward_macs_per_token``
    gives them, for a symbol that may hold ``Mamba2Mixer`` and experts of
    two matrices in a latent."""
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
        elif op == 'Mamba2Mixer':
            heads, groups, _, size, state, chunk, mixed, taps = \
                _ssm(node, produced)
            rows.append((node['name'] + '/conv', op, mixed * taps))
            rows.append((node['name'] + '/scan', op,
                         ssd_scan_macs(heads, groups, size, state, chunk)))
        elif op == 'SparseExperts':
            experts, hidden, held, latent, width, matrices = \
                _experts(node, produced)
            per_assignment = matrices * latent * width
            rows.append((node['name'] + '/router', op, experts * hidden))
            share = flops_lm._attr(node, 'experts_per_tok') * held / \
                float(experts)
            rows.append((node['name'] + '/experts', op,
                         int(round(share * per_assignment))))
        elif op in ('Convolution', 'Deconvolution', 'RNN', 'batch_dot',
                    'dot', 'GatedShortConv', 'KimiDeltaAttention'):
            raise NotImplementedError(
                'benchmark/flops_nemotron_h.py does not count %s (node %s)'
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
    """What the counts below take, from the symbol: every ``Mamba2Mixer``
    node as ``(heads, groups, length, head_dim, state, chunk)``; of the
    ``SparseExperts`` nodes the experts held in all, one expert's ``(width
    in, width)`` and how many matrices it has; and the names of the latent's
    projections, the ``FullyConnected`` nodes that feed a ``SparseExperts``
    node's ``latent`` input and that take its output."""
    nodes, produced = flops_lm._graph(symbol, input_shapes)
    out = {'ssm': [], 'experts_held_total': 0, 'expert_width_in': 0,
           'expert_width': 0, 'expert_matrices': 0, 'latent_projections': []}
    for index, node in enumerate(nodes):
        if node['op'] == 'Mamba2Mixer':
            out['ssm'].append(_ssm(node, produced)[:6])
        elif node['op'] == 'SparseExperts':
            _, _, held, out['expert_width_in'], out['expert_width'], \
                out['expert_matrices'] = _experts(node, produced)
            out['experts_held_total'] += held
            around = [nodes[node['inputs'][1][0]]] + [
                n for n in nodes if n['inputs'] and
                n['inputs'][0][0] == index]
            out['latent_projections'] += [
                n['name'] for n in around if n['op'] == 'FullyConnected']
    return out


def ssd_scan_flops(sequences, heads, groups, length, head_dim, state, chunk):
    """The recurrence of one layer and step: ``ssd_scan_macs`` a token
    forward, twice that backward."""
    return 3 * 2 * sequences * length * \
        ssd_scan_macs(heads, groups, head_dim, state, chunk)


def ssd_scan_bytes(sequences, heads, groups, length, head_dim, state,
                   itemsize=2):
    """The least the recurrence moves: the values and the outputs a head and
    ``B`` and ``C`` a group in the compute dtype, the step a head in
    float32, once forward; backward those again with the outputs'
    cotangent, and every input's gradient written."""
    token = (2 * heads * head_dim + 2 * groups * state) * itemsize + \
        4 * heads
    return 3 * sequences * length * token


def experts_flops(assignments_held, width_in, width, matrices=2):
    """``matrices`` grouped products an assignment, forward; twice that
    backward (by the rows and by the weights)."""
    return 3 * 2 * assignments_held * matrices * width_in * width


def experts_bytes(assignments_held, experts_held, width_in, width,
                  matrices=2, itemsize=2):
    """The least the grouped products move: every held expert's matrices
    once forward and twice backward (read, and their gradient written), the
    rows in and out of each product."""
    weights = 3 * experts_held * matrices * width_in * width * itemsize
    rows = 3 * assignments_held * matrices * (width_in + width) * itemsize
    return weights + rows
