"""Model FLOPs of one training step, counted from the symbol's shapes, and
what a configuration's file pins of the model it names.

What the algorithm needs, not what the compiler emitted: 2 FLOPs per
multiply-add, convolutions and fully connected layers only (BatchNorm,
activations, pooling and the loss are bandwidth, not MXU work, and are
left out on purpose), and the backward pass as twice the forward
(gradient by the input and gradient by the weight), so a step is three
times the forward.  Nothing here reads ``cost_analysis()``.
"""
import json


def forward_macs(symbol, input_shapes):
    """Multiply-adds of one forward pass for the whole batch in
    ``input_shapes`` (``{'data': (n, c, h, w)}``), as ``(total,
    per_layer)`` with ``per_layer`` a list of ``(node name, op, macs,
    weight shape)``."""
    internals = symbol.get_internals()
    _, out_shapes, _ = internals.infer_shape(**input_shapes)
    shape_of = dict(zip(internals.list_outputs(), out_shapes))
    graph = json.loads(symbol.tojson())
    nodes = graph['nodes']

    def out_shape(node_index):
        node = nodes[node_index]
        name = node['name']
        return shape_of[name if node['op'] == 'null' else name + '_output']

    rows = []
    for index, node in enumerate(nodes):
        if node['op'] == 'Convolution':
            weight = out_shape(node['inputs'][1][0])     # (O, I/g, kh, kw)
            out = out_shape(index)                       # (N, O, oh, ow)
            per_output = 1
            for v in weight[1:]:
                per_output *= v
            positions = 1
            for v in out:
                positions *= v
            rows.append((node['name'], 'Convolution',
                         positions * per_output, tuple(weight)))
        elif node['op'] == 'FullyConnected':
            weight = out_shape(node['inputs'][1][0])     # (hidden, in)
            batch = out_shape(index)[0]
            rows.append((node['name'], 'FullyConnected',
                         batch * weight[0] * weight[1], tuple(weight)))
        elif node['op'] in ('Deconvolution', 'RNN', 'batch_dot', 'dot'):
            raise NotImplementedError(
                'benchmark/flops.py does not count %s (node %s): add the '
                'count before reporting a FLOP share for this model'
                % (node['op'], node['name']))
    return sum(r[2] for r in rows), rows


def train_step_flops(symbol, input_shapes):
    """FLOPs one optimizer step needs: forward plus backward as 3 x the
    forward's, 2 per multiply-add."""
    macs, _ = forward_macs(symbol, input_shapes)
    return 3 * 2 * macs


def pinned(symbol, image_shape):
    """What ``"pinned"`` in a configuration's file holds the built model
    to: the forward multiply-adds of one sample, the count of learnable
    numbers, and every convolution's and FC's weight shape in the
    graph's order.  The symbol comes from the program, which later PRs
    may change; a narrower or shallower model under the same name does
    not match these, and the run ends before it measures anything."""
    shapes = {'data': (1,) + tuple(image_shape)}
    macs, rows = forward_macs(symbol, shapes)
    arg_shapes, _, _ = symbol.infer_shape(**shapes)
    parameters = 0
    for name, shape in zip(symbol.list_arguments(), arg_shapes):
        if name not in shapes and not name.endswith('label'):
            size = 1
            for v in shape:
                size *= v
            parameters += size
    return {'forward_macs_per_sample': macs, 'parameters': parameters,
            'weights': [[name, list(weight)]
                        for name, _, _, weight in rows]}
