"""LFM2-MoE: LiquidAI's sparse decoder-only language model
(``model_type`` ``lfm2_moe``, e.g. LFM2-24B-A2B) as a Symbol.

Blocks ``h = x + Op(RMSNorm(x))``, ``x' = h + FF(RMSNorm(h))``.  ``Op`` is,
by ``layer_types``, a gated short convolution (``conv``) or grouped-query
attention with rotary embedding and an RMS norm on every head's query and
key (``full_attention``).  ``FF`` is a dense SwiGLU MLP in the first
``num_dense_layers`` layers and a ``SparseExperts`` layer after them.  One
table is embedding and output head.  Every size is an argument; the
arguments carry the names of the published ``config.json``.

``experts_held`` = (first, count) says which of the ``num_experts`` experts
live on this device, and ``vocab_size`` how many rows of the vocabulary:
under expert parallelism a device holds its share of both, the router still
scores all ``num_experts``, and what the absent experts would add is left
out (``ops/lm.py SparseExperts``).

Every feed-forward and convolution sub-block is one ``__mirror_stage__``: the
step keeps its input and recomputes its inside in the backward pass
(``executor._build_graph_fn``), which is what lets 16384 tokens of a
width-11776 MLP train on one chip.  ``models/lfm2_moe_reference.py`` is the
plain float32 statement of the same model.
"""
import math

from .. import symbol as sym
from ..base import AttrScope


def _short_conv(z, prefix, hidden, seq_len, taps):
    bcu = sym.FullyConnected(z, num_hidden=3 * hidden, no_bias=True,
                             name=prefix + 'conv_in')
    bcu = sym.Reshape(bcu, shape=(-1, seq_len, 3 * hidden),
                      name=prefix + 'conv_in_seq')
    mixed = sym.GatedShortConv(bcu, kernel=taps, name=prefix + 'conv')
    mixed = sym.Reshape(mixed, shape=(-1, hidden),
                        name=prefix + 'conv_flat')
    return sym.FullyConnected(mixed, num_hidden=hidden, no_bias=True,
                              name=prefix + 'conv_out')


def _attention(z, prefix, hidden, seq_len, heads, kv_heads, theta, eps):
    size = hidden // heads

    def head_major(x, count, what, normed):
        x = sym.Reshape(x, shape=(-1, seq_len, count, size),
                        name=prefix + what + '_heads')
        if normed:
            x = sym.RMSNorm(x, eps=eps, name=prefix + what + '_norm')
        x = sym.SwapAxis(x, dim1=1, dim2=2, name=prefix + what + '_t')
        if normed:
            x = sym.RotaryEmbedding(x, theta=theta,
                                    name=prefix + what + '_rope')
        return x

    q = sym.FullyConnected(z, num_hidden=heads * size, no_bias=True,
                           name=prefix + 'q')
    k = sym.FullyConnected(z, num_hidden=kv_heads * size, no_bias=True,
                           name=prefix + 'k')
    v = sym.FullyConnected(z, num_hidden=kv_heads * size, no_bias=True,
                           name=prefix + 'v')
    out = sym.FlashAttention(head_major(q, heads, 'q', True),
                             head_major(k, kv_heads, 'k', True),
                             head_major(v, kv_heads, 'v', False),
                             causal=True, scale=1.0 / math.sqrt(size),
                             name=prefix + 'att')
    out = sym.SwapAxis(out, dim1=1, dim2=2, name=prefix + 'att_t')
    out = sym.Reshape(out, shape=(-1, heads * size),
                      name=prefix + 'att_flat')
    return sym.FullyConnected(out, num_hidden=hidden, no_bias=True,
                              name=prefix + 'o')


def get_symbol(vocab_size=65536, hidden_size=2048,
               layer_types=('conv', 'conv', 'full_attention'),
               num_dense_layers=2, intermediate_size=11776,
               moe_intermediate_size=1536, num_experts=64,
               num_experts_per_tok=4, experts_held=None,
               num_attention_heads=32, num_key_value_heads=8,
               rope_theta=1000000.0, norm_eps=1e-5, conv_L_cache=3,
               norm_topk_prob=True, routed_scaling_factor=1.0,
               seq_len=8192, **kwargs):
    """The model over (N, ``seq_len``) token ids ``data`` and next-token
    ``softmax_label``, ending in ``SoftmaxOutput`` over (N * seq_len,
    ``vocab_size``).  ``experts_held`` defaults to all of them."""
    if experts_held is None:
        experts_held = (0, num_experts)
    experts_held = tuple(int(v) for v in experts_held)
    data = sym.Variable('data')
    label = sym.Variable('softmax_label')
    table = sym.Variable('embed_weight', shape=(vocab_size, hidden_size))
    x = sym.Embedding(data, weight=table, input_dim=vocab_size,
                      output_dim=hidden_size, name='embed')
    x = sym.Reshape(x, shape=(-1, hidden_size), name='embed_flat')
    for index, kind in enumerate(layer_types):
        p = 'l%d_' % index
        if kind == 'conv':
            with AttrScope(__mirror_stage__=p + 'op'):
                z = sym.RMSNorm(x, eps=norm_eps, name=p + 'op_norm')
                h = x + _short_conv(z, p, hidden_size, seq_len, conv_L_cache)
        elif kind == 'full_attention':
            z = sym.RMSNorm(x, eps=norm_eps, name=p + 'op_norm')
            h = x + _attention(z, p, hidden_size, seq_len,
                               num_attention_heads, num_key_value_heads,
                               rope_theta, norm_eps)
        else:
            raise ValueError('unknown layer type %r' % (kind,))
        with AttrScope(__mirror_stage__=p + 'ff'):
            z = sym.RMSNorm(h, eps=norm_eps, name=p + 'ff_norm')
            if index < num_dense_layers:
                gate = sym.FullyConnected(z, num_hidden=intermediate_size,
                                          no_bias=True, name=p + 'w1')
                up = sym.FullyConnected(z, num_hidden=intermediate_size,
                                        no_bias=True, name=p + 'w3')
                ff = sym.FullyConnected(
                    sym.SwiGLU(gate, up, name=p + 'swiglu'),
                    num_hidden=hidden_size, no_bias=True, name=p + 'w2')
            else:
                ff = sym.SparseExperts(
                    z, router_weight=sym.Variable(p + 'router_weight'),
                    w1_weight=sym.Variable(p + 'experts_w1_weight'),
                    w3_weight=sym.Variable(p + 'experts_w3_weight'),
                    w2_weight=sym.Variable(p + 'experts_w2_weight'),
                    num_experts=num_experts, experts_held=experts_held,
                    experts_per_tok=num_experts_per_tok,
                    expert_hidden=moe_intermediate_size,
                    norm_topk_prob=norm_topk_prob,
                    routed_scaling_factor=routed_scaling_factor,
                    name=p + 'moe')
            x = h + ff
    z = sym.RMSNorm(x, eps=norm_eps, name='final_norm')
    logits = sym.FullyConnected(z, weight=table, num_hidden=vocab_size,
                                no_bias=True, name='lm_head')
    return sym.SoftmaxOutput(logits, sym.Reshape(label, shape=(-1,),
                                                 name='label_flat'),
                             name='softmax')
