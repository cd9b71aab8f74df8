"""Nemotron-H: NVIDIA's hybrid of Mamba-2, attention and sparse experts
(``model_type`` ``nemotron_h``, e.g. Nemotron 3 Super 120B-A12B) as a Symbol.

Blocks of one sub-block each, ``x' = x + Mixer(RMSNorm(x))``, a final
``RMSNorm`` and an output head with a table of its own.  ``Mixer`` is, by the
block's letter in ``hybrid_override_pattern``: ``M``, Mamba-2 (``ops/lm.py
Mamba2Mixer`` between one projection to ``[z | xBC | dt]`` and one back);
``*``, grouped-query attention without bias and without a rotary embedding,
through ``FlashAttention``; ``E``, LatentMoE: ``SparseExperts`` with ungated
``relu2`` experts on the rows of a latent of ``moe_latent_size`` (sigmoid
scores over the full hidden vector, the ``num_experts_per_tok`` largest of
score plus selection bias, weights renormalised and scaled by
``routed_scaling_factor``) between the latent's down- and up-projection,
beside ``n_shared_experts`` shared experts on the full hidden vector.  Every
size is an argument under the name the published ``config.json`` gives it;
``num_hidden_layers`` is the pattern's length.

``experts_held`` = (first, count) says which of the ``n_routed_experts``
experts live on this device and ``vocab_size`` how many rows of the
vocabulary, as in ``models/kimi_linear.py``.  ``mixers_held`` = (rank, ranks)
says that this device is one of ``ranks`` that share every mixer's heads: a
Mamba-2 mixer then builds ``mamba_num_heads / ranks`` heads and ``n_groups /
ranks`` groups (``n_groups`` is Mamba-2's own knob for this: one group of
``B``, ``C`` and of the gated norm a rank), attention ``num_attention_heads /
ranks`` query heads over ``max(1, num_key_value_heads / ranks)`` key-value
heads (the ones its query heads use), and ``W_out`` / ``W_o`` give this
share's part of the sum over heads; the latent's projections, the router and
the shared expert are whole on every device.  With ``ranks`` 1 the mixers are
whole.  Every sub-block is one ``__mirror_stage__``.
``models/nemotron_h_reference.py`` is the plain float32 statement of the same
model.
"""
import math

from .. import symbol as sym
from ..base import AttrScope

PUBLISHED_PATTERN = (
    'MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*'
    'EMEMEMEM*EMEMEMEME')


def _linear(z, width, name):
    return sym.FullyConnected(z, num_hidden=width, no_bias=True, name=name)


def _mamba(u, prefix, hidden, seq_len, heads, size, groups, states, taps,
           chunk, eps):
    channels, mixed = heads * size, heads * size + 2 * groups * states
    both = _linear(u, channels + mixed + heads, prefix + 'in')

    def part(begin, end, what):
        return sym.Reshape(
            sym.slice_axis(both, axis=1, begin=begin, end=end,
                           name=prefix + what),
            shape=(-1, seq_len, end - begin), name=prefix + what + '_seq')
    y = sym.Mamba2Mixer(
        z=part(0, channels, 'z'), xBC=part(channels, channels + mixed, 'xBC'),
        dt=part(channels + mixed, channels + mixed + heads, 'dt'),
        num_heads=heads, head_dim=size, state_size=states, num_groups=groups,
        kernel=taps, chunk_size=chunk, eps=eps, name=prefix + 'ssm')
    y = sym.Reshape(y, shape=(-1, channels), name=prefix + 'ssm_flat')
    return _linear(y, hidden, prefix + 'out')


def _attention(u, prefix, hidden, seq_len, heads, kv_heads, size):
    def head_major(x, count, what):
        x = sym.Reshape(x, shape=(-1, seq_len, count, size),
                        name=prefix + what + '_heads')
        return sym.SwapAxis(x, dim1=1, dim2=2, name=prefix + what + '_t')
    out = sym.FlashAttention(
        head_major(_linear(u, heads * size, prefix + 'q'), heads, 'q'),
        head_major(_linear(u, kv_heads * size, prefix + 'k'), kv_heads, 'k'),
        head_major(_linear(u, kv_heads * size, prefix + 'v'), kv_heads, 'v'),
        causal=True, scale=1.0 / math.sqrt(size), name=prefix + 'att')
    out = sym.Reshape(sym.SwapAxis(out, dim1=1, dim2=2,
                                   name=prefix + 'att_t'),
                      shape=(-1, heads * size), name=prefix + 'att_flat')
    return _linear(out, hidden, prefix + 'o')


def _relu2_mlp(u, prefix, names, width, hidden):
    up, down = names
    act = sym.Activation(_linear(u, width, prefix + up), act_type='relu',
                         name=prefix + up + '_relu')
    return _linear(sym.square(act, name=prefix + up + '_relu2'), hidden,
                   prefix + down)


def _latent_moe(u, prefix, hidden, latent, experts, held, per_tok, width,
                shared, shared_width, renormalise, scaling):
    routed = sym.SparseExperts(
        u, latent=_linear(u, latent, prefix + 'down'),
        router_weight=sym.Variable(prefix + 'router_weight'),
        w1_weight=sym.Variable(prefix + 'experts_w1_weight'),
        w2_weight=sym.Variable(prefix + 'experts_w2_weight'),
        num_experts=experts, experts_held=held, experts_per_tok=per_tok,
        expert_hidden=width, norm_topk_prob=renormalise,
        routed_scaling_factor=scaling, expert_form='relu2',
        latent_input=True, topk_eps=1e-20, name=prefix + 'moe')
    out = _linear(routed, hidden, prefix + 'up')
    if shared:
        out = out + _relu2_mlp(u, prefix, ('shared_w1', 'shared_w2'),
                               shared * shared_width, hidden)
    return out


def get_symbol(vocab_size=131072, hidden_size=4096,
               hybrid_override_pattern=PUBLISHED_PATTERN,
               num_hidden_layers=None, mamba_num_heads=128, mamba_head_dim=64,
               ssm_state_size=128, n_groups=8, conv_kernel=4, chunk_size=128,
               use_conv_bias=True, mamba_hidden_act='silu',
               mamba_proj_bias=False, num_attention_heads=32,
               num_key_value_heads=2, head_dim=128, attention_bias=False,
               n_routed_experts=512, num_experts_per_tok=22,
               moe_intermediate_size=2688, moe_latent_size=1024,
               n_shared_experts=1, moe_shared_expert_intermediate_size=5376,
               mlp_hidden_act='relu2', mlp_bias=False, norm_topk_prob=True,
               routed_scaling_factor=5.0, n_group=1, topk_group=1,
               layer_norm_epsilon=1e-5, tie_word_embeddings=False,
               num_nextn_predict_layers=0, experts_held=None,
               mixers_held=None, seq_len=8192, **kwargs):
    """The model over (N, ``seq_len``) token ids ``data`` and next-token
    ``softmax_label``, ending in ``SoftmaxOutput`` over (N * seq_len,
    ``vocab_size``).  ``experts_held`` defaults to all the experts and
    ``mixers_held`` to (0, 1), every head."""
    for name, value, only in (
            ('use_conv_bias', use_conv_bias, True),
            ('mamba_hidden_act', mamba_hidden_act, 'silu'),
            ('mamba_proj_bias', mamba_proj_bias, False),
            ('attention_bias', attention_bias, False),
            ('mlp_hidden_act', mlp_hidden_act, 'relu2'),
            ('mlp_bias', mlp_bias, False), ('n_group', n_group, 1),
            ('topk_group', topk_group, 1),
            ('tie_word_embeddings', tie_word_embeddings, False),
            ('num_nextn_predict_layers', num_nextn_predict_layers, 0)):
        if value != only:
            raise ValueError('nemotron_h builds %s=%r only, not %r'
                             % (name, only, value))
    pattern = str(hybrid_override_pattern)
    if num_hidden_layers is not None and num_hidden_layers != len(pattern):
        raise ValueError('hybrid_override_pattern has %d blocks, '
                         'num_hidden_layers says %d'
                         % (len(pattern), num_hidden_layers))
    if experts_held is None:
        experts_held = (0, n_routed_experts)
    experts_held = tuple(int(v) for v in experts_held)
    rank, ranks = (int(v) for v in (mixers_held or (0, 1)))
    if not 0 <= rank < ranks or mamba_num_heads % ranks or \
            n_groups % ranks or num_attention_heads % ranks or \
            (num_key_value_heads % ranks and ranks % num_key_value_heads):
        raise ValueError(
            'mixers_held = (%d, %d): %d Mamba-2 heads in %d groups and %d '
            'query heads over %d key-value heads do not go %d ways'
            % (rank, ranks, mamba_num_heads, n_groups, num_attention_heads,
               num_key_value_heads, ranks))
    eps = layer_norm_epsilon
    data = sym.Variable('data')
    label = sym.Variable('softmax_label')
    x = sym.Embedding(data, input_dim=vocab_size, output_dim=hidden_size,
                      name='embed')
    x = sym.Reshape(x, shape=(-1, hidden_size), name='embed_flat')
    for index, kind in enumerate(pattern):
        p = 'l%d_' % index
        with AttrScope(__mirror_stage__=p + 'mixer'):
            u = sym.RMSNorm(x, eps=eps, name=p + 'norm')
            if kind == 'M':
                mixed = _mamba(u, p, hidden_size, seq_len,
                               mamba_num_heads // ranks, mamba_head_dim,
                               n_groups // ranks, ssm_state_size, conv_kernel,
                               chunk_size, eps)
            elif kind == '*':
                mixed = _attention(u, p, hidden_size, seq_len,
                                   num_attention_heads // ranks,
                                   max(1, num_key_value_heads // ranks),
                                   head_dim)
            elif kind == 'E':
                mixed = _latent_moe(
                    u, p, hidden_size, moe_latent_size, n_routed_experts,
                    experts_held, num_experts_per_tok, moe_intermediate_size,
                    n_shared_experts, moe_shared_expert_intermediate_size,
                    norm_topk_prob, routed_scaling_factor)
            else:
                raise ValueError('hybrid_override_pattern: block %d is %r, '
                                 'not M, * or E' % (index, kind))
            x = x + mixed
    z = sym.RMSNorm(x, eps=eps, name='final_norm')
    logits = _linear(z, vocab_size, 'lm_head')
    return sym.SoftmaxOutput(logits, sym.Reshape(label, shape=(-1,),
                                                 name='label_flat'),
                             name='softmax')
