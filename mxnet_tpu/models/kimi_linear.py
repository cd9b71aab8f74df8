"""Kimi Linear: Moonshot AI's hybrid linear-attention mixture of experts
(``model_type`` ``kimi_linear``, e.g. Kimi-Linear-48B-A3B; arXiv:2510.26692)
as a Symbol.

Blocks ``h = x + Op(RMSNorm(x))``, ``x' = h + FF(RMSNorm(h))``, a final
``RMSNorm`` and an output head with a table of its own.  ``Op`` is, by
``linear_attn_config`` (whose layer numbers start at 1), Kimi Delta Attention
(``kda_layers``: ``ops/lm.py KimiDeltaAttention`` between its projections,
the decay and the output gate each through a low-rank pair) or multi-head
latent attention without a rotary embedding (``full_attn_layers``: keys and
values expanded from a latent of ``kv_lora_rank`` plus ``qk_rope_head_dim``
key channels shared by all heads, values narrower than keys, through
``FlashAttention``).  ``FF`` is a dense SwiGLU MLP in the first
``first_k_dense_replace`` layers and after them ``SparseExperts`` (sigmoid
scores, the ``num_experts_per_token`` largest of score plus selection bias,
weights renormalised and scaled by ``routed_scaling_factor``) beside
``num_shared_experts`` shared experts that every token passes.  Every size
is an argument under the name the published ``config.json`` gives it;
``num_hidden_layers`` is how many layers are built, from the first.

``experts_held`` = (first, count) says which of the ``num_experts`` experts
live on this device and ``vocab_size`` how many rows of the vocabulary, as in
``models/lfm2_moe.py``; the shared expert is whole on every device.  Every
sub-block is one ``__mirror_stage__``.
``models/kimi_linear_reference.py`` is the plain float32 statement of the
same model.
"""
import math

from .. import symbol as sym
from ..base import AttrScope

PUBLISHED_LINEAR_ATTN = {
    'full_attn_layers': [4, 8, 12, 16, 20, 24, 27],
    'kda_layers': [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19, 21,
                   22, 23, 25, 26],
    'head_dim': 128, 'num_heads': 32, 'short_conv_kernel_size': 4}


def _linear(z, width, name):
    return sym.FullyConnected(z, num_hidden=width, no_bias=True, name=name)


def _swiglu_mlp(z, prefix, names, width, hidden):
    gate, up, down = names
    return _linear(sym.SwiGLU(_linear(z, width, prefix + gate),
                              _linear(z, width, prefix + up),
                              name=prefix + gate + '_swiglu'),
                   hidden, prefix + down)


def _kda(z, prefix, hidden, seq_len, heads, size, taps, rank, chunk, eps):
    channels = heads * size

    def seq(x, width, name):
        return sym.Reshape(x, shape=(-1, seq_len, width), name=name)
    inputs = {
        what: seq(_linear(z, channels, prefix + what[0]), channels,
                  prefix + what[0] + '_seq')
        for what in ('query', 'key', 'value')}
    decay = _linear(_linear(z, rank, prefix + 'f_a'), channels,
                    prefix + 'f_b')
    gate = _linear(_linear(z, rank, prefix + 'g_a'), channels,
                   prefix + 'g_b')
    mixed = sym.KimiDeltaAttention(
        decay=seq(decay, channels, prefix + 'f_seq'),
        beta=seq(_linear(z, heads, prefix + 'b'), heads, prefix + 'b_seq'),
        gate=seq(gate, channels, prefix + 'g_seq'),
        num_heads=heads, kernel=taps, chunk_size=chunk, eps=eps,
        name=prefix + 'kda', **inputs)
    mixed = sym.Reshape(mixed, shape=(-1, channels), name=prefix + 'kda_flat')
    return _linear(mixed, hidden, prefix + 'o')


def _mla(z, prefix, hidden, seq_len, heads, latent, nope, shared, v_size,
         eps):
    def head_major(x, name):
        return sym.SwapAxis(x, dim1=1, dim2=2, name=prefix + name + '_t')
    q = sym.Reshape(_linear(z, heads * (nope + shared), prefix + 'q'),
                    shape=(-1, seq_len, heads, nope + shared),
                    name=prefix + 'q_heads')
    kv_a = _linear(z, latent + shared, prefix + 'kv_a')
    c = sym.slice_axis(kv_a, axis=1, begin=0, end=latent,
                       name=prefix + 'kv_c')
    k_shared = sym.slice_axis(kv_a, axis=1, begin=latent,
                              end=latent + shared, name=prefix + 'k_r')
    kv = _linear(sym.RMSNorm(c, eps=eps, name=prefix + 'kv_norm'),
                 heads * (nope + v_size), prefix + 'kv_b')
    kv = sym.Reshape(kv, shape=(-1, seq_len, heads, nope + v_size),
                     name=prefix + 'kv_heads')
    k_shared = sym.broadcast_axis(
        sym.Reshape(k_shared, shape=(-1, seq_len, 1, shared),
                    name=prefix + 'k_r_heads'),
        axis=2, size=heads, name=prefix + 'k_r_all')
    k = sym.Concat(sym.slice_axis(kv, axis=3, begin=0, end=nope,
                                  name=prefix + 'k_nope'),
                   k_shared, dim=3, name=prefix + 'k')
    v = sym.slice_axis(kv, axis=3, begin=nope, end=nope + v_size,
                       name=prefix + 'v')
    out = sym.FlashAttention(head_major(q, 'q'), head_major(k, 'k'),
                             head_major(v, 'v'), causal=True,
                             scale=1.0 / math.sqrt(nope + shared),
                             name=prefix + 'att')
    out = sym.Reshape(sym.SwapAxis(out, dim1=1, dim2=2,
                                   name=prefix + 'att_t'),
                      shape=(-1, heads * v_size), name=prefix + 'att_flat')
    return _linear(out, hidden, prefix + 'o')


def get_symbol(vocab_size=163840, hidden_size=2304, num_hidden_layers=27,
               first_k_dense_replace=1, intermediate_size=9216,
               moe_intermediate_size=1024, num_experts=256,
               num_experts_per_token=8, num_shared_experts=1,
               experts_held=None, moe_renormalize=True,
               moe_router_activation_func='sigmoid',
               routed_scaling_factor=2.446, num_expert_group=1, topk_group=1,
               num_attention_heads=32, kv_lora_rank=512, q_lora_rank=None,
               qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
               mla_use_nope=True, linear_attn_config=None,
               kda_gate_rank=None, kda_chunk_size=64, rms_norm_eps=1e-5,
               tie_word_embeddings=False, seq_len=8192, **kwargs):
    """The model over (N, ``seq_len``) token ids ``data`` and next-token
    ``softmax_label``, ending in ``SoftmaxOutput`` over (N * seq_len,
    ``vocab_size``).  ``experts_held`` defaults to all the experts,
    ``linear_attn_config`` to the published one, ``kda_gate_rank`` (the rank
    of Kimi Delta Attention's two low-rank gates, which the published
    configuration does not give) to its head size."""
    for name, value, only in (
            ('moe_router_activation_func', moe_router_activation_func,
             'sigmoid'), ('num_expert_group', num_expert_group, 1),
            ('topk_group', topk_group, 1), ('q_lora_rank', q_lora_rank, None),
            ('mla_use_nope', mla_use_nope, True),
            ('tie_word_embeddings', tie_word_embeddings, False)):
        if value != only:
            raise ValueError('kimi_linear builds %s=%r only, not %r'
                             % (name, only, value))
    linear = dict(linear_attn_config or PUBLISHED_LINEAR_ATTN)
    if experts_held is None:
        experts_held = (0, num_experts)
    experts_held = tuple(int(v) for v in experts_held)
    kda_size = int(linear['head_dim'])
    if kda_gate_rank is None:
        kda_gate_rank = kda_size
    data = sym.Variable('data')
    label = sym.Variable('softmax_label')
    x = sym.Embedding(data, input_dim=vocab_size, output_dim=hidden_size,
                      name='embed')
    x = sym.Reshape(x, shape=(-1, hidden_size), name='embed_flat')
    for index in range(num_hidden_layers):
        p = 'l%d_' % index
        if index + 1 in linear['kda_layers']:
            with AttrScope(__mirror_stage__=p + 'op'):
                z = sym.RMSNorm(x, eps=rms_norm_eps, name=p + 'op_norm')
                h = x + _kda(z, p, hidden_size, seq_len,
                             int(linear['num_heads']), kda_size,
                             int(linear['short_conv_kernel_size']),
                             kda_gate_rank, kda_chunk_size, rms_norm_eps)
        elif index + 1 in linear['full_attn_layers']:
            with AttrScope(__mirror_stage__=p + 'op'):
                z = sym.RMSNorm(x, eps=rms_norm_eps, name=p + 'op_norm')
                h = x + _mla(z, p, hidden_size, seq_len, num_attention_heads,
                             kv_lora_rank, qk_nope_head_dim,
                             qk_rope_head_dim, v_head_dim, rms_norm_eps)
        else:
            raise ValueError('linear_attn_config names layer %d neither in '
                             'kda_layers nor in full_attn_layers'
                             % (index + 1))
        with AttrScope(__mirror_stage__=p + 'ff'):
            z = sym.RMSNorm(h, eps=rms_norm_eps, name=p + 'ff_norm')
            if index < first_k_dense_replace:
                ff = _swiglu_mlp(z, p, ('w1', 'w3', 'w2'), intermediate_size,
                                 hidden_size)
            else:
                ff = sym.SparseExperts(
                    z, router_weight=sym.Variable(p + 'router_weight'),
                    w1_weight=sym.Variable(p + 'experts_w1_weight'),
                    w3_weight=sym.Variable(p + 'experts_w3_weight'),
                    w2_weight=sym.Variable(p + 'experts_w2_weight'),
                    num_experts=num_experts, experts_held=experts_held,
                    experts_per_tok=num_experts_per_token,
                    expert_hidden=moe_intermediate_size,
                    norm_topk_prob=moe_renormalize,
                    routed_scaling_factor=routed_scaling_factor,
                    name=p + 'moe')
                if num_shared_experts:
                    ff = ff + _swiglu_mlp(
                        z, p, ('shared_w1', 'shared_w3', 'shared_w2'),
                        num_shared_experts * moe_intermediate_size,
                        hidden_size)
            x = h + ff
    z = sym.RMSNorm(x, eps=rms_norm_eps, name='final_norm')
    logits = _linear(z, vocab_size, 'lm_head')
    return sym.SoftmaxOutput(logits, sym.Reshape(label, shape=(-1,),
                                                 name='label_flat'),
                             name='softmax')
