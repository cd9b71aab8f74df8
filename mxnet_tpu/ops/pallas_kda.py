"""The gated delta rule of ``KimiDeltaAttention`` as Pallas kernels.

What ``ops/lm.py _rule_segment`` computes for one segment of chunks between
its inputs (``q``, ``k``, ``v``, the float32 log-decay ``g`` already held to
the floor, float32 ``beta``, the entering state) and its results (the
outputs and the state after), in one ``pl.pallas_call`` forward and one
backward, joined by ``jax.custom_vjp`` (``rule_segment``).  The equations,
the sub-blocks and the floor are those of the comment block over
``lm.KDA_SUB``; the jnp form there (``_chunk_parts``, ``_chunk_step``) is
the path off the TPU and the second oracle of the tests.

Design
------
The projections lie as (N, T, H * d): a block of (T, d) at column block
``h`` is a head's segment, tiled as Mosaic wants it, so no relayout stands
before or after the rule.  The grid is (sequence, group of heads), both
parallel; a grid step holds its heads' segment in VMEM and walks the chunks
in a loop, the state of each head (d_v x d_k float32, held transposed so
that the decay of a key's channel scales lanes) in VMEM scratch from the
entering state to the state after.  A chunk's running sum ``G``, the two
factors through each sub-block's start, the triangles ``A`` and ``B``, the
inverse of ``I + Diag(beta) A``, ``W``, ``U``, the outputs and the state
never leave VMEM: HBM sees the rule's inputs and outputs once.  A chunk is
a chain of some twenty-five small products forward and sixty backward, each
waiting for the one before; the heads of a step are independent, and their
chains are written a product of each head in turn (``_in_turn``), which is
the order the chip runs them in: eight heads a step are three times as fast
as one.

The backward pass of a segment starts from the state it entered with: the
forward kernel, run again by the ``custom_vjp``'s forward rule, also writes
the state every chunk entered with (chunks x sequences x heads x 64 KiB,
freed with the segment); the backward kernel walks the chunks in reverse
with the state's cotangent in VMEM scratch, makes each chunk's parts again
and writes ``dq``, ``dk``, ``dv``, ``dg``, ``dbeta`` and at the end the
entering state's cotangent.  The inverse is differentiated as an inverse
(``dM = -T^T dT T^T`` on the strict lower triangle).  The cotangent of
``G`` keeps each sub-block's start as the jnp form's does (a row's factor
falls with it, a column's rises): in exact arithmetic the two cancel, but
each is rounded, and dropping them lets every rounding of a pair (t, i)
reach the log-decays of all the rows before i.

Precision
---------
Every product takes operands of the inputs' dtype where the jnp form's
does and accumulates in float32; the triangular algebra takes operands of
the inputs' dtype, which is what a float32 product at the TPU's default
precision rounds them to; the running sum and its transpose in the
backward pass are exact float32 sums (a product with a triangle of ones in
three bf16 parts); exponentials, the state and its cotangent are float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# A grid step takes several heads side by side (``_in_turn``): as many as
# divide the heads and whose blocks, held twice by the pipeline, fit
# ``BLOCKS_BYTES`` in the backward kernel, which holds the most; beside them
# a chunk's temporaries for every head.  Measured on the v5e at the segment
# of ``kimi_linear_fit_8k`` (2 sequences, 32 heads of 128, 16 chunks of 64,
# bf16; the rule alone, PERF.md, PR 35): 1 head a step 2.25 ms forward and
# 5.16 forward and backward, 2 heads 1.35 and 3.08, 4 heads 0.95 and 2.20,
# 8 heads 0.75 and 1.84; the jnp form 1.49 and 8.31.
HEADS = 8
BLOCKS_BYTES = 64 * 1024 * 1024
# the chip's default scoped limit is 16 MiB of its 128
VMEM_LIMIT_BYTES = 100 * 1024 * 1024
# a block of (rows, 128) may be this long (rows of a segment)
MAX_SEGMENT_ROWS = 2048
MAX_CHUNK = 128

NN = (((1,), (0,)), ((), ()))       # a @ b
NT = (((1,), (1,)), ((), ()))       # a @ b.T
TN = (((0,), (0,)), ((), ()))       # a.T @ b


def _mode():
    from .. import config
    return config.pallas_mode(cpu_default='reference')


def admits(rows, d_k, d_v, chunk, sub, dtype):
    """The static predicate: whether the kernels were written for a segment
    of ``rows`` tokens in chunks of ``chunk`` with heads of ``d_k`` and
    ``d_v`` channels in ``dtype``.  Head widths a multiple of 128 (a
    head is a column block of the projections), a chunk that is a multiple
    of the sub-block and of 16 (the rows of a bf16 tile), float32 or
    bfloat16."""
    return (jnp.dtype(dtype) in (jnp.dtype(jnp.float32),
                                 jnp.dtype(jnp.bfloat16)) and
            d_k % 128 == 0 and d_v == d_k and sub % 8 == 0 and
            chunk % sub == 0 and chunk % 16 == 0 and chunk <= MAX_CHUNK and
            rows % chunk == 0 and rows <= MAX_SEGMENT_ROWS)


def engages(rows, d_k, d_v, chunk, sub, dtype):
    """Whether the rule of such a segment runs in the kernels: on a TPU (or
    under the interpreter) and at shapes the kernels were written for."""
    return _mode() != 'reference' and admits(rows, d_k, d_v, chunk, sub,
                                             dtype)


def _dot(a, b, dims=NN):
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _sum_dot(ones, x, dims=NN):
    """``ones @ x`` for a float32 ``x`` and a matrix of zeros and ones in
    bf16, exact to float32: ``x`` in three bf16 parts."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    hi = x.astype(bf16)
    rest = x - hi.astype(f32)
    mid = rest.astype(bf16)
    lo = (rest - mid.astype(f32)).astype(bf16)
    return _dot(ones, hi, dims) + _dot(ones, mid, dims) + \
        _dot(ones, lo, dims)


def _ones(mask):
    return jnp.where(mask, 1.0, 0.0).astype(jnp.bfloat16)


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _in_turn(generators):
    """Runs ``generators`` side by side, a step of each in turn, and returns
    what each returned.  A chunk is a chain of small products, each waiting
    for the one before it, and the chip takes products in the order of the
    program: written head after head the chains run one after another;
    taken in turn, one head's product runs while another's drains.  The
    generators below ``yield`` after a product that the next step needs."""
    results, live = [None] * len(generators), list(enumerate(generators))
    while live:
        waiting = []
        for i, generator in live:
            try:
                next(generator)
                waiting.append((i, generator))
            except StopIteration as stop:
                results[i] = stop.value
        live = waiting
    return results


def _unit_lower_inverse(lower, sub, low):
    """``lm._unit_lower_inverse``, a generator: the inverse of ``I + N``,
    ``N`` = ``lower`` (C, C) strictly lower triangular, in two steps of
    Neumann products, the products' operands through ``low``."""
    c = lower.shape[0]
    rows, cols = _iota((c, c), 0), _iota((c, c), 1)
    eye = (rows == cols).astype(jnp.float32)

    def neumann(n, size):
        out, power = eye - n, n
        for _ in range(max(0, (size - 1).bit_length() - 1)):
            power = _dot(low(power), low(power))
            yield
            out = out + _dot(low(out), low(power))
            yield
        return out
    same = (rows // sub) == (cols // sub)
    inside = yield from neumann(jnp.where(same, lower, 0.0), sub)
    if sub >= c:
        return inside
    outside = _dot(low(inside), low(jnp.where(same, 0.0, lower)))
    yield
    over = yield from neumann(outside, c // sub)
    return _dot(low(over), low(inside))


class _Chunk(object):
    """A chunk of a head.  ``parts`` makes everything that does not need the
    state, ``step`` takes the state through it, ``backward`` their
    cotangents: generators, for ``_in_turn``."""

    def parts(self, q, k, v, g, beta, sums, sub, clamp):
        """From ``q``, ``k``, ``v`` (C, d) in the compute dtype, ``g`` (C,
        d) float32 at or over the floor and ``beta`` (C, 1) float32;
        ``sums`` the triangles of ones that make ``G`` (``_sums``)."""
        f32 = jnp.float32
        dtype = q.dtype
        c, d_k = q.shape
        self.low = low = lambda x: x.astype(dtype)
        self.blocks = c // sub
        self.qf, self.kf = qf, kf = q.astype(f32), k.astype(f32)
        self.beta = beta
        rows, cols = _iota((c, c), 0), _iota((c, c), 1)
        self.strict, self.lower, self.eye = cols < rows, cols <= rows, \
            rows == cols
        # G and, for every row, G at the start of its sub-block
        sums = _sum_dot(sums, g)
        yield
        self.total = total = sums[:c]
        start = sums[c:]
        self.row = row = jnp.exp(total - start)
        self.k_rows, self.q_rows = k_rows, q_rows = kf * row, qf * row
        # sub-block s: its rows of k and q times e^(G_t - start), and every
        # row i up to its end times e^(start - G_i)
        self.ecol, self.col = [], []
        a, b = [], []
        for s in range(self.blocks):
            at, end = s * sub, (s + 1) * sub
            lhs = low(jnp.concatenate([k_rows[at:end], q_rows[at:end]],
                                      axis=0))
            ecol = jnp.exp(jnp.minimum(start[at:at + 1] - total[:end],
                                       clamp))
            col = ecol * kf[:end]
            if end < c:
                zeros = jnp.zeros((c - end, d_k), f32)
                ecol = jnp.concatenate([ecol, zeros], axis=0)
                col = jnp.concatenate([col, zeros], axis=0)
            col = low(col)
            both = _dot(lhs, col, NT)                     # (2 sub, C)
            a.append(both[:sub])
            b.append(both[sub:])
            self.ecol.append(ecol)
            self.col.append(col)
        yield
        self.a = jnp.where(self.strict, jnp.concatenate(a, axis=0), 0.0)
        self.b = jnp.where(self.lower, jnp.concatenate(b, axis=0), 0.0)
        self.inverse = yield from _unit_lower_inverse(beta * self.a, sub,
                                                      low)
        yield
        self.decay = decay = jnp.exp(total)
        self.kd = kd = kf * decay
        # T = inverse Diag(beta): beta along the columns, as a row
        self.beta_row = jnp.sum(jnp.where(self.eye, beta, 0.0), axis=0,
                                keepdims=True)
        self.t = low(self.inverse * self.beta_row)
        self.kv = jnp.concatenate([low(kd), v], axis=1)
        wu = _dot(self.t, self.kv)                        # (C, d_k + d_v)
        yield
        self.w, self.u0 = low(wu[:, :d_k]), low(wu[:, d_k:])
        self.q_decayed = low(qf * decay)
        last = total[c - 1:c]
        self.rest = rest = jnp.exp(last - total)
        self.k_rest = low(kf * rest)
        self.decay_last = jnp.exp(last)                   # (1, d_k)

    def step(self, state):
        """The chunk from ``state`` (d_v, d_k) float32, the state
        transposed: the state as the products take it, ``U``, the outputs,
        the state after."""
        low = self.low
        c = self.w.shape[0]
        carried = low(state)
        through = _dot(jnp.concatenate([self.w, self.q_decayed], axis=0),
                       carried, NT)                       # (2 C, d_v)
        yield
        u = low(self.u0.astype(jnp.float32) - through[:c])
        out = through[c:] + _dot(low(self.b), u)
        after = state * self.decay_last + _dot(u, self.k_rest, TN)
        yield
        return carried, u, low(out), after

    def backward(self, state, d_out, d_after, sums):
        """The chunk's cotangents from those of its outputs ``d_out`` (C,
        d_v) and of the state after it ``d_after`` (d_v, d_k): of ``q``,
        ``k``, ``v``, ``g``, of beta as a row (1, C) and of ``state``.
        ``sums`` are the triangles of ones that turn the cotangent of ``G``
        into that of ``g`` (``_sums_backward``)."""
        f32 = jnp.float32
        p, low = self, self.low
        c, d_k = p.kf.shape
        sub = c // p.blocks
        carried, u, _, _ = yield from p.step(state)
        d_carried = low(d_after)
        # through the state: U, the outputs, the state after
        d_u = low(_dot(low(p.b), d_out, TN) + _dot(p.k_rest, d_carried, NT))
        d_b = jnp.where(p.lower, _dot(d_out, u, NT), 0.0)
        d_q_decayed = _dot(d_out, carried)                 # (C, d_k)
        d_k_rest = _dot(u, d_carried) * p.rest             # (C, d_k)
        d_decay_last = jnp.sum(d_after * state, axis=0, keepdims=True)
        yield
        d_w = low(-_dot(d_u, carried))                     # (C, d_k)
        d_state = d_after * p.decay_last + _dot(
            jnp.concatenate([d_out, -d_u], axis=0),
            jnp.concatenate([p.q_decayed, p.w], axis=0), TN)
        yield
        # through W = T (K e^G) and U0 = T V, T = inverse Diag(beta), and
        # through the inverse of M = I + Diag(beta) A
        d_wu = jnp.concatenate([d_w, d_u], axis=1)         # (C, d_k + d_v)
        d_t = _dot(d_wu, p.kv, NT)                         # (C, C)
        d_kv = _dot(p.t, d_wu, TN)                         # (C, d_k + d_v)
        yield
        d_kd, d_v = d_kv[:, :d_k], d_kv[:, d_k:]
        inverse = low(p.inverse)
        d_m = _dot(inverse, low(d_t * p.beta_row), TN)
        yield
        d_m = jnp.where(p.strict, -_dot(low(d_m), inverse, NT), 0.0)
        yield
        d_beta = jnp.sum(d_t * p.inverse, axis=0, keepdims=True) + jnp.sum(
            jnp.where(p.eye, jnp.sum(d_m * p.a, axis=1, keepdims=True), 0.0),
            axis=0, keepdims=True)
        d_a = p.beta * d_m
        # through the two triangles: the rows' factors and the columns'.
        # [dA; dB]^T, so that a sub-block's rows are a mask of lanes
        d_both = jnp.concatenate([d_a, d_b], axis=0).T     # (C, 2 C)
        lanes = _iota(d_both.shape, 1) % c // sub
        both_rows = low(jnp.concatenate([p.k_rows, p.q_rows], axis=0))
        d_rows, d_col_k = [], jnp.zeros((c, d_k), f32)
        # a sub-block's start, the row before it, gathers what its
        # columns' factors gain (``starts``) and its rows' lose (below)
        starts = []
        last_row = _iota((sub, d_k), 0) == sub - 1
        for s in range(p.blocks):
            at, end = s * sub, (s + 1) * sub
            d_lhs = low(jnp.concatenate([d_a[at:end], d_b[at:end]], axis=0))
            d_rows.append(_dot(d_lhs, p.col[s]))           # (2 sub, d_k)
            d_col = _dot(low(jnp.where(lanes == s, d_both, 0.0)),
                         both_rows) * p.ecol[s]
            d_col_k = d_col_k + d_col
            if s:
                starts.append(jnp.where(last_row, jnp.sum(
                    d_col * p.kf, axis=0, keepdims=True), 0.0))
        yield
        starts.append(jnp.zeros((sub, d_k), f32))
        d_k_rows = jnp.concatenate([r[:sub] for r in d_rows], axis=0)
        d_q_rows = jnp.concatenate([r[sub:] for r in d_rows], axis=0)
        d_q = low(d_q_rows * p.row + d_q_decayed * p.decay)
        d_k = low(d_k_rows * p.row + d_col_k + d_kd * p.decay + d_k_rest)
        # G: a row's factor rises with G_t and falls with its sub-block's
        # start, a column's rises with the start and falls with G_i
        d_row = d_k_rows * p.k_rows + d_q_rows * p.q_rows
        d_total = d_row + jnp.concatenate(starts, axis=0) + \
            d_q_decayed * p.qf * p.decay + \
            (d_kd * p.decay - d_col_k - d_k_rest) * p.kf
        d_last = jnp.sum(d_k_rest * p.kf, axis=0, keepdims=True) + \
            d_decay_last * p.decay_last
        # dg_j: the sum of dG over the rows from j on, less the rows'
        # shares of every sub-block that starts after j
        d_g = d_last + _sum_dot(
            sums, jnp.concatenate([d_total, -d_row], axis=0))
        return d_q, d_k, low(d_v), d_g, d_beta, d_state


def _sums(c, sub):
    """(2 C, C) ones: row t of the first C sums the rows up to t, row t of
    the last C the rows before t's sub-block."""
    rows, cols = _iota((2 * c, c), 0), _iota((2 * c, c), 1)
    return _ones(((rows < c) & (cols <= rows)) |
                 (cols < (rows - c) // sub * sub))


def _sums_backward(c, sub):
    """(C, 2 C) ones: row j sums the first C rows from j on, and of the
    last C the rows of every sub-block that starts after j."""
    rows, cols = _iota((c, 2 * c), 0), _iota((c, 2 * c), 1)
    return _ones(((cols < c) & (cols >= rows)) |
                 ((cols - c) // sub * sub > rows))


def _chunk_of(refs, i, b, heads, first, chunk, sums, sub, clamp):
    """Chunk ``i`` of head ``b`` of the step's ``heads``, the first of which
    is head ``first`` of the sequence: a generator that returns its rows,
    its columns among the step's keys and among its values, and its parts
    from ``refs`` (``q``, ``k``, ``v``, ``g`` (rows, heads * d) and beta
    (rows, H))."""
    q_ref, k_ref, v_ref, g_ref, beta_ref = refs
    d_k, d_v = q_ref.shape[1] // heads, v_ref.shape[1] // heads
    rows = pl.ds(pl.multiple_of(i * chunk, chunk), chunk)
    keys, values = pl.ds(b * d_k, d_k), pl.ds(b * d_v, d_v)
    # beta lies (rows, H): the head's column as (C, 1)
    beta = beta_ref[rows, :]
    beta = jnp.sum(jnp.where(_iota(beta.shape, 1) == first + b, beta, 0.0),
                   axis=1, keepdims=True)
    part = _Chunk()
    yield from part.parts(q_ref[rows, keys], k_ref[rows, keys],
                          v_ref[rows, values], g_ref[rows, keys], beta, sums,
                          sub, clamp)
    return rows, keys, values, part


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, state_ref, out_ref,
                after_ref, *rest, chunk, sub, clamp, keep, heads):
    """A step's heads' segment: the chunks in order, the (transposed)
    states in ``carry``."""
    entered_ref = rest[0] if keep else None
    carry = rest[-1]
    refs = (q_ref, k_ref, v_ref, g_ref, beta_ref)
    first = pl.program_id(1) * heads
    sums = _sums(chunk, sub)
    for b in range(heads):
        carry[b] = state_ref[b].T

    def one(i, _):
        def head(b):
            rows, _, values, part = yield from _chunk_of(
                refs, i, b, heads, first, chunk, sums, sub, clamp)
            state = carry[b]
            if keep:
                entered_ref[b, i] = state
            _, _, out, after = yield from part.step(state)
            out_ref[rows, values] = out
            carry[b] = after
        _in_turn([head(b) for b in range(heads)])
        return 0
    jax.lax.fori_loop(0, q_ref.shape[0] // chunk, one, 0)
    for b in range(heads):
        after_ref[b] = carry[b].T


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, entered_ref, d_out_ref,
                d_after_ref, dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref,
                d_state_ref, carry, *, chunk, sub, clamp, heads):
    """A step's heads' segment backward: the chunks in reverse, the
    cotangents of the (transposed) states in ``carry``."""
    refs = (q_ref, k_ref, v_ref, g_ref, beta_ref)
    first = pl.program_id(1) * heads
    chunks = q_ref.shape[0] // chunk
    sums, sums_backward = _sums(chunk, sub), _sums_backward(chunk, sub)
    for b in range(heads):
        carry[b] = d_after_ref[b].T

    def one(step, _):
        i = chunks - 1 - step

        def head(b):
            rows, keys, values, part = yield from _chunk_of(
                refs, i, b, heads, first, chunk, sums, sub, clamp)
            dq_ref[rows, keys], dk_ref[rows, keys], dv_ref[rows, values], \
                dg_ref[rows, keys], dbeta_ref[b, pl.ds(i, 1), :], carry[b] = \
                yield from part.backward(entered_ref[b, i],
                                         d_out_ref[rows, values], carry[b],
                                         sums_backward)
        _in_turn([head(b) for b in range(heads)])
        return 0
    jax.lax.fori_loop(0, chunks, one, 0)
    for b in range(heads):
        d_state_ref[b] = carry[b].T


def _heads(h, rows, d_k, d_v, chunk, dtype):
    """The heads a grid step takes: the most, up to ``HEADS``, that divide
    ``h`` and whose blocks fit ``BLOCKS_BYTES`` twice over."""
    low = jnp.dtype(dtype).itemsize
    a_head = 2 * (rows * (4 * d_k + 3 * d_v) * low + 2 * rows * d_k * 4 +
                  rows // chunk * d_v * d_k * 4)
    most = max(1, min(HEADS, BLOCKS_BYTES // a_head))
    return next(b for b in range(min(most, h), 0, -1) if h % b == 0)


def _specs(rows, h, heads, d_k, d_v, chunk):
    """For arrays (N, rows, H * d), beta (N, rows, H), a state (N, H, d_k,
    d_v), every chunk's state (N, H, chunks, d_v, d_k) and beta's cotangent
    by chunk (N, H, chunks, C): the block specs of a grid step (i, j), the
    segment of ``heads`` heads from head j x ``heads`` of sequence i."""
    vmem = functools.partial(pl.BlockSpec, memory_space=pltpu.VMEM)
    chunks = rows // chunk
    of_heads = lambda i, j: (i, j) + (0,) * 3
    return dict(
        keys=vmem((None, rows, heads * d_k), lambda i, j: (i, 0, j)),
        values=vmem((None, rows, heads * d_v), lambda i, j: (i, 0, j)),
        beta=vmem((None, rows, h), lambda i, j: (i, 0, 0)),
        state=vmem((None, heads, d_k, d_v), lambda i, j: of_heads(i, j)[:4]),
        entered=vmem((None, heads, chunks, d_v, d_k), of_heads),
        d_beta=vmem((None, heads, chunks, chunk),
                    lambda i, j: of_heads(i, j)[:4]))


def _call(kernel, interpret, n, groups, **kwargs):
    if not interpret:
        kwargs['compiler_params'] = pltpu.CompilerParams(
            dimension_semantics=('parallel', 'parallel'),
            vmem_limit_bytes=VMEM_LIMIT_BYTES)
    return pl.pallas_call(kernel, grid=(n, groups),
                          interpret=interpret, **kwargs)


def _forward(static, q, k, v, g, beta, state, keep):
    chunk, sub, clamp, interpret = static
    n, rows, h, d_k = q.shape
    d_v = v.shape[-1]
    heads = _heads(h, rows, d_k, d_v, chunk, q.dtype)
    spec = _specs(rows, h, heads, d_k, d_v, chunk)
    flat = lambda x: x.reshape(n, rows, -1)
    out_shape = [jax.ShapeDtypeStruct((n, rows, h * d_v), q.dtype),
                 jax.ShapeDtypeStruct(state.shape, jnp.float32)]
    out_specs = [spec['values'], spec['state']]
    if keep:
        out_shape.append(jax.ShapeDtypeStruct(
            (n, h, rows // chunk, d_v, d_k), jnp.float32))
        out_specs.append(spec['entered'])
    got = _call(
        functools.partial(_fwd_kernel, chunk=chunk, sub=sub, clamp=clamp,
                          keep=keep, heads=heads),
        interpret, n, h // heads,
        in_specs=[spec['keys'], spec['keys'], spec['values'], spec['keys'],
                  spec['beta'], spec['state']],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((heads, d_v, d_k), jnp.float32)],
    )(flat(q), flat(k), flat(v), flat(g), beta, state)
    return (got[1], got[0].reshape(n, rows, h, d_v)) + tuple(got[2:])


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _rule(static, q, k, v, g, beta, state):
    return _forward(static, q, k, v, g, beta, state, False)


def _rule_fwd(static, q, k, v, g, beta, state):
    after, out, entered = _forward(static, q, k, v, g, beta, state, True)
    return (after, out), (q, k, v, g, beta, entered)


def _rule_bwd(static, res, cotangent):
    chunk, sub, clamp, interpret = static
    q, k, v, g, beta, entered = res
    d_after, d_out = cotangent
    n, rows, h, d_k = q.shape
    d_v = v.shape[-1]
    heads = _heads(h, rows, d_k, d_v, chunk, q.dtype)
    spec = _specs(rows, h, heads, d_k, d_v, chunk)
    flat = lambda x: x.reshape(n, rows, -1)
    like = lambda d, dtype: jax.ShapeDtypeStruct((n, rows, h * d), dtype)
    dq, dk, dv, dg, dbeta, d_state = _call(
        functools.partial(_bwd_kernel, chunk=chunk, sub=sub, clamp=clamp,
                          heads=heads),
        interpret, n, h // heads,
        in_specs=[spec['keys'], spec['keys'], spec['values'], spec['keys'],
                  spec['beta'], spec['entered'], spec['values'],
                  spec['state']],
        out_specs=[spec['keys'], spec['keys'], spec['values'], spec['keys'],
                   spec['d_beta'], spec['state']],
        out_shape=[like(d_k, q.dtype), like(d_k, k.dtype),
                   like(d_v, v.dtype), like(d_k, jnp.float32),
                   jax.ShapeDtypeStruct((n, h, rows // chunk, chunk),
                                        jnp.float32),
                   jax.ShapeDtypeStruct((n, h, d_k, d_v), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((heads, d_v, d_k), jnp.float32)],
    )(flat(q), flat(k), flat(v), flat(g), beta, entered,
      flat(d_out.astype(q.dtype)), d_after.astype(jnp.float32))
    by_head = lambda x, d: x.reshape(n, rows, h, d)
    return (by_head(dq, d_k), by_head(dk, d_k), by_head(dv, d_v),
            by_head(dg, d_k),
            jnp.transpose(dbeta.reshape(n, h, rows), (0, 2, 1)), d_state)


_rule.defvjp(_rule_fwd, _rule_bwd)


def rule_segment(q, k, v, g, beta, state, chunk, sub, floor):
    """One segment of the rule in the kernels: ``q``, ``k`` (N, rows, H,
    d_k), ``v`` (N, rows, H, d_v), float32 ``g`` (N, rows, H, d_k) at or
    over ``floor``, float32 ``beta`` (N, rows, H) and the entering
    ``state`` (N, H, d_k, d_v) float32, ``rows`` a multiple of ``chunk``
    and that of the sub-block ``sub``; shapes ``engages`` accepts.  Returns
    the state after and the outputs (N, rows, H, d_v)."""
    static = (int(chunk), int(sub), float(-sub * floor),
              _mode() == 'interpret')
    return _rule(static, q, k, v, g, beta, state)
