"""Weights from the seed, made on the device in one jitted call.

Every learnable array of a symbol, float32 (the type the program keeps
master parameters and serves in): ``*_weight`` drawn from a normal
distribution with variance 2 / fan-in (He et al. 2015; what
``mx.init.Xavier(rnd_type='gaussian', factor_type='in', magnitude=2)``,
the initializer of ``examples/train_imagenet.py``, draws), ``*_gamma``
and ``*_moving_var`` one, ``*_bias``, ``*_beta`` and ``*_moving_mean``
zero.
"""
import jax
import jax.numpy as jnp
import numpy as np


def param_shapes(symbol, input_shapes):
    """``(arguments, auxiliary states)`` as name -> shape, without the
    inputs named in ``input_shapes`` and without labels."""
    arg_shapes, _, aux_shapes = symbol.infer_shape(**input_shapes)
    args = {n: tuple(s) for n, s in zip(symbol.list_arguments(), arg_shapes)
            if n not in input_shapes and not n.endswith('label')}
    aux = {n: tuple(s) for n, s in
           zip(symbol.list_auxiliary_states(), aux_shapes)}
    return args, aux


def make(symbol, input_shapes, seed):
    """``(arg_params, aux_params)`` as name -> float32 device array."""
    args, aux = param_shapes(symbol, input_shapes)
    shapes = dict(args, **aux)
    drawn_names = sorted(n for n in shapes if n.endswith('_weight'))
    sizes = [int(np.prod(shapes[n])) for n in drawn_names]

    @jax.jit
    def make_all(key):
        # one draw for every weight, cut up: a program of a few ops
        flat = jax.random.normal(key, (sum(sizes),), jnp.float32)
        out, start = {}, 0
        for name, size in zip(drawn_names, sizes):
            shape = shapes[name]
            scale = np.float32(np.sqrt(2.0 / np.prod(shape[1:])))
            out[name] = flat[start:start + size].reshape(shape) * scale
            start += size
        for name, shape in shapes.items():
            if name in out:
                continue
            if name.endswith(('_gamma', '_moving_var')):
                out[name] = jnp.ones(shape, jnp.float32)
            elif name.endswith(('_bias', '_beta', '_moving_mean')):
                out[name] = jnp.zeros(shape, jnp.float32)
            else:
                raise ValueError('benchmark/weights.py does not know how '
                                 'to make %r' % name)
        return out

    # any whole number is a seed: fold it into the 31 bits a key takes
    made = make_all(jax.random.PRNGKey(int(seed) % (2 ** 31 - 1)))
    return ({n: made[n] for n in args}, {n: made[n] for n in aux})
