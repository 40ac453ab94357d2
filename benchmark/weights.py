"""The benchmark's own weights: every leaf from --seed, on the device, in
one jitted call, in the dtype the configuration is served in.

The program's constructors draw their own initial values leaf by leaf on
the host; the benchmark replaces them, so that a change to an initializer
or to the order of construction cannot move the inputs of a cell, and the
reference gets the very same arrays.

The rule, by the leaf's name and rank in the model's `state_dict`:
  rank >= 2                 normal(0, std)        matrices, embeddings
  rank 1, name ends "bias"  `bias` (0)            every bias
  rank 1, otherwise         `norm_scale` (1)      LayerNorm / RMSNorm scales
"""
import functools

import jax
import jax.numpy as jnp


def leaf_rule(name, shape):
    if len(shape) >= 2:
        return "normal"
    return "bias" if name.endswith("bias") else "scale"


@functools.partial(jax.jit, static_argnames=("spec", "std", "scale", "bias",
                                             "dtype"))
def _make(key, spec, std, scale, bias, dtype):
    out = {}
    for i, (name, shape) in enumerate(spec):
        rule = leaf_rule(name, shape)
        if rule == "normal":
            k = jax.random.fold_in(key, i)
            out[name] = (jax.random.normal(k, shape, jnp.float32)
                         * std).astype(dtype)
        else:
            out[name] = jnp.full(shape, scale if rule == "scale" else bias,
                                 dtype)
    return out


def make_weights(named_shapes, seed, std=0.02, norm_scale=1.0, bias=0.0,
                 dtype="bfloat16"):
    """{name: array} for [(name, shape), ...]; the same seed gives the
    same arrays. One compiled program, so set-up pays one dispatch."""
    spec = tuple((n, tuple(int(d) for d in s)) for n, s in named_shapes)
    return _make(jax.random.PRNGKey(int(seed)), spec, float(std),
                 float(norm_scale), float(bias), jnp.dtype(dtype).name)
