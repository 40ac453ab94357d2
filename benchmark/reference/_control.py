"""What the references' control forwards share: `forward(.., lower=<dtype>)`
is the same equations with both operands of every matmul rounded to
`<dtype>` first; the arithmetic stays float32. A referee's limit has to
call that forward wrong."""
import jax.numpy as jnp


def rounded(x, dtype):
    """float32 `x` with its values rounded to `dtype` (None: as it is);
    a one-byte type is scaled to the tensor's largest magnitude, as an
    8-bit forward scales its tensors."""
    x = x.astype(jnp.float32)
    if dtype is None:
        return x
    dtype = jnp.dtype(dtype)
    if dtype.itemsize > 1:
        return x.astype(dtype).astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) \
        / float(jnp.finfo(dtype).max)
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


def mm(a, b, lower):
    return rounded(a, lower) @ rounded(b, lower)
