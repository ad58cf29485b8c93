"""What the configurations' model files share on the benchmark's side: the
PRNG key of a run's seed, and the rounding the control applies. Nothing here
comes from the program."""
import functools

import jax
import jax.numpy as jnp


def seed_key(seed):
    """A PRNG key from any whole number, also past 2**31."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def _round(x, dtype):
    top = float(jnp.finfo(dtype).max)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def rounded(x, dtype):
    """x rounded to ``dtype`` with one scale per tensor (amax onto the
    type's largest value), as low-precision training recipes do; the
    cotangent is rounded the same way. Only the control uses it."""
    return _round(x, dtype)


rounded.defvjp(lambda x, dtype: (_round(x, dtype), None),
               lambda dtype, _, g: (_round(g, dtype),))

# precision of the reference -> what it does to every matmul/convolution
# operand and every activation: nothing, or the control's rounding
OPERAND = {"float32": lambda x: x,
           "fp8": lambda x: rounded(x, jnp.float8_e4m3fn)}
