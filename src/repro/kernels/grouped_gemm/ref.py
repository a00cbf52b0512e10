"""Pure-jnp oracle of the ragged grouped expert FFN: XLA's ``ragged_dot``."""
import jax


def ragged_swiglu_ref(xs, wg, wu, wd, sizes):
    """The SwiGLU expert FFN as three ragged products, each rounded to
    ``xs.dtype``: ``(silu(xs @ wg[g]) * (xs @ wu[g])) @ wd[g]`` for the
    rows of each group g."""
    g = jax.nn.silu(jax.lax.ragged_dot(xs, wg, sizes))
    u = jax.lax.ragged_dot(xs, wu, sizes)
    return jax.lax.ragged_dot(g * u, wd, sizes)
