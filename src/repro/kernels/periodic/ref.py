"""Pure-jnp oracle for the Sherman–Morrison correction kernel."""

import jax

from repro.core.tridiag.partition import rank_one_update


def periodic_correction_ref(
    y: jax.Array, z: jax.Array, beta: jax.Array, axis: int
) -> jax.Array:
    return rank_one_update(y, z, beta, axis)
