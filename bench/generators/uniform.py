"""GAP's ``urand``: both endpoints of every arc uniform over the 2**scale
vertices; ``edge_factor`` arcs a vertex."""
import jax
import jax.numpy as jnp


def arcs(config: dict, key):
    """(n, src, dst)."""
    n = 1 << int(config["scale"])
    m = n * int(config["edge_factor"])
    k_src, k_dst = jax.random.split(key)
    return (n, jax.random.randint(k_src, (m,), 0, n, jnp.int32),
            jax.random.randint(k_dst, (m,), 0, n, jnp.int32))
