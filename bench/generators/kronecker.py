"""The Graph500 Kronecker generator: at each of ``scale`` levels every arc
picks one quadrant of the adjacency matrix with probabilities A, B, C and
D = 1 - A - B - C (``initiator``); ``edge_factor`` arcs a vertex."""
import jax
import jax.numpy as jnp


def arcs(config: dict, key):
    """(n, src, dst): the arcs as labelled before scrambling, so the hubs
    sit in the lowest labels."""
    scale = int(config["scale"])
    n = 1 << scale
    m = n * int(config["edge_factor"])
    a, b, c = (float(config["initiator"][k]) for k in "abc")

    def level(i, arcs):
        src, dst = arcs
        r = jax.random.uniform(jax.random.fold_in(key, i), (m,))
        lower = r >= a + b                                  # quadrant C or D
        right = ((r >= a) & (r < a + b)) | (r >= a + b + c)       # B or D
        return (2 * src + lower.astype(jnp.int32),
                2 * dst + right.astype(jnp.int32))
    zeros = jnp.zeros((m,), jnp.int32)
    return (n, *jax.lax.fori_loop(0, scale, level, (zeros, zeros)))
