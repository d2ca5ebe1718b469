"""The graphs of the benchmark's configurations, made on the device from
the run's seed in one jitted call.

A configuration names its generator, ``bench/generators/<generator>.py``,
whose ``arcs(config, key)`` draws the arcs.  The arcs come from the
configuration's fixed ``structure_seed``, as GAP (arXiv:1508.03619) and
Graph500 fix one graph per benchmark; the run's seed draws a random
permutation of the vertex labels, as Graph500 requires of its Kronecker
graphs.  So every seed gets the same graph relabelled: the same work in
another order.

Arcs are kept as generated, duplicates and self-loops included, and
directed: none is mirrored.  The CSR is sorted on the device, stable by
source so a row keeps generation order, and so is the transpose, stable
by target.  Nothing passes through the host; :func:`to_host` copies the
arrays out for the plain references.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int):
    """A PRNG key that depends on every bit of ``seed`` (``jax.random.key``
    keeps only the low 32)."""
    if not 0 <= seed < 1 << 64:
        raise ValueError(f"seed must lie in [0, 2**64), got {seed}")
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def build(generator, config: dict, seed: int):
    """(G, Gᵀ) as ``CSRGraph`` pairs on the default device; ``generator``
    is the module of ``bench/generators/`` that the configuration names."""
    from repro.core import CSRGraph

    @jax.jit
    def make(arc_key, label_key):
        n, src, dst = generator.arcs(config, jax.random.fold_in(arc_key, 0))
        perm = jax.random.permutation(jax.random.fold_in(label_key, 1),
                                      n).astype(jnp.int32)
        src, dst = perm[src], perm[dst]
        return (*_csr(src, dst, n), *_csr(dst, src, n))

    arrays = make(seed_key(int(config["structure_seed"])), seed_key(seed))
    return CSRGraph(*arrays[:2]), CSRGraph(*arrays[2:])


def _csr(rows, cols, n):
    rows, cols = jax.lax.sort((rows, cols), num_keys=1, is_stable=True)
    indptr = jnp.searchsorted(rows, jnp.arange(n + 1, dtype=jnp.int32),
                              side="left").astype(jnp.int32)
    return indptr, cols


def to_host(g) -> tuple[np.ndarray, np.ndarray]:
    """(indptr, indices) of a device ``CSRGraph`` as numpy arrays."""
    return np.asarray(g.indptr), np.asarray(g.indices)
