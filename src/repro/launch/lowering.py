"""Shared abstract-lowering cache (DESIGN.md §15).

``repro.analysis`` traces plan jaxprs for the purity lint and the
instrument-diff pass through :func:`trace_jaxpr`, which traces each plan
once per process.  Jitted runners are lru-cached per static
configuration, so the cache key is the runner's identity plus the
abstract input pytree.
"""
from __future__ import annotations

from typing import Callable

import jax

_JAXPR_CACHE: dict = {}
_STATS = {"jaxpr_hits": 0, "jaxpr_misses": 0}


def _args_key(abstract_args: tuple) -> tuple:
    leaves, treedef = jax.tree_util.tree_flatten(abstract_args)
    return (treedef, tuple((tuple(x.shape), str(x.dtype)) for x in leaves))


def trace_jaxpr(fn: Callable, *abstract_args):
    """``jax.make_jaxpr(fn)(*abstract_args)``, cached process-wide.

    ``fn`` must be a stable callable (the engines' lru-cached jitted
    runners qualify: one object per static configuration); the abstract
    args are ``ShapeDtypeStruct`` pytrees.
    """
    key = (id(fn), _args_key(abstract_args))
    if key in _JAXPR_CACHE:
        _STATS["jaxpr_hits"] += 1
        return _JAXPR_CACHE[key][1]
    _STATS["jaxpr_misses"] += 1
    jaxpr = jax.make_jaxpr(fn)(*abstract_args)
    # the cache entry pins fn: a collected callable's id could be reused
    # by a different function with same-shaped args, aliasing the key
    _JAXPR_CACHE[key] = (fn, jaxpr)
    return jaxpr


def cache_stats() -> dict:
    return dict(_STATS, jaxprs=len(_JAXPR_CACHE))

