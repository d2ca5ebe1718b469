"""Engine checkpoint/restore over the ``fault/checkpoint.py`` writer.

The engines serialize through the ``state_dict()/state_meta()/
load_state()`` protocol on :class:`~repro.core.enginebase.EngineBase`
(DESIGN.md §14): ``state_dict`` is a flat ``{name: array}`` tree (the
graph, transpose/overlay caches, persistent fixpoint state),
``state_meta`` is the JSON side — engine family, the plan kwargs needed
to re-plan in a fresh process, and the accounting counters.  This module
is the glue: it writes both through the existing manifest-based
``fault.checkpoint`` layout (atomic tmp-dir rename, one ``.npy`` per
leaf), arms the ``"checkpoint-write"`` fault point, feeds the
``repro_checkpoint_seconds`` metric family, and rebuilds a live engine
from a checkpoint with :func:`restore_engine`.

Saves go through :func:`save_tree`, either synchronously or via a
``fault.checkpoint.AsyncCheckpointer`` (the host copy happens inline
either way, so the engine may mutate immediately after the call).
"""
from __future__ import annotations

import time

import numpy as np

from . import checkpoint as _ckpt
from .plane import get_fault_plane


def _observe_checkpoint(elapsed: float, mode: str) -> None:
    from .. import obs
    mp = obs.get_plane()
    if mp.enabled:
        mp.histogram(
            "repro_checkpoint_seconds",
            "checkpoint save latency on the caller's thread (async mode "
            "measures the inline host copy + enqueue)",
        ).observe(elapsed, mode=mode)


def save_tree(ckpt_dir: str, step: int, tree: dict,
              metadata: dict | None = None, *, checkpointer=None) -> int:
    """Write one checkpoint through the manifest-based writer.

    Arms the ``"checkpoint-write"`` fault point first — a fired fault
    aborts *before* any bytes move, and the writer's atomic tmp-dir
    rename guarantees a torn write can never shadow the previous good
    step either way.  ``checkpointer`` (an ``AsyncCheckpointer``) moves
    the disk IO off the caller's thread.  Returns ``step``."""
    plane = get_fault_plane()
    if plane.enabled:
        plane.arm("checkpoint-write", step=step, dir=ckpt_dir)
    t0 = time.perf_counter()
    if checkpointer is not None:
        checkpointer.save(step, tree, metadata)
        mode = "async"
    else:
        _ckpt.save(ckpt_dir, step, tree, metadata)
        mode = "sync"
    _observe_checkpoint(time.perf_counter() - t0, mode)
    return step


def save_engine(ckpt_dir: str, engine, step: int, *,
                extra_tree: dict | None = None,
                extra_meta: dict | None = None, checkpointer=None) -> int:
    """Checkpoint one engine (plus optional caller state riding along,
    e.g. serve's feed arrays).  The engine's meta lands under the
    ``"engine"`` metadata key, where :func:`restore_engine` expects it."""
    tree = dict(engine.state_dict())
    if extra_tree:
        tree.update(extra_tree)
    meta = {"engine": engine.state_meta()}
    if extra_meta:
        meta.update(extra_meta)
    return save_tree(ckpt_dir, step, tree, meta, checkpointer=checkpointer)


def engine_from_state(tree: dict, em: dict):
    """Rebuild a live engine from a checkpoint tree + its ``"engine"``
    metadata: re-plan from the recorded plan kwargs (compiled runners
    come back from the process-wide jit cache or retrace once), then
    ``load_state`` overwrites every state array with the checkpoint's
    exact values — resume is bit-identical, not merely equivalent."""
    import jax.numpy as jnp

    from ..core.graph import CSRGraph

    family = em["family"]
    kwargs = dict(em.get("plan_kwargs", {}))
    if family == "stream":
        from ..core.stream import plan_stream
        base = CSRGraph(jnp.asarray(np.asarray(tree["base_indptr"]),
                                    jnp.int32),
                        jnp.asarray(np.asarray(tree["base_indices"]),
                                    jnp.int32))
        engine = plan_stream(base, **kwargs)
    elif family in ("trim", "reach", "peel"):
        graph = CSRGraph(jnp.asarray(np.asarray(tree["graph_indptr"]),
                                     jnp.int32),
                         jnp.asarray(np.asarray(tree["graph_indices"]),
                                     jnp.int32))
        if family == "trim":
            from ..core.engine import plan as plan_fn
        elif family == "reach":
            from ..core.reach import plan_reach as plan_fn
        else:
            from ..core.peel import plan_peel as plan_fn
        engine = plan_fn(graph, **kwargs)
    else:
        raise ValueError(f"cannot restore unknown engine family "
                         f"{family!r}")
    engine.load_state(tree, em)
    return engine


def restore_engine(ckpt_dir: str, step: int | None = None):
    """Load the latest (or a specific) checkpoint and rebuild its engine.

    Returns ``(engine, step, tree, meta)`` — the raw tree and metadata
    ride along so callers can recover their own state saved via
    ``save_engine(extra_tree=..., extra_meta=...)``."""
    tree, step, meta = _ckpt.load_flat(ckpt_dir, step)
    if "engine" not in meta:
        raise ValueError(f"checkpoint step {step} in {ckpt_dir!r} has no "
                         "'engine' metadata (not written by save_engine)")
    engine = engine_from_state(tree, meta["engine"])
    return engine, step, tree, meta


__all__ = ["save_tree", "save_engine", "engine_from_state",
           "restore_engine"]
