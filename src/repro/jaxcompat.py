"""The few jax APIs this repo wraps, written for the one pinned jax
release (see ``requirements.txt``).  Import from here, not from jax
directly:

* ``shard_map`` — ``jax.shard_map`` (vma-typed).
* ``mark_varying`` — casts loop carries to device-varying under the vma
  type system (``jax.lax.pcast``), so ``while_loop`` carries built from
  replicated constants type-check inside ``shard_map``.
* ``make_mesh`` — ``jax.make_mesh`` with Auto axis types:
  plain ``jax.make_mesh`` builds Explicit axes, under which ordinary
  indexing of a sharded result (``x[:n]``) raises ``ShardingTypeError``.
"""
from __future__ import annotations

import jax

shard_map = jax.shard_map


def mark_varying(tree, axis):
    """Mark loop carries as device-varying along ``axis`` (shard_map vma
    typing); leaves that already vary are passed through."""
    names = (axis,) if isinstance(axis, str) else tuple(axis)

    def cast(x):
        vma = getattr(getattr(x, "aval", None), "vma", frozenset())
        missing = tuple(a for a in names if a not in vma)
        return jax.lax.pcast(x, missing, to="varying") if missing else x

    return jax.tree.map(cast, tree)


def make_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axis types."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
