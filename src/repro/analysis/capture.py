"""Capture ``pallas_call`` configurations without running the kernels.

The race detector needs every kernel's ``grid`` and output ``BlockSpec``
``index_map``s exactly as the kernel wrapper constructs them for a given
concrete shape — including data-dependent grid sizes (``pl.cdiv``) and
closure-captured block sizes.  Rather than re-deriving that logic here
(which would drift), we trace the *real* wrapper under ``jax.eval_shape``
with ``jax.experimental.pallas.pallas_call`` temporarily replaced by a
recorder.  The recorder stores the full call configuration and returns a
zeros-stub with the declared ``out_shape`` structure so tracing proceeds;
nothing is compiled or executed.

Kernel wrappers in this repo are ``jax.jit``-wrapped; the capture helper
traces with jit disabled, so a previously cached jit trace, of the
wrapper or of a jitted helper it calls, can never skip our recorder.
"""
from __future__ import annotations

import functools
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Any, Callable, Iterator

import jax
import jax.numpy as jnp
from jax.experimental import pallas

_REAL_PALLAS_CALL = pallas.pallas_call


def _as_tuple(x: Any) -> tuple:
    if x is None:
        return ()
    if isinstance(x, (tuple, list)):
        return tuple(x)
    return (x,)


def unwrap_body(fn: Callable) -> Callable:
    """Strip ``functools.partial`` layers off a kernel body."""
    while isinstance(fn, functools.partial):
        fn = fn.func
    return fn


@dataclass
class PallasCapture:
    """One recorded ``pallas_call`` invocation (abstract, never executed)."""

    body: Callable
    grid: tuple[int, ...]
    in_specs: tuple
    out_specs: tuple
    out_shape: Any  # original pytree of ShapeDtypeStruct
    out_shapes: tuple  # flattened leaves, aligned with out_specs
    scratch_shapes: tuple
    kwargs: dict = field(default_factory=dict)

    @property
    def body_key(self) -> tuple[str, str]:
        b = unwrap_body(self.body)
        return (getattr(b, "__module__", "?"), getattr(b, "__qualname__", repr(b)))

    @property
    def body_name(self) -> str:
        mod, qual = self.body_key
        return f"{mod}.{qual}"

    @property
    def has_carry(self) -> bool:
        """True when the kernel asks for scratch memory (cross-step carry)."""
        return len(self.scratch_shapes) > 0


def _record(records: list[PallasCapture], kernel: Callable, **kwargs) -> Callable:
    out_shape = kwargs.get("out_shape")
    leaves = jax.tree_util.tree_leaves(
        out_shape, is_leaf=lambda x: hasattr(x, "shape") and hasattr(x, "dtype")
    )
    cap = PallasCapture(
        body=kernel,
        grid=_as_tuple(kwargs.get("grid")),
        in_specs=_as_tuple(kwargs.get("in_specs")),
        out_specs=_as_tuple(kwargs.get("out_specs")),
        out_shape=out_shape,
        out_shapes=tuple(leaves),
        scratch_shapes=_as_tuple(kwargs.get("scratch_shapes")),
        kwargs={k: v for k, v in kwargs.items()
                if k not in ("out_shape", "grid", "in_specs", "out_specs",
                             "scratch_shapes")},
    )
    records.append(cap)

    def _stub(*args, **_):
        return jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype),
            out_shape,
            is_leaf=lambda x: hasattr(x, "shape") and hasattr(x, "dtype"),
        )

    return _stub


@contextmanager
def captured_calls() -> Iterator[list[PallasCapture]]:
    """Swap ``pallas.pallas_call`` for a recorder within the block."""
    records: list[PallasCapture] = []

    def fake_pallas_call(kernel, **kwargs):
        return _record(records, kernel, **kwargs)

    pallas.pallas_call = fake_pallas_call
    try:
        yield records
    finally:
        pallas.pallas_call = _REAL_PALLAS_CALL


def capture_kernel(fn: Callable, *abstract_args, **static_kwargs) -> list[PallasCapture]:
    """Trace ``fn`` on abstract args, returning every pallas_call it makes.

    ``fn`` may be a ``jax.jit`` wrapper: jit is disabled while it is
    traced, so process-wide jit caches cannot bypass the recorder, nor
    keep a trace made with it.  A wrapper may legitimately make several
    pallas calls (``frontier_compact_pallas`` calls the
    ``prefix_positions`` scan first); all are returned in call order.
    """
    with captured_calls() as records, jax.disable_jit():
        jax.eval_shape(lambda *a: fn(*a, **static_kwargs), *abstract_args)
    return records
