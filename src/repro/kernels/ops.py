"""Jit'd public wrappers for the Pallas kernels.

``use_kernel=None`` picks the Pallas kernel when a TPU is attached and the
jnp reference twin (``kernels.ref``) otherwise.  A kernel forced on
without a TPU runs in the Pallas interpreter; on a TPU it is always
compiled.  Engine code calls these wrappers, never pallas_call directly.

Every wrapper notes its kernel choice (``use_kernel`` and ``interpret``)
to the span recorder via :func:`repro.obs.note_kernel`.  Inside a jitted
caller that Python runs at *trace* time only, so each note marks a kernel
selection being baked into a fresh executable — retrace attribution for
free, and a no-op (one attribute read) when no recorder is installed.
"""
from __future__ import annotations

import jax

from .. import obs
from . import ref
from .bucket_peel import bucket_peel_pallas as _bpl
from .counter_scatter import counter_scatter_pallas as _csc
from .first_live_scan import first_live_scan as _fls
from .frontier_compact import frontier_compact_pallas as _fcp
from .frontier_compact import sparse_expand_pallas as _sxp
from .frontier_expand import frontier_expand as _fex


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _select(kernel: str, use_kernel: bool | None) -> tuple[bool, bool]:
    """Resolve ``use_kernel=None`` (the Pallas kernel iff a TPU is
    attached), note the choice to the span recorder, and return
    ``(use_kernel, interpret)``: off-TPU a forced kernel runs in the
    Pallas interpreter, on a TPU it is compiled by Mosaic."""
    if use_kernel is None:
        use_kernel = on_tpu()
    use_kernel = bool(use_kernel)
    interpret = use_kernel and not on_tpu()
    obs.note_kernel(kernel, use_kernel=use_kernel, interpret=interpret)
    return use_kernel, interpret


def first_live_scan(flags, valid, active, use_kernel: bool | None = None,
                    **kw):
    use_kernel, interpret = _select("first_live_scan", use_kernel)
    if use_kernel:
        return _fls(flags, valid, active, interpret=interpret, **kw)
    return ref.first_live_ref(flags, valid, active)


def frontier_expand(flags, valid, pending, use_kernel: bool | None = None,
                    **kw):
    use_kernel, interpret = _select("frontier_expand", use_kernel)
    if use_kernel:
        return _fex(flags, valid, pending, interpret=interpret, **kw)
    return ref.frontier_expand_ref(flags, valid, pending)


def frontier_compact(mask, capacity: int, use_kernel: bool | None = None,
                     **kw):
    """(n,) bool -> (ids, count): frontier members compacted into a
    static (capacity,) int32 buffer (sentinel n) + the member count."""
    use_kernel, interpret = _select("frontier_compact", use_kernel)
    if use_kernel:
        return _fcp(mask, capacity, interpret=interpret, **kw)
    return ref.frontier_compact_ref(mask, capacity)


def sparse_expand(indptr, indices, ids, ecap: int,
                  use_kernel: bool | None = None, **kw):
    """CSR rows of compacted ``ids`` expanded into a static (ecap,) edge
    buffer: ``(src, tgt, pos, valid)`` per slot."""
    use_kernel, interpret = _select("sparse_expand", use_kernel)
    if use_kernel:
        return _sxp(indptr, indices, ids, ecap, interpret=interpret, **kw)
    return ref.sparse_expand_ref(indptr, indices, ids, ecap)


def counter_scatter(counters, status, upd_src, upd_delta,
                    use_kernel: bool | None = None, **kw):
    use_kernel, interpret = _select("counter_scatter", use_kernel)
    if use_kernel:
        return _csc(counters, status, upd_src, upd_delta,
                    interpret=interpret, **kw)
    return ref.counter_scatter_ref(counters, status, upd_src, upd_delta)


def bucket_peel(counters, alive, k, use_kernel: bool | None = None, **kw):
    use_kernel, interpret = _select("bucket_peel", use_kernel)
    if use_kernel:
        return _bpl(counters, alive, k, interpret=interpret, **kw)
    return ref.bucket_peel_ref(counters, alive, k)
