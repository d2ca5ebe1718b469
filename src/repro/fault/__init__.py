"""FaultPlane: checkpoint/resume + deterministic fault injection
(DESIGN.md §14).

The robustness layer of the graph stack, mirroring the MetricsPlane
pattern (§13): a process-global, disabled-by-default plane the engines
arm at named fault points, a seeded replayable
:class:`~repro.fault.schedule.FaultSchedule`, bounded-backoff
:func:`~repro.fault.retry.call_with_retries`, and engine
checkpoint/restore glue over the ``fault/checkpoint.py`` manifest writer.

The checkpoint helpers (``save_engine``/``restore_engine``/...) are
re-exported lazily so importing :mod:`repro.fault` from the engine hot
path (``core/enginebase.py``) never drags in the checkpoint writer.
"""
from .plane import (FaultPlane, get_fault_plane, injecting_faults,
                    set_fault_plane)
from .retry import backoff_delay, call_with_retries
from .schedule import (FAULT_POINTS, IO_POINTS, DeviceFault, FaultSchedule,
                       IOFault, fault_kind)

_CKPT_EXPORTS = ("save_tree", "save_engine", "engine_from_state",
                 "restore_engine")


def __getattr__(name):
    if name in _CKPT_EXPORTS:
        from . import ckpt
        return getattr(ckpt, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


__all__ = [
    "FaultPlane", "get_fault_plane", "set_fault_plane", "injecting_faults",
    "FaultSchedule", "DeviceFault", "IOFault", "fault_kind",
    "FAULT_POINTS", "IO_POINTS",
    "call_with_retries", "backoff_delay",
    *_CKPT_EXPORTS,
]
