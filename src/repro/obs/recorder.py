"""Process-global span recorder (DESIGN.md §11).

A span is a named, categorized wall-time interval with free-form
attributes.  The engines' shared ``EngineBase._dispatch`` emits one span
per device dispatch (engine family, plan signature, compile-vs-execute
phase, retrace attribution); drivers add their own structural spans (the
SCC driver's plan, transpose, generations, phases and host syncs; the
serving loop's ticks).

Every ``span(name, cat, **attrs)`` is one ``jax.profiler.TraceAnnotation``
named ``<cat>.<name>`` (``engine.dispatch``, ``scc.generation``,
``serve.tick``) with the attributes as its metadata, so it lands in any
profiler trace on the host clock the device events are aligned to.
Without an active trace the annotation is an inactive ``TraceMe``.

A :class:`Recorder` additionally collects each span as a :class:`Span`
record.  The process-global recorder is **disabled** by default: a span
then records nothing (the ``with`` target is ``None``) and
``add``/``instant`` return immediately.  Install an enabled recorder for
a scope with::

    with obs.recording() as rec:
        engine.run()
    rec.to_chrome_trace("trace.json")        # chrome://tracing
    rec.to_jsonl("spans.jsonl")              # one span per line

Either way the span object keeps its duration in ``seconds`` after it
exits, from the same ``time.perf_counter`` pair the record uses, for
callers that keep a counter of it (``scc_decompose``'s ``plan_s``,
``transpose_s`` and ``sync_s``).

Record timestamps are ``time.perf_counter`` seconds relative to the
recorder's epoch (its construction time), so spans from one recorder
share a monotonic timeline regardless of wall-clock adjustments.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Any, Dict, List, Optional

from jax.profiler import TraceAnnotation

from . import export as _export
from . import metrics as _metrics


@dataclasses.dataclass
class Span:
    """One recorded interval (``ph="X"``) or instant event (``ph="i"``).

    ts/dur are seconds relative to the owning recorder's epoch; exporters
    convert to microseconds (the chrome ``trace_event`` unit).
    """

    name: str
    cat: str = "span"
    ts: float = 0.0
    dur: float = 0.0
    ph: str = "X"
    attrs: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def to_dict(self) -> dict:
        return {"name": self.name, "cat": self.cat, "ph": self.ph,
                "ts": self.ts, "dur": self.dur, "attrs": dict(self.attrs)}


class Recorder:
    """Span collector.  Construct enabled; the module-global default is a
    disabled instance (see :func:`get_recorder`)."""

    def __init__(self, enabled: bool = True):
        self.enabled = enabled
        self.spans: List[Span] = []
        self.epoch = time.perf_counter()

    def clear(self) -> None:
        self.spans = []
        self.epoch = time.perf_counter()

    # -- recording ---------------------------------------------------------
    def span(self, name: str, cat: str = "span", **attrs) -> "SpanScope":
        """Context manager timing its body as one :class:`SpanScope`.
        Yields the mutable :class:`Span` (attrs may be filled in from
        inside the body); yields ``None`` and records nothing when
        disabled."""
        return SpanScope(self, name, cat, attrs)

    def _open(self, name: str, cat: str, t0: float, attrs) -> Span:
        return Span(name=name, cat=cat, ts=t0 - self.epoch,
                    attrs=dict(attrs))

    def _close(self, sp: Span, t0: float, dur: float) -> None:
        sp.dur = dur
        self.spans.append(sp)

    def add(self, name: str, cat: str = "span", *, ts: float, dur: float,
            **attrs) -> Optional[Span]:
        """Record an already-measured interval (``ts`` in perf_counter
        seconds, absolute — converted to the recorder's epoch)."""
        if not self.enabled:
            return None
        sp = Span(name=name, cat=cat, ts=ts - self.epoch, dur=dur,
                  attrs=dict(attrs))
        self.spans.append(sp)
        return sp

    def instant(self, name: str, cat: str = "instant",
                **attrs) -> Optional[Span]:
        if not self.enabled:
            return None
        sp = Span(name=name, cat=cat, ph="i",
                  ts=time.perf_counter() - self.epoch, attrs=dict(attrs))
        self.spans.append(sp)
        return sp

    # -- queries -----------------------------------------------------------
    def select(self, name: Optional[str] = None, cat: Optional[str] = None,
               **attrs) -> List[Span]:
        """Spans matching every given criterion (attrs match by
        equality on ``span.attrs``)."""
        out = []
        for sp in self.spans:
            if name is not None and sp.name != name:
                continue
            if cat is not None and sp.cat != cat:
                continue
            if any(sp.attrs.get(k) != v for k, v in attrs.items()):
                continue
            out.append(sp)
        return out

    def total(self, name: Optional[str] = None, cat: Optional[str] = None,
              **attrs) -> float:
        """Summed duration (seconds) of the matching spans."""
        return sum(sp.dur for sp in self.select(name, cat, **attrs))

    # -- exporters ---------------------------------------------------------
    def to_jsonl(self, path: str) -> str:
        return _export.to_jsonl(self.spans, path)

    def to_chrome_trace(self, path: str) -> str:
        return _export.to_chrome_trace(self.spans, path)

    def __repr__(self):
        state = "enabled" if self.enabled else "disabled"
        return f"Recorder({state}, spans={len(self.spans)})"


class TeeRecorder(Recorder):
    """Records into a ``primary`` recorder while forwarding every event
    to additional target recorders.

    This is how nested :func:`recording` scopes compose: the inner scope
    installs a tee over (inner, outer) so the inner recorder sees only
    its own scope while the outer recorder's timeline stays gap-free.
    Queries and exporters read the primary's spans; each target gets a
    copy stamped against its own epoch.
    """

    def __init__(self, primary: Recorder, *others: Recorder):
        self.primary = primary
        self.others = tuple(others)
        self.enabled = True

    @property
    def epoch(self) -> float:
        return self.primary.epoch

    @property
    def spans(self) -> List[Span]:
        return self.primary.spans

    def clear(self) -> None:
        self.primary.clear()

    def _close(self, sp: Span, t0: float, dur: float) -> None:
        sp.dur = dur
        self.primary.spans.append(sp)
        for rec in self.others:
            # attrs may have been filled in from inside the body;
            # forward the final contents.
            rec.add(sp.name, sp.cat, ts=t0, dur=dur, **sp.attrs)

    def add(self, name: str, cat: str = "span", *, ts: float, dur: float,
            **attrs) -> Optional[Span]:
        sp = self.primary.add(name, cat, ts=ts, dur=dur, **attrs)
        for rec in self.others:
            rec.add(name, cat, ts=ts, dur=dur, **attrs)
        return sp

    def instant(self, name: str, cat: str = "instant",
                **attrs) -> Optional[Span]:
        t0 = time.perf_counter()
        sp = self.primary.add(name, cat, ts=t0, dur=0.0, **attrs)
        if sp is not None:
            sp.ph = "i"
        for rec in self.others:
            isp = rec.add(name, cat, ts=t0, dur=0.0, **attrs)
            if isp is not None:
                isp.ph = "i"
        return sp

    def __repr__(self):
        return (f"TeeRecorder(primary={self.primary!r}, "
                f"others={len(self.others)})")


class SpanScope:
    """One span: a ``TraceAnnotation`` named ``<cat>.<name>`` around the
    body, and one :class:`Span` record when the recorder is enabled, both
    timed by one ``perf_counter`` pair.  ``seconds`` holds the body's
    duration once the scope has exited, recorder or not."""

    __slots__ = ("recorder", "name", "cat", "attrs", "seconds",
                 "_annotation", "_t0", "_span")

    def __init__(self, recorder: Recorder, name: str, cat: str, attrs):
        self.recorder = recorder
        self.name = name
        self.cat = cat
        self.attrs = attrs
        self.seconds = 0.0

    def __enter__(self) -> Optional[Span]:
        # TraceMe encodes the metadata only while a trace is active
        self._annotation = TraceAnnotation(f"{self.cat}.{self.name}",
                                           **self.attrs)
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        self._span = (self.recorder._open(self.name, self.cat, self._t0,
                                          self.attrs)
                      if self.recorder.enabled else None)
        return self._span

    def __exit__(self, *exc) -> None:
        self.seconds = time.perf_counter() - self._t0
        if self._span is not None:
            self.recorder._close(self._span, self._t0, self.seconds)
        self._annotation.__exit__(*exc)


_GLOBAL = Recorder(enabled=False)


def get_recorder() -> Recorder:
    """The process-global recorder (disabled unless one was installed)."""
    return _GLOBAL


def set_recorder(rec: Recorder) -> Recorder:
    """Install ``rec`` as the process-global recorder; returns the
    previous one (so callers can restore it)."""
    global _GLOBAL
    prev = _GLOBAL
    _GLOBAL = rec
    return prev


@contextlib.contextmanager
def recording(recorder: Optional[Recorder] = None, *, tee: bool = True):
    """Install an enabled recorder for the scope of the ``with`` block and
    restore the previous global on exit (exception-safe).  Yields the
    recorder.

    Nested scopes compose: when an enabled recorder is already installed
    and ``tee=True`` (the default), the scope installs a
    :class:`TeeRecorder` so spans land in *both* the new recorder and
    the enclosing one.  Pass ``tee=False`` for last-wins isolation (the
    outer recorder sees a gap for the inner scope's duration).
    """
    rec = Recorder() if recorder is None else recorder
    prev = get_recorder()
    if tee and prev.enabled and prev is not rec:
        set_recorder(TeeRecorder(rec, prev))
    else:
        set_recorder(rec)
    try:
        yield rec
    finally:
        set_recorder(prev)


def span(name: str, cat: str = "span", **attrs) -> SpanScope:
    """``get_recorder().span(...)``: a profiler annotation always, a
    record only when the global recorder is enabled."""
    return _GLOBAL.span(name, cat=cat, **attrs)


def instant(name: str, cat: str = "instant", **attrs):
    return _GLOBAL.instant(name, cat=cat, **attrs)


def note_kernel(kernel: str, **attrs) -> None:
    """Trace-time kernel-selection note, called by the ``kernels.ops``
    wrappers.  Inside a jitted caller this Python code runs at *trace*
    time only, so each instant event marks a kernel choice being baked
    into a fresh executable — retrace attribution for free.  The
    MetricsPlane counts the same events as
    ``repro_kernel_traces{kernel=,use_kernel=}``."""
    if _GLOBAL.enabled:
        _GLOBAL.instant(kernel, cat="kernel", **attrs)
    plane = _metrics.get_plane()
    if plane.enabled:
        plane.counter(
            "repro_kernel_traces",
            "kernel-choice trace events from the ops wrappers (one per "
            "kernel baked into a fresh executable)",
        ).inc(kernel=kernel, use_kernel=str(attrs.get("use_kernel", "")))
