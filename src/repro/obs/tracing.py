"""Structured span tracer: nested spans, injected clock, JSONL export.

Design mirrors the scheduler's clock idiom: a ``Tracer`` takes any
``clock: () -> float`` — ``time.perf_counter`` for live serving, a
virtual/fake clock for deterministic replay tests — so the same
instrumentation yields wall timings in production and bit-identical
span streams under replay.

Two usage shapes:

  * stacked spans (the common case) — ``with span("solver.solve", ...)``
    nests under whatever span is currently open on this tracer:

        with span("planner.plan_gemms", rows=64) as sp:
            ...              # solver.solve spans open inside parent here
            if sp: sp.attrs["solved"] = n     # late attributes are fine

  * detached spans — long-lived spans that interleave across ticks and
    therefore cannot live on the stack (per-request admit→finish in the
    scheduler).  ``tracer.start("sched.request", detached=True)`` +
    ``tracer.end(sp)``; point-in-time marks (first token) attach via
    ``tracer.event("first_token", parent=sp)`` as zero-length children.

On the profiler's clock: while JAX's profiler records, every stacked
span also enters a ``jax.profiler.TraceAnnotation`` of its name, with
its opening attributes as the annotation's keyword arguments, and leaves
it when the span ends; a span opened with ``step_num=`` enters a
``StepTraceAnnotation`` instead (xprof's step view).  A profile captured
while a tracer is installed (``jax.profiler.trace`` or
``start_server``) therefore holds the program's spans beside the
device's ops, on one clock.  Detached spans and zero-length events stay
host-only, since they do not nest.  The module imports no JAX itself, so
numpy-only planner subprocesses can import it.

When no tracer is installed (the default), ``span()`` returns a shared
no-op context manager and ``trace_event`` returns ``None`` — the cost
at every instrumented site is one global read and a dict pack, which is
what keeps the serving overhead gate (benchmarks/bench_obs.py, on the
CPU) under 5%.  What tracing costs when on, on the chip, is in PERF.md
(section 6, the tracing findings).

JSONL schema, one object per span, ordered by ``sid``::

    {"sid": 3, "parent": 1, "name": "solver.solve",
     "t0": 0.013, "t1": 0.192, "attrs": {"dims": [256, 256, 64]}}

``Tracer.to_jsonl`` / ``Tracer.from_jsonl`` round-trip exactly (tested
in tests/test_obs.py).
"""
from __future__ import annotations

import dataclasses
import io
import json
import sys
import time
from typing import Any, Callable, Optional

from .registry import inc


@dataclasses.dataclass
class Span:
    sid: int
    name: str
    t0: float
    t1: Optional[float] = None
    parent: Optional[int] = None
    attrs: dict[str, Any] = dataclasses.field(default_factory=dict)

    @property
    def duration(self) -> float:
        return (self.t1 if self.t1 is not None else self.t0) - self.t0

    def to_json(self) -> dict:
        return {"sid": self.sid, "parent": self.parent, "name": self.name,
                "t0": self.t0, "t1": self.t1, "attrs": self.attrs}

    @classmethod
    def from_json(cls, obj: dict) -> "Span":
        return cls(sid=obj["sid"], name=obj["name"], t0=obj["t0"],
                   t1=obj.get("t1"), parent=obj.get("parent"),
                   attrs=dict(obj.get("attrs") or {}))


class _NullSpan:
    """Absorbs every span operation; shared singleton for the off path.

    Truthiness is False so call sites can guard late-attribute writes
    with ``if sp: sp.attrs[...] = ...``."""

    attrs: dict[str, Any] = {}

    def __enter__(self) -> None:
        return None

    def __exit__(self, *exc) -> bool:
        return False

    def __bool__(self) -> bool:
        return False


NULL_SPAN = _NullSpan()


class Tracer:
    """Collects spans; single-threaded by design (one tracer per loop,
    matching the scheduler / benchmark harnesses that drive it)."""

    def __init__(self, clock: Callable[[], float] | None = None):
        self.clock = clock if clock is not None else time.perf_counter
        self.spans: list[Span] = []
        self._stack: list[int] = []
        self._next_sid = 0
        # the open profiler annotation of each open stacked span, by sid
        self._annotations: dict[int, Any] = {}

    # ------------------------------------------------------------ spans
    def start(self, name: str, *, detached: bool = False,
              parent: Span | None = None, step_num: int | None = None,
              **attrs: Any) -> Span:
        """Open a span.  Stacked spans parent under the innermost open
        span and enter a profiler annotation (``step_num`` makes it a
        step annotation); detached spans record the current parent but
        do not join the stack (they may outlive it)."""
        if parent is not None:
            pid: Optional[int] = parent.sid
        else:
            pid = self._stack[-1] if self._stack else None
        sp = Span(sid=self._next_sid, name=name, t0=self.clock(),
                  parent=pid, attrs=dict(attrs))
        self._next_sid += 1
        self.spans.append(sp)
        if not detached:
            self._stack.append(sp.sid)
            ann = _annotation(name, step_num, attrs)
            if ann is not None:
                self._annotations[sp.sid] = ann
        return sp

    def end(self, sp: Span, **attrs: Any) -> Span:
        sp.t1 = self.clock()
        if attrs:
            sp.attrs.update(attrs)
        if self._stack and self._stack[-1] == sp.sid:
            self._stack.pop()
        ann = self._annotations.pop(sp.sid, None)
        if ann is not None:
            ann.__exit__(None, None, None)
        return sp

    def span(self, name: str, *, step_num: int | None = None,
             **attrs: Any) -> "_OpenSpan":
        """A context manager over a stacked span: opened on entry (and
        bound by ``as``), ended on exit."""
        return _OpenSpan(self, name, step_num, attrs)

    def event(self, name: str, *, parent: Span | None = None,
              **attrs: Any) -> Span:
        """Zero-length span: a point-in-time mark (first token, eviction)."""
        sp = self.start(name, detached=True, parent=parent, **attrs)
        sp.t1 = sp.t0
        return sp

    # ------------------------------------------------------------ export
    def clear(self) -> None:
        self.spans.clear()
        self._stack.clear()
        self._next_sid = 0

    def dumps_jsonl(self) -> str:
        buf = io.StringIO()
        for sp in self.spans:
            buf.write(json.dumps(sp.to_json(), sort_keys=True))
            buf.write("\n")
        return buf.getvalue()

    def to_jsonl(self, path) -> None:
        with open(path, "w") as fh:
            fh.write(self.dumps_jsonl())

    @classmethod
    def from_jsonl(cls, path) -> list[Span]:
        spans = []
        with open(path) as fh:
            for line in fh:
                line = line.strip()
                if line:
                    spans.append(Span.from_json(json.loads(line)))
        return spans

    # ----------------------------------------------------------- queries
    def children(self, sp: Span) -> list[Span]:
        return [s for s in self.spans if s.parent == sp.sid]

    def by_name(self, name: str) -> list[Span]:
        return [s for s in self.spans if s.name == name]


class _OpenSpan:
    """``Tracer.span``'s context manager (a class, not a generator: the
    scheduler opens several of these a tick)."""

    __slots__ = ("tracer", "name", "step_num", "attrs", "sp")

    def __init__(self, tracer: Tracer, name: str, step_num: int | None,
                 attrs: dict):
        self.tracer, self.name = tracer, name
        self.step_num, self.attrs = step_num, attrs

    def __enter__(self) -> Span:
        self.sp = self.tracer.start(self.name, step_num=self.step_num,
                                    **self.attrs)
        return self.sp

    def __exit__(self, *exc) -> bool:
        self.tracer.end(self.sp)
        return False


def _annotation(name: str, step_num: int | None, attrs: dict):
    """An entered ``jax.profiler`` annotation for a stacked span, or None
    where JAX is not imported (this module never imports it) or no
    profile is being recorded."""
    jax = sys.modules.get("jax")
    if jax is None or not jax.profiler.TraceAnnotation.is_enabled():
        return None
    if step_num is None:
        ann = jax.profiler.TraceAnnotation(name, **attrs)
    else:
        ann = jax.profiler.StepTraceAnnotation(name, step_num=step_num,
                                               **attrs)
    ann.__enter__()
    return ann


# --------------------------------------------------------------- global
_TRACER: Tracer | None = None


def set_tracer(tracer: Tracer | None) -> Tracer | None:
    """Install (or clear, with None) the process tracer; returns the
    previous one so callers can restore it."""
    global _TRACER
    prev = _TRACER
    _TRACER = tracer
    return prev


def get_tracer() -> Tracer | None:
    return _TRACER


def span(name: str, *, step_num: int | None = None, **attrs: Any):
    """Instrumentation entry point: a context manager that is a shared
    no-op when no tracer is installed.  ``step_num`` marks a step of a
    loop (the scheduler's tick) for the profiler's step view."""
    t = _TRACER
    if t is None:
        return NULL_SPAN
    return t.span(name, step_num=step_num, **attrs)


def trace_event(name: str, **attrs: Any) -> Span | None:
    t = _TRACER
    if t is None:
        return None
    return t.event(name, **attrs)


# ------------------------------------------------------------- compiles
# the event JAX records once per backend compile (a persistent-cache
# load included), with the program's name as ``fun_name``
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_counting_compiles = False


def count_compiles() -> None:
    """Count every backend compile of this process as ``jit.compiles``
    in the registry and, under a tracer, record a ``jit.compile`` event
    with the program's ``fun_name`` and ``duration_s``.  Installs one
    ``jax.monitoring`` listener per process (listeners are process-wide
    and cannot be removed); later calls do nothing."""
    global _counting_compiles
    if _counting_compiles:
        return
    import jax.monitoring
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    _counting_compiles = True


def _on_duration(event: str, duration_s: float, **kwargs: Any) -> None:
    if event != COMPILE_EVENT:
        return
    inc("jit.compiles")
    trace_event("jit.compile", fun_name=kwargs.get("fun_name"),
                duration_s=duration_s)
