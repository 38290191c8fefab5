"""Per-query trace spans.

A span tree covers one request end to end: plan (memo hit/miss,
candidates, predicted words/us), compile (circuit-cache hit/miss),
dispatch (engine, launches, tiles by case), decode (words gathered by
container kind).  Every span carries *predicted* cost attributes next
to *measured* wall time and words, so predicted-vs-realised drift is a
first-class queryable quantity rather than something reconstructed from
logs.

Spans parent through a contextvar, so instrumented layers never thread
a span argument through call signatures -- ``span("compile")`` inside a
running ``span("execute")`` nests automatically, including across the
serving front-end's batcher thread (each thread/context gets its own
stack).  When tracing is disabled, ``span()`` returns a shared no-op
singleton: one branch, zero allocation.

While tracing is enabled every entered span also opens a
``jax.profiler.TraceAnnotation`` named ``repro:<span name>`` on the
thread that runs it, carrying the attributes the span opened with, so a
profiler trace shows each span on the device trace's clock, on that
thread's host line.  :func:`annotation` opens such an annotation alone,
for host phases that belong to no request (the batcher's coalescing
sleep, the garbage collector).
"""
from __future__ import annotations

import time
from contextvars import ContextVar

enabled = False  # toggled by repro.obs.enable()/disable()

_CURRENT: ContextVar["Span | None"] = ContextVar("repro_obs_span", default=None)
_ROOT_LISTENERS: list = []
#: profiler annotation prefix: every span shows in a trace as ``repro:<name>``
PREFIX = "repro:"
_TraceAnnotation = None  # jax.profiler.TraceAnnotation, imported on first use


def annotation(name: str, **attrs):
    """A profiler annotation ``repro:<name>`` carrying ``attrs``, to be
    entered and exited on one thread (JAX's profiler is imported on the
    first call); the no-op :data:`NULL_SPAN` when tracing is disabled."""
    global _TraceAnnotation
    if not enabled:
        return NULL_SPAN
    if _TraceAnnotation is None:
        from jax.profiler import TraceAnnotation as _TraceAnnotation
    return _TraceAnnotation(PREFIX + name, **attrs)


class Span:
    __slots__ = ("name", "attrs", "children", "t0", "wall_s", "_token", "_note")

    def __init__(self, name: str, attrs: dict | None = None) -> None:
        self.name = name
        self.attrs = dict(attrs) if attrs else {}
        self.children: list[Span] = []
        self.t0 = 0.0
        self.wall_s = 0.0
        self._token = None
        self._note = None

    def set(self, **attrs) -> "Span":
        self.attrs.update(attrs)
        return self

    def __enter__(self) -> "Span":
        parent = _CURRENT.get()
        if parent is not None:
            parent.children.append(self)
        self._token = _CURRENT.set(self)
        self._note = annotation(self.name, **self.attrs)
        self._note.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.wall_s = time.perf_counter() - self.t0
        self._note.__exit__(None, None, None)
        self._note = None
        _CURRENT.reset(self._token)
        if _CURRENT.get() is None:
            for fn in _ROOT_LISTENERS:
                fn(self)

    def find(self, name: str) -> "Span | None":
        """Depth-first search for the first descendant span named *name*."""
        for c in self.children:
            if c.name == name:
                return c
            hit = c.find(name)
            if hit is not None:
                return hit
        return None

    def iter(self):
        yield self
        for c in self.children:
            yield from c.iter()

    def to_dict(self) -> dict:
        return {
            "name": self.name,
            "wall_us": self.wall_s * 1e6,
            "attrs": dict(self.attrs),
            "children": [c.to_dict() for c in self.children],
        }

    def format(self, indent: int = 0) -> str:
        """Human-readable span tree (quickstart/docs surface)."""
        pad = "  " * indent
        attrs = " ".join(f"{k}={v}" for k, v in self.attrs.items())
        lines = [f"{pad}{self.name} [{self.wall_s * 1e6:.0f}us] {attrs}".rstrip()]
        for c in self.children:
            lines.append(c.format(indent + 1))
        return "\n".join(lines)


class _NullSpan:
    """Disabled-mode span: every operation is a no-op on a singleton."""

    __slots__ = ()
    attrs: dict = {}
    children: list = []
    wall_s = 0.0
    name = ""

    def set(self, **attrs) -> "_NullSpan":
        return self

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> None:
        pass

    def find(self, name: str):
        return None

    def to_dict(self) -> dict:
        return {}


NULL_SPAN = _NullSpan()


def span(name: str, **attrs):
    """Open a span (context manager).  No-op singleton when disabled."""
    if not enabled:
        return NULL_SPAN
    return Span(name, attrs)


def current_span():
    """The innermost open span in this context (NULL_SPAN when none/off)."""
    if not enabled:
        return NULL_SPAN
    return _CURRENT.get() or NULL_SPAN


def add_root_listener(fn) -> None:
    """Call *fn(root_span)* whenever a root span completes."""
    if fn not in _ROOT_LISTENERS:
        _ROOT_LISTENERS.append(fn)


def merge_span_trees(name: str, roots: list) -> Span:
    """Fold per-shard span trees under one synthetic parent (dist path)."""
    out = Span(name)
    out.children = [r for r in roots if isinstance(r, Span)]
    out.wall_s = max((r.wall_s for r in out.children), default=0.0)
    return out
