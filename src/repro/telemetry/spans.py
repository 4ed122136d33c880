"""Always-on host spans and compile counters.

Every fit and grid call records a tree of named wall-clock spans in a
bounded in-memory buffer, whether or not a
:class:`~repro.telemetry.Diagnostics` handle is attached:

* :func:`span` — a context manager (also usable as a decorator) that
  records its name, start and end (``time.perf_counter_ns``), its id, its
  parent's id and the id of the *root* span shared by every span of one
  call.  It opens a ``jax.profiler.TraceAnnotation`` of the same name
  carrying the root id, so inside a profiler trace the span sits on the
  profiler's clock next to the device planes;
* compile counters — one ``jax.monitoring`` listener counts jaxpr
  traces, lowerings, backend compiles (which include persistent-cache
  loads) and cache loads, with their seconds; each span records how many
  of each fell inside it;
* :meth:`Span.hold` — a root span may keep references to result arrays
  (the engine's iteration counters); they are read only when a reader
  calls :meth:`Span.held`, so recording never waits on the device.

Readers: :func:`recent` (finished spans, oldest first), :func:`children`
and :func:`export` (JSONL).  The buffer keeps the newest
:data:`CAPACITY` spans.
"""

from __future__ import annotations

import collections
import contextvars
import functools
import itertools
import json
import threading
import time

import jax
import numpy as np

CAPACITY = 4096

# jax.monitoring duration events -> (count key, seconds key).  A backend
# compile event times compile-or-load, so a persistent-cache load counts
# under ``compiles`` as well as under ``cache_loads``.
_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": ("traces", "trace_s"),
    "/jax/core/compile/jaxpr_to_mlir_module_duration": ("lowerings",
                                                        "lower_s"),
    "/jax/core/compile/backend_compile_duration": ("compiles", "compile_s"),
    "/jax/compilation_cache/cache_retrieval_time_sec": ("cache_loads",
                                                        "cache_load_s"),
}
COUNTERS = tuple(k for pair in _EVENTS.values() for k in pair)

_totals = dict.fromkeys(COUNTERS, 0)
_lock = threading.Lock()        # compiles may finish on several threads
_spans: collections.deque = collections.deque(maxlen=CAPACITY)
_current: contextvars.ContextVar = contextvars.ContextVar(
    "repro_span", default=None)
_ids = itertools.count(1)


def _on_duration(event: str, secs: float, **_kw) -> None:
    keys = _EVENTS.get(event)
    if keys is not None:
        with _lock:
            _totals[keys[0]] += 1
            _totals[keys[1]] += secs


jax.monitoring.register_event_duration_secs_listener(_on_duration)


class Span:
    """One named host interval; see the module docstring.

    Open it with ``with span("fit.solve"): ...``, or decorate a function
    with ``@span(name)`` for a fresh span per call.  ``attrs`` may be
    updated while the span is open (the facades add the engine they
    picked).  ``counts`` holds the counter deltas once it has closed.
    """

    def __init__(self, name: str, **attrs):
        self.name = name
        self.attrs = attrs
        self.id = self.parent = self.root = None
        self.start_ns = self.end_ns = None
        self.counts: dict = {}
        self._held: dict = {}

    def __call__(self, fn):
        @functools.wraps(fn)
        def spanned(*args, **kwargs):
            with Span(self.name, **self.attrs):
                return fn(*args, **kwargs)
        return spanned

    def __enter__(self) -> "Span":
        up = _current.get()
        self.id = next(_ids)
        self.parent = None if up is None else up.id
        self.root = self.id if up is None else up.root
        self._base = tuple(_totals[k] for k in COUNTERS)
        self._token = _current.set(self)
        self._ann = jax.profiler.TraceAnnotation(self.name, root=self.root)
        self._ann.__enter__()
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        self.end_ns = time.perf_counter_ns()
        self._ann.__exit__(*exc)
        _current.reset(self._token)
        self.counts = {k: _totals[k] - b
                       for k, b in zip(COUNTERS, self._base)}
        _spans.append(self)
        return False

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9

    def hold(self, **values) -> None:
        """Keep references to ``values`` (device arrays); nothing is read."""
        self._held.update(values)

    def held(self) -> dict:
        """The held values as numpy arrays (waits for the device)."""
        return {k: np.asarray(v) for k, v in self._held.items()}

    def to_dict(self) -> dict:
        from repro.telemetry.sink import _to_plain
        return {"name": self.name, "id": self.id, "parent": self.parent,
                "root": self.root, "start_ns": self.start_ns,
                "end_ns": self.end_ns, "seconds": self.seconds,
                "attrs": _to_plain(self.attrs), "counts": self.counts,
                "held": _to_plain(self.held())}


span = Span


def recent(n: int | None = None, *, name: str | None = None,
           roots: bool = False) -> list[Span]:
    """The newest ``n`` finished spans (all when ``None``), oldest first;
    ``name`` keeps one name, ``roots`` keeps root spans only."""
    out = [s for s in _spans
           if (name is None or s.name == name)
           and (not roots or s.parent is None)]
    return out if n is None else out[-n:] if n > 0 else []


def children(parent: Span, name: str | None = None) -> list[Span]:
    """Finished direct children of ``parent``, oldest first."""
    return [s for s in _spans if s.parent == parent.id
            and (name is None or s.name == name)]


def export(path) -> int:
    """Append every buffered span to ``path`` as JSONL; returns the count.
    Reads held values, so it waits for the device."""
    spans = list(_spans)
    with open(path, "a") as fh:
        for s in spans:
            fh.write(json.dumps(s.to_dict()) + "\n")
    return len(spans)


def clear() -> None:
    """Drop every buffered span (the counters keep running)."""
    _spans.clear()
