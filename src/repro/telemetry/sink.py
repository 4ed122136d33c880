"""Host-tier telemetry: JSONL event sink, phase scopes, env fingerprint.

The device tier (:mod:`repro.telemetry.ring`) samples iteration dynamics
inside the fused while_loop; this module is everything that happens on
the host around it:

* :func:`env_fingerprint` — the machine/runtime identity stamped into
  every benchmark record and telemetry artifact, so perf drift across
  runners (the ``doubled_row_parity`` 0.91 -> 0.66 -> 0.77 incident) is
  attributable;
* :class:`JsonlSink` — an append-only structured event stream (one JSON
  object per line) that also keeps the events in memory for in-process
  consumers (the report CLI reads either);
* :func:`phase_scope` — a :func:`~repro.telemetry.spans.span` that
  also lands in the JSONL stream as a ``phase`` event, so solver phases
  show up in the stream, in the span buffer and in profiler traces.
"""

from __future__ import annotations

import contextlib
import hashlib
import json
import os
import platform
import socket
import time

import jax
import numpy as np

from repro.telemetry.spans import span

FINGERPRINT_KEYS = ("jax_version", "backend", "device_kind",
                    "device_count", "cpu_count", "host")


def env_fingerprint() -> dict:
    """Runtime identity for benchmark records and telemetry artifacts.

    The hostname is hashed — records are committed to the repo and
    uploaded as CI artifacts, so the raw name stays out of them.  A
    backend that cannot start raises here: a record must never name a
    device it did not run on.
    """
    devs = jax.devices()
    host = hashlib.sha256(socket.gethostname().encode()).hexdigest()[:12]
    return {
        "jax_version": jax.__version__,
        "backend": devs[0].platform,
        "device_kind": devs[0].device_kind,
        "device_count": len(devs),
        "cpu_count": os.cpu_count() or 0,
        "host": host,
        "python": platform.python_version(),
        "machine": platform.machine(),
    }


def fingerprint_diff(stored: dict | None, current: dict | None) -> list:
    """Human-readable stored-vs-current mismatch lines (empty = match)."""
    stored = stored or {}
    current = current or {}
    lines = []
    for k in FINGERPRINT_KEYS:
        a, b = stored.get(k), current.get(k)
        if a != b:
            lines.append(f"{k}: recorded={a!r} current={b!r}")
    return lines


def _to_plain(v):
    """JSON-safe coercion for jax/numpy leaves (incl. arrays -> lists)."""
    if isinstance(v, (jax.Array, np.ndarray, np.generic)):
        return np.asarray(v).tolist()
    if isinstance(v, dict):
        return {k: _to_plain(x) for k, x in v.items()}
    if isinstance(v, (list, tuple)):
        return [_to_plain(x) for x in v]
    return v


class JsonlSink:
    """Append-only JSONL event stream (+ in-memory mirror).

    ``path=None`` keeps events in memory only — the default for tests
    and for callers that just want :meth:`events` / the summary dict.
    """

    def __init__(self, path: str | os.PathLike | None = None):
        self.path = os.fspath(path) if path is not None else None
        self.events: list[dict] = []
        self._fh = open(self.path, "a") if self.path is not None else None

    def emit(self, event: str, **payload) -> dict:
        rec = {"event": event, "ts": time.time()}
        rec.update({k: _to_plain(v) for k, v in payload.items()})
        return self._append(rec)

    def emit_plain(self, event: str, payload: dict) -> dict:
        """:meth:`emit` minus the ``_to_plain`` walk.

        For hot callers (the per-lane ring drain) whose payload is
        already JSON-safe — ``tolist()`` output and python scalars; the
        recursive coercion walk over hundreds of already-plain floats
        per lane was the drain's dominant cost.
        """
        rec = {"event": event, "ts": time.time()}
        rec.update(payload)
        return self._append(rec)

    def _append(self, rec: dict) -> dict:
        self.events.append(rec)
        if self._fh is not None:
            self._fh.write(json.dumps(rec) + "\n")
            self._fh.flush()
        return rec

    def close(self):
        if self._fh is not None:
            self._fh.close()
            self._fh = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


@contextlib.contextmanager
def phase_scope(name: str, sink: JsonlSink | None = None, **meta):
    """A :func:`~repro.telemetry.spans.span` around a solver phase that
    also emits a ``phase`` event into ``sink``.

    Yields the span; the event carries the span's ``seconds`` and its
    attributes (``meta`` plus whatever the caller added while it was
    open), and is emitted on exit, exceptions included.  With
    ``sink=None`` it is the span alone.
    """
    sp = span(name, **meta)
    try:
        with sp:
            yield sp
    finally:
        if sink is not None:
            sink.emit("phase", name=name, seconds=sp.seconds, **sp.attrs)


def read_jsonl(path) -> list[dict]:
    """Load a JSONL artifact back into event dicts (blank lines skipped)."""
    out = []
    with open(path) as fh:
        for line in fh:
            line = line.strip()
            if line:
                out.append(json.loads(line))
    return out
