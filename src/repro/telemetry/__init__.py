"""Solver observability: flight recorder for the fused PA-SMO engine.

Three tiers (see ISSUE 8 / the README "Observability" section):

* **device** — :class:`~repro.telemetry.ring.TelemetryRing`, a bounded
  per-lane ring-buffer pytree carried through the fused while_loop
  (:mod:`repro.telemetry.ring`);
* **host** — always-on spans and compile counters
  (:mod:`repro.telemetry.spans`: :func:`span`, :func:`recent`,
  :func:`export`), the JSONL event sink, phase scopes (a span plus a
  ``phase`` event), and the environment fingerprint
  (:mod:`repro.telemetry.sink`);
* **report** — ``python -m repro.launch.telemetry_report`` renders
  convergence tables and a straggler diagnosis from the JSONL artifact.

:class:`Diagnostics` is the user-facing knob threaded through the grid
drivers and the ``SVC``/``SVR``/``OneClassSVM`` facades
(``diagnostics=``).  It is a *host* object (sink handles aren't
hashable), so the engines themselves take the static
:class:`~repro.telemetry.ring.RingConfig` via ``telemetry=`` and the
drivers drain the returned rings into the ``Diagnostics`` sink.
"""

from __future__ import annotations

import numpy as np

from repro.telemetry.ring import (RingConfig, TelemetryRing, ring_init,
                                  ring_slice, ring_update)
from repro.telemetry.sink import (JsonlSink, _to_plain, env_fingerprint,
                                  fingerprint_diff, phase_scope, read_jsonl)
from repro.telemetry.spans import (Span, children, clear, export, recent,
                                   span)

__all__ = [
    "Diagnostics", "RingConfig", "TelemetryRing", "ring_init",
    "ring_update", "ring_slice", "JsonlSink", "env_fingerprint",
    "fingerprint_diff", "phase_scope", "read_jsonl", "Span", "span",
    "recent", "children", "export", "clear",
]


class Diagnostics:
    """Host-side flight-recorder handle for one or more solver runs.

    Parameters
    ----------
    path : optional JSONL output path (``None`` keeps events in memory;
        read them back via ``diag.sink.events``).
    ring : device-tier sampling geometry, or ``None`` to record host
        phases only (the engines then run their telemetry-free jaxpr).
    """

    def __init__(self, path=None, *, ring: RingConfig | None = RingConfig(),
                 sink: JsonlSink | None = None):
        self.ring_config = ring
        self.sink = sink if sink is not None else JsonlSink(path)
        self.lanes: list[dict] = []
        self.sink.emit("fingerprint", **env_fingerprint())

    # -- host tier ---------------------------------------------------------

    def scope(self, name: str, **meta):
        """A span (:func:`~repro.telemetry.spans.span`) that also emits a
        ``phase`` event into this handle's sink."""
        return phase_scope(name, self.sink, **meta)

    def event(self, event: str, **payload):
        return self.sink.emit(event, **payload)

    # -- device tier drain -------------------------------------------------

    def drain_ring(self, ring: TelemetryRing, meta=None, result=None):
        """Convert a returned ring into per-lane ``lane`` events.

        ``meta`` is an optional per-lane list of dicts (gamma/C/labels —
        what the straggler report keys on); ``result`` an optional
        :class:`~repro.core.solver_fused.FusedResult` view of the same
        lanes contributing final scalars.
        """
        if ring is None or self.ring_config is None:
            return []
        cfg = self.ring_config
        r = {k: np.asarray(getattr(ring, k)) for k in (
            "t", "gap", "n_active", "n_unshrink", "n_samples",
            "ratio", "ratio_t", "n_ratio")}
        B = r["n_samples"].shape[0]
        res = {}
        if result is not None:
            # tolerant: SolveResult-shaped objects lack n_unshrink (the
            # drain then falls back to the ring's last sample)
            for key in ("iterations", "kkt_gap", "converged",
                        "n_planning", "n_unshrink"):
                v = getattr(result, key, None)
                if v is not None:
                    res[key] = np.asarray(v)
        out = []
        for lane in range(B):
            ns = int(min(r["n_samples"][lane], cfg.cap))
            nr = int(min(r["n_ratio"][lane], cfg.ratio_cap))
            rec = {
                "lane": len(self.lanes),
                "n_samples": int(r["n_samples"][lane]),
                "n_ratio": int(r["n_ratio"][lane]),
                "samples": {
                    "t": r["t"][lane, :ns].tolist(),
                    "gap": r["gap"][lane, :ns].tolist(),
                    "n_active": r["n_active"][lane, :ns].tolist(),
                    "n_unshrink": r["n_unshrink"][lane, :ns].tolist(),
                },
                "ratio": {
                    "t": r["ratio_t"][lane, :nr].tolist(),
                    "value": r["ratio"][lane, :nr].tolist(),
                },
            }
            if meta is not None:
                rec.update({k: _to_plain(v) for k, v in meta[lane].items()})
            if "iterations" in res:
                rec["iterations"] = int(res["iterations"][lane])
            if "kkt_gap" in res:
                rec["kkt_gap"] = float(res["kkt_gap"][lane])
            if "converged" in res:
                rec["converged"] = bool(res["converged"][lane])
            if "n_planning" in res:
                rec["n_planning"] = int(res["n_planning"][lane])
                if rec.get("iterations"):
                    # share of iterations whose 2-direction step was
                    # accepted: the planning rate under algorithm="pasmo",
                    # the conjugate acceptance rate under step="conjugate"
                    # (same channel — the modes are mutually exclusive)
                    rec["accepted_step_share"] = (
                        rec["n_planning"] / rec["iterations"])
            if "n_unshrink" in res:
                rec["total_unshrink"] = int(res["n_unshrink"][lane])
            elif ns:
                rec["total_unshrink"] = int(r["n_unshrink"][lane, ns - 1])
            self.lanes.append(rec)
            # emit_plain: everything above is tolist() output / python
            # scalars — the per-element coercion walk dominated the drain
            out.append(self.sink.emit_plain("lane", rec))
        return out

    # -- summary -----------------------------------------------------------

    def summary(self, top_k: int = 5) -> dict:
        """Aggregate view: iteration histogram, straggler top-k, totals."""
        iters = np.asarray(
            [rec.get("iterations", 0) for rec in self.lanes], np.int64)
        s = {"n_lanes": len(self.lanes),
             "total_planning": int(sum(rec.get("n_planning", 0)
                                       for rec in self.lanes)),
             "total_unshrink": int(sum(rec.get("total_unshrink", 0)
                                       for rec in self.lanes)),
             "n_converged": int(sum(bool(rec.get("converged", False))
                                    for rec in self.lanes))}
        if len(iters):
            edges = np.histogram_bin_edges(iters, bins=min(8, max(
                1, len(iters))))
            hist, _ = np.histogram(iters, bins=edges)
            order = np.argsort(iters)[::-1][:top_k]
            total = max(1, int(iters.sum()))
            s["iteration_histogram"] = {
                "edges": [float(e) for e in edges],
                "counts": [int(c) for c in hist]}
            s["stragglers"] = [{
                "lane": int(k),
                "iterations": int(iters[k]),
                "iter_share": float(iters[k] / total),
                **{key: self.lanes[k][key] for key in ("gamma", "C", "label")
                   if key in self.lanes[k]},
            } for k in order]
            s["total_iterations"] = int(iters.sum())
            s["max_iterations"] = int(iters.max())
        return s

    def finalize(self, top_k: int = 5) -> dict:
        """Emit the ``summary`` event and close the sink file handle."""
        s = self.summary(top_k)
        self.sink.emit("summary", **s)
        self.sink.close()
        return s
