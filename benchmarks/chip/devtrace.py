"""Reduce a JAX profiler trace (``.xplane.pb``) to device intervals.

What is read, and how:

* Device planes are ``/device:TPU:<n>``.  Their ``XLA Ops`` line holds
  one event per executed HLO op, named by the op's HLO text
  (``%pad.281 = f32[50176,128]... pad(...)``); the ``XLA Modules`` line
  one event per executed program.  The reduction keeps the instruction
  name (``pad.281``).  The two batched Pallas passes are custom calls
  that keep the names of their jitted wrappers in the compiled HLO: an
  instruction named ``rbf_row_wss_batched_pallas.<n>`` is pass A, one
  named ``rbf_update_wss_batched_pallas.<n>`` is pass B, and every other
  op is "other" (the loop glue: the X pad, state stacks, scatters, step
  algebra).  Only the instruction name counts: other ops' HLO text
  names the passes among their operands.  The ``Async XLA Ops`` line
  (copies that overlap the core's work) is not read.
* Host markers are the benchmark's own zero-length
  ``jax.profiler.TraceAnnotation`` events named ``bench.*``:
  ``bench.trace_on``/``bench.trace_off`` bound the traced window,
  ``bench.job_start``/``bench.job_dispatched``/``bench.job_end`` mark the
  host's progress through each job.
* Busy time is the union of a device's op intervals inside the window, so
  nested events are counted once: a ``while`` loop whose whole run lies
  in the trace has an event of its own around its body's ops.  Such a
  container op counts towards busy time but not as an op of its own in
  the counts and the heaviest ops.  Idle is the rest of the window.
  Idle gaps are labelled with the last host marker before their middle
  and the innermost host event of the marker thread around it.

Run ``python devtrace.py <trace.xplane.pb>`` to print a trace's planes,
lines and heaviest events, the first look before trusting the reduction.
"""

from __future__ import annotations

import bisect
import re
import sys
from collections import Counter, defaultdict
from dataclasses import dataclass, field

import numpy as np

PASSES = {"pass_a": "rbf_row_wss_batched_pallas",
          "pass_b": "rbf_update_wss_batched_pallas"}
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
MARK = "bench."

# host phase each marker opens
PHASES = {"bench.trace_on": "harness before the next job",
          "bench.job_start": "entry call before its launches",
          "bench.job_dispatched": "host waiting on the device",
          "bench.job_end": "harness between jobs"}


@dataclass
class DeviceOps:
    """Op events of one device: parallel arrays sorted by start (ns)."""
    index: int
    start: np.ndarray
    end: np.ndarray
    name: np.ndarray          # int ids into Trace.names
    cls: np.ndarray           # 0 other, 1 pass A, 2 pass B
    container: np.ndarray     # holds the next op (a while loop's event)
    mod_start: np.ndarray
    mod_end: np.ndarray


@dataclass
class Trace:
    devices: list[DeviceOps]
    names: list[str]
    markers: list[tuple[float, str]]            # (time ns, name), sorted
    host: list[tuple[float, float, str]] = field(default_factory=list)


CLASSES = ("other", "pass_a", "pass_b")


def short_name(name: str) -> str:
    """The HLO instruction name of an op event (``pad.281``)."""
    return name.split(" = ", 1)[0].lstrip("%") if name.startswith("%") \
        else name


def classify(name: str) -> int:
    """0 other, 1 pass A, 2 pass B, from the instruction name."""
    for i, key in enumerate(PASSES.values(), start=1):
        if name == key or name.startswith(key + "."):
            return i
    return 0


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    names: list[str] = []
    ids: dict[str, int] = {}
    cls_of: dict[str, int] = {}
    devices = []
    markers = []
    host = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = {ln.name: ln for ln in plane.lines}
            if OPS_LINE not in lines:
                raise ValueError(f"{plane.name} has no {OPS_LINE!r} line: "
                                 f"{sorted(lines)}")
            st, en, nm, cl = [], [], [], []
            for ev in lines[OPS_LINE].events:
                n = ev.name
                if n not in ids:
                    short = short_name(n)
                    if short not in ids:
                        ids[short] = len(names)
                        names.append(short)
                        cls_of[short] = classify(short)
                    ids[n], cls_of[n] = ids[short], cls_of[short]
                s = ev.start_ns
                st.append(s)
                en.append(s + ev.duration_ns)
                nm.append(ids[n])
                cl.append(cls_of[n])
            ms, me = [], []
            if MODULES_LINE in lines:
                for ev in lines[MODULES_LINE].events:
                    ms.append(ev.start_ns)
                    me.append(ev.start_ns + ev.duration_ns)
            st, en = np.asarray(st, np.float64), np.asarray(en, np.float64)
            o = np.lexsort((-en, st))          # by start, containers first
            st, en = st[o], en[o]
            mo = np.argsort(np.asarray(ms, np.float64), kind="stable")
            devices.append(DeviceOps(
                int(m.group(1)), st, en, np.asarray(nm, np.int64)[o],
                np.asarray(cl, np.int8)[o], np.append(st[1:] < en[:-1], False),
                np.asarray(ms, np.float64)[mo],
                np.asarray(me, np.float64)[mo]))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                evs = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                       for ev in ln.events]
                mk = [(s, n) for s, _, n in evs if n.startswith(MARK)]
                if mk:
                    markers.extend(mk)
                    host.extend(e for e in evs if not e[2].startswith(MARK))
    devices.sort(key=lambda d: d.index)
    markers.sort()
    host.sort()
    return Trace(devices, names, markers, host)


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------


def union(start: np.ndarray, end: np.ndarray, lo: float, hi: float
          ) -> list[tuple[float, float]]:
    """Union of [start, end) intervals clipped to [lo, hi), sorted."""
    s = np.clip(start, lo, hi)
    e = np.clip(end, lo, hi)
    keep = e > s
    s, e = s[keep], e[keep]
    if s.size == 0:
        return []
    o = np.argsort(s, kind="stable")
    s, e = s[o], np.maximum.accumulate(e[o])
    # a new block starts where an interval begins after every earlier end
    new = np.ones(s.size, bool)
    new[1:] = s[1:] > e[:-1]
    starts = s[new]
    ends = np.append(e[np.flatnonzero(new)[1:] - 1], e[-1])
    return list(zip(starts.tolist(), ends.tolist()))


def length(iv: list[tuple[float, float]]) -> float:
    return float(sum(b - a for a, b in iv))


def gaps(iv: list[tuple[float, float]], lo: float, hi: float
         ) -> list[tuple[float, float]]:
    """Complement of sorted disjoint ``iv`` inside [lo, hi)."""
    out, t = [], lo
    for a, b in iv:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def intersect(a: list[tuple[float, float]], b: list[tuple[float, float]]
              ) -> float:
    """Length of the intersection of two sorted disjoint interval lists."""
    i = j = 0
    tot = 0.0
    while i < len(a) and j < len(b):
        lo = max(a[i][0], b[j][0])
        hi = min(a[i][1], b[j][1])
        if hi > lo:
            tot += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


# ---------------------------------------------------------------------------
# reduction
# ---------------------------------------------------------------------------


def window(tr: Trace) -> tuple[float, float]:
    """The traced window: between the ``bench.trace_on`` and
    ``bench.trace_off`` markers, else the extent of the device ops."""
    on = [t for t, n in tr.markers if n == "bench.trace_on"]
    off = [t for t, n in tr.markers if n == "bench.trace_off"]
    if on and off:
        return on[0], off[-1]
    lo = min(float(d.start.min()) for d in tr.devices if d.start.size)
    hi = max(float(d.end.max()) for d in tr.devices if d.end.size)
    return lo, hi


def job_spans(tr: Trace, lo: float, hi: float) -> list[tuple[float, float]]:
    """Host spans of jobs inside [lo, hi): from each ``bench.job_start``
    to its ``bench.job_end``, cut at the window's edges."""
    spans, open_at = [], lo
    inside = False
    for t, n in tr.markers:
        if n == "bench.job_start":
            open_at, inside = t, True
        elif n == "bench.job_end":
            spans.append((max(lo, open_at if inside else lo), min(hi, t)))
            inside = False
    if inside:
        spans.append((max(lo, open_at), hi))
    return [(a, b) for a, b in spans if b > a]


def _label(tr: Trace, a: float, b: float) -> str:
    mid = 0.5 * (a + b)
    times = [t for t, _ in tr.markers]
    k = bisect.bisect_right(times, mid) - 1
    phase = PHASES.get(tr.markers[k][1], tr.markers[k][1]) if k >= 0 \
        else "before any marker"
    inner = [(e - s, n) for s, e, n in tr.host if s <= mid < e]
    return f"{phase}: {min(inner)[1]}" if inner else phase


def reduce(tr: Trace, top: int = 10) -> dict:
    """Numbers of one traced window, per device and averaged over them.

    Returns ``window_s``, ``busy_s`` (mean over devices), ``per_device``
    (busy, launch counts and device time of each pass, the host-idle time
    inside job spans), ``n_job_starts`` (job boundaries in the window),
    and the ``breakdown`` the result line carries.
    """
    lo, hi = window(tr)
    spans = job_spans(tr, lo, hi)
    n_starts = sum(1 for t, n in tr.markers
                   if n == "bench.job_start" and lo <= t < hi)
    per_dev = []
    op_time: dict[str, float] = defaultdict(float)
    all_gaps = []
    for dev in tr.devices:
        busy_iv = union(dev.start, dev.end, lo, hi)
        mod_iv = (union(dev.mod_start, dev.mod_end, lo, hi)
                  if dev.mod_start.size else busy_iv)
        inside = (dev.start >= lo) & (dev.end <= hi)
        row = {"device": dev.index, "busy_s": length(busy_iv) * 1e-9,
               "host_idle_in_jobs_s":
                   (length(spans) - intersect(spans, mod_iv)) * 1e-9}
        for c, cname in enumerate(CLASSES):
            sel = inside & (dev.cls == c) & ~dev.container
            row[f"n_{cname}"] = int(sel.sum())
            row[f"t_{cname}_s"] = float((dev.end[sel] - dev.start[sel])
                                        .sum()) * 1e-9
        per_dev.append(row)
        dur = np.where(dev.container, 0.0,
                       np.clip(dev.end, lo, hi) - np.clip(dev.start, lo, hi))
        sums = np.bincount(dev.name, weights=np.maximum(dur, 0.0),
                           minlength=len(tr.names))
        for nid in np.flatnonzero(sums):
            op_time[tr.names[nid]] += sums[nid] * 1e-9 / len(tr.devices)
        all_gaps.extend((b - a, a, b) for a, b in gaps(busy_iv, lo, hi))
    all_gaps.sort(reverse=True)
    ops_top = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    idle_top = [[_label(tr, a, b), d * 1e-9] for d, a, b in all_gaps[:top]]
    n = max(1, len(per_dev))
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(r["busy_s"] for r in per_dev) / n,
        "n_job_starts": n_starts,
        "per_device": per_dev,
        "breakdown": {"device_ops": [[k, v] for k, v in ops_top],
                      "idle_gaps": idle_top},
    }


def describe(path: str, top: int = 25) -> None:
    """Print the planes, lines and heaviest events of a trace."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        print(f"PLANE {plane.name}")
        for ln in plane.lines:
            cnt, dur, stats = Counter(), Counter(), {}
            for ev in ln.events:
                cnt[ev.name] += 1
                dur[ev.name] += ev.duration_ns
                if ev.name not in stats and len(stats) < top:
                    stats[ev.name] = [(k, str(v)[:80]) for k, v in ev.stats]
            print(f"  LINE {ln.name!r}: {sum(cnt.values())} events, "
                  f"{len(cnt)} names")
            if DEVICE_PLANE.match(plane.name) or any(
                    n.startswith(MARK) for n in cnt):
                for n, t in dur.most_common(top):
                    print(f"    {t * 1e-6:12.3f} ms {cnt[n]:8d} x {n[:100]}"
                          f"  {stats.get(n, '')}")


if __name__ == "__main__":
    describe(sys.argv[1])
