"""``engine.planning_share``: the share of iterations that took an
accepted planning-ahead step, in percent: the sum of ``n_planning`` over
the sum of ``iterations``, over every lane of the window's fits.  Read
from the engine's counters that each fit's root span holds in the
program's span recorder (read from the device here, never during the
fit).  Nothing to read where the program records no spans or a fit
holds no counters."""


def read(ctx):
    try:
        from repro.telemetry import recent
    except ImportError:
        return None
    n = len(ctx.jobs)
    roots = recent(n, roots=True)
    if n == 0 or len(roots) < n:
        return None
    held = [r.held() for r in roots]
    if not all("n_planning" in h and "iterations" in h for h in held):
        return None
    its = sum(float(h["iterations"].sum()) for h in held)
    plan = sum(float(h["n_planning"].sum()) for h in held)
    return 100.0 * plan / its if its > 0 else None
