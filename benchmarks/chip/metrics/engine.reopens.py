"""``engine.reopens``: per job, the exit checks that failed, summed over
the lanes of the fit: ``n_unshrink`` as each fit's root span holds it in
the program's span recorder (read from the device here, never during the
fit), averaged over the window's jobs.  A lane reopens when its carried
gradient reads a gap of eps but the gap recomputed from exact rows does
not; shrinking's unshrink events count there too.  Nothing to read where
the program records no spans or a fit holds no such counter."""


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
    if not all("n_unshrink" in h for h in held):
        return None
    return sum(float(h["n_unshrink"].sum()) for h in held) / n
