"""``driver.fit_host_ms``: the host time of a fit, in milliseconds, from
the program's own span recorder (``repro.telemetry.recent``): the root
span each facade or grid call opens at its first line (``svc_fit`` ...)
and closes when it returns, after dispatching the solve and before the
device has finished it.  The window's fits are the newest root spans,
one per job; the mean over them.  Nothing to read where the program
records no spans, or fewer root spans than jobs."""


def read(ctx):
    try:
        from repro.telemetry import recent
    except ImportError:
        return None
    n = len(ctx.jobs)
    roots = recent(n, roots=True)
    if n == 0 or len(roots) < n:
        return None
    return 1e3 * sum(r.seconds for r in roots) / n
