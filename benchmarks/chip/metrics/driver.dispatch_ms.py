"""``driver.dispatch_ms``: the host time of a fit's solve call, in
milliseconds: the ``fit.solve`` span around the engine call, read from
the program's span recorder.  It returns once the solve is dispatched,
so it holds any trace or compile and the host's launch work, not the
device's.  The mean over the window's fits, the newest root spans, one
per job.  Nothing to read where a fit has no such span or the program
records none."""


def read(ctx):
    try:
        from repro.telemetry import children, recent
    except ImportError:
        return None
    n = len(ctx.jobs)
    roots = recent(n, roots=True)
    if n == 0 or len(roots) < n:
        return None
    spans = [children(r, "fit.solve") for r in roots]
    if not all(spans):
        return None
    return 1e3 * sum(s.seconds for ss in spans for s in ss) / n
