"""``driver.gamma_ms``: the host time of ``gamma="scale"`` in a fit, in
milliseconds: the ``fit.gamma`` span the facade opens around its gamma
resolution (for ``"scale"``, a copy of X to the host and its variance),
read from the program's span recorder.  The mean over the window's fits,
the newest root spans, one per job.  Nothing to read where a fit has no
such span or the program records none."""


def read(ctx):
    try:
        from repro.telemetry import children, recent
    except ImportError:
        return None
    n = len(ctx.jobs)
    roots = recent(n, roots=True)
    if n == 0 or len(roots) < n:
        return None
    spans = [children(r, "fit.gamma") for r in roots]
    if not all(spans):
        return None
    return 1e3 * sum(s.seconds for ss in spans for s in ss) / n
