"""``driver.compile_events``: jaxpr traces plus backend compiles (or
persistent-cache loads) inside the window's fits, per job, counted by
the program's ``jax.monitoring`` listener on each fit's root span.  After
the warm-up every program is compiled, so anything above 0 is a retrace.
Nothing to read where the program records no spans, or fewer root spans
than jobs."""


def read(ctx):
    try:
        from repro.telemetry import recent
    except ImportError:
        return None
    n = len(ctx.jobs)
    roots = recent(n, roots=True)
    if n == 0 or len(roots) < n:
        return None
    return sum(r.counts["traces"] + r.counts["compiles"] for r in roots) / n
