"""``driver.warmup_compile_s``: seconds the warm-up fit spent tracing,
lowering and compiling or loading its programs from the persistent cache
(a backend compile event times the compile or the load), summed by the
program's ``jax.monitoring`` listener on the warm-up's root span: the
root span just before the window's, one per job.  The part of
``setup_s`` that compiling takes.  Nothing to read where the program
records no spans, or fewer root spans than the warm-up and the jobs."""


def read(ctx):
    try:
        from repro.telemetry import recent
    except ImportError:
        return None
    n = len(ctx.jobs) + 1
    roots = recent(n, roots=True)
    if len(roots) < n:
        return None
    c = roots[0].counts
    return c["trace_s"] + c["lower_s"] + c["compile_s"]
