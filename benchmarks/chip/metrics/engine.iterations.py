"""``engine.iterations``: per job, the largest iteration count over the
lanes of the returned result (the loop runs until its slowest lane
converges), averaged over the run's jobs."""


def read(ctx):
    its = [float(j["iterations"].max()) for j in ctx.jobs]
    return sum(its) / len(its) if its else None
