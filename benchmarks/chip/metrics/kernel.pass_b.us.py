"""``kernel.pass_b.us``: summed device time of the pass B launches over
their count, in microseconds, over the chips used."""


def read(ctx):
    tr = ctx.trace
    if tr is None:
        return None
    n = sum(d["n_pass_b"] for d in tr["per_device"])
    t = sum(d["t_pass_b_s"] for d in tr["per_device"])
    return 1e6 * t / n if n else None
