"""``kernel.pass_b_roofline``: the least time of the pass B launches over
their summed device time, in percent (``work.py``).

Each chip's launches are counted at that chip's fewest real lanes,
B // chips, so the least time stays a lower bound wherever the lanes
land.  Nothing to read where X fits on chip: the byte side counts X once
per launch from HBM, which is then no lower bound.
"""


def read(ctx):
    tr = ctx.trace
    if tr is None or not ctx.work.x_streams(ctx.l, ctx.d, ctx.device_kind):
        return None
    b_chip = max(1, ctx.B // ctx.n_chips)
    w = ctx.work.pass_work(ctx.l, ctx.d, b_chip, ctx.H)["pass_b"]
    least, _ = ctx.work.least_time(w, ctx.device_kind)
    n = sum(d["n_pass_b"] for d in tr["per_device"])
    t = sum(d["t_pass_b_s"] for d in tr["per_device"])
    return 100.0 * n * least / t if n and t > 0 else None
