"""``ops.glue_share``: device time of every op other than the two Pallas
passes (the X pad, lane-state stacking and unstacking, scatters, the
O(B) step algebra, the facade's own ops), over device busy time, in
percent, summed over the chips used."""


def read(ctx):
    tr = ctx.trace
    if tr is None:
        return None
    busy = sum(d["busy_s"] for d in tr["per_device"])
    if busy <= 0:
        return None
    passes = sum(d["t_pass_a_s"] + d["t_pass_b_s"] for d in tr["per_device"])
    return 100.0 * (busy - passes) / busy
