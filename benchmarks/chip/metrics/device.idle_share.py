"""``device.idle_share``: 1 - busy / window over the traced window, in
percent, where busy is the union of the device's op intervals; the mean
over the chips used."""


def read(ctx):
    tr = ctx.trace
    if tr is None or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
