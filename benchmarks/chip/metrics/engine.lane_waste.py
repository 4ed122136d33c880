"""``engine.lane_waste``: the straggler share, 1 - sum of lane iterations
/ (B x the largest), in percent, averaged over the run's jobs: loop trips
the batch spends carrying lanes that have already frozen.  Nothing to read
with one lane."""


def read(ctx):
    out = []
    for j in ctx.jobs:
        it = j["iterations"]
        if it.size < 2 or it.max() <= 0:
            return None
        out.append(100.0 * (1.0 - it.sum() / (it.size * it.max())))
    return sum(out) / len(out) if out else None
