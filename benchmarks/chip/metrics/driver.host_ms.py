"""``driver.host_ms``: per job, the milliseconds the device sits idle
inside the benchmark's job spans, between programs.

A job span runs from the entry call to the host seeing alpha and b
ready.  Inside it, time not covered by any executing program (the
trace's ``XLA Modules`` line) is host work before the first launch and
after the last: ``gamma="scale"``, labels, host-to-device transfer,
unpacking.  The traced window holds the end of one job and the start of
the next, so the idle time is divided by the job starts in the window.
Averaged over the chips used.
"""


def read(ctx):
    tr = ctx.trace
    if tr is None or tr["n_job_starts"] == 0:
        return None
    idle = [d["host_idle_in_jobs_s"] for d in tr["per_device"]]
    return 1e3 * sum(idle) / len(idle) / tr["n_job_starts"]
