"""The device idle while the host was in ``engine.schedule`` (the
scheduler's cycle, the queue reorder and the partition switch), % of the
traced window."""

from harness import phases


def read(run):
    return phases.idle_share(run, __file__, "schedule")
