"""The device idle while the host was inside ``engine.step`` under no
child span that another idle share reads, % of the traced window."""

from harness import phases


def read(run):
    return phases.idle_share(run, __file__, "step_other")
