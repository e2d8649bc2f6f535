"""The device idle while the host was outside ``engine.step`` (the
frontend's release and loop, the benchmark's hooks), % of the traced
window."""

from harness import phases


def read(run):
    return phases.idle_share(run, __file__, "outside_step")
