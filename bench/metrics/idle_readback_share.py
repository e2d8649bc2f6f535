"""The device idle while the host was in ``engine.readback`` (a
device->host read), % of the traced window."""

from harness import phases


def read(run):
    return phases.idle_share(run, __file__, "readback")
