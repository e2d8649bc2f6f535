"""The device idle while the host was in ``engine.admit``,
``engine.prefill`` or ``engine.migrate`` (forming a prompt batch,
launching a prefill layer group, handing a finished batch to decode), %
of the traced window."""

from harness import phases


def read(run):
    return phases.idle_share(run, __file__, "prefill_host")
