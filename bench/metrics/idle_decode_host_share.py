"""The device idle while the host was in ``engine.tables``,
``engine.decode`` or ``engine.emit`` (block tables, the decode
iteration's launch, per-slot token bookkeeping), % of the traced
window."""

from harness import phases


def read(run):
    return phases.idle_share(run, __file__, "decode_host")
