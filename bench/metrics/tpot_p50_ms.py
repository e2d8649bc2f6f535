"""Median, ms, over every request due in the window, of its mean
gap between output tokens; one that never finished counts as above every
finished one."""

from harness.record import percentile


def read(run):
    reqs = run.counted()
    if not reqs:
        return None
    return 1e3 * percentile(run.latencies(run.tpot_s), 50)
