"""Median of time to first token, ms, over every request due in
the window, timed from when it was due; one that never finished counts
as above every finished one."""

from harness.record import percentile


def read(run):
    reqs = run.counted()
    if not reqs:
        return None
    return 1e3 * percentile(run.latencies(run.ttft_s), 50)
