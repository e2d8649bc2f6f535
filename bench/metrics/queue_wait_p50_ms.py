"""Median wait, ms, of a request due in the window between when it was
due and its admission to a prefill batch (the engine's ``admit`` span:
the top of the step that admitted it)."""

from harness.record import percentile


def read(run):
    reqs = run.counted()
    if not reqs:
        return None
    waits = []
    for r in reqs:
        t = run.window.admit_times.get(r.rid)
        waits.append((t if t is not None else run.stop_time()) - run.due(r))
    return 1e3 * percentile(waits, 50)
