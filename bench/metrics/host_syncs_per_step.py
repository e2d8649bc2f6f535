"""Device->host reads per engine step (``EngineStats.host_syncs``
delta), averaged over the window's steps that ran a decode iteration or
a prefill layer group."""


def read(run):
    steps = [s for s in run.window_steps() if s.decode or s.prefill]
    if not steps or "host_syncs" not in steps[0].stats:
        return None
    return sum(s.stats["host_syncs"] for s in steps) / len(steps)
