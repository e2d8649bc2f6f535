"""Share, %, of the window's engine steps that ran device work and ran a
prefill layer group (EngineStats.prefill_cycles delta)."""


def read(run):
    steps = [s for s in run.window_steps() if s.decode or s.prefill]
    if not steps:
        return None
    return 100.0 * sum(1 for s in steps if s.prefill) / len(steps)
