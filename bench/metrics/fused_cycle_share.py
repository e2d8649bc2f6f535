"""Share, %, of the window's engine steps that ran device work and ran a
fused prefill+decode cycle (EngineStats.fused_cycles delta). The cells
serve with the fused cycle off (``server.fused`` in the traffic file), so
it reads 0 there; a change that serves them fused shows here."""


def read(run):
    steps = [s for s in run.window_steps()
             if s.decode or s.prefill or s.stats.get("fused_cycles")]
    if not steps:
        return None
    return 100.0 * sum(1 for s in steps
                       if s.stats.get("fused_cycles")) / len(steps)
