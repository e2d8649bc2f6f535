"""Host time per decode-only engine step in the window, ms: the summed
time of the steps that ran one decode iteration and no prefill group,
over their count (release + ``server.step``, the on_cycle hook left
out)."""

from harness.record import cycle_ms


def read(run):
    return cycle_ms(run, "decode")
