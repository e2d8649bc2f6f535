"""Host time per engine step that ran a prefill layer group in the
window, ms (with the decode iteration the step also ran)."""

from harness.record import cycle_ms


def read(run):
    return cycle_ms(run, "prefill")
