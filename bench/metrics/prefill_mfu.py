"""FLOPs of the prompts whose first token came in the window (matmuls,
causal attention, output head) over the window's seconds and the chip's
bf16 peak, %."""

from harness.record import prefill_flops_in_window


def read(run):
    flops = prefill_flops_in_window(run)
    return 100.0 * flops / (run.seconds * run.peak["bf16_flops"]) or None
