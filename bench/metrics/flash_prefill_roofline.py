"""The flash (prefill) attention kernel's share of its roofline, %: the
least time its calls in the traced window need at the chip's peaks
(causal attention over each prompt's real tokens, harness.costs), over
the kernel's summed device time in the trace."""

from harness import costs


def read(run):
    t = run.trace
    if t is None:
        return None
    dev = t.kernel_seconds("flash_prefill")
    if not dev:
        return None
    c = run.conf
    least = 0.0
    for lengths in t.prefill_groups:
        f, b = costs.flash_prefill(lengths, c["num_attention_heads"],
                                   c["num_key_value_heads"], c["head_dim"])
        least += costs.least_time(f, b, run.peak)[0]
    return 100.0 * least / dev if least else None
