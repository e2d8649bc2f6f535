"""The paged decode attention kernel's share of its roofline, %: the
least time its calls in the traced window need at the chip's peaks (the
operations and bytes of each call's live contexts, harness.costs), over
the kernel's summed device time in the trace."""

from harness import costs


def read(run):
    t = run.trace
    if t is None:
        return None
    dev = t.kernel_seconds("paged_decode")
    if not dev:
        return None
    c = run.conf
    least = 0.0
    for s in t.steps:
        if s.decode:
            f, b = costs.paged_decode(s.contexts, c["num_attention_heads"],
                                      c["num_key_value_heads"], c["head_dim"])
            least += c["num_hidden_layers"] * costs.least_time(f, b, run.peak)[0]
    return 100.0 * least / dev if least else None
