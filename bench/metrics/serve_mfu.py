"""Model FLOPs of the window over its seconds and the chip's bf16 peak,
%: every prompt whose first token came in the window (its matmuls, causal
attention and output head) and every output token decoded in it (matmuls,
attention at its context, output head)."""

from harness import costs
from harness.record import prefill_flops_in_window


def read(run):
    flops = prefill_flops_in_window(run) + decode(run)
    return 100.0 * flops / (run.seconds * run.peak["bf16_flops"]) or None


def decode(run) -> float:
    total = 0.0
    for rid, idx in run.tokens_in_window().items():
        p = len(next(r for r in run.requests() if r.rid == rid).prompt)
        for i in idx:
            if i:                     # token 0 comes from the prefill
                total += costs.decode_token_flops(run.conf, p + i)
    return total
