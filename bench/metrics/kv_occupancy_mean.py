"""KV pool blocks in use over all blocks, %, sampled after every engine
step in the window and averaged over the steps."""


def read(run):
    steps = run.window_steps()
    if not steps:
        return None
    n = run.window.n_blocks
    return 100.0 * sum(s.kv_used for s in steps) / (n * len(steps))
