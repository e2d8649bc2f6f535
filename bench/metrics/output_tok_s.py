"""Every output token the host received inside the window, over the
window's seconds."""


def read(run):
    n = sum(len(i) for i in run.tokens_in_window().values())
    return n / run.seconds if n else None
