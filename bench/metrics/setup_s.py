"""Process start to the window's start: weights, warm-up (compiling or
loading every program the cell reaches), filling the traffic, and for a
closed loop its lead-in."""


def read(run):
    return run.setup_s
