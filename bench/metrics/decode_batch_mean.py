"""Slots each decode iteration in the window ran, averaged over the
iterations (DecodeWork.batch)."""


def read(run):
    batches = [s.batch for s in run.window_steps() if s.decode]
    return sum(batches) / len(batches) if batches else None
